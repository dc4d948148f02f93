"""RMSNorm kernel: jnp reference + Pallas TPU version.

Reference analog: paddle/phi/kernels/fusion/gpu rms_norm (upstream-canonical,
unverified — SURVEY.md §0). On TPU the win is fusing the reduce + scale into
one VMEM pass instead of XLA's usual two; the Pallas kernel tiles rows into
VMEM blocks (lane dim = feature).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .naming import named_jit


def rms_norm_ref(x, weight=None, epsilon: float = 1e-6):
    """Reference path (CPU + fallback). Accumulates in f32 for bf16 inputs —
    same accumulation contract as the reference's fused kernel."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(ms + epsilon)
    if weight is not None:
        out = out * weight.astype(jnp.float32)
    return out.astype(dt)


def _rms_norm_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(ms + eps)
    o_ref[:] = (out * w_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("epsilon", "block_rows"))
def rms_norm_pallas(x, weight, epsilon: float = 1e-6, block_rows: int = 256):
    """Pallas TPU path: rows blocked into VMEM, feature dim as lanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    orig_shape = x.shape
    d = x.shape[-1]
    xr = x.reshape(-1, d)
    n = xr.shape[0]
    blk = min(block_rows, n)
    # pad rows to a multiple of the block
    pad = (-n) % blk
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
    grid = (xr.shape[0] // blk,)
    with jax.enable_x64(False):  # 64-bit index math breaks Mosaic lowering
        out = pl.pallas_call(
            functools.partial(_rms_norm_kernel, eps=epsilon),
            out_shape=jax.ShapeDtypeStruct(xr.shape, x.dtype),
            grid=grid,
            in_specs=[
                pl.BlockSpec((blk, d), lambda i: (i, 0)),
                # weight as a (1, d) block: TPU tiling wants 2D trailing dims
                pl.BlockSpec((1, d), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((blk, d), lambda i: (i, 0)),
        )(xr, weight.reshape(1, d))
    if pad:
        out = out[:n]
    return out.reshape(orig_shape)


def rms_norm(x, weight=None, epsilon: float = 1e-6):
    """Dispatch: Pallas on TPU (when enabled + weight present), ref otherwise."""
    from ..core.flags import flag
    from .flash_attention import _platform_of

    on_tpu = _platform_of(x) != "cpu"
    if flag("FLAGS_use_pallas") and on_tpu and weight is not None and x.shape[-1] % 128 == 0:
        return rms_norm_pallas(x, weight, epsilon)
    return rms_norm_ref(x, weight, epsilon)


# ---------------------------------------------------------------------------
# Differentiable fused RMSNorm (round 4). XLA's autodiff of rms_norm_ref
# emits backward fusions whose cross-lane reductions run at ~50 GB/s — the
# dense-2B xplane profile shows ~210 ms/step (of a ~930 ms step) in the
# norm fusions alone, ~7x the HBM-bound floor. The Pallas pair below does
# the forward in one VMEM pass (saving rstd as the residual) and the
# backward in one pass producing dx and accumulating d_weight across grid
# steps. Formulas (out = x·r·w, r = rsqrt(mean(x²)+eps)):
#   dx  = r·(w⊙dy) − x · (r³/D) · Σ_j dy_j w_j x_j      (per row)
#   dw  = Σ_rows dy ⊙ x ⊙ r
# ---------------------------------------------------------------------------


def _blk_rows(d: int) -> int:
    # ~5 f32 row-temps of [blk, d] must fit scoped VMEM (16MB): the
    # backward's stack is 21.5 bytes an element (19.71M at 256 x 3584, the
    # chip's compiler, PR 36), so 256 rows hold up to d = 2600
    return 128 if d > 2600 else 256


def _rms_fwd_kernel(x_ref, w_ref, o_ref, r_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(ms + eps)
    o_ref[...] = (x * r * w_ref[0].astype(jnp.float32)).astype(o_ref.dtype)
    r_ref[...] = r


def _rms_bwd_kernel(x_ref, w_ref, r_ref, dy_ref, dx_ref, dw_ref, *, d):
    from jax.experimental import pallas as pl

    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    r = r_ref[...]
    dyw = dy * w
    s = jnp.sum(dyw * x, axis=-1, keepdims=True)
    dx = r * dyw - x * (r * r * r / d) * s
    dx_ref[...] = dx.astype(dx_ref.dtype)
    part = jnp.sum(dy * x * r, axis=0, keepdims=True)     # [1, d] f32

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[...] = part

    @pl.when(pl.program_id(0) != 0)
    def _acc():
        dw_ref[...] += part


def _rows(x, blk):
    d = x.shape[-1]
    xr = x.reshape(-1, d)
    pad = (-xr.shape[0]) % blk
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
    return xr, pad


@named_jit("rms_norm", static_argnames=("eps", "interpret"))
def _rms_fwd_pallas(x, weight, eps, interpret=False):
    from jax.experimental import pallas as pl

    d = x.shape[-1]
    blk = _blk_rows(d)
    xr, pad = _rows(x, blk)
    n = xr.shape[0]
    with jax.enable_x64(False):
        out, rstd = pl.pallas_call(
            functools.partial(_rms_fwd_kernel, eps=eps),
            grid=(n // blk,),
            in_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0)),
                      pl.BlockSpec((1, d), lambda i: (0, 0))],
            out_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0)),
                       pl.BlockSpec((blk, 1), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((n, d), x.dtype),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32)],
            interpret=interpret,
            name="rms_norm",
        )(xr, weight.reshape(1, d))
    nrows = n - pad
    return (out[:nrows].reshape(x.shape) if pad else out.reshape(x.shape),
            rstd[:nrows])


@named_jit("rms_norm_bwd", static_argnames=("interpret",))
def _rms_bwd_pallas(x, weight, rstd, dy, interpret=False):
    from jax.experimental import pallas as pl

    d = x.shape[-1]
    blk = _blk_rows(d)
    xr, pad = _rows(x, blk)
    dyr, _ = _rows(dy, blk)
    rr = jnp.pad(rstd, ((0, pad), (0, 0))) if pad else rstd
    n = xr.shape[0]
    with jax.enable_x64(False):
        dx, dw = pl.pallas_call(
            functools.partial(_rms_bwd_kernel, d=d),
            grid=(n // blk,),
            in_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0)),
                      pl.BlockSpec((1, d), lambda i: (0, 0)),
                      pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                      pl.BlockSpec((blk, d), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0)),
                       pl.BlockSpec((1, d), lambda i: (0, 0))],
            out_shape=[jax.ShapeDtypeStruct((n, d), x.dtype),
                       jax.ShapeDtypeStruct((1, d), jnp.float32)],
            interpret=interpret,
            name="rms_norm_bwd",
        )(xr, weight.reshape(1, d), rr, dyr)
    nrows = n - pad
    dx = dx[:nrows].reshape(x.shape) if pad else dx.reshape(x.shape)
    return dx, dw[0].astype(weight.dtype)


def _rms_train_ref_bwd(x, weight, dy, eps):
    """jnp twin of the backward kernel (CPU / GSPMD / double-grad path)."""
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    wf = weight.astype(jnp.float32)
    d = x.shape[-1]
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    dyw = dyf * wf
    s = jnp.sum(dyw * xf, axis=-1, keepdims=True)
    dx = (r * dyw - xf * (r * r * r / d) * s).astype(x.dtype)
    dw = jnp.sum(
        (dyf * xf * r).reshape(-1, d), axis=0).astype(weight.dtype)
    return dx, dw


def _use_pallas_norm(x):
    from .flash_attention import _use_pallas
    return _use_pallas(x) and x.shape[-1] % 128 == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rms_norm_train(x, weight, epsilon: float = 1e-6, use_pallas=True):
    """Fused-backward RMSNorm for the training stacks. Matches
    rms_norm_ref in value; callers pass use_pallas=False under a mesh so
    GSPMD can partition the jnp formulation."""
    from .flash_attention import _interpret
    if use_pallas and _use_pallas_norm(x):
        return _rms_fwd_pallas(x, weight, epsilon,
                               interpret=_interpret())[0]
    return rms_norm_ref(x, weight, epsilon)


def _rms_train_fwd(x, weight, epsilon, use_pallas):
    from .flash_attention import _interpret
    if use_pallas and _use_pallas_norm(x):
        out, rstd = _rms_fwd_diffable(x, weight, epsilon, _interpret())
        return out, (x, weight, rstd)
    return rms_norm_ref(x, weight, epsilon), (x, weight, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_fwd_diffable(x, weight, epsilon, interpret):
    """The Pallas forward wrapped differentiable: in grad-of-grad the
    custom_vjp FWD RULE's ops land in the differentiated jaxpr, so the
    bare pallas_call there also broke double-grad (ADVICE r4 item 2).
    First-order still runs the fused kernel; differentiating through it
    falls back to the jnp twin."""
    return _rms_fwd_pallas(x, weight, epsilon, interpret=interpret)


def _rms_fwd_twin(x, weight, epsilon):
    xf = x.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                         + epsilon)
    out = (xf * rstd * weight.astype(jnp.float32)).astype(x.dtype)
    return out, rstd.reshape(-1, 1)


def _rms_fwd_diffable_fwd(x, weight, epsilon, interpret):
    return (_rms_fwd_pallas(x, weight, epsilon, interpret=interpret),
            (x, weight))


def _rms_fwd_diffable_bwd(epsilon, interpret, res, cots):
    x, weight = res
    _, vjp = jax.vjp(lambda x_, w_: _rms_fwd_twin(x_, w_, epsilon),
                     x, weight)
    return vjp(cots)


_rms_fwd_diffable.defvjp(_rms_fwd_diffable_fwd, _rms_fwd_diffable_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _rms_bwd_diffable(x, weight, rstd, dy, epsilon, interpret):
    """The Pallas backward wrapped so it is itself differentiable:
    double-grad/HVPs through the training stacks previously hit the bare
    pallas_call (no transpose rule) and raised (ADVICE r4 item 2). The
    second-order rule differentiates the jnp twin — rstd is a pure
    function of x there, so its cotangent is zero by construction."""
    return _rms_bwd_pallas(x, weight, rstd, dy, interpret=interpret)


def _rms_bwd_diffable_fwd(x, weight, rstd, dy, epsilon, interpret):
    return (_rms_bwd_pallas(x, weight, rstd, dy, interpret=interpret),
            (x, weight, rstd, dy))


def _rms_bwd_diffable_bwd(epsilon, interpret, res, cots):
    x, weight, rstd, dy = res
    _, vjp = jax.vjp(
        lambda x_, w_, dy_: _rms_train_ref_bwd(x_, w_, dy_, epsilon),
        x, weight, dy)
    dx2, dw2, ddy = vjp(cots)
    return dx2, dw2, jnp.zeros_like(rstd), ddy


_rms_bwd_diffable.defvjp(_rms_bwd_diffable_fwd, _rms_bwd_diffable_bwd)


def _rms_train_bwd(epsilon, use_pallas, res, dy):
    from .flash_attention import _interpret
    x, weight, rstd = res
    if rstd is not None:
        dx, dw = _rms_bwd_diffable(x, weight, rstd, dy, epsilon,
                                   _interpret())
    else:
        dx, dw = _rms_train_ref_bwd(x, weight, dy, epsilon)
    return dx, dw


rms_norm_train.defvjp(_rms_train_fwd, _rms_train_bwd)


def rms_norm_train_sharded(x, weight, epsilon, mesh, spec):
    """Fused-backward RMSNorm UNDER A MESH: shard_map the Pallas kernel
    over the activation shards so TP/FSDP runs the same fused kernels as
    the single-chip bench (VERDICT r4 next-3 — a bare pallas_call is
    opaque to the SPMD partitioner, which is why the mesh path previously
    dropped to jnp). `spec` is x's activation PartitionSpec (the feature
    dim must be unsharded — the norm reduces over it); weight is
    replicated, and shard_map's transpose psums its gradient across the
    shards. Off-TPU each shard falls through rms_norm_train's internal
    gate to the jnp formulation, so CPU meshes behave as before."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    fn = lambda xs, ws: rms_norm_train(xs, ws, epsilon, True)  # noqa: E731
    return shard_map(fn, mesh=mesh, in_specs=(spec, P(None)),
                     out_specs=spec, check_vma=False)(x, weight)
