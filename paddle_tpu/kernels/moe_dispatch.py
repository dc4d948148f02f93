"""Pallas MoE ragged dispatch: masked row-gather kernel.

Reference analog: the fused MoE dispatch CUDA kernels under
paddle/phi/kernels/fusion/ driving incubate moe_layer's capacity dispatch
(upstream-canonical, unverified — SURVEY.md §0, §2.6 item 1, §7 M7).

TPU-native design: both halves of capacity-based MoE routing — dispatch
(token rows → [E, C] expert slots) and combine (expert slots → token rows)
— are the SAME primitive once routing is index-form: a masked row gather
`out[m] = src[idx[m]] if idx[m] >= 0 else 0`. The kernel streams the index
table through scalar-prefetch SMEM and DMAs rows from HBM one by one, so
nothing materializes the [T, E, C] one-hot dispatch tensors and VMEM holds
only the current output block. The jnp path (take_along_axis on clipped
indices) is the CPU/GSPMD fallback — XLA can partition that gather under a
mesh, whereas a pallas_call is opaque to the SPMD partitioner.

Backward: gather transposes to scatter-add; the custom VJP runs it as a
jnp scatter-ADD — indices are NOT unique in general (the dispatch-direction
gather receives each token id up to k times, once per expert choice, so
duplicate contributions must accumulate); the forward is the hot,
memory-bound direction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _gather_rows_jnp(src, idx):
    """src [B, N, D]; idx [B, M] int32, -1 = zero row → [B, M, D]."""
    take = jnp.take_along_axis(src, jnp.clip(idx, 0)[..., None], axis=1)
    return take * (idx >= 0)[..., None].astype(src.dtype)


def _row_dma_pipeline(pl, pltpu, idx_ref, src_ref, scratch, sems, b, mb,
                      nmb, rows, masked):
    """Shared double-buffer discipline for the row-gather kernels: start
    block 0 in the prologue, keep block mb+1's DMAs in flight while
    waiting block mb's (buffer mb%2). `rows` = DMAs per block; `masked`
    skips DMAs for idx < 0 and zeroes those scratch rows (the pre-clipped
    kernels pass masked=False and mask via weights instead)."""
    def start_block(mb_, buf):
        for r in range(rows):
            i = idx_ref[b, mb_ * rows + r]
            if masked:
                cp = pltpu.make_async_copy(
                    src_ref.at[b, jnp.maximum(i, 0)], scratch.at[buf, r],
                    sems.at[buf, r])
                pl.when(i >= 0)(cp.start)

                @pl.when(i < 0)
                def _zero():
                    scratch[buf, r] = jnp.zeros_like(scratch[buf, r])
            else:
                pltpu.make_async_copy(src_ref.at[b, i], scratch.at[buf, r],
                                      sems.at[buf, r]).start()

    @pl.when(mb == 0)
    def _prologue():
        start_block(0, 0)

    @pl.when(mb + 1 < nmb)
    def _next():
        start_block(mb + 1, (mb + 1) % 2)

    for r in range(rows):
        i = idx_ref[b, mb * rows + r]
        if masked:
            cp = pltpu.make_async_copy(
                src_ref.at[b, jnp.maximum(i, 0)], scratch.at[mb % 2, r],
                sems.at[mb % 2, r])
            pl.when(i >= 0)(cp.wait)
        else:
            pltpu.make_async_copy(src_ref.at[b, i], scratch.at[mb % 2, r],
                                  sems.at[mb % 2, r]).wait()


def _gather_rows_kernel(idx_ref, src_ref, out_ref, scratch, sems, *, bm):
    """Grid (B, M // bm). idx_ref: scalar-prefetched [B, M] (SMEM);
    src_ref: [B, N, D/128, 128] in ANY (HBM) — rows are laid out as
    (D/128, 128) tiles so the per-row slice cuts only MAJOR (untiled)
    dims; Mosaic rejects size-1 slices of the sublane dim, which a flat
    [B, N, D] layout would require. out block [1, bm, D].

    DOUBLE-BUFFERED across grid steps (_row_dma_pipeline): the 4KB-row
    random reads of block mb+1 overlap block mb's drain — random row
    reads are latency/issue-bound, so keeping two blocks of DMAs
    outstanding is the lever. Grid iteration order is minor-dim-first, so
    steps of one batch row run consecutively; the b-boundary prologue
    refills the pipe."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _row_dma_pipeline(pl, pltpu, idx_ref, src_ref, scratch, sems,
                      pl.program_id(0), pl.program_id(1), pl.num_programs(1),
                      bm, masked=True)
    out_ref[0] = scratch[pl.program_id(1) % 2].reshape(out_ref.shape[1:])


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def gather_rows_pallas(src, idx, bm=128, interpret=False):
    """src [B, N, D]; idx [B, M] int32 (-1 = zero row) → [B, M, D]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, N, D = src.shape
    M = idx.shape[1]
    while M % bm:
        bm //= 2
    grid = (B, M // bm)
    lanes = 128
    src4 = src.reshape(B, N, D // lanes, lanes)
    with jax.enable_x64(False):  # Mosaic: i64 index arithmetic untileable
        return pl.pallas_call(
            functools.partial(_gather_rows_kernel, bm=bm),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, bm, D), lambda b, m, idx: (b, m, 0)),
                scratch_shapes=[pltpu.VMEM((2, bm, D // lanes, lanes),
                                           src.dtype),
                                pltpu.SemaphoreType.DMA((2, bm))],
            ),
            out_shape=jax.ShapeDtypeStruct((B, M, D), src.dtype),
            interpret=interpret,
        )(idx.astype(jnp.int32), src4)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gather_rows_p(src, idx, interpret=False):
    return gather_rows_pallas(src, idx, interpret=interpret)


def _gather_rows_p_fwd(src, idx, interpret):
    # residuals must be jax types: a [N, 0] placeholder carries src's row
    # count and dtype into the bwd without holding data
    shape_probe = jnp.zeros((src.shape[1], 0), src.dtype)
    return gather_rows_pallas(src, idx, interpret=interpret), (
        idx, shape_probe)


def _gather_rows_p_bwd(interpret, res, g):
    import numpy as np
    idx, shape_probe = res
    src_dtype = shape_probe.dtype
    B, N, D = idx.shape[0], shape_probe.shape[0], g.shape[-1]
    # transpose of a unique-index masked gather: scatter-add of g rows
    safe = jnp.where(idx >= 0, idx, N)  # dump row N, dropped below
    dsrc = jnp.zeros((B, N + 1, D), jnp.float32)
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], idx.shape)
    dsrc = dsrc.at[bidx, safe].add(g.astype(jnp.float32))
    return (dsrc[:, :N].astype(src_dtype),
            np.zeros(idx.shape, jax.dtypes.float0))


_gather_rows_p.defvjp(_gather_rows_p_fwd, _gather_rows_p_bwd)


def _use_pallas_here(src):
    from .flash_attention import _use_pallas
    return _use_pallas(src) and src.shape[-1] % 128 == 0


def gather_rows(src, idx, use_pallas=True):
    """Masked row gather — the MoE dispatch/combine primitive.

    src [B, N, D]; idx [B, M] int32, -1 = zero row → [B, M, D]. Routes to
    the Pallas kernel when allowed (use_pallas — callers disable it under a
    mesh so GSPMD can partition the jnp gather) and eligible (TPU backend
    or FLAGS_pallas_interpret, lane-aligned D)."""
    from .flash_attention import _interpret
    if use_pallas and _use_pallas_here(src):
        return _gather_rows_p(src, idx, _interpret())
    return _gather_rows_jnp(src, idx)


# ---------------------------------------------------------------------------
# Paired-transpose gathers: because GShard slot assignment is INJECTIVE
# (each [e, c] slot holds at most one (token, choice) and each (token,
# choice) fills at most one slot), the transpose of "gather by one map" is
# exactly "gather by the inverse map" — never a scatter. The f32
# scatter-adds the generic VJP emits were ~16 ms/layer on the profiled
# config-4 bench (VERDICT r3 weak 1); these custom pairs turn all four
# backward directions into the same bm-blocked Pallas gather as forward.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def dispatch_gather(x, inv_tok, flat, k, use_pallas=True):
    """MoE dispatch: x [B, S, D]; inv_tok [B, E*C] (token id filling each
    slot, -1 = empty) → expert_in [B, E*C, D].

    flat [B, S*k] (slot id for each (token, choice), -1 = dropped) is the
    inverse map used ONLY by the gradient: dx[t] = Σ_j d_out[flat[t, j]]
    — a gather, not a scatter-add. The forward runs the CONDITIONAL-FREE
    wsum kernel (clipped indices + zero weights for empty slots): the
    per-row pl.when/zero-scratch branches of the masked kernel cost ~20%
    of the scalar-issue budget the gathers are bound by."""
    if use_pallas and _use_pallas_here(x):
        idx1 = jnp.clip(inv_tok, 0)[..., None]
        w1 = (inv_tok >= 0)[..., None].astype(jnp.float32)
        return gather_wsum(x, idx1, w1, use_pallas=True)
    return gather_rows(x, inv_tok, use_pallas=use_pallas)


def _dispatch_fwd(x, inv_tok, flat, k, use_pallas):
    return dispatch_gather(x, inv_tok, flat, k, use_pallas), flat


def _dispatch_bwd(k, use_pallas, flat, g):
    import numpy as np
    B, M = flat.shape
    # fused k-sum gather: dx[t] = sum_j g[flat[t, j]] — the old
    # gather-then-reshape-sum materialized a [B, S, k, D] intermediate
    # whose k-minor axis tiled as T(2,128) (~35 ms/step of physical
    # reshape+reduce on the round-4 profile)
    idx_tk = jnp.clip(flat, 0).reshape(B, M // k, k)
    w = (flat >= 0).reshape(B, M // k, k).astype(jnp.float32)
    dx = gather_wsum(g, idx_tk, w, use_pallas=use_pallas)
    return (dx, np.zeros((B, g.shape[1]), jax.dtypes.float0),
            np.zeros(flat.shape, jax.dtypes.float0))


dispatch_gather.defvjp(_dispatch_fwd, _dispatch_bwd)


# ---------------------------------------------------------------------------
# Fused weighted combine (round 4). The einsum formulation of the MoE
# combine (gather to [B, S, k, D] `got`, then "bskd,bsk->bsd") made XLA
# materialize [B, S, k, D] intermediates whose k=2 minor axis tiles as
# T(2,128) — the round-4 xplane profile shows ~100 ms/step of physical
# reshape/reduce traffic at ~20 GB/s on exactly these tensors. Folding the
# probs-weighted k-sum INTO the gather kernel removes those intermediates:
#   y[t] = sum_j w[t,j] * src[idx[t,j]]
# and the backward gathers dy rows ONCE, producing BOTH d_eout (scaled
# rows) and the per-slot dot that yields d_probs — zero extra row DMAs
# versus the unfused backward.
# ---------------------------------------------------------------------------


def _gather_wsum_kernel(idx_ref, src_ref, w_ref, out_ref, scratch, sems,
                        *, bm, k):
    """out[0, m] = sum_j w[0, m, j] * src[b, idx[b, m*k+j]] — idx is
    pre-clipped (invalid slots carry w=0). Double-buffered via
    _row_dma_pipeline (bm*k DMAs per block)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    mb = pl.program_id(1)
    _row_dma_pipeline(pl, pltpu, idx_ref, src_ref, scratch, sems,
                      pl.program_id(0), mb, pl.num_programs(1),
                      bm * k, masked=False)
    rows = scratch[mb % 2].reshape(bm, k, -1)
    w = w_ref[0]                                     # [bm, k] f32
    # f32 weights/accum: Mosaic only supports non-no-op minor-dim
    # inserts/broadcasts for 32-bit types
    acc = rows[:, 0, :].astype(jnp.float32) * w[:, 0:1]
    for j in range(1, k):
        acc = acc + rows[:, j, :].astype(jnp.float32) * w[:, j:j + 1]
    out_ref[0] = acc.astype(out_ref.dtype).reshape(out_ref.shape[1:])


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def gather_wsum_pallas(src, idx, w, bm=None, interpret=False):
    """src [B, N, D]; idx [B, M, k] int32 PRE-CLIPPED to [0, N); w
    [B, M, k] (w = 0 marks dropped choices) → [B, M, D]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, N, D = src.shape
    M, k = idx.shape[1], idx.shape[2]
    if bm is None:
        bm = max(128 // k, 8)   # 128 row-DMAs per block (sflag budget; 160 and k=2 bm=80 both measured neutral)
    while M % bm:
        bm //= 2
    lanes = 128
    src4 = src.reshape(B, N, D // lanes, lanes)
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_gather_wsum_kernel, bm=bm, k=k),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, M // bm),
                in_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec((1, bm, k), lambda b, m, idx: (b, m, 0)),
                ],
                out_specs=pl.BlockSpec((1, bm, D), lambda b, m, idx: (b, m, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, bm * k, D // lanes, lanes), src.dtype),
                    pltpu.SemaphoreType.DMA((2, bm * k))],
            ),
            out_shape=jax.ShapeDtypeStruct((B, M, D), src.dtype),
            interpret=interpret,
        )(idx.reshape(B, M * k).astype(jnp.int32), src4,
          w.astype(jnp.float32))


def _gather_wsum_jnp(src, idx, w):
    B, M, k = idx.shape
    rows = jnp.take_along_axis(
        src, idx.reshape(B, M * k, 1), axis=1).reshape(B, M, k, -1)
    return jnp.einsum("bmkd,bmk->bmd", rows, w.astype(src.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gather_wsum(src, idx, w, use_pallas=True):
    """Weighted k-row gather-sum (idx pre-clipped; w zeros mark drops).

    Carries its own (jnp-formulated) VJP so the fused MoE backwards that
    call it remain differentiable — grad-of-grad through moe_block
    (double-grad, HVPs) transposes this op; a bare pallas_call would
    raise there."""
    from .flash_attention import _interpret
    if use_pallas and _use_pallas_here(src):
        return gather_wsum_pallas(src, idx, w, interpret=_interpret())
    return _gather_wsum_jnp(src, idx, w)


def _gather_wsum_fwd(src, idx, w, use_pallas):
    return gather_wsum(src, idx, w, use_pallas), (src, idx, w)


def _gather_wsum_bwd(use_pallas, res, dy):
    import numpy as np
    src, idx, w = res
    B, N, D = src.shape
    M, k = idx.shape[1], idx.shape[2]
    contrib = dy[:, :, None, :] * w[..., None].astype(dy.dtype)  # [B,M,k,D]
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, M * k))
    dsrc = jnp.zeros((B, N, D), jnp.float32).at[
        bidx, idx.reshape(B, M * k)].add(
            contrib.reshape(B, M * k, D).astype(jnp.float32))
    rows = jnp.take_along_axis(
        src, idx.reshape(B, M * k, 1), axis=1).reshape(B, M, k, D)
    dw = jnp.einsum("bmd,bmkd->bmk", dy.astype(jnp.float32),
                    rows.astype(jnp.float32)).astype(w.dtype)
    return (dsrc.astype(src.dtype),
            np.zeros(idx.shape, jax.dtypes.float0), dw)


gather_wsum.defvjp(_gather_wsum_fwd, _gather_wsum_bwd)


def _gather_scale_dot_kernel(idx_ref, src_ref, s_ref, other_ref, out_ref,
                             dot_ref, scratch, sems, *, bm):
    """One dy-row gather serving the fused-combine backward:
    out[0, m] = s[0, m] * src[b, idx[b, m]]           (d_eout rows)
    dot[0, m] = sum_d src[b, idx[b, m]] * other[0, m] (d_probs per slot).
    Double-buffered via _row_dma_pipeline."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    mb = pl.program_id(1)
    _row_dma_pipeline(pl, pltpu, idx_ref, src_ref, scratch, sems,
                      pl.program_id(0), mb, pl.num_programs(1),
                      bm, masked=False)
    rows = scratch[mb % 2].reshape(bm, -1)           # [bm, D]
    sf = s_ref[0].astype(jnp.float32)[:, None]       # f32: see wsum kernel
    out_ref[0] = (rows.astype(jnp.float32) * sf).astype(
        out_ref.dtype).reshape(out_ref.shape[1:])
    other = other_ref[0].reshape(bm, -1)
    dot_ref[0] = jnp.sum(rows.astype(jnp.float32)
                         * other.astype(jnp.float32), axis=-1,
                         keepdims=True)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def gather_scale_dot_pallas(src, idx, scale, other, bm=128, interpret=False):
    """src [B, N, D]; idx [B, M] PRE-CLIPPED; scale [B, M]; other
    [B, M, D] → (out [B, M, D] = scale*src[idx],
                 dot [B, M] f32 = src[idx]·other)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, N, D = src.shape
    M = idx.shape[1]
    while M % bm:
        bm //= 2
    lanes = 128
    src4 = src.reshape(B, N, D // lanes, lanes)
    with jax.enable_x64(False):
        out, dot = pl.pallas_call(
            functools.partial(_gather_scale_dot_kernel, bm=bm),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, M // bm),
                in_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec((1, bm), lambda b, m, idx: (b, m)),
                    pl.BlockSpec((1, bm, D), lambda b, m, idx: (b, m, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((1, bm, D), lambda b, m, idx: (b, m, 0)),
                    pl.BlockSpec((1, bm, 1), lambda b, m, idx: (b, m, 0)),
                ],
                scratch_shapes=[
                    pltpu.VMEM((2, bm, D // lanes, lanes), src.dtype),
                    pltpu.SemaphoreType.DMA((2, bm))],
            ),
            out_shape=[jax.ShapeDtypeStruct((B, M, D), src.dtype),
                       jax.ShapeDtypeStruct((B, M, 1), jnp.float32)],
            interpret=interpret,
        )(idx.astype(jnp.int32), src4, scale.astype(jnp.float32), other)
    return out, dot[..., 0]


def _gather_scale_dot_jnp(src, idx, scale, other):
    B, M = idx.shape
    rows = jnp.take_along_axis(src, idx[..., None], axis=1)
    out = rows * scale[..., None].astype(src.dtype)
    dot = jnp.sum(rows.astype(jnp.float32) * other.astype(jnp.float32),
                  axis=-1)
    return out, dot


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def gather_scale_dot(src, idx, scale, other, use_pallas=True):
    """out = scale⊙src[idx]; dot = src[idx]·other — with a jnp VJP so the
    fused combine backward stays twice-differentiable (see gather_wsum)."""
    from .flash_attention import _interpret
    if use_pallas and _use_pallas_here(src):
        return gather_scale_dot_pallas(src, idx, scale, other,
                                       interpret=_interpret())
    return _gather_scale_dot_jnp(src, idx, scale, other)


def _gather_scale_dot_fwd(src, idx, scale, other, use_pallas):
    return (gather_scale_dot(src, idx, scale, other, use_pallas),
            (src, idx, scale, other))


def _gather_scale_dot_bwd(use_pallas, res, cots):
    import numpy as np
    src, idx, scale, other = res
    d_out, d_dot = cots
    B, N, D = src.shape
    rows = jnp.take_along_axis(src, idx[..., None], axis=1)  # [B, M, D]
    contrib = (d_out.astype(jnp.float32)
               * scale[..., None].astype(jnp.float32)
               + d_dot[..., None].astype(jnp.float32)
               * other.astype(jnp.float32))
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], idx.shape)
    dsrc = jnp.zeros((B, N, D), jnp.float32).at[bidx, idx].add(contrib)
    d_scale = jnp.sum(d_out.astype(jnp.float32) * rows.astype(jnp.float32),
                      axis=-1).astype(scale.dtype)
    d_other = (d_dot[..., None].astype(jnp.float32)
               * rows.astype(jnp.float32)).astype(other.dtype)
    return (dsrc.astype(src.dtype),
            np.zeros(idx.shape, jax.dtypes.float0), d_scale, d_other)


gather_scale_dot.defvjp(_gather_scale_dot_fwd, _gather_scale_dot_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def combine_wsum(eout, idx_tk, w, inv_pos, use_pallas=True):
    """Fused MoE combine: y[b,t] = sum_j w[b,t,j] * eout[b, idx_tk[b,t,j]].

    CONTRACT (ADVICE r4 item 4 — the backward depends on it): callers
    MUST pass idx_tk CLIPPED to valid range AND w PRE-ZEROED at dropped
    choices, i.e. w = where(flat >= 0, probs, 0). The backward returns
    d_w = 0 for empty/dropped slots, which is only correct under that
    pre-zeroing — calling with RAW gate probs and clipped indices
    silently produces wrong gate-prob gradients (the literal forward
    would have d_w = dy·eout[0] there). Both moe_block branches honor
    this; see w_tk construction in nlp/moe.py.

    idx_tk [B, T, k]: pre-clipped slot id per (token, choice); w [B, T,
    k] f32 gate probs with 0 at dropped choices. inv_pos [B, M] is the
    inverse map (flat (t*k+j) position filling each slot, -1 = empty),
    consumed by the backward only."""
    return gather_wsum(eout, idx_tk, w, use_pallas=use_pallas)


def _combine_wsum_fwd(eout, idx_tk, w, inv_pos, use_pallas):
    return (combine_wsum(eout, idx_tk, w, inv_pos, use_pallas),
            (eout, idx_tk, w, inv_pos))


def _combine_wsum_bwd(use_pallas, res, dy):
    import numpy as np
    eout, idx_tk, w, inv_pos = res
    B, T, k = idx_tk.shape
    M = inv_pos.shape[1]
    # per-slot scale = the gate prob of the (token, choice) filling it
    w_slot = jnp.where(
        inv_pos >= 0,
        jnp.take_along_axis(w.reshape(B, T * k),
                            jnp.clip(inv_pos, 0), axis=1), 0.0)
    safe_inv = jnp.where(inv_pos >= 0, inv_pos // k, 0)
    d_eout, dot = gather_scale_dot(dy, safe_inv, w_slot, eout,
                                   use_pallas=use_pallas)
    # d_w[t,j] = dy[t] · eout[slot(t,j)] — route the per-slot dot back to
    # (t, j) positions through the forward map (scalar gather)
    dp_flat = jnp.zeros((B, T * k + 1), jnp.float32)
    pos = jnp.where(inv_pos >= 0, inv_pos, T * k)
    dp_flat = jax.vmap(lambda d, p, v: d.at[p].set(v, mode="drop"))(
        dp_flat, pos, dot)
    d_w = dp_flat[:, :T * k].reshape(B, T, k)
    return (d_eout, np.zeros(idx_tk.shape, jax.dtypes.float0), d_w,
            np.zeros(inv_pos.shape, jax.dtypes.float0))


combine_wsum.defvjp(_combine_wsum_fwd, _combine_wsum_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def combine_gather(eout, flat, inv_pos, use_pallas=True):
    """MoE combine: eout [B, E*C, D]; flat [B, S*k] (slot id per (token,
    choice), -1 = dropped) → got [B, S*k, D].

    inv_pos [B, E*C] ((s*k + j) position filling each slot, -1 = empty)
    is the inverse map for the gradient: d_eout[m] = d_got[inv_pos[m]] —
    exact because at most one (token, choice) reads each slot."""
    return gather_rows(eout, flat, use_pallas=use_pallas)


def _combine_fwd(eout, flat, inv_pos, use_pallas):
    return combine_gather(eout, flat, inv_pos, use_pallas), inv_pos


def _combine_bwd(use_pallas, inv_pos, g):
    import numpy as np
    B, M = inv_pos.shape
    de = gather_rows(g, inv_pos, use_pallas=use_pallas)    # [B, E*C, D]
    return (de, np.zeros((B, g.shape[1]), jax.dtypes.float0),
            np.zeros(inv_pos.shape, jax.dtypes.float0))


combine_gather.defvjp(_combine_fwd, _combine_bwd)
