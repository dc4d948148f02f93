"""Blockwise-quantized Adam state: 8-bit moments, optax-compatible.

Reference analog: the memory-saving optimizer variants in
paddle.incubate.optimizer / PaddleNLP's quantization-aware AdamW recipes
(upstream-canonical, unverified — SURVEY.md §0); technique per the public
8-bit-optimizer literature (blockwise dynamic scaling).

TPU-native rationale: a single v5e chip holds 16GB. AdamW's f32 moments
cost 8 bytes/param — the round-1 bench capped at ~0.5B params because
state, not compute, filled HBM (VERDICT item 6). Storing m (and v in
sqrt-space) as float8_e4m3 codes with one f32 scale per 256-value block
(overhead 1/64) cuts state to ~2 bytes/param and puts a 2B-param Llama
on one chip. Quantize/dequantize is elementwise and fuses into the update
— invisible next to the matmuls.

Numerics: float8_e4m3 codes with one f32 scale per block — the float
exponent gives ~5 orders of dynamic range inside a block (linear int8
codes underflow small v entries to zero there, and m/(sqrt(v)+eps)
explodes); the loss trajectory tracks f32 AdamW closely (tests assert it).
The multi-chip path needs none of this: ZeRO ('sharding' axis) divides
f32 state across chips instead.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from ..kernels.naming import named_jit

BLOCK = 256
# leaves under this many elements keep float32 moments (bitsandbytes'
# min_8bit_size): a gain of three scalars or a bias of 24 shares ONE block
# scale, so a float8 code's 6% step lands whole on what the optimizer
# applies, and the bytes saved are nothing
MIN_QUANT = 4096

# blocks per lax.map chunk: 65536 * 256 = 16M params; each chunk holds
# ~4 f32 transients of that size before XLA fusion (g, dequant m, dequant
# v, upd) ≈ 256MB peak — the dequant/update/requant stream never
# materializes a full-leaf f32 moment (a 2B model's stacked [L, F, D]
# leaf would be ~2GB and blow the single-chip HBM budget), while chunks
# stay large enough that the serial lax.map adds negligible launches
# (the old 2M-param chunks cost ~195 launches on the big leaf)
CHUNK_BLOCKS = 65536


class _QTensor(NamedTuple):
    """Blockwise-quantized tensor: float8_e4m3 codes [nb, BLOCK] + f32
    scale [nb, 1] (x ≈ codes * scale). The second moment is stored in
    sqrt-space (codes of sqrt(v)/scale), doubling its effective exponent
    range."""
    codes: jax.Array
    scale: jax.Array


F8 = jnp.float8_e4m3fn
# e4m3 max finite value — normalize block maxima to this so the codes use
# the full exponent range
F8_MAX = 448.0


def _pad_len(n: int) -> int:
    return (-n) % BLOCK


def _q_blocks(blocks: jax.Array, sqrt_space: bool) -> _QTensor:
    """blocks [c, BLOCK] f32 → f8 codes + per-block scale."""
    if sqrt_space:
        blocks = jnp.sqrt(blocks)
    amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return _QTensor((blocks / scale).astype(F8), scale)


def _dq_blocks(q: _QTensor, sqrt_space: bool) -> jax.Array:
    blocks = q.codes.astype(jnp.float32) * q.scale
    return blocks * blocks if sqrt_space else blocks


def _quantize(x: jax.Array, sqrt_space: bool) -> _QTensor:
    flat = x.astype(jnp.float32).reshape(-1)
    flat = jnp.pad(flat, (0, _pad_len(flat.size)))
    return _q_blocks(flat.reshape(-1, BLOCK), sqrt_space)


def _dequantize(q, shape, sqrt_space: bool) -> jax.Array:
    if not isinstance(q, _QTensor):     # a small leaf's float32 moment
        return q.reshape(shape)
    blocks = _dq_blocks(q, sqrt_space)
    n = 1
    for s in shape:
        n *= s
    return blocks.reshape(-1)[:n].reshape(shape)


def _global_norm_scale(grads, clip_norm):
    """Streamed ClipGradByGlobalNorm factor: min(1, clip/(norm + 1e-6)) —
    the single source for both the chunked update and the fused apply."""
    if clip_norm is None:
        return jnp.float32(1.0)
    gnorm = jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree.leaves(grads)))
    return jnp.minimum(1.0, clip_norm / (gnorm + 1e-6))


class ScaleByAdamQState(NamedTuple):
    count: jax.Array
    m: Any   # pytree of _QTensor
    v: Any   # pytree of _QTensor


def _plain_moments(g, m, v, gscale, bc1, bc2, b1, b2, eps):
    """Adam's moments and bias-corrected update of one small leaf whose
    moments are float32 arrays (v in linear space): (update, m', v')."""
    g = g.astype(jnp.float32) * gscale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    return (m / bc1) / (jnp.sqrt(v / bc2) + eps), m, v


def scale_by_adam_q(b1: float = 0.9, b2: float = 0.999,
                    eps: float = 1e-8, clip_norm: Optional[float] = None
                    ) -> optax.GradientTransformation:
    """optax scale_by_adam with 8-bit blockwise state (f8 codes + block
    scales; v stored in sqrt-space).

    clip_norm: STREAMED clip-by-global-norm fused into the update
    (VERDICT r2 weak 5 / next 7): pass 1 reduces sum-of-squares per leaf
    to scalars (XLA fuses the square into the reduction — no second grad
    tree); the clip factor then multiplies each chunk INSIDE the existing
    lax.map stream, so peak memory is identical to the unclipped path —
    unlike optax.clip_by_global_norm, whose scaled output tree is a full
    extra grad copy (~4GB at 2B params, the difference between fitting
    and OOM on one 16GB chip). Semantics match ClipGradByGlobalNorm:
    scale = min(1, clip / (norm + 1e-6))."""

    def init(params):
        # zero state needs no data-dependent quantization — build the code
        # blocks directly (quantizing a materialized f32 zero tree would
        # cost ~2 full-leaf f32 transients per moment, the very peak the
        # chunked update path exists to avoid)
        def zero_q(p):
            if p.size < MIN_QUANT:
                return jnp.zeros(p.shape, jnp.float32)
            nb = (p.size + BLOCK - 1) // BLOCK
            return _QTensor(jnp.zeros((nb, BLOCK), F8),
                            jnp.full((nb, 1), 1e-30 / F8_MAX, jnp.float32))

        return ScaleByAdamQState(jnp.zeros((), jnp.int32),
                                 jax.tree.map(zero_q, params),
                                 jax.tree.map(zero_q, params))

    def update(grads, state, params=None):
        chunk_blocks = CHUNK_BLOCKS
        count = state.count + 1
        bc1 = 1.0 - b1 ** count.astype(jnp.float32)
        bc2 = 1.0 - b2 ** count.astype(jnp.float32)

        gscale = _global_norm_scale(grads, clip_norm)

        def blockwise(gb, mq, vq):
            """One chunk: gb [c, BLOCK] in the grad dtype (cast to f32 HERE
            so the lax.map stream never materializes a full-leaf f32 copy —
            the old pre-cast cost two extra full-leaf HBM passes); mq/vq
            _QTensor over [c] blocks. The update leaves in the grad dtype
            for the same reason (the f32 math stays inside the chunk)."""
            out_dt = gb.dtype if gb.dtype != jnp.float64 else jnp.float32
            gb = gb.astype(jnp.float32) * gscale
            m = b1 * _dq_blocks(mq, False) + (1 - b1) * gb
            v = b2 * _dq_blocks(vq, True) + (1 - b2) * gb * gb
            upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            return upd.astype(out_dt), _q_blocks(m, False), _q_blocks(v, True)

        def leaf(g, mq, vq):
            if not isinstance(mq, _QTensor):        # a small leaf: float32
                upd, m, v = _plain_moments(g, mq, vq, gscale, bc1, bc2,
                                           b1, b2, eps)
                return upd.astype(g.dtype), m, v
            nb = mq.codes.shape[0]
            gf = jnp.pad(g.reshape(-1),
                         (0, _pad_len(g.size))).reshape(nb, BLOCK)
            if nb <= chunk_blocks:
                upd, new_m, new_v = blockwise(gf, mq, vq)
            else:
                # pad the block axis to whole chunks, stream with lax.map
                k = -(-nb // chunk_blocks)
                pad = k * chunk_blocks - nb

                def padb(x):
                    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)
                                   ).reshape((k, chunk_blocks) + x.shape[1:])

                upd, new_m, new_v = jax.lax.map(
                    lambda c: blockwise(*c),
                    (padb(gf), _QTensor(padb(mq.codes), padb(mq.scale)),
                     _QTensor(padb(vq.codes), padb(vq.scale))))
                upd = upd.reshape(-1, BLOCK)[:nb]
                new_m = _QTensor(new_m.codes.reshape(-1, BLOCK)[:nb],
                                 new_m.scale.reshape(-1, 1)[:nb])
                new_v = _QTensor(new_v.codes.reshape(-1, BLOCK)[:nb],
                                 new_v.scale.reshape(-1, 1)[:nb])
            upd = upd.reshape(-1)[:g.size].reshape(g.shape).astype(g.dtype)
            return upd, new_m, new_v

        flat_g, treedef = jax.tree.flatten(grads)
        flat_m = treedef.flatten_up_to(state.m)
        flat_v = treedef.flatten_up_to(state.v)
        out = [leaf(g, m, v) for g, m, v in zip(flat_g, flat_m, flat_v)]
        updates = treedef.unflatten([o[0] for o in out])
        new_m = treedef.unflatten([o[1] for o in out])
        new_v = treedef.unflatten([o[2] for o in out])
        return updates, ScaleByAdamQState(count, new_m, new_v)

    return optax.GradientTransformation(init, update)


def adamw_q(learning_rate, b1: float = 0.9, b2: float = 0.999,
            eps: float = 1e-8, weight_decay: float = 0.0,
            clip_norm: Optional[float] = None
            ) -> optax.GradientTransformation:
    """AdamW with 8-bit moments — drop-in for optax.adamw where optimizer
    state must fit alongside the params (single-chip flagship bench).
    clip_norm streams clip-by-global-norm through the chunked update (no
    second grad tree — see scale_by_adam_q)."""
    return optax.chain(
        scale_by_adam_q(b1, b2, eps, clip_norm=clip_norm),
        optax.add_decayed_weights(weight_decay),
        optax.scale_by_learning_rate(learning_rate),
    )


# ---------------------------------------------------------------------------
# Fused single-pass AdamW-8bit (Pallas). The optax chain above makes ~5
# full-tree HBM passes per step (adam update tree, decayed-weights pass,
# lr pass, apply_updates pass, plus the serialized lax.map chunk streams —
# the round-4 xplane profile of the config-4 bench shows ~170-270 ms of
# serialized optimizer DMA per step). One Pallas kernel reads g/p/m8/v8 and
# writes p'/m8'/v8' in a single pipelined pass: ~10 bytes/param of traffic,
# HBM-bound (~30 ms at 1.6B params).
# ---------------------------------------------------------------------------

_FUSED_ROWS = 512    # block rows (x BLOCK lanes) per grid step: 128K params
# (bm=2048 put ~24MB of f32 temporaries on the scoped-VMEM stack, over the
# 16MB limit; 512 keeps the kernel ~6MB with the DMA chunks still 256KB)


def _fused_adamw_kernel(sc_ref, g_ref, p_ref, mc_ref, ms_ref, vc_ref,
                        vs_ref, po_ref, mco_ref, mso_ref, vco_ref, vso_ref,
                        *, b1, b2, eps, wd):
    """One row-chunk of the fused update. sc = [gscale, lr, bc1, bc2] in
    SMEM; moments decode/requant and the AdamW param update all happen in
    one VPU pass over the chunk."""
    # the kernel is VPU-bound (~25 elementwise ops/param) — per-element
    # divides cost ~7x a multiply, so every div below is either hoisted to
    # a scalar or turned into a per-ROW reciprocal broadcast; the two
    # sqrt(v)-family values share one sqrt
    gscale, lr, bc1, bc2 = sc_ref[0], sc_ref[1], sc_ref[2], sc_ref[3]
    inv_bc1 = 1.0 / bc1
    rs_bc2 = jax.lax.rsqrt(bc2)
    g = g_ref[...].astype(jnp.float32) * gscale
    m = b1 * (mc_ref[...].astype(jnp.float32) * ms_ref[...]) + (1 - b1) * g
    sv = vc_ref[...].astype(jnp.float32) * vs_ref[...]
    v = b2 * sv * sv + (1 - b2) * g * g
    sq = jnp.sqrt(v)
    upd = (m * inv_bc1) / (sq * rs_bc2 + eps)
    p = p_ref[...].astype(jnp.float32)
    po_ref[...] = (p * (1.0 - lr * wd) - lr * upd).astype(po_ref.dtype)
    amax = jnp.maximum(jnp.max(jnp.abs(m), axis=1, keepdims=True), 1e-30)
    mco_ref[...] = (m * (F8_MAX / amax)).astype(F8)
    mso_ref[...] = amax * (1.0 / F8_MAX)
    amax = jnp.maximum(jnp.max(sq, axis=1, keepdims=True), 1e-30)
    vco_ref[...] = (sq * (F8_MAX / amax)).astype(F8)
    vso_ref[...] = amax * (1.0 / F8_MAX)


@named_jit("adam8bit_update",
           static_argnames=("b1", "b2", "eps", "wd", "interpret"))
def _fused_leaf_update(scalars, g, p, mq, vq, *, b1, b2, eps, wd,
                       interpret=False):
    """Run the fused kernel over one leaf. g/p keep their shapes (flatten
    is a bitcast for the contiguous [.., BLOCK]-divisible leaves this
    optimizer stores); returns (p', m', v')."""
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb = mq.codes.shape[0]
    gf = g.reshape(-1)
    if gf.size != nb * BLOCK:
        gf = jnp.pad(gf, (0, nb * BLOCK - gf.size))
    gf = gf.reshape(nb, BLOCK)
    pf = p.reshape(-1)
    if pf.size != nb * BLOCK:
        pf = jnp.pad(pf, (0, nb * BLOCK - pf.size))
    pf = pf.reshape(nb, BLOCK)

    bm = min(_FUSED_ROWS, nb)
    grid = (-(-nb // bm),)
    row = lambda i: (i, 0)  # noqa: E731
    with jax.enable_x64(False):
        po, mc, ms, vc, vs = pl.pallas_call(
            functools.partial(_fused_adamw_kernel, b1=b1, b2=b2, eps=eps,
                              wd=wd),
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((bm, BLOCK), row),
                pl.BlockSpec((bm, BLOCK), row),
                pl.BlockSpec((bm, BLOCK), row),
                pl.BlockSpec((bm, 1), row),
                pl.BlockSpec((bm, BLOCK), row),
                pl.BlockSpec((bm, 1), row),
            ],
            out_specs=[
                pl.BlockSpec((bm, BLOCK), row),
                pl.BlockSpec((bm, BLOCK), row),
                pl.BlockSpec((bm, 1), row),
                pl.BlockSpec((bm, BLOCK), row),
                pl.BlockSpec((bm, 1), row),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((nb, BLOCK), p.dtype),
                jax.ShapeDtypeStruct((nb, BLOCK), F8),
                jax.ShapeDtypeStruct((nb, 1), jnp.float32),
                jax.ShapeDtypeStruct((nb, BLOCK), F8),
                jax.ShapeDtypeStruct((nb, 1), jnp.float32),
            ],
            input_output_aliases={2: 0, 3: 1, 4: 2, 5: 3, 6: 4},
            interpret=interpret,
            name="adam8bit_update",
        )(scalars, gf, pf, mq.codes, mq.scale, vq.codes, vq.scale)
    pnew = po.reshape(-1)[:p.size].reshape(p.shape)
    return pnew, _QTensor(mc, ms), _QTensor(vc, vs)


class FusedTransformation(NamedTuple):
    """optax.GradientTransformation plus a fused param-updating apply —
    duck-type compatible everywhere a (init, update) pair is expected."""
    init: Any
    update: Any
    apply_fused: Any


def adamw_q_fused(learning_rate, b1: float = 0.9, b2: float = 0.999,
                  eps: float = 1e-8, weight_decay: float = 0.0,
                  clip_norm: Optional[float] = None) -> FusedTransformation:
    """Single-transform AdamW-8bit: state is one ScaleByAdamQState (no
    chain tuple). `update` keeps the pure-jnp chunked stream (GSPMD-able,
    used under a mesh / in tests); `apply_fused(grads, state, params)`
    runs the one-pass Pallas kernel and returns (new_params, new_state)
    directly — the single-chip training benches call this. learning_rate
    may be a float or an optax schedule of the step count."""
    sched = (learning_rate if callable(learning_rate)
             else (lambda _: learning_rate))
    inner = scale_by_adam_q(b1, b2, eps, clip_norm=clip_norm)

    def init(params):
        return inner.init(params)

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("adamw_q_fused.update needs params (AdamW)")
        # lr/wd folded into the update tree so apply_updates is the only
        # remaining pass (legacy path; apply_fused skips even that)
        upd, new_state = inner.update(grads, state, params)
        lr = sched(state.count)
        out = jax.tree.map(
            lambda u, p: (-lr * (u.astype(jnp.float32)
                                 + weight_decay * p.astype(jnp.float32))
                          ).astype(u.dtype), upd, params)
        return out, new_state

    def apply_fused(grads, state, params):
        from ..kernels.flash_attention import _interpret, _use_pallas
        probe = jax.tree.leaves(params)[0]
        interpret = _interpret()
        if not (_use_pallas(probe) or interpret):
            upd, new_state = update(grads, state, params)
            return optax.apply_updates(params, upd), new_state
        count = state.count + 1
        bc1 = 1.0 - b1 ** count.astype(jnp.float32)
        bc2 = 1.0 - b2 ** count.astype(jnp.float32)
        lr = jnp.asarray(sched(state.count), jnp.float32)
        gscale = _global_norm_scale(grads, clip_norm)
        scalars = jnp.stack([gscale, lr, bc1, bc2])

        flat_g, treedef = jax.tree.flatten(grads)
        flat_p = treedef.flatten_up_to(params)
        flat_m = treedef.flatten_up_to(state.m)
        flat_v = treedef.flatten_up_to(state.v)

        def small(g, p, m, v):
            upd, m, v = _plain_moments(g, m, v, gscale, bc1, bc2, b1, b2,
                                       eps)
            p32 = p.astype(jnp.float32)
            return ((p32 * (1.0 - lr * weight_decay) - lr * upd
                     ).astype(p.dtype), m, v)

        out = [_fused_leaf_update(scalars, g, p, mq, vq, b1=b1, b2=b2,
                                  eps=eps, wd=weight_decay,
                                  interpret=interpret)
               if isinstance(mq, _QTensor) else small(g, p, mq, vq)
               for g, p, mq, vq in zip(flat_g, flat_p, flat_m, flat_v)]
        new_params = treedef.unflatten([o[0] for o in out])
        new_m = treedef.unflatten([o[1] for o in out])
        new_v = treedef.unflatten([o[2] for o in out])
        return new_params, ScaleByAdamQState(count, new_m, new_v)

    return FusedTransformation(init, update, apply_fused)
