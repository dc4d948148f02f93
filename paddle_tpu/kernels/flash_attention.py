"""Flash attention: jnp reference + Pallas TPU kernel.

Reference analog: paddle/phi/kernels/fusion flash_attn_kernel wrapping
third_party/flashattn (upstream-canonical, unverified — SURVEY.md §0).
TPU-native design: a Pallas splash-style blocked-softmax kernel (online
softmax over KV blocks held in VMEM) with a custom VJP; the jnp reference
path is exact softmax(QK^T)V used on CPU and in tests. Layout is
[batch, seq, heads, head_dim] (paddle flash_attention layout).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def mha_ref(q, k, v, *, causal=False, bias=None, scale=None, mask=None):
    """Exact attention reference. q,k,v: [B, S, H, D] → [B, S, H, D].
    Supports GQA: k/v may have fewer heads (H % Hkv == 0)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    hq, hkv = q.shape[2], k.shape[2]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if bias is not None:
        logits = logits + bias
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        cm = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(cm[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel (forward). Grid: (batch*heads, q_blocks); the kernel
# streams KV blocks with an online-softmax accumulator in VMEM scratch.
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(off_ref, *refs, block_k, causal, scale, seq_k,
                      masked=False):
    from jax.experimental import pallas as pl

    # q_ref: [1, block_q, d]; k_ref/v_ref: [1, seq_k, d]; o_ref: [1, block_q, d]
    # off_ref: [1, 1] int32 — the causal-diagonal offset: position iq of this
    # call's q range attends to k positions ik <= iq + off. off = sk - sq is
    # the bottom-right alignment (mha_ref's tril k=sk-sq); ring attention
    # passes (my_idx - kv_idx) * sq, so off < 0 == fully-masked block (the
    # kv loop then runs ZERO iterations) and off >= sq == no mask.
    # masked: a [1, 1, seq_k] int32 key-padding mask ref precedes q_ref
    # (nonzero = key visible) — the bidirectional-encoder path (VERDICT r4
    # next-1: ERNIE needs flash with padding masks, upstream-canonical
    # flash_attn_kernel's padded/varlen mode).
    # int() coercion matters: np.int64 shape entries poison Mosaic's index
    # arithmetic (i32*i64 muli) and dtype-conversion lowering
    if masked:
        mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    else:
        mask_ref = None
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    block_q, d = int(q_ref.shape[1]), int(q_ref.shape[2])
    q = q_ref[0].astype(jnp.float32) * scale
    q_idx = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    qblk = pl.program_id(1)
    q_offset = qblk * block_q
    off = off_ref[0, 0] if causal else 0

    def body(kb, carry):
        m_prev, l_prev, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)  # [bq, bk]
        vis = None
        if causal:
            k_idx = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + kb * block_k
            vis = (q_idx + q_offset + off) >= k_idx
        if masked:
            m_blk = (mask_ref[0, 0, pl.ds(kb * block_k, block_k)] != 0)
            m2 = jnp.broadcast_to(m_blk[None, :], (block_q, block_k))
            vis = m2 if vis is None else (vis & m2)
        if vis is not None:
            s = jnp.where(vis, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        if vis is not None:
            # fully-masked rows have m_cur == NEG_INF, where exp(s - m) == 1
            # for every masked entry — re-mask so l stays 0 and lse == -inf
            p = jnp.where(vis, p, 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jnp.dot(p, v_blk, preferred_element_type=jnp.float32)
        return m_cur, l_cur, acc

    n_kb = seq_k // block_k
    if causal:
        # only blocks up to the (offset) diagonal contribute
        last = (q_offset + block_q + off + block_k - 1) // block_k
        n_iter = jnp.clip(last, 0, n_kb)
    else:
        n_iter = n_kb
    m0 = jnp.full((block_q,), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((block_q,), dtype=jnp.float32)
    a0 = jnp.zeros((block_q, d), dtype=jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_iter, body, (m0, l0, a0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # log-sum-exp residual for the flash backward (softmax re-derivable as
    # exp(s - lse) without the O(S^2) probs tensor). Kept [.., 1]-shaped:
    # TPU block tiling wants >=2 trailing dims.
    lse_ref[0] = (m + jnp.log(l_safe))[:, None]


def _fit_block(block: int, s: int) -> int:
    """Largest power-of-two-halving of `block` that divides s (s is always
    a multiple of 128 here). 512 blocks measure ~2pt MFU over 256 on the
    2B v5e bench, but 256-multiples like 768 still need a 256 grid."""
    block = min(block, s)
    while s % block:
        block //= 2
    return block


def _to_folded(x, layout):
    """[B,S,H,D] ('bshd') or [B,H,S,D] ('bhsd') → [B*H, S, D]. The bhsd
    fold is a FREE reshape (adjacent dims, row-major): callers that keep
    activations head-major (einsum-form attention, nlp/ernie.py) skip the
    [B,S,H,D]→[B,H,S,D] relayout copies that the r5 ERNIE xplane measured
    at ~76 ms/step around the flash custom-calls."""
    if layout == "bhsd":
        b, h, s, d = x.shape
        return x.reshape(b * h, s, d)
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_folded(x, b, h, layout):
    out = x.reshape(b, h, x.shape[1], x.shape[2])
    if layout == "bhsd":
        return out
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret",
                                             "return_lse", "layout"))
def flash_attention_pallas(q, k, v, causal=False, scale=None, offset=None,
                           block_q=None, block_k=None, interpret=False,
                           return_lse=False, key_mask=None, layout="bshd"):
    """q,k,v: [B, S, H, D] (layout='bshd', default) or [B, H, S, D]
    (layout='bhsd'); equal heads — GQA expanded by caller.

    offset: causal-diagonal offset (int or traced int32 scalar). Position
    iq attends to ik <= iq + offset. None = sk - sq, the bottom-right
    alignment matching mha_ref's rectangular causal mask; ring attention
    passes (my_idx - kv_idx) * sq per KV block. Ignored unless causal.

    key_mask: optional [B, Sk] bool/int key-padding mask (nonzero = key
    visible to every query) — the bidirectional-encoder path. Rows whose
    keys are ALL masked return 0 (not mha_ref's uniform attention).

    block_q/block_k default to 512: isolated kernel timings prefer 1024
    at head_dim 128 (59% vs 29% of peak), but inside a full train step
    the 1024 blocks measure ~13% SLOWER than 512 (49.7 vs 43.9 ms/step
    on the 12-layer MoE bench) — scheduling/HBM context beats the
    microbenchmark, so the in-situ number wins.

    Traced with x64 disabled: the framework enables jax_enable_x64 globally
    (paddle dtype parity), but 64-bit index arithmetic is untileable for
    Mosaic (i64->f32 casts recurse in its lowering).
    """
    h_ax = 1 if layout == "bhsd" else 2
    s_ax = 2 if layout == "bhsd" else 1
    if layout == "bhsd":
        b, h, sq, d = q.shape
    else:
        b, sq, h, d = q.shape
    hkv, sk = k.shape[h_ax], k.shape[s_ax]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if offset is None:
        offset = sk - sq
    block_q = _fit_block(block_q or 512, sq)
    block_k = _fit_block(block_k or 512, sk)
    # fold batch*heads into the grid's first dim. GQA: k/v may arrive
    # with FEWER heads (h % hkv == 0) — the kernel maps each q head to
    # its kv group via the BlockSpec index_map, so the expanded K/V
    # (jnp.repeat — ~31 ms/step of copies on the r5 MoE profile) never
    # materializes.
    qt, kt, vt = (_to_folded(x, layout) for x in (q, k, v))
    grid = (b * h, sq // block_q)
    with jax.enable_x64(False):
        off = jnp.asarray(offset, jnp.int32).reshape(1, 1)
        mask = (None if key_mask is None else
                key_mask.astype(jnp.int32).reshape(b, 1, sk))
        out, lse = _fwd_call(off, qt, kt, vt, grid, block_q, block_k, causal,
                             scale, sk, b, h, sq, d, q.dtype, interpret,
                             mask, hkv)
    out = _from_folded(out, b, h, layout)
    if return_lse:
        return out, lse.reshape(b, h, sq)
    return out


def _fwd_call(off, qt, kt, vt, grid, block_q, block_k, causal, scale, sk, b,
              h, sq, d, out_dtype, interpret, mask=None, hkv=None):
    from jax.experimental import pallas as pl

    hkv = h if hkv is None else hkv
    rep = h // hkv

    def kv_ix(bh, qb):
        # q head (bh % h) reads kv head (bh % h) // rep of batch bh // h
        return ((bh // h) * hkv + (bh % h) // rep, 0, 0)

    in_specs = [pl.BlockSpec((1, 1), lambda bh, qb: (0, 0))]
    operands = [off]
    if mask is not None:
        # per-BATCH mask (shared across this batch row's h heads)
        in_specs.append(pl.BlockSpec((1, 1, sk),
                                     lambda bh, qb: (bh // h, 0, 0)))
        operands.append(mask)
    in_specs += [
        pl.BlockSpec((1, block_q, d), lambda bh, qb: (bh, qb, 0)),
        pl.BlockSpec((1, sk, d), kv_ix),
        pl.BlockSpec((1, sk, d), kv_ix),
    ]
    operands += [qt, kt, vt]
    return pl.pallas_call(
        functools.partial(_flash_fwd_kernel, block_k=block_k, causal=causal,
                          scale=scale, seq_k=sk, masked=mask is not None),
        out_shape=[jax.ShapeDtypeStruct((b * h, sq, d), out_dtype),
                   jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32)],
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, block_q, d), lambda bh, qb: (bh, qb, 0)),
                   pl.BlockSpec((1, block_q, 1), lambda bh, qb: (bh, qb, 0))],
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Pallas TPU kernels (backward). Standard flash backward: softmax re-derived
# per block from the LSE residual; D = rowsum(dO*O). Two formulations, both
# atomics-free:
#   RESIDENT (seq <= _RESIDENT_MAX_SEQ): the counterpart tensor stays in a
#   full-seq VMEM window and an in-kernel fori_loop streams blocks with a
#   DYNAMIC trip count — causal blocks past the diagonal cost zero
#   iterations. Fastest at the common 2k training length, but the windows
#   hit Mosaic's 16MB scoped-vmem stack limit from seq 4096 up (measured:
#   the 2B model at seq 4096 batch 4 fails to compile resident, compiles
#   and runs streamed).
#   STREAMED (longer): primary path is the COMBINED (bh, kb, qb) kernel —
#   block operands only, except a seq-scaling full-seq f32 dq accumulator
#   (+ the dq output block); when those exceed the scoped-VMEM budget
#   (seq ~16k+ at d=128) it falls back to the SPLIT kernels — dq over
#   (bh, qb, kb), dk/dv over (bh, kb, qb) — where truly nothing is
#   full-sequence. Causal invisibility is a pl.when compute skip (the
#   block DMA still runs, ~1pt MFU at 2k — why the resident path is
#   kept).
# ---------------------------------------------------------------------------

_RESIDENT_MAX_SEQ = 2048


def _flash_bwd_combined_kernel_res(off_ref, *refs, block_q, causal,
                                   scale, seq_q, masked=False, rep=1):
    """Combined resident backward: one pass over (bkv, kv-block) produces
    dk/dv for this block AND accumulates dq into a full-seq f32 scratch
    (flushed at the last kv block). The separate dq/dkv kernels each
    recomputed s, p and dp — 7 block matmuls where 5 suffice; sharing
    them cuts the resident backward's MXU work by ~2/7.

    masked: a [1, 1, block_k] int32 key-padding-mask ref (this kv block's
    slice) precedes q_ref; p is re-masked so masked keys contribute to no
    gradient (matches the fwd kernel's masked path).

    rep (r5): GQA-NATIVE — the grid's first dim runs over KV heads and
    each program handles its group of `rep` consecutive q heads (q/do/
    lse/dcap/dq blocks are [rep, sq, ·]); dk/dv accumulate across the
    group IN the kernel, so the expanded K/V and the post-hoc
    group-reduction of dk/dv never materialize."""
    from jax.experimental import pallas as pl

    if masked:
        (mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
         dq_ref, dk_ref, dv_ref, dq_acc) = refs
    else:
        mask_ref = None
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
         dq_ref, dk_ref, dv_ref, dq_acc) = refs
    block_k, d = int(k_ref.shape[1]), int(k_ref.shape[2])
    kb = pl.program_id(1)
    n_kb = pl.num_programs(1)
    k_blk = k_ref[0].astype(jnp.float32)
    v_blk = v_ref[0].astype(jnp.float32)
    k_offset = kb * block_k
    k_idx = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    off = off_ref[0, 0] if causal else 0

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def body_r(r, qb, carry):
        dk, dv = carry
        q = q_ref[r, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[r, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[r, pl.ds(qb * block_q, block_q), 0]
        dcap = dcap_ref[r, pl.ds(qb * block_q, block_q), 0]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse[:, None])
        if causal:
            q_idx = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + qb * block_q
            p = jnp.where((q_idx + off) >= (k_idx + k_offset), p, 0.0)
        if masked:
            m_blk = (mask_ref[0, 0, :] != 0)
            p = jnp.where(jnp.broadcast_to(m_blk[None, :],
                                           (block_q, block_k)), p, 0.0)
        dv = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dcap[:, None]) * scale
        dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        dq_acc[r, pl.ds(qb * block_q, block_q), :] += jnp.dot(
            ds, k_blk, preferred_element_type=jnp.float32)
        return dk, dv

    n_qb = seq_q // block_q
    if causal:
        # q blocks fully before this kv block's (offset) diagonal touch
        # neither dk/dv nor dq-from-this-kb
        start = jnp.clip((k_offset - off) // block_q, 0, n_qb)
    else:
        start = 0
    dk = jnp.zeros((block_k, d), jnp.float32)
    dv = jnp.zeros((block_k, d), jnp.float32)
    for r in range(rep):   # static unroll over the q-head group
        dk, dv = jax.lax.fori_loop(
            start, n_qb, functools.partial(body_r, r), (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(kb == n_kb - 1)
    def _flush():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_combined_kernel_str(off_ref, *refs, causal, scale, n_kb,
                                   n_qb, masked=False):
    """Combined STREAMED backward: grid (bh, kb, qb) with every operand a
    single block; dk/dv accumulate over the inner qb loop, dq accumulates
    into a full-seq f32 scratch across the whole (kb, qb) sub-grid and is
    flushed at the last step. Shares s/p/dp between the dq and dk/dv
    halves (7 block matmuls -> 5), like the resident combined kernel but
    with nothing full-sequence in VMEM except the dq accumulator
    (seq*d*4 bytes — the wrapper falls back to the split kernels when
    that exceeds the scoped-VMEM budget)."""
    from jax.experimental import pallas as pl

    if masked:
        (mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
         dq_ref, dk_ref, dv_ref, dq_sc, dk_acc, dv_acc) = refs
    else:
        mask_ref = None
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
         dq_ref, dk_ref, dv_ref, dq_sc, dk_acc, dv_acc) = refs
    block_k, d = int(k_ref.shape[1]), int(k_ref.shape[2])
    block_q = int(q_ref.shape[1])
    kb = pl.program_id(1)
    qb = pl.program_id(2)
    k_offset = kb * block_k
    q_offset = qb * block_q
    off = off_ref[0, 0] if causal else 0

    @pl.when((kb == 0) & (qb == 0))
    def _init_dq():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(qb == 0)
    def _init_dkv():
        dk_acc[...] = jnp.zeros((block_k, d), jnp.float32)
        dv_acc[...] = jnp.zeros((block_k, d), jnp.float32)

    visible = True
    if causal:
        # block contributes iff its LAST q row reaches this kv block:
        # row iq sees ik <= iq + off
        visible = (q_offset + block_q - 1 + off) >= k_offset

    @pl.when(visible)
    def _compute():
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        dcap = dcap_ref[0, :, 0]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse[:, None])
        if causal:
            q_idx = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + q_offset
            k_idx = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + k_offset
            # mask p, not s: fully-masked rows have lse == -inf and
            # exp(NEG_INF - lse) would be exp(0) == 1 there
            p = jnp.where((q_idx + off) >= k_idx, p, 0.0)
        if masked:
            m_blk = (mask_ref[0, 0, :] != 0)
            p = jnp.where(jnp.broadcast_to(m_blk[None, :],
                                           (block_q, block_k)), p, 0.0)
        dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dcap[:, None]) * scale
        dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        dq_sc[pl.ds(q_offset, block_q), :] += jnp.dot(
            ds, k_blk, preferred_element_type=jnp.float32)

    @pl.when(qb == n_qb - 1)
    def _flush_dkv():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((kb == n_kb - 1) & (qb == n_qb - 1))
    def _flush_dq():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


# dq scratch budget for the combined streamed kernel: seq*d*4 bytes of
# scoped VMEM (16MB limit, leave room for the block operands)
_COMBINED_STREAMED_DQ_BYTES = 12 * 1024 * 1024


def _flash_bwd_dq_kernel(off_ref, *refs, causal, scale, n_kb, masked=False):
    from jax.experimental import pallas as pl

    if masked:
        (mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
         dq_ref, acc_ref) = refs
    else:
        mask_ref = None
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
         dq_ref, acc_ref) = refs
    block_q, d = int(q_ref.shape[1]), int(q_ref.shape[2])
    block_k = int(k_ref.shape[1])
    kb = pl.program_id(2)
    q_offset = pl.program_id(1) * block_q
    k_offset = kb * block_k
    off = off_ref[0, 0] if causal else 0

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros((block_q, d), jnp.float32)

    visible = True
    if causal:
        visible = (q_offset + block_q - 1 + off) >= k_offset

    @pl.when(visible)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        dcap = dcap_ref[0, :, 0]
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse[:, None])
        if causal:
            q_idx = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + q_offset
            k_idx = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + k_offset
            # mask p, not s: fully-masked rows have lse == -inf and
            # exp(NEG_INF - lse) would be exp(0) == 1 there
            p = jnp.where((q_idx + off) >= k_idx, p, 0.0)
        if masked:
            m_blk = (mask_ref[0, 0, :] != 0)
            p = jnp.where(jnp.broadcast_to(m_blk[None, :],
                                           (block_q, block_k)), p, 0.0)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dcap[:, None]) * scale
        acc_ref[...] += jnp.dot(ds, k_blk,
                                preferred_element_type=jnp.float32)

    @pl.when(kb == n_kb - 1)
    def _flush():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(off_ref, *refs, causal, scale, n_qb, masked=False):
    from jax.experimental import pallas as pl

    if masked:
        (mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        mask_ref = None
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    block_k, d = int(k_ref.shape[1]), int(k_ref.shape[2])
    block_q = int(q_ref.shape[1])
    qb = pl.program_id(2)
    k_offset = pl.program_id(1) * block_k
    q_offset = qb * block_q
    off = off_ref[0, 0] if causal else 0

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros((block_k, d), jnp.float32)
        dv_acc[...] = jnp.zeros((block_k, d), jnp.float32)

    visible = True
    if causal:
        # block contributes iff its LAST q row reaches this kv block:
        # row iq sees ik <= iq + off
        visible = (q_offset + block_q - 1 + off) >= k_offset

    @pl.when(visible)
    def _compute():
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        dcap = dcap_ref[0, :, 0]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse[:, None])
        if causal:
            q_idx = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + q_offset
            k_idx = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + k_offset
            p = jnp.where((q_idx + off) >= k_idx, p, 0.0)
        if masked:
            m_blk = (mask_ref[0, 0, :] != 0)
            p = jnp.where(jnp.broadcast_to(m_blk[None, :],
                                           (block_q, block_k)), p, 0.0)
        dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dcap[:, None]) * scale
        dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(qb == n_qb - 1)
    def _flush():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret",
                                             "streamed", "layout"))
def flash_attention_pallas_bwd(q, k, v, out, lse, g, causal=False,
                               scale=None, offset=None, dlse=None,
                               block_q=512, block_k=512, interpret=False,
                               streamed=None, key_mask=None, layout="bshd"):
    """Blocked flash backward. q,k,v,out,g: [B,S,H,D] (or [B,H,S,D] with
    layout='bhsd'); lse: [B,H,S]. Returns (dq, dk, dv) with O(S) memory
    per block row, in the input layout.

    offset: causal-diagonal offset, as in flash_attention_pallas.
    dlse: optional [B,H,S] cotangent of the lse output (callers that merge
    partial-attention blocks, e.g. ring attention, differentiate through
    lse). d(lse)/d(s_ij) = p_ij, which folds into the kernels' existing
    ds = p * (dp - dcap) as dcap -> dcap - dlse.
    key_mask: optional [B, Sk] key-padding mask, as in
    flash_attention_pallas (must match what the forward used)."""
    h_ax = 1 if layout == "bhsd" else 2
    s_ax = 2 if layout == "bhsd" else 1
    if layout == "bhsd":
        b, h, sq, d = q.shape
    else:
        b, sq, h, d = q.shape
    hkv, sk = k.shape[h_ax], k.shape[s_ax]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if offset is None:
        offset = sk - sq
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    if streamed is None:  # auto: resident kernels up to the VMEM-safe seq
        streamed = max(sq, sk) > _RESIDENT_MAX_SEQ
    # GQA (r5): the resident path can run GQA-NATIVE — grid over KV heads
    # with the q-head group looped in-kernel, dk/dv accumulated across the
    # group, no expanded K/V. Verified in interpret mode and compiled at
    # sq <= 1024, but at the training shapes that matter (rep 2, sq 2048,
    # d 128) Mosaic compilation effectively hangs (>8 min vs ~90 s for
    # the expanded kernel; r5 measured) — so the gate holds it to the
    # small shapes where it compiles, and larger GQA falls back to
    # expand+reduce. Revisit if the toolchain's scheduling of the
    # rep-unrolled double loop improves.
    rep = h // hkv
    native_gqa = (hkv != h and not streamed and rep * sq * d <= 2 ** 18)
    if hkv != h and not native_gqa:
        k, v = _expand_gqa(q, k, v, layout)
    qt, kt, vt = (_to_folded(x, layout) for x in (q, k, v))
    dot = _to_folded(g, layout)
    ot = _to_folded(out, layout)
    lse_t = lse.reshape(b * h, sq, 1)
    # D_i = rowsum(dO * O) — cheap, fused by XLA
    dcap = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                   axis=-1, keepdims=True)
    if dlse is not None:
        dcap = dcap - dlse.astype(jnp.float32).reshape(b * h, sq, 1)
    with jax.enable_x64(False):  # see flash_attention_pallas docstring
        off = jnp.asarray(offset, jnp.int32).reshape(1, 1)
        mask = (None if key_mask is None else
                key_mask.astype(jnp.int32).reshape(b, 1, sk))
        dq, dk, dv = _bwd_call(
            off, qt, kt, vt, dot, lse_t, dcap, b, h, sq, sk, d,
            block_q, block_k, causal, scale, q.dtype, k.dtype,
            v.dtype, interpret, streamed, mask,
            hkv if native_gqa else None)
    h_kv_out = hkv if native_gqa else h
    dk = _from_folded(dk, b, h_kv_out, layout)
    dv = _from_folded(dv, b, h_kv_out, layout)
    if hkv != h and not native_gqa:
        dk, dv = _gqa_reduce(dk, dv, hkv, layout)
    return _from_folded(dq, b, h, layout), dk, dv


def _mask_spec(block_k, h, grid_order):
    """BlockSpec for the [B, 1, Sk] int32 key mask in the bwd kernels.
    grid_order: 'kq' — grid (bh, kb, qb); 'qk' — grid (bh, qb, kb)."""
    from jax.experimental import pallas as pl
    if grid_order == "kq":
        return pl.BlockSpec((1, 1, block_k), lambda bh, kb, qb: (bh // h, 0, kb))
    return pl.BlockSpec((1, 1, block_k), lambda bh, qb, kb: (bh // h, 0, kb))


def _bwd_call(off, qt, kt, vt, dot, lse_t, dcap, b, h, sq, sk, d, block_q,
              block_k, causal, scale, q_dtype, k_dtype, v_dtype, interpret,
              streamed, mask=None, hkv=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if not streamed:
        return _bwd_call_resident(
            off, qt, kt, vt, dot, lse_t, dcap, b, h, sq, sk, d, block_q,
            block_k, causal, scale, q_dtype, k_dtype, v_dtype, interpret,
            mask, hkv)

    n_kb = sk // block_k
    n_qb = sq // block_q
    # budget: the f32 dq scratch AND the full-seq dq output block both
    # live in VMEM and scale with seq — count both or near-budget configs
    # compile-fail instead of falling back to the split kernels
    dq_vmem = sq * d * (4 + jnp.dtype(q_dtype).itemsize)
    if dq_vmem <= _COMBINED_STREAMED_DQ_BYTES and sq == sk:
        in_specs = [pl.BlockSpec((1, 1), lambda bh, kb, qb: (0, 0))]
        operands = [off]
        if mask is not None:
            in_specs.append(_mask_spec(block_k, h, "kq"))
            operands.append(mask)
        operands += [qt, kt, vt, dot, lse_t, dcap]
        in_specs += [
            pl.BlockSpec((1, block_q, d), lambda bh, kb, qb: (bh, qb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb, qb: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb, qb: (bh, kb, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, kb, qb: (bh, qb, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, kb, qb: (bh, qb, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, kb, qb: (bh, qb, 0)),
        ]
        dq, dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_combined_kernel_str, causal=causal,
                              scale=scale, n_kb=n_kb, n_qb=n_qb,
                              masked=mask is not None),
            out_shape=[jax.ShapeDtypeStruct((b * h, sq, d), q_dtype),
                       jax.ShapeDtypeStruct((b * h, sk, d), k_dtype),
                       jax.ShapeDtypeStruct((b * h, sk, d), v_dtype)],
            grid=(b * h, n_kb, n_qb),
            in_specs=in_specs,
            out_specs=[
                # dq revisits one full-seq block per bh (flush at the end)
                pl.BlockSpec((1, sq, d), lambda bh, kb, qb: (bh, 0, 0)),
                pl.BlockSpec((1, block_k, d), lambda bh, kb, qb: (bh, kb, 0)),
                pl.BlockSpec((1, block_k, d), lambda bh, kb, qb: (bh, kb, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((sq, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
            interpret=interpret,
        )(*operands)

        return dq, dk, dv

    in_specs = [pl.BlockSpec((1, 1), lambda bh, qb, kb: (0, 0))]
    operands = [off]
    if mask is not None:
        in_specs.append(_mask_spec(block_k, h, "qk"))
        operands.append(mask)
    operands += [qt, kt, vt, dot, lse_t, dcap]
    in_specs += [
        pl.BlockSpec((1, block_q, d), lambda bh, qb, kb: (bh, qb, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qb, kb: (bh, kb, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qb, kb: (bh, kb, 0)),
        pl.BlockSpec((1, block_q, d), lambda bh, qb, kb: (bh, qb, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, qb, kb: (bh, qb, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, qb, kb: (bh, qb, 0)),
    ]
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal, scale=scale,
                          n_kb=n_kb, masked=mask is not None),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q_dtype),
        grid=(b * h, n_qb, n_kb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qb, kb: (bh, qb, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*operands)

    in_specs = [pl.BlockSpec((1, 1), lambda bh, kb, qb: (0, 0))]
    operands = [off]
    if mask is not None:
        in_specs.append(_mask_spec(block_k, h, "kq"))
        operands.append(mask)
    operands += [qt, kt, vt, dot, lse_t, dcap]
    in_specs += [
        pl.BlockSpec((1, block_q, d), lambda bh, kb, qb: (bh, qb, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, kb, qb: (bh, kb, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, kb, qb: (bh, kb, 0)),
        pl.BlockSpec((1, block_q, d), lambda bh, kb, qb: (bh, qb, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, kb, qb: (bh, qb, 0)),
        pl.BlockSpec((1, block_q, 1), lambda bh, kb, qb: (bh, qb, 0)),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal, scale=scale,
                          n_qb=n_qb, masked=mask is not None),
        out_shape=[jax.ShapeDtypeStruct((b * h, sk, d), k_dtype),
                   jax.ShapeDtypeStruct((b * h, sk, d), v_dtype)],
        grid=(b * h, n_kb, n_qb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, kb, qb: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb, qb: (bh, kb, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(*operands)

    return dq, dk, dv


def _bwd_call_resident(off, qt, kt, vt, dot, lse_t, dcap, b, h, sq, sk, d,
                       block_q, block_k, causal, scale, q_dtype, k_dtype,
                       v_dtype, interpret, mask=None, hkv=None):
    """GQA-native (r5): kt/vt come folded [b*hkv, sk, d]; the grid runs
    over KV heads, each program owning its group of rep = h//hkv q heads,
    and dk/dv come back UNEXPANDED [b*hkv, sk, d] — no jnp.repeat of K/V
    and no post-hoc group reduction."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hkv = h if hkv is None else hkv
    rep = h // hkv
    in_specs = [pl.BlockSpec((1, 1), lambda bkv, kb: (0, 0))]
    operands = [off]
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, 1, block_k),
                                     lambda bkv, kb: (bkv // hkv, 0, kb)))
        operands.append(mask)
    operands += [qt, kt, vt, dot, lse_t, dcap]
    in_specs += [
        pl.BlockSpec((rep, sq, d), lambda bkv, kb: (bkv, 0, 0)),
        pl.BlockSpec((1, block_k, d), lambda bkv, kb: (bkv, kb, 0)),
        pl.BlockSpec((1, block_k, d), lambda bkv, kb: (bkv, kb, 0)),
        pl.BlockSpec((rep, sq, d), lambda bkv, kb: (bkv, 0, 0)),
        pl.BlockSpec((rep, sq, 1), lambda bkv, kb: (bkv, 0, 0)),
        pl.BlockSpec((rep, sq, 1), lambda bkv, kb: (bkv, 0, 0)),
    ]
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_combined_kernel_res, block_q=block_q,
                          causal=causal, scale=scale, seq_q=sq,
                          masked=mask is not None, rep=rep),
        out_shape=[jax.ShapeDtypeStruct((b * h, sq, d), q_dtype),
                   jax.ShapeDtypeStruct((b * hkv, sk, d), k_dtype),
                   jax.ShapeDtypeStruct((b * hkv, sk, d), v_dtype)],
        grid=(b * hkv, sk // block_k),
        in_specs=in_specs,
        out_specs=[
            # dq revisits one group block per bkv; written at the flush
            pl.BlockSpec((rep, sq, d), lambda bkv, kb: (bkv, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bkv, kb: (bkv, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bkv, kb: (bkv, kb, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((rep, sq, d), jnp.float32)],
        interpret=interpret,
    )(*operands)

    return dq, dk, dv


def _interpret():
    from ..core.flags import flag
    return bool(flag("FLAGS_pallas_interpret"))


def _pallas_available():
    """Platform-level gate (no array to probe): True when Pallas kernels
    would engage for arrays on the default backend."""
    from ..core.flags import flag

    if not flag("FLAGS_use_pallas"):
        return False
    if flag("FLAGS_pallas_force") or _interpret():
        return True
    return jax.default_backend() not in ("cpu",)


def _use_pallas(x):
    from ..core.flags import flag

    if not flag("FLAGS_use_pallas"):
        return False
    if flag("FLAGS_pallas_force"):
        # lowering-only tests: compile the REAL Mosaic kernels while
        # lowering for platforms=('tpu',) from a CPU host (jax.export) —
        # the HLO-golden assertion that mesh paths contain the pallas
        # custom-call needs real lowering, which interpret mode replaces
        # with plain jax ops. Never set this where the program will RUN
        # on CPU.
        return True
    if _interpret():  # testing: run the kernels in interpret mode anywhere
        return True
    return _platform_of(x) != "cpu"


def _platform_of(x) -> str:
    """Platform an op on `x` compiles for: a concrete array's own
    device; a tracer (inside jit) has none and compiles for the default
    backend."""
    if isinstance(x, jax.core.Tracer):
        return jax.default_backend()
    return next(iter(x.devices())).platform


_warned_gates = set()


def _warn_gate(site: str, q, k, causal, layout):
    """Log once per call site when the SHAPE gate (`_pallas_ok`) sends a
    Pallas-enabled call down the exact path for a shape that path was
    not designed for (`_intentional_exact`) — an O(S) kernel becomes
    O(S^2) memory there, so the choice should be visible. A kernel that
    fails to lower or run raises; nothing is caught."""
    if site not in _warned_gates and _use_pallas(q) \
            and not _intentional_exact(q, k, causal, layout):
        _warned_gates.add(site)
        import logging
        logging.getLogger("paddle_tpu.kernels").warning(
            "flash attention shape gate at %s takes the exact path: "
            "q=%s k=%s causal=%s", site, q.shape, k.shape, causal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_fwd(q, k, v, causal=False, scale=None, layout="bshd"):
    """Differentiable flash attention entry. When the Pallas forward runs,
    the backward runs the blocked Pallas flash-backward kernels off the LSE
    residual (O(S) memory); otherwise both directions use the exact
    reference.

    layout='bhsd' takes/returns [B, H, S, D] tensors — callers that keep
    activations head-major (einsum-form attention) skip the relayout
    copies around the custom-call (see _to_folded)."""
    return _flash_impl(q, k, v, causal, scale, layout)


def flash_attention_sharded(q, k, v, mesh, spec, causal=True, scale=None):
    """`flash_attention_fwd` under a mesh. GSPMD refuses a Mosaic kernel
    ("Mosaic kernels cannot be automatically partitioned"), so each
    device runs the kernel on its own shard through shard_map. `spec` is
    the one PartitionSpec of q, k, v and the output ([B, S, H, hd]):
    batch and heads may split — every shard keeps whole GQA groups, so H
    and the KV head count must both divide by the head axis — sequence
    and hd stay whole. Differentiable like the kernel it wraps."""
    fn = lambda q_, k_, v_: flash_attention_fwd(  # noqa: E731
        q_, k_, v_, causal, scale)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def block_aligned(s: int) -> bool:
    """True when seq length s divides cleanly into the kernel's blocks:
    block = min(256, s), grid = s // block — so s must be a multiple of 256,
    or itself a single lane-aligned block (s <= 256, s % 128 == 0).
    Misaligned lengths no longer fall back to O(S^2): the padded wrappers
    below pad to the next aligned length and mask/slice the tail."""
    return s % 128 == 0 and (s <= 256 or s % 256 == 0)


def _pad_len(s: int) -> int:
    """Next block-aligned length >= s (multiple of 128 up to 256, of 256
    beyond)."""
    if s <= 256:
        return max(128, -(-s // 128) * 128)
    return -(-s // 256) * 256


def _pad_seq(x, s_to: int, axis: int = 1):
    """Zero-pad along the seq axis (1 for bshd tensors, 2 for bhsd)."""
    s = x.shape[axis]
    if s == s_to:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, s_to - s)
    return jnp.pad(x, pads)


def _seq_axis(layout):
    return 2 if layout == "bhsd" else 1


def flash_attention_padded(q, k, v, causal=False, scale=None,
                           return_lse=False, interpret=False,
                           key_mask=None, layout="bshd"):
    """Pad-to-block flash forward: arbitrary seq lengths keep O(S) memory
    (VERDICT r2 missing 8 — the reference's flashattn handles any length).

    Causal: q/k pad at the END and the kernel gets the UNPADDED diagonal
    offset sk - sq, so the real query rows (iq < sq) attend exactly
    ik <= iq + sk - sq < sk — padded keys are never visible to real rows;
    padded query rows produce garbage that the final slice drops.
    Non-causal: only q may need padding (padded keys would enter the
    softmax — the gate sends unaligned-k non-causal to the exact path)
    UNLESS key_mask is given: the mask pads with 0, hiding padded keys."""
    ax = _seq_axis(layout)
    sq, sk = q.shape[ax], k.shape[ax]
    sq_p, sk_p = _pad_len(sq), _pad_len(sk)
    if key_mask is not None and sk_p != sk:
        key_mask = jnp.pad(key_mask.astype(jnp.int32),
                           ((0, 0), (0, sk_p - sk)))
    if sq_p == sq and sk_p == sk:
        return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                      return_lse=return_lse,
                                      interpret=interpret,
                                      key_mask=key_mask, layout=layout)
    if not causal and sk_p != sk and key_mask is None:
        raise ValueError(
            f"non-causal flash with misaligned KV length {sk}: padded keys "
            f"would enter the softmax unmasked — use the exact path "
            f"(_pallas_ok gates this)")
    qp = _pad_seq(q, sq_p, ax)
    kp, vp = _pad_seq(k, sk_p, ax), _pad_seq(v, sk_p, ax)
    res = flash_attention_pallas(
        qp, kp, vp, causal=causal, scale=scale,
        offset=(sk - sq) if causal else None,
        return_lse=return_lse, interpret=interpret, key_mask=key_mask,
        layout=layout)
    sl = ((slice(None), slice(None), slice(None, sq)) if ax == 2
          else (slice(None), slice(None, sq)))
    if return_lse:
        out, lse = res
        return out[sl], lse[:, :, :sq]
    return res[sl]


def flash_attention_padded_bwd(q, k, v, out, lse, g, causal=False,
                               scale=None, interpret=False, key_mask=None,
                               layout="bshd"):
    """Pad-to-block flash backward. Padded query rows contribute nothing:
    their dO is zero-padded, so dp, dcap and hence ds all vanish — dk/dv
    stay exact regardless of the (finite) values padded into out/lse."""
    ax = _seq_axis(layout)
    sq, sk = q.shape[ax], k.shape[ax]
    sq_p, sk_p = _pad_len(sq), _pad_len(sk)
    if key_mask is not None and sk_p != sk:
        key_mask = jnp.pad(key_mask.astype(jnp.int32),
                           ((0, 0), (0, sk_p - sk)))
    if sq_p == sq and sk_p == sk:
        return flash_attention_pallas_bwd(q, k, v, out, lse, g,
                                          causal=causal, scale=scale,
                                          interpret=interpret,
                                          key_mask=key_mask, layout=layout)
    dq, dk, dv = flash_attention_pallas_bwd(
        _pad_seq(q, sq_p, ax), _pad_seq(k, sk_p, ax), _pad_seq(v, sk_p, ax),
        _pad_seq(out, sq_p, ax),
        jnp.pad(lse, ((0, 0), (0, 0), (0, sq_p - sq))),
        _pad_seq(g, sq_p, ax), causal=causal, scale=scale,
        offset=(sk - sq) if causal else None, interpret=interpret,
        key_mask=key_mask, layout=layout)
    slq = ((slice(None), slice(None), slice(None, sq)) if ax == 2
           else (slice(None), slice(None, sq)))
    slk = ((slice(None), slice(None), slice(None, sk)) if ax == 2
           else (slice(None), slice(None, sk)))
    return dq[slq], dk[slk], dv[slk]


def _pallas_ok(q, k, causal=True, layout="bshd"):
    # Eligibility gate. Causal accepts any seq lengths with 128 <= sq <= sk
    # — the padded wrappers mask the tail via the runtime diagonal offset.
    # sq < 128 (decode-shaped: one token against a long cache) stays on the
    # exact path: padding 1 -> 128 rows plus a full K/V pad-copy per step
    # costs more than the O(sk) matvec it replaces. sq > sk causal is
    # excluded: its fully-masked rows are 0 in the kernel but
    # uniform-attention in mha_ref's softmax — the two paths would
    # diverge. Non-causal needs an aligned KV length (padded keys would
    # join the softmax; padded q rows are merely sliced off).
    if not _use_pallas(q):
        return False
    ax = _seq_axis(layout)
    if causal:
        return 128 <= q.shape[ax] <= k.shape[ax]
    # non-causal: KV length must already be block-aligned (padded keys
    # would join the softmax; _pad_len returns the aligned LENGTH, so
    # equality means "already aligned"); padded q rows are sliced off.
    return _pad_len(k.shape[ax]) == k.shape[ax]


def _intentional_exact(q, k, causal, layout="bshd"):
    """Shapes where the exact path is the DESIGNED fast path, not a
    fallback worth warning about: decode-shaped causal sq < 128 (a matvec
    beats padding 1 -> 128 rows + a K/V pad copy)."""
    ax = _seq_axis(layout)
    return causal and q.shape[ax] < 128 and q.shape[ax] <= k.shape[ax]


def _expand_gqa(q, k, v, layout="bshd"):
    ax = 1 if layout == "bhsd" else 2  # heads axis
    rep = q.shape[ax] // k.shape[ax]
    if rep == 1:
        return k, v
    return jnp.repeat(k, rep, axis=ax), jnp.repeat(v, rep, axis=ax)


def _gqa_reduce(dk, dv, hkv, layout):
    """Sum k/v grads over each KV head's query-head group."""
    if layout == "bhsd":
        b, hq, s, d = dk.shape
        rep = hq // hkv
        dk = dk.reshape(b, hkv, rep, s, d).sum(axis=2)
        dv = dv.reshape(b, hkv, rep, s, d).sum(axis=2)
    else:
        b, s, hq, d = dk.shape
        rep = hq // hkv
        dk = dk.reshape(b, s, hkv, rep, d).sum(axis=3)
        dv = dv.reshape(b, s, hkv, rep, d).sum(axis=3)
    return dk, dv


def _ref_any(q, k, v, causal=False, scale=None, mask=None, layout="bshd"):
    """mha_ref for either layout (the exact fallback path)."""
    if layout == "bhsd":
        t = lambda x: x.transpose(0, 2, 1, 3)
        return t(mha_ref(t(q), t(k), t(v), causal=causal, scale=scale,
                         mask=mask))
    return mha_ref(q, k, v, causal=causal, scale=scale, mask=mask)


def _flash_impl(q, k, v, causal, scale, layout="bshd"):
    if _pallas_ok(q, k, causal, layout):
        # GQA k/v go in UNEXPANDED — the kernel's BlockSpec index_map
        # folds each q head onto its kv group
        return flash_attention_padded(q, k, v, causal=causal,
                                      scale=scale, layout=layout,
                                      interpret=_interpret())
    _warn_gate("flash_gate", q, k, causal, layout)
    return _ref_any(q, k, v, causal=causal, scale=scale, layout=layout)


def _flash_fwd_rule(q, k, v, causal, scale, layout="bshd"):
    if _pallas_ok(q, k, causal, layout):
        out, lse = flash_attention_padded(q, k, v, causal=causal,
                                          scale=scale, return_lse=True,
                                          layout=layout,
                                          interpret=_interpret())
        # residuals keep the ORIGINAL k/v (their static head count tells
        # the bwd how to reduce GQA grads); expansion is re-done there
        return out, (q, k, v, out, lse)
    _warn_gate("flash_gate_vjp", q, k, causal, layout)
    return (_ref_any(q, k, v, causal=causal, scale=scale, layout=layout),
            (q, k, v, None, None))


def _flash_bwd_rule(causal, scale, layout, res, g):
    q, k, v, out, lse = res
    if lse is not None:
        # GQA handled inside the wrapper (native resident kernel or
        # expand+reduce for the streamed paths)
        return flash_attention_padded_bwd(
            q, k, v, out, lse, g, causal=causal, scale=scale,
            layout=layout, interpret=_interpret())
    _, vjp = jax.vjp(lambda q_, k_, v_: _ref_any(
        q_, k_, v_, causal=causal, scale=scale, layout=layout), q, k, v)
    return vjp(g)


# ---------------------------------------------------------------------------
# Bidirectional attention with a key-padding mask — the encoder (ERNIE/BERT)
# path. The reference's fused flash_attn kernel takes padded/varlen batches;
# here the mask rides into the kernels as a [B, Sk] visibility vector
# (VERDICT r4 next-1).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def flash_attention_masked(q, k, v, key_mask, scale=None, layout="bshd"):
    """Bidirectional (non-causal) flash attention with a key-padding mask.

    q,k,v: [B, S, H, D] ('bshd') or [B, H, S, D] ('bhsd'); GQA allowed.
    key_mask: [B, Sk] bool/int, nonzero = key visible to every query in
    that batch row. Pallas path on TPU (any seq length — the mask hides
    pad keys), exact mha_ref elsewhere.

    Caveat: rows whose keys are ALL masked return 0 from the kernel but
    uniform attention from mha_ref's softmax; real padding masks always
    keep >= 1 visible key, so the paths agree where it matters."""
    return _flash_masked_impl(q, k, v, key_mask, scale, layout)


def _key_mask4(key_mask):
    """[B, Sk] → broadcastable mask for mha_ref ([B, 1, 1, Sk]; both
    layouts share it since mha_ref's mask indexes [b, h, q, k])."""
    return (key_mask != 0)[:, None, None, :]


def _flash_masked_impl(q, k, v, key_mask, scale, layout="bshd"):
    if _use_pallas(q):
        return flash_attention_padded(q, k, v, causal=False,
                                      scale=scale, key_mask=key_mask,
                                      layout=layout,
                                      interpret=_interpret())
    return _ref_any(q, k, v, scale=scale, layout=layout,
                    mask=_key_mask4(key_mask))


def _flash_masked_fwd_rule(q, k, v, key_mask, scale, layout="bshd"):
    if _use_pallas(q):
        out, lse = flash_attention_padded(q, k, v, causal=False,
                                          scale=scale, key_mask=key_mask,
                                          return_lse=True, layout=layout,
                                          interpret=_interpret())
        return out, (q, k, v, key_mask, out, lse)
    out = _ref_any(q, k, v, scale=scale, layout=layout,
                   mask=_key_mask4(key_mask))
    return out, (q, k, v, key_mask, None, None)


def _flash_masked_bwd_rule(scale, layout, res, g):
    import numpy as np
    q, k, v, key_mask, out, lse = res
    d_mask = np.zeros(key_mask.shape, jax.dtypes.float0)
    if lse is not None:
        dq, dk, dv = flash_attention_padded_bwd(
            q, k, v, out, lse, g, causal=False, scale=scale,
            key_mask=key_mask, layout=layout, interpret=_interpret())
        return dq, dk, dv, d_mask
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref_any(q_, k_, v_, scale=scale, layout=layout,
                                    mask=_key_mask4(key_mask)),
        q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, d_mask


flash_attention_masked.defvjp(_flash_masked_fwd_rule, _flash_masked_bwd_rule)


# ---------------------------------------------------------------------------
# Partial-attention block with LSE output — the ring-attention building
# block. custom_vjp so the pallas kernels differentiate, INCLUDING the lse
# cotangent (ring's online-softmax merge differentiates through lse).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def flash_block(q, k, v, offset, causal=True, scale=None):
    """One KV block of flash attention: returns (out, lse) where out is the
    block-normalized attention and lse the per-row log-sum-exp, mergeable
    across blocks via logaddexp. offset is the runtime causal-diagonal
    offset (see flash_attention_pallas); q/k/v need equal head counts."""
    return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                  offset=offset, return_lse=True,
                                  interpret=_interpret())


def _flash_block_fwd(q, k, v, offset, causal, scale):
    out, lse = flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                      offset=offset, return_lse=True,
                                      interpret=_interpret())
    return (out, lse), (q, k, v, offset, out, lse)


def _flash_block_bwd(causal, scale, res, cts):
    import numpy as np
    q, k, v, offset, out, lse = res
    g, gl = cts
    dq, dk, dv = flash_attention_pallas_bwd(
        q, k, v, out, lse, g, causal=causal, scale=scale, offset=offset,
        dlse=gl, interpret=_interpret())
    d_off = np.zeros((), jax.dtypes.float0)  # int arg: symbolic-zero tangent
    return dq, dk, dv, d_off


flash_block.defvjp(_flash_block_fwd, _flash_block_bwd)


flash_attention_fwd.defvjp(_flash_fwd_rule, _flash_bwd_rule)
