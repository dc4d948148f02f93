"""Pallas ragged paged-attention (PR 6): the kernel that walks only
each request's LIVE block chain, pinned against the XLA gather path.

Two layers:

  * kernel parity — `ragged_paged_attention` matches
    `_paged_gqa_attention` (the XLA reference) on every ragged shape
    the serving path produces: single-token decode rows, bucketed
    cached-prefix prefill rows, the fused mixed decode+prefill batch,
    and the edge cases (exactly-one-block chains, length == block_size
    boundaries, single-slot batches, fully padded batches, chains
    sharing prefix blocks with a COW-cloned tail). CPU runs the kernel
    in Pallas interpret mode — the CI parity path.
  * end-to-end parity — `ContinuousBatcher(attention_impl="pallas")`
    emits token-identical greedy output to the XLA backend across
    decode, chunked prefill, fused admission-during-decode, and
    prefix-cache COW-hit schedules, and `attention_impl="xla"` IS the
    pre-switch code path (the reference stays the fallback).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.nlp import llama, paged
from paddle_tpu.nlp.ragged_attention import (ragged_paged_attention,
                                             resolve_attention_impl)
from paddle_tpu.quantization import kv as kvq


def _pools(seed, N, bs, KV, hd):
    rng = np.random.RandomState(seed)
    kp = jnp.asarray(rng.randn(N, bs, KV, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(N, bs, KV, hd), jnp.float32)
    return rng, kp, vp


def _chains(rng, lengths, M, bs, N):
    """Distinct live block chains per row, padded table rows -> 0."""
    table = np.zeros((len(lengths), M), np.int32)
    free = list(rng.permutation(np.arange(1, N)))
    for r, L in enumerate(lengths):
        need = -(-L // bs) if L else 0
        for j in range(need):
            table[r, j] = free.pop()
    return jnp.asarray(table)


def _suffix_qpv(rng, lengths, P, M, bs):
    """Suffix-prefill style positions/valid: row r's P queries end at
    position lengths[r]-1 (rows shorter than P left-pad as invalid)."""
    R = len(lengths)
    pos = np.zeros((R, P), np.int32)
    val = np.zeros((R, P), np.bool_)
    maxpos = M * bs - 1
    for r, L in enumerate(lengths):
        for p in range(P):
            j = L - P + p
            pos[r, p] = min(max(j, 0), maxpos)
            val[r, p] = 0 <= j
    return jnp.asarray(pos), jnp.asarray(val)


def _assert_parity(q, kp, vp, table, pos, val, tol=2e-5):
    """pallas == xla on valid rows; pallas == 0 on padded rows."""
    ref = paged._paged_gqa_attention(q, kp, vp, table, pos)
    ref = np.where(np.asarray(val)[:, :, None, None], np.asarray(ref), 0.0)
    out = np.asarray(ragged_paged_attention(q, kp, vp, table, pos, val))
    np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)


class TestKernelParity:
    N, bs, KV, hd, H, M = 12, 4, 2, 8, 4, 5

    def _q(self, rng, R, P):
        return jnp.asarray(rng.randn(R, P, self.H, self.hd), jnp.float32)

    def test_decode_rows(self):
        """P=1 decode rows at heterogeneous live lengths — the shape
        every steady-state decode step produces."""
        rng, kp, vp = _pools(0, self.N, self.bs, self.KV, self.hd)
        lengths = [1, 6, 17, 9]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        pos, val = _suffix_qpv(rng, lengths, 1, self.M, self.bs)
        _assert_parity(self._q(rng, 4, 1), kp, vp, table, pos, val)

    def test_bucketed_prefill_rows(self):
        """P=8 bucket-padded suffix rows (cached-prefix prefill): the
        invalid left-pad must not contaminate the real queries."""
        rng, kp, vp = _pools(1, self.N, self.bs, self.KV, self.hd)
        lengths = [3, 11, 19]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        pos, val = _suffix_qpv(rng, lengths, 8, self.M, self.bs)
        _assert_parity(self._q(rng, 3, 8), kp, vp, table, pos, val)

    def test_fused_mixed_batch(self):
        """The PR 5 fused shape: B decode rows (column 0 valid at the
        slot's position, inactive rows fully masked) stacked on top of
        bucket-width prefill rows — one kernel call serves both."""
        rng, kp, vp = _pools(2, self.N, self.bs, self.KV, self.hd)
        P = 4
        dlen, plen = [7, 13, 0], [P, 2 * P + 1]     # slot 2 inactive
        table = _chains(rng, dlen + plen, self.M, self.bs, self.N)
        dpos = np.zeros((3, P), np.int32)
        dval = np.zeros((3, P), np.bool_)
        maxpos = self.M * self.bs - 1
        for r, L in enumerate(dlen):
            dpos[r] = np.minimum(np.arange(L, L + P), maxpos)
            dval[r, 0] = L > 0
        ppos, pval = _suffix_qpv(rng, plen, P, self.M, self.bs)
        pos = jnp.concatenate([jnp.asarray(dpos), ppos], 0)
        val = jnp.concatenate([jnp.asarray(dval), pval], 0)
        _assert_parity(self._q(rng, 5, P), kp, vp, table, pos, val)

    def test_exactly_one_block(self):
        """A request whose whole live chain is ONE pool block."""
        rng, kp, vp = _pools(3, self.N, self.bs, self.KV, self.hd)
        lengths = [2, self.bs - 1]                   # both within block 0
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        pos, val = _suffix_qpv(rng, lengths, 2, self.M, self.bs)
        _assert_parity(self._q(rng, 2, 2), kp, vp, table, pos, val)

    def test_block_size_boundary(self):
        """length == block_size exactly: the chain walk must include
        the boundary block's last key and must NOT step into the next
        (garbage) table entry."""
        rng, kp, vp = _pools(4, self.N, self.bs, self.KV, self.hd)
        lengths = [self.bs, 2 * self.bs, self.bs + 1]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        pos, val = _suffix_qpv(rng, lengths, 1, self.M, self.bs)
        _assert_parity(self._q(rng, 3, 1), kp, vp, table, pos, val)

    def test_single_slot_batch(self):
        """R=1 — the one-request grid still initializes, accumulates
        and finalizes correctly."""
        rng, kp, vp = _pools(5, self.N, self.bs, self.KV, self.hd)
        lengths = [10]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        pos, val = _suffix_qpv(rng, lengths, 3, self.M, self.bs)
        _assert_parity(self._q(rng, 1, 3), kp, vp, table, pos, val)

    def test_all_padded_batch(self):
        """Every query invalid (empty batch of padded slots): the
        kernel emits exact zeros and touches no live chain at all."""
        rng, kp, vp = _pools(6, self.N, self.bs, self.KV, self.hd)
        R, P = 3, 2
        q = self._q(rng, R, P)
        table = jnp.zeros((R, self.M), jnp.int32)
        pos = jnp.zeros((R, P), jnp.int32)
        val = jnp.zeros((R, P), bool)
        out = np.asarray(ragged_paged_attention(q, kp, vp, table, pos, val))
        assert (out == 0.0).all()

    def test_cow_cloned_chain(self):
        """Two chains share prefix blocks; the second's tail block is a
        COW clone (identical KV content under a different block id) —
        the prefix-cache hit shape. Rows must agree with the reference
        AND with each other where their visible keys coincide."""
        rng, kp, vp = _pools(7, self.N, self.bs, self.KV, self.hd)
        L = 2 * self.bs + 2
        table = np.zeros((2, self.M), np.int32)
        table[0, :3] = [3, 7, 5]
        table[1, :3] = [3, 7, 9]                     # 9 := clone of 5
        kp = kp.at[9].set(kp[5])
        vp = vp.at[9].set(vp[5])
        pos, val = _suffix_qpv(rng, [L, L], 2, self.M, self.bs)
        q = self._q(rng, 1, 2)
        q = jnp.concatenate([q, q], 0)               # identical queries
        _assert_parity(q, kp, vp, jnp.asarray(table), pos, val)
        out = np.asarray(ragged_paged_attention(
            q, kp, vp, jnp.asarray(table), pos, val))
        np.testing.assert_allclose(out[0], out[1], atol=2e-6)

    def test_query_tiling_parity(self):
        """q_tile < P: the grid grows a query-tile dimension (VMEM
        bound for wide prefill buckets) and each tile walks only ITS
        OWN visible chain prefix — output identical to untiled."""
        rng, kp, vp = _pools(9, self.N, self.bs, self.KV, self.hd)
        lengths = [3, 11, 19]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        pos, val = _suffix_qpv(rng, lengths, 8, self.M, self.bs)
        q = self._q(rng, 3, 8)
        ref = paged._paged_gqa_attention(q, kp, vp, table, pos)
        ref = np.where(np.asarray(val)[:, :, None, None],
                       np.asarray(ref), 0.0)
        for tile in (2, 4):                          # 4 and 2 tiles
            out = np.asarray(ragged_paged_attention(
                q, kp, vp, table, pos, val, q_tile=tile))
            np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_query_tiling_indivisible_falls_back(self):
        """P % q_tile != 0 (exact unbucketed shapes): the largest
        divisor of P that fits becomes the tile — here Pt=1, the
        worst case (P=5 prime, q_tile=3) — same result."""
        rng, kp, vp = _pools(10, self.N, self.bs, self.KV, self.hd)
        lengths = [9, 14]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        pos, val = _suffix_qpv(rng, lengths, 5, self.M, self.bs)
        q = self._q(rng, 2, 5)
        out = np.asarray(ragged_paged_attention(
            q, kp, vp, table, pos, val, q_tile=3))
        ref = np.asarray(ragged_paged_attention(q, kp, vp, table, pos, val))
        np.testing.assert_allclose(out, ref, atol=2e-6)

    def test_query_dtype_roundtrip(self):
        """Output lands in q's dtype (the pool may be wider)."""
        rng, kp, vp = _pools(8, self.N, self.bs, self.KV, self.hd)
        lengths = [5]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        pos, val = _suffix_qpv(rng, lengths, 1, self.M, self.bs)
        q = self._q(rng, 1, 1).astype(jnp.bfloat16)
        out = ragged_paged_attention(q, kp, vp, table, pos, val)
        assert out.dtype == jnp.bfloat16


def _quantize_pools(kp, vp):
    """Per-block abs-max int8 quantization of an fp pool — the layout
    PagedKVCache's sibling scale pool stores ([N] scales per layer)."""
    ks = jnp.max(jnp.abs(kp), axis=(1, 2, 3)) / kvq.BOUND
    vs = jnp.max(jnp.abs(vp), axis=(1, 2, 3)) / kvq.BOUND
    kq = kvq.quantize(kp, ks[:, None, None, None])
    vq = kvq.quantize(vp, vs[:, None, None, None])
    return kq, vq, ks, vs


class TestKernelParityInt8:
    """int8 paged KV: the kernel's in-block-loop dequant (scales on
    scalar prefetch) pinned against the XLA path's after-the-gather
    dequant — the bit-stable reference — in interpret mode. Same math
    (quantization.kv) on both sides, so parity is the online-softmax
    tolerance, exactly like the fp rows."""

    N, bs, KV, hd, H, M = 12, 4, 2, 8, 4, 5

    def _q(self, rng, R, P):
        return jnp.asarray(rng.randn(R, P, self.H, self.hd), jnp.float32)

    def _assert_parity_q(self, q, kq, vq, ks, vs, table, pos, val,
                         tol=2e-5):
        ref = paged._paged_gqa_attention(q, kq, vq, table, pos,
                                         k_scale=ks, v_scale=vs)
        ref = np.where(np.asarray(val)[:, :, None, None],
                       np.asarray(ref), 0.0)
        out = np.asarray(ragged_paged_attention(
            q, kq, vq, table, pos, val, k_scale=ks, v_scale=vs))
        np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)

    def test_decode_rows_int8(self):
        """P=1 decode rows over an int8 pool at heterogeneous live
        lengths — the quantized steady-state decode shape."""
        rng, kp, vp = _pools(20, self.N, self.bs, self.KV, self.hd)
        kq, vq, ks, vs = _quantize_pools(kp, vp)
        lengths = [1, 6, 17, 9]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        pos, val = _suffix_qpv(rng, lengths, 1, self.M, self.bs)
        self._assert_parity_q(self._q(rng, 4, 1), kq, vq, ks, vs,
                              table, pos, val)

    def test_bucketed_prefill_rows_int8(self):
        """Bucket-padded cached-prefix suffix rows against quantized
        prefix blocks — the warm-admission shape."""
        rng, kp, vp = _pools(21, self.N, self.bs, self.KV, self.hd)
        kq, vq, ks, vs = _quantize_pools(kp, vp)
        lengths = [3, 11, 19]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        pos, val = _suffix_qpv(rng, lengths, 8, self.M, self.bs)
        self._assert_parity_q(self._q(rng, 3, 8), kq, vq, ks, vs,
                              table, pos, val)

    def test_block_size_boundary_int8(self):
        """length == block_size under int8: the boundary block's last
        key dequantizes and the walk must not read the next (garbage)
        table entry's scale either."""
        rng, kp, vp = _pools(22, self.N, self.bs, self.KV, self.hd)
        kq, vq, ks, vs = _quantize_pools(kp, vp)
        lengths = [self.bs, 2 * self.bs, self.bs + 1]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        pos, val = _suffix_qpv(rng, lengths, 1, self.M, self.bs)
        self._assert_parity_q(self._q(rng, 3, 1), kq, vq, ks, vs,
                              table, pos, val)

    def test_all_padded_batch_int8_exact_zeros(self):
        """Every query invalid: the quantized kernel emits EXACT zeros
        (never-written blocks carry scale 0, and no live chain is
        touched at all)."""
        rng, kp, vp = _pools(23, self.N, self.bs, self.KV, self.hd)
        kq, vq, ks, vs = _quantize_pools(kp, vp)
        R, P = 3, 2
        q = self._q(rng, R, P)
        table = jnp.zeros((R, self.M), jnp.int32)
        pos = jnp.zeros((R, P), jnp.int32)
        val = jnp.zeros((R, P), bool)
        out = np.asarray(ragged_paged_attention(
            q, kq, vq, table, pos, val, k_scale=ks, v_scale=vs))
        assert (out == 0.0).all()

    def test_cow_cloned_chain_int8(self):
        """The prefix-cache COW shape under int8: the clone block
        copies the source's CODES AND SCALE (paged._apply_cow copies
        both pools) — identical queries over the shared prefix must
        agree across the original and the cloned chain."""
        rng, kp, vp = _pools(24, self.N, self.bs, self.KV, self.hd)
        kq, vq, ks, vs = _quantize_pools(kp, vp)
        L = 2 * self.bs + 2
        table = np.zeros((2, self.M), np.int32)
        table[0, :3] = [3, 7, 5]
        table[1, :3] = [3, 7, 9]                     # 9 := clone of 5
        kq = kq.at[9].set(kq[5])
        vq = vq.at[9].set(vq[5])
        ks = ks.at[9].set(ks[5])
        vs = vs.at[9].set(vs[5])
        pos, val = _suffix_qpv(rng, [L, L], 2, self.M, self.bs)
        q = self._q(rng, 1, 2)
        q = jnp.concatenate([q, q], 0)               # identical queries
        self._assert_parity_q(q, kq, vq, ks, vs, jnp.asarray(table),
                              pos, val)
        out = np.asarray(ragged_paged_attention(
            q, kq, vq, jnp.asarray(table), pos, val,
            k_scale=ks, v_scale=vs))
        np.testing.assert_allclose(out[0], out[1], atol=2e-6)


def _slab(rng, B, S, KV, hd):
    """An in-register draft/verify suffix slab (full precision — slab
    rows never pass through the pool's quantizer before commit)."""
    sk = jnp.asarray(rng.randn(B, S, KV, hd), jnp.float32)
    sv = jnp.asarray(rng.randn(B, S, KV, hd), jnp.float32)
    return sk, sv


class TestSuffixSlabParity:
    """The spec verify's suffix-slab operand: the Pallas kernel folds
    the in-register draft slab into the SAME online softmax as the
    pool sweep at each row's last work item, pinned in
    interpret mode against the XLA concat formulation
    (`paged._spec_gqa_attention(impl="xla")` — the bit-stable
    reference the verify path keeps). Chain triangles and packed-tree
    ancestor masks, fp and int8 pools, block-boundary straddles and
    the all-padded batch, in the TestKernelParityInt8 style."""

    N, bs, KV, hd, H, M = 12, 4, 2, 8, 4, 5

    def _q(self, rng, B, P):
        return jnp.asarray(rng.randn(B, P, self.H, self.hd),
                           jnp.float32)

    def _parity(self, q, kp, vp, table, base_len, sk, sv, vis,
                ks=None, vs=None, tol=2e-5):
        ref = np.asarray(paged._spec_gqa_attention(
            q, kp, vp, table, base_len, sk, sv, vis,
            k_scale=ks, v_scale=vs, impl="xla"))
        out = np.asarray(paged._spec_gqa_attention(
            q, kp, vp, table, base_len, sk, sv, vis,
            k_scale=ks, v_scale=vs, impl="pallas"))
        np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)

    def test_chain_triangle_fp(self):
        """The chain verify shape: S = k+1 slab rows, causal-triangle
        visibility, heterogeneous committed lengths."""
        from paddle_tpu.serving.speculative import SpecConfig
        rng, kp, vp = _pools(30, self.N, self.bs, self.KV, self.hd)
        vis = jnp.asarray(SpecConfig(k=4).ancestor_mask())
        S = vis.shape[0]
        lengths = [1, 6, 17]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        sk, sv = _slab(rng, 3, S, self.KV, self.hd)
        self._parity(self._q(rng, 3, S), kp, vp, table,
                     jnp.asarray(lengths, jnp.int32), sk, sv, vis)

    def test_tree_ancestor_mask_fp(self):
        """The packed-tree verify shape: every node's query sees the
        pool plus exactly its root-to-node path (arbitrary per-row
        visibility, NOT a triangle)."""
        from paddle_tpu.serving.speculative import SpecConfig
        rng, kp, vp = _pools(31, self.N, self.bs, self.KV, self.hd)
        sc = SpecConfig(tree=[2, 2])
        vis = jnp.asarray(sc.ancestor_mask())
        S = sc.slab_rows()
        lengths = [2, 9, 14]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        sk, sv = _slab(rng, 3, S, self.KV, self.hd)
        self._parity(self._q(rng, 3, S), kp, vp, table,
                     jnp.asarray(lengths, jnp.int32), sk, sv, vis)

    def test_tree_draft_level_rows(self):
        """A draft sweep's level shape: P < S queries (one level's
        nodes) against the full slab, each seeing its own path — the
        visibility rows are a SLICE of the ancestor mask."""
        from paddle_tpu.serving.speculative import SpecConfig
        rng, kp, vp = _pools(32, self.N, self.bs, self.KV, self.hd)
        sc = SpecConfig(tree=[2, 2])
        A = jnp.asarray(sc.ancestor_mask())
        offs = sc.level_offsets()
        vis = A[offs[1]:offs[2]]                     # level-1 nodes
        S = sc.slab_rows()
        lengths = [5, 11]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        sk, sv = _slab(rng, 2, S, self.KV, self.hd)
        self._parity(self._q(rng, 2, vis.shape[0]), kp, vp, table,
                     jnp.asarray(lengths, jnp.int32), sk, sv, vis)

    def test_block_boundary_straddle(self):
        """Committed length exactly at / one past a block boundary:
        the pool sweep must include the boundary block's last key and
        the slab fold must not shift by one."""
        from paddle_tpu.serving.speculative import SpecConfig
        rng, kp, vp = _pools(33, self.N, self.bs, self.KV, self.hd)
        vis = jnp.asarray(SpecConfig(k=3).ancestor_mask())
        S = vis.shape[0]
        lengths = [self.bs, 2 * self.bs, self.bs + 1]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        sk, sv = _slab(rng, 3, S, self.KV, self.hd)
        self._parity(self._q(rng, 3, S), kp, vp, table,
                     jnp.asarray(lengths, jnp.int32), sk, sv, vis)

    def test_chain_and_tree_int8_pool(self):
        """int8 committed pool under the slab fold: pool scores
        dequantize inside the block-chunk loop (scales on scalar
        prefetch), slab rows stay fp — parity vs the XLA reference's
        after-the-gather dequant, chain AND tree visibility."""
        from paddle_tpu.serving.speculative import SpecConfig
        rng, kp, vp = _pools(34, self.N, self.bs, self.KV, self.hd)
        kq, vq, ks, vs = _quantize_pools(kp, vp)
        lengths = [3, self.bs, 13]
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        for sc in (SpecConfig(k=4), SpecConfig(tree=[2, 1, 1])):
            vis = jnp.asarray(sc.ancestor_mask())
            S = sc.slab_rows()
            sk, sv = _slab(rng, 3, S, self.KV, self.hd)
            self._parity(self._q(rng, 3, S), kq, vq, table,
                         jnp.asarray(lengths, jnp.int32), sk, sv, vis,
                         ks=ks, vs=vs)

    def test_all_padded_exact_zeros(self):
        """Every query invalid: the suffix-slab grid (pool chunks PLUS
        the slab chunk) emits EXACT zeros — the slab fold must respect
        row validity exactly like the pool sweep does."""
        rng, kp, vp = _pools(35, self.N, self.bs, self.KV, self.hd)
        B, S = 2, 4
        q = self._q(rng, B, S)
        sk, sv = _slab(rng, B, S, self.KV, self.hd)
        out = np.asarray(ragged_paged_attention(
            q, kp, vp, jnp.zeros((B, self.M), jnp.int32),
            jnp.zeros((B, S), jnp.int32), jnp.zeros((B, S), bool),
            suffix_k=sk, suffix_v=sv,
            suffix_vis=jnp.ones((B, S, S), bool)))
        assert (out == 0.0).all()


class TestResolveImpl:
    def test_auto_resolves_off_tpu(self):
        """CPU CI: auto means the XLA reference (pallas off-TPU is
        interpret mode — a testing path, not a serving path)."""
        expect = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert resolve_attention_impl("auto") == expect

    def test_passthrough_and_reject(self):
        assert resolve_attention_impl("pallas") == "pallas"
        assert resolve_attention_impl("xla") == "xla"
        with pytest.raises(ValueError):
            resolve_attention_impl("cuda")


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _batcher(params, cfg, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_total_len", 32)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("chunk", 2)
    return paged.ContinuousBatcher(params, cfg, **kw)


def _prompts(seed, lengths):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(1, 200, n))) for n in lengths]


def _run_both(params, cfg, schedule, **kw):
    outs = []
    for impl in ("xla", "pallas"):
        cb = _batcher(params, cfg, attention_impl=impl, **kw)
        outs.append(schedule(cb))
    return outs


class TestBatcherParity:
    """pallas == xla greedy tokens through the real serving paths."""

    def test_decode_parity(self, setup):
        cfg, params = setup
        prompts = _prompts(11, (5, 9, 3))

        def schedule(cb):
            rids = [cb.submit(p) for p in prompts]
            out = cb.run()
            return [out[r] for r in rids]

        a, b = _run_both(params, cfg, schedule, prefill_buckets=(8,))
        assert a == b

    def test_fused_mid_decode_parity(self, setup):
        """Admissions landing mid-decode take the fused mixed batch —
        the kernel's hardest shape — with identical tokens."""
        cfg, params = setup
        first, late = _prompts(12, (6, 7))

        def schedule(cb):
            rids = [cb.submit(first)]
            cb.step()
            rids.append(cb.submit(late))
            out = cb.run()
            assert cb.fused_steps >= 1
            return [out[r] for r in rids]

        a, b = _run_both(params, cfg, schedule, prefill_buckets=(8,))
        assert a == b

    def test_chunked_prefill_parity(self, setup):
        """A prompt past the largest bucket streams bucket-sized chunks
        through the ragged path."""
        cfg, params = setup
        (long,) = _prompts(13, (19,))

        def schedule(cb):
            rid = cb.submit(long)
            return cb.run()[rid]

        a, b = _run_both(params, cfg, schedule, prefill_buckets=(8,))
        assert a == b

    def test_cow_prefix_hit_parity(self, setup):
        """Same prompt twice with the prefix cache on: the second
        admission COW-clones the cached tail block — chains built from
        shared + cloned blocks must decode identically."""
        cfg, params = setup
        (p,) = _prompts(14, (9,))

        def schedule(cb):
            r1 = cb.submit(p)
            cb.run()
            r2 = cb.submit(list(p))
            out = cb.run()
            stats = cb.prefix_stats()
            assert stats["hits"] >= 1
            return out[r2]

        a, b = _run_both(params, cfg, schedule, prefix_cache=True,
                         prefill_buckets=(8,))
        assert a == b

    def test_xla_is_default_off_tpu(self, setup):
        cfg, params = setup
        cb = _batcher(params, cfg)           # attention_impl="auto"
        if jax.default_backend() != "tpu":
            assert cb.attention_impl == "xla"

    def test_compile_memo_keys_on_impl(self, setup):
        """Every compiled-shape memo keys on the resolved impl, so a
        pallas batcher never aliases an xla executable."""
        cfg, params = setup
        cb = _batcher(params, cfg, attention_impl="pallas",
                      prefill_buckets=(8,))
        cb.warmup_prefill()
        keys = (list(cb._prefill_cache) + list(cb._fused_cache)
                + list(cb._chunk_cache))
        # ... and on the resolved quantization config (the trailing
        # (weight_dtype, kv_dtype) pair), so a quantized batcher never
        # aliases an fp executable either
        assert keys and all("pallas" in k and k[-2:] == ("fp", "fp")
                            for k in keys)


def test_flight_records_carry_the_gqa_kernels_work(setup):
    """Every decode and fused tick of a GQA batcher whose attention is the
    kernel notes `attn_work_steps` (the items ONE layer's calls walked,
    added up on the device) beside `attn_grid_steps` (the full grid of
    the same calls, from shapes), and both follow the schedule; the
    gather reference walks no grid and notes neither."""
    from attn_work_expect import work_steps
    cfg, params = setup
    long, short = _prompts(15, (70, 5))

    def serve(impl):
        cb = _batcher(params, cfg, attention_impl=impl, max_total_len=160,
                      max_batch=3, prefill_buckets=(8,), prefix_cache=False)
        cb.submit(long)
        while not any(cb.active):
            cb.step()
        cb.submit(short)        # joins mid-decode: its chunk rides fused
        cb.run()
        recs = [r for r in cb.flight.records()
                if r["mode"] in ("decode", "fused")]
        assert {r["mode"] for r in recs} == {"decode", "fused"}
        return cb, recs

    cb, recs = serve("pallas")
    # a table of 40 blocks, 8 a decode step: 5 chunks a row, the long
    # row's 18 blocks and more 3 items; 16 a prefill tile's step
    kinds = [(cb.M, None, 8, 16)]
    assert cb.M == 40
    exact = 0
    for r in recs:
        work, grid = work_steps(r, cb.bs, cb.B, kinds)
        assert r["attn_grid_steps"] == grid
        assert 0 < r["attn_work_steps"] <= work < grid
        if r["live_after"] == r["active_slots"]:    # no row retired in it
            assert r["attn_work_steps"] == work
            exact += 1
    assert exact >= 2
    _, recs = serve("xla")
    assert not any("attn_work_steps" in r or "attn_grid_steps" in r
                   for r in recs)


# ---- the work list (PR 34): the grid is the call's live items ------------
from attn_work_expect import enumerate_work, work_items  # noqa: E402


class TestWorkList:
    """The kernel's grid is a list of the live (row, query tile, chunk)
    items of the call, a chunk several blocks: every form against its XLA
    twin where the list is empty, short, the whole grid, and ragged over
    a row's tiles; the list itself against a plain enumeration; a list
    handed in against the one built inside, bit for bit."""

    bs, KV, hd, H, M = 4, 2, 8, 4, 40       # 8 blocks a decode step (5
    N = 4 * M + 1                           # chunks a row), 16 a tile's

    # case -> (P, q_tile, each row's keys; 0 = the row is not live)
    CASES = {
        "none-live": (1, 128, [0, 0, 0, 0]),
        "one-row": (1, 128, [0, 0, 70, 0]),
        "all-rows": (1, 128, [3, 64, 65, 130]),
        "full-width": (1, 128, [160, 0, 17, 0]),
        # 16-query suffixes cut into tiles of 4: a row's early tiles end
        # before its late ones, and row 1's first two tiles are padding
        "ragged-tiles": (16, 4, [134, 7, 0, 77]),
    }

    def _inputs(self, case, seed):
        from paddle_tpu.nlp.ragged_attention import _attn_tiling
        P, q_tile, lengths = self.CASES[case]
        rng, kp, vp = _pools(seed, self.N, self.bs, self.KV, self.hd)
        table = _chains(rng, lengths, self.M, self.bs, self.N)
        pos, val = _suffix_qpv(rng, lengths, P, self.M, self.bs)
        q = jnp.asarray(rng.randn(len(lengths), P, self.H, self.hd),
                        jnp.float32)
        Pt, _, nb, _ = _attn_tiling(P, self.M, q_tile, pools=2)
        assert nb == (8 if P == 1 else 16)
        return rng, q, kp, vp, table, pos, val, q_tile, Pt, nb

    @pytest.mark.parametrize("case", list(CASES))
    @pytest.mark.parametrize("form", ["plain", "int8"])
    def test_list_form_matches_its_xla_twin(self, form, case):
        from paddle_tpu.nlp.ragged_attention import gqa_work_list
        rng, q, kp, vp, table, pos, val, q_tile, Pt, nb = self._inputs(
            case, 40 + len(case))
        scales = {}
        if form == "int8":
            kp, vp, ks, vs = _quantize_pools(kp, vp)
            scales = {"k_scale": ks, "v_scale": vs}
        ref = paged._paged_gqa_attention(q, kp, vp, table, pos, val,
                                         impl="xla", **scales)
        keep = np.asarray(val)[:, :, None, None]
        out = np.asarray(ragged_paged_attention(
            q, kp, vp, table, pos, val, q_tile=q_tile, **scales))
        np.testing.assert_allclose(out, np.where(keep, np.asarray(ref), 0.0),
                                   atol=2e-5, rtol=2e-5)
        # the list, against the enumeration by hand
        work = gqa_work_list(pos, val, self.M, kp.shape, kp.dtype,
                             q_tile=q_tile)
        want, _ = enumerate_work(pos, val, self.bs, self.M, Pt, nb)
        assert work_items(work) == want
        assert work.first is None
        if case == "none-live":
            assert int(work.count) == 0 and not out.any()
        if case == "full-width":
            assert want[:5] == [(0, 0, c) for c in range(5)]
        if case == "ragged-tiles":
            per_tile = [sum(1 for r, t, _ in want if (r, t) == (0, tt))
                        for tt in range(4)]
            assert per_tile == sorted(per_tile) and per_tile[0] < per_tile[-1]
            assert not any(r == 1 and t < 2 for r, t, _ in want)
        # handed in or built inside: the same program on the same list
        again = np.asarray(ragged_paged_attention(
            q, kp, vp, table, pos, val, q_tile=q_tile, work=work, **scales))
        assert np.array_equal(out, again)

    @pytest.mark.parametrize("int8", [False, True], ids=["fp", "int8"])
    @pytest.mark.parametrize("case", ["none-live", "one-row", "all-rows",
                                      "full-width", "tiles"])
    def test_suffix_list_form_matches_its_xla_twin(self, case, int8):
        """The suffix slab folds into each row's last item; a row whose
        pool chain is still empty gets one item all the same."""
        P, q_tile, base = {
            "none-live": (3, 128, [5, 70, 0, 33]),
            "one-row": (3, 128, [0, 70, 0, 0]),
            "all-rows": (3, 128, [3, 64, 65, 130]),
            "full-width": (3, 128, [160, 0, 17, 1]),
            "tiles": (6, 3, [150, 0, 9, 77]),
        }[case]
        rng, kp, vp = _pools(60 + len(case), self.N, self.bs, self.KV,
                             self.hd)
        R, S = len(base), P + 2
        table = _chains(rng, base, self.M, self.bs, self.N)
        sk, sv = _slab(rng, R, S, self.KV, self.hd)
        vis = jnp.asarray(np.tril(np.ones((P, S), bool), k=S - P))
        q = jnp.asarray(rng.randn(R, P, self.H, self.hd), jnp.float32)
        base_len = jnp.asarray(base, jnp.int32)
        scales = {}
        if int8:
            kp, vp, ks, vs = _quantize_pools(kp, vp)
            scales = {"k_scale": ks, "v_scale": vs}
        ref = np.asarray(paged._spec_gqa_attention(
            q, kp, vp, table, base_len, sk, sv, vis, impl="xla", **scales))
        pos, ones = paged._spec_queries(base_len, P)
        live = np.asarray(base) > 0 if case == "one-row" else np.ones(R, bool)
        if case == "none-live":
            live[:] = False
        val = jnp.asarray(np.broadcast_to(live[:, None], (R, P)))
        kw = dict(suffix_k=sk, suffix_v=sv, q_tile=q_tile,
                  suffix_vis=jnp.broadcast_to(vis[None], (R, P, S)), **scales)
        out = np.asarray(ragged_paged_attention(q, kp, vp, table, pos, val,
                                                **kw))
        np.testing.assert_allclose(
            out, np.where(live[:, None, None, None], ref, 0.0),
            atol=2e-5, rtol=2e-5)
        from paddle_tpu.nlp.ragged_attention import (_attn_tiling,
                                                     gqa_work_list)
        work = gqa_work_list(pos, val, self.M, kp.shape, kp.dtype, slab=True,
                             q_tile=q_tile)
        Pt, _, nb, _ = _attn_tiling(P, self.M, q_tile, pools=2)
        # an empty chain (position -1) still has its one item
        want, _ = enumerate_work(np.maximum(np.asarray(pos), 0), val,
                                 self.bs, self.M, Pt, nb)
        assert work_items(work) == want
        if case == "none-live":
            assert int(work.count) == 0 and not out.any()
        if case == "full-width":
            assert (1, 0, 0) in want          # the row with no pool key
        again = np.asarray(ragged_paged_attention(
            q, kp, vp, table, pos, val, work=work, **kw))
        assert np.array_equal(out, again)
        if case == "all-rows":
            # the served call: `_spec_gqa_attention` builds the same list
            served = np.asarray(paged._spec_gqa_attention(
                q, kp, vp, table, base_len, sk, sv, vis, impl="pallas",
                **scales))
            assert np.array_equal(served, out)

    def test_a_list_of_another_call_is_refused(self):
        from paddle_tpu.nlp.ragged_attention import gqa_work_list
        rng, q, kp, vp, table, pos, val, q_tile, Pt, nb = self._inputs(
            "all-rows", 0)
        work = gqa_work_list(pos, val, self.M, kp.shape, kp.dtype,
                             window=8)
        with pytest.raises(ValueError, match="work list"):
            ragged_paged_attention(q, kp, vp, table, pos, val, work=work)
        work = gqa_work_list(pos[:2], val[:2], self.M, kp.shape, kp.dtype)
        with pytest.raises(ValueError, match="work list"):
            ragged_paged_attention(q, kp, vp, table, pos, val, work=work)
