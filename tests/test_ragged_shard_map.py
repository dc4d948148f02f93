"""shard_map composition of the Pallas ragged kernel (PR 20): each
device runs the existing `ragged_paged_attention` kernel on its
KV-head shard of the paged pool — block tables, per-row positions and
validity replicated, int8 scales riding scalar prefetch per shard —
and GSPMD stitches the per-shard outputs on the head axis. Interpret
mode over the conftest's forced host devices, tp ∈ {1, 2, 4}, across
every ragged shape the serving path produces: decode rows, bucketed
prefill rows, block-boundary straddles, int8 KV scales and the spec
verify's suffix-slab operand.

Two claims per shape:

  * STITCH EXACTNESS — the mesh'd kernel output is BIT-identical to
    concatenating mesh-off kernel runs over each shard's contiguous
    head slice. shard_map adds zero numerics: the mesh only stitches,
    and the GQA head→kv-head mapping survives contiguous slicing
    because the grouping ratio is constant per shard.
  * REFERENCE PARITY — the mesh'd kernel matches the XLA gather
    reference at the parity suite's online-softmax tolerance, exactly
    like the mesh-off kernel does in test_ragged_attention.py.

Bitwise equality is asserted against the per-shard-slice runs, NOT
against the mesh-off full-width kernel: elementwise ops are
shape-sensitive at the last ulp in interpret mode (SIMD lane packing
over differently-sized buffers), so full-width vs sliced can drift by
~1 ulp while serving-level greedy TOKENS stay bit-identical — that
end-to-end claim is gated by tests/test_tp_serving.py (mesh x pallas
x tree speculation against mesh-off plain decode).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.nlp import paged
from paddle_tpu.nlp.ragged_attention import (_shard_specs,
                                             ragged_paged_attention)
from paddle_tpu.quantization import kv as kvq
from paddle_tpu.serving.speculative import SpecConfig

# H=8 / KV=4 so the head axes divide every tp under test (tp=4 needs
# KV % 4 == 0 — the same constraint MeshConfig.validate_for enforces
# on a real model config)
N, BS, KV, HD, H, M = 12, 4, 4, 8, 8, 5
TPS = (1, 2, 4)


def _mesh(tp):
    return Mesh(np.asarray(jax.devices()[:tp]), ("mp",))


def _pools(seed):
    rng = np.random.RandomState(seed)
    kp = jnp.asarray(rng.randn(N, BS, KV, HD), jnp.float32)
    vp = jnp.asarray(rng.randn(N, BS, KV, HD), jnp.float32)
    return rng, kp, vp


def _chains(rng, lengths):
    """Distinct live block chains per row, padded table entries -> 0."""
    table = np.zeros((len(lengths), M), np.int32)
    free = list(rng.permutation(np.arange(1, N)))
    for r, L in enumerate(lengths):
        for j in range(-(-L // BS) if L else 0):
            table[r, j] = free.pop()
    return jnp.asarray(table)


def _suffix_qpv(lengths, Pq):
    """Suffix-style positions/validity: row r's Pq queries end at
    position lengths[r]-1 (shorter rows left-pad as invalid)."""
    R = len(lengths)
    pos = np.zeros((R, Pq), np.int32)
    val = np.zeros((R, Pq), np.bool_)
    for r, L in enumerate(lengths):
        for p in range(Pq):
            j = L - Pq + p
            pos[r, p] = min(max(j, 0), M * BS - 1)
            val[r, p] = 0 <= j
    return jnp.asarray(pos), jnp.asarray(val)


def _q(rng, R, Pq):
    return jnp.asarray(rng.randn(R, Pq, H, HD), jnp.float32)


def _quantize(kp, vp):
    ks = jnp.max(jnp.abs(kp), axis=(1, 2, 3)) / kvq.BOUND
    vs = jnp.max(jnp.abs(vp), axis=(1, 2, 3)) / kvq.BOUND
    return (kvq.quantize(kp, ks[:, None, None, None]),
            kvq.quantize(vp, vs[:, None, None, None]), ks, vs)


def _hslice(a, s, tp):
    """Shard s's contiguous slice of a [.., .., heads, hd] operand."""
    w = a.shape[2] // tp
    return a[:, :, s * w:(s + 1) * w]


def _check(tp, q, kp, vp, table, pos, val, **kw):
    """Mesh'd kernel == concat of per-shard-slice runs (bit-exact)
    and == the XLA gather reference (parity tolerance)."""
    out = np.asarray(ragged_paged_attention(
        q, kp, vp, table, pos, val, mesh=_mesh(tp), **kw))
    shards = []
    for s in range(tp):
        skw = dict(kw)
        if "suffix_k" in kw:
            skw["suffix_k"] = _hslice(kw["suffix_k"], s, tp)
            skw["suffix_v"] = _hslice(kw["suffix_v"], s, tp)
        shards.append(np.asarray(ragged_paged_attention(
            _hslice(q, s, tp), _hslice(kp, s, tp), _hslice(vp, s, tp),
            table, pos, val, **skw)))
    np.testing.assert_array_equal(out, np.concatenate(shards, 2))
    if "suffix_k" not in kw:
        ref = paged._paged_gqa_attention(
            q, kp, vp, table, pos, k_scale=kw.get("k_scale"),
            v_scale=kw.get("v_scale"))
        ref = np.where(np.asarray(val)[:, :, None, None],
                       np.asarray(ref), 0.0)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    return out


@pytest.mark.parametrize("tp", TPS)
class TestShardMapParity:
    def test_decode_rows(self, tp):
        """P=1 decode rows at heterogeneous live lengths — the
        steady-state decode shape every mesh'd step runs."""
        rng, kp, vp = _pools(40)
        lengths = [1, 6, 17, 9]
        table = _chains(rng, lengths)
        pos, val = _suffix_qpv(lengths, 1)
        _check(tp, _q(rng, 4, 1), kp, vp, table, pos, val)

    def test_bucketed_prefill_rows(self, tp):
        """P=8 bucket-padded suffix rows: the invalid left-pad must
        stay zero on every shard independently."""
        rng, kp, vp = _pools(41)
        lengths = [3, 11, 19]
        table = _chains(rng, lengths)
        pos, val = _suffix_qpv(lengths, 8)
        _check(tp, _q(rng, 3, 8), kp, vp, table, pos, val)

    def test_block_boundary_straddle(self, tp):
        """length == block_size exactly / one past it: every shard's
        chain walk must include the boundary block's last key and not
        step into the next (garbage) table entry."""
        rng, kp, vp = _pools(42)
        lengths = [BS, 2 * BS, BS + 1]
        table = _chains(rng, lengths)
        pos, val = _suffix_qpv(lengths, 1)
        _check(tp, _q(rng, 3, 1), kp, vp, table, pos, val)

    def test_int8_kv_scales(self, tp):
        """int8 pool codes shard on the head axis while the per-block
        scales ride scalar prefetch REPLICATED — every shard
        dequantizes its slice with the same [N] scale vectors."""
        rng, kp, vp = _pools(43)
        kq, vq, ks, vs = _quantize(kp, vp)
        lengths = [3, BS, 13]
        table = _chains(rng, lengths)
        pos, val = _suffix_qpv(lengths, 1)
        _check(tp, _q(rng, 3, 1), kq, vq, table, pos, val,
               k_scale=ks, v_scale=vs)

    def test_suffix_slab_direct(self, tp):
        """The spec verify's suffix-slab operand through the kernel
        directly: the in-register slab shards on its kv-head axis
        alongside the pool, the ancestor-visibility mask replicates."""
        rng, kp, vp = _pools(44)
        sc = SpecConfig(tree=[2, 1, 1])
        vis = jnp.asarray(sc.ancestor_mask())
        S = vis.shape[0]
        lengths = [2, 9, 14]
        table = _chains(rng, lengths)
        pos = jnp.asarray([[L + i for i in range(S)] for L in lengths],
                          jnp.int32)
        val = jnp.ones((3, S), bool)
        sk = jnp.asarray(rng.randn(3, S, KV, HD), jnp.float32)
        sv = jnp.asarray(rng.randn(3, S, KV, HD), jnp.float32)
        _check(tp, _q(rng, 3, S), kp, vp, table, pos, val,
               suffix_k=sk, suffix_v=sv,
               suffix_vis=jnp.broadcast_to(vis, (3, S, S)))

    def test_suffix_slab_spec_path(self, tp):
        """The verify path itself (_spec_gqa_attention): mesh'd pallas
        == concat of per-shard pallas runs (bit) == the XLA concat
        reference (tolerance), chain triangle AND packed tree."""
        rng, kp, vp = _pools(45)
        lens = [2, 9, 14]
        base = jnp.asarray(lens, jnp.int32)
        table = _chains(rng, lens)
        for sc in (SpecConfig(k=3), SpecConfig(tree=[2, 1, 1])):
            vis = jnp.asarray(sc.ancestor_mask())
            S = vis.shape[0]
            sk = jnp.asarray(rng.randn(3, S, KV, HD), jnp.float32)
            sv = jnp.asarray(rng.randn(3, S, KV, HD), jnp.float32)
            q = _q(rng, 3, S)
            out = np.asarray(paged._spec_gqa_attention(
                q, kp, vp, table, base, sk, sv, vis,
                impl="pallas", mesh=_mesh(tp)))
            shards = [np.asarray(paged._spec_gqa_attention(
                _hslice(q, s, tp), _hslice(kp, s, tp),
                _hslice(vp, s, tp), table, base,
                _hslice(sk, s, tp), _hslice(sv, s, tp), vis,
                impl="pallas")) for s in range(tp)]
            np.testing.assert_array_equal(
                out, np.concatenate(shards, 2))
            ref = np.asarray(paged._spec_gqa_attention(
                q, kp, vp, table, base, sk, sv, vis, impl="xla"))
            np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestShardSpecs:
    def test_operand_specs(self):
        """_shard_specs mirrors the kernel's operand order exactly:
        scalar-prefetch operands (table, the work list's four arrays,
        scales), the list's count and positions/validity replicate; q,
        the pools and the slab shard on their head axis; the visibility
        mask replicates."""
        head = P(None, None, "mp", None)
        repl = P()
        specs, out = _shard_specs("mp", 4, False, False)
        assert specs == (repl,) * 8 + (head, head, head)
        assert out == head
        specs, _ = _shard_specs("mp", 4, True, False)
        assert specs == (repl,) * 10 + (head, head, head)
        specs, _ = _shard_specs("mp", 4, True, True)
        assert len(specs) == 16 and specs[-3:] == (head, head, repl)

    def test_indivisible_heads_rejected(self):
        """H=8/KV=4 on a 3-wide axis: the kernel refuses loudly at
        trace time instead of silently mis-slicing."""
        rng, kp, vp = _pools(46)
        table = _chains(rng, [5])
        pos, val = _suffix_qpv([5], 1)
        with pytest.raises(ValueError, match="must divide"):
            ragged_paged_attention(_q(rng, 1, 1), kp, vp, table, pos,
                                   val, mesh=_mesh(3))
