"""The MLA + sparse-expert decoder on the served path (nlp/mla.py,
moe.expert_share_ffn, the latent pool of nlp/paged.py) at a tiny size on
the CPU, against the benchmark's plain reference
(benchmark/reference/mla_moe_decoder.py: float32, no cache, expanded
attention, a loop over the held experts).

Tolerances: everything here runs in float32 on the CPU, program and
reference alike, so the two differ by the order of float32 sums only
(absorbed against expanded attention reassociates a 3-matrix product; the
grouped GEMM sums in another order than the loop over experts): on logits
of magnitude 0.6 the largest difference read is 1.8e-7 (cold prefill) and
1.2e-7 (a decode step); 2e-5 leaves a hundredfold room for another backend's
sums and is a thousandth of what a small fault moves them (the gates'
factor at 2.4 in place of 2.5: 0.016).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.models import mla_moe_decoder as family        # noqa: E402
from benchmark.reference import mla_moe_decoder as reference  # noqa: E402
from paddle_tpu.kernels import rope                           # noqa: E402
from paddle_tpu.nlp import mla, moe, paged                    # noqa: E402
from paddle_tpu.nlp import ragged_attention                   # noqa: E402
from paddle_tpu.nlp.ragged_attention import mla_paged_attention  # noqa: E402

TOL = 2e-5

MODEL = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 160,
    "kv_lora_rank": 32, "max_position_embeddings": 256,
    "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_routed_experts": 6,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 3, "q_lora_rank": 48,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_method": "none", "v_head_dim": 16,
    "vocab_size": 128}
CONFIG = {"family": "mla_moe_decoder", **MODEL, "served_dtype": "float32",
          "share": {"router_experts": 16, "experts_first": 4}}
SEED = 3


@pytest.fixture(scope="module")
def model():
    d = family.dims(CONFIG)
    cfg = family.program_config(CONFIG)
    params = family.make_params(SEED, d, jnp.float32)
    return d, cfg, params


def _cache(cfg, rows, max_len, bs=4, nblocks=64):
    M = -(-max_len // bs)
    pool, v, _, _ = paged.init_pool(cfg, nblocks, bs)
    assert v is None
    table = jnp.asarray(np.arange(1, 1 + rows * M).reshape(rows, M),
                        jnp.int32)
    return paged.PagedKVCache(pool, None, table,
                              jnp.zeros((rows,), jnp.int32))


def test_yarn_frequencies_and_scale_hand_worked():
    # A.X-K1's rope block: theta 10000 on 64 dims, factor 32 over 4096,
    # beta_fast 32, beta_slow 1. Correction dims by hand:
    # 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47 -> low 10,
    # 64 ln(4096 / (1 * 2 pi)) / (2 ln 10000) = 22.51 -> high 23
    inv = np.asarray(rope.yarn_inv_freq(64, 10000.0, 32.0, 4096, 32, 1))
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)   # kept
    np.testing.assert_allclose(inv[23:], base[23:] / 32, rtol=1e-6)
    # halfway up the ramp (i = 16.5 lies between 16 and 17)
    r16 = (16 - 10) / 13
    np.testing.assert_allclose(
        inv[16], base[16] / 32 * r16 + base[16] * (1 - r16), rtol=1e-6)
    assert abs(rope.yarn_mscale(32, 1) - 1.3465736) < 1e-6
    cfg = mla.MlaMoeConfig(
        qk_nope_head_dim=128, qk_rope_head_dim=64,
        rope_scaling={"factor": 32, "original_max_position_embeddings": 4096,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                      "mscale_all_dim": 1})
    assert abs(cfg.softmax_scale - 0.13086080) < 1e-7
    cos, sin = cfg.rope_tables(8)            # m(32, 1) / m(32, 1) = 1
    np.testing.assert_allclose(np.asarray(cos[3]), np.cos(3 * inv), 1e-5)
    # the reference computes the same frequencies on its own
    np.testing.assert_allclose(
        reference.yarn_inv_freq(64, 10000.0, {
            "factor": 32, "original_max_position_embeddings": 4096,
            "beta_fast": 32, "beta_slow": 1}), inv, rtol=1e-6)


def test_absorbed_equals_expanded_float32(model):
    d, cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["moe_layers"])
    rng = np.random.default_rng(0)
    G, P = 2, 12
    h = jnp.asarray(rng.normal(size=(G, P, cfg.hidden_size)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(P)[None], (G, P))
    cos, sin = cfg.rope_tables(64)
    q = mla.project_q(h, lp, cfg)
    c, k_r = mla.project_latent(h, lp, cfg)
    q, k_r = mla.rotate(q, k_r, cos, sin, pos, cfg)
    rows = jnp.concatenate([c, k_r], -1)
    expanded = mla.attend_expanded(q, rows, lp, cfg)
    cache = _cache(cfg, G, 16)
    pool = paged._write_pool(cache.k[0], cache.table, pos, rows,
                             jnp.ones((G, P), bool))
    o_lat = mla.latent_paged_attention(mla.absorb_q(q, lp, cfg), pool,
                                       cache.table, pos, None, cfg, "xla")
    absorbed = mla.unabsorb_o(o_lat, lp, cfg)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=2e-6)


@pytest.mark.parametrize("shape", [(3, 1, 1, 8), (2, 8, 4, 2), (2, 6, 3, 3)])
def test_latent_kernel_matches_gather_reference(shape):
    R, P, q_tile, nb = shape
    rng = np.random.default_rng(R * 10 + P)
    H, W, V, N, bs, M = 4, 40, 32, 40, 4, 8
    q = jnp.asarray(rng.normal(size=(R, P, H, W)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(N, bs, W)), jnp.float32)
    table = jnp.asarray(rng.permutation(N)[:R * M].reshape(R, M), jnp.int32)
    start = rng.integers(0, M * bs - P, R)
    pos = jnp.asarray(start[:, None] + np.arange(P)[None], jnp.int32)
    valid = jnp.asarray(rng.random((R, P)) < 0.8).at[:, 0].set(True)
    out = mla_paged_attention(q, pool, table, pos, valid, scale=0.2,
                              v_width=V, q_tile=q_tile, blocks_per_step=nb)
    ref = mla.latent_paged_attention_xla(q, pool, table, pos, 0.2, V)
    keep = np.asarray(valid)[:, :, None, None]
    np.testing.assert_allclose(np.where(keep, out, 0), np.where(keep, ref, 0),
                               atol=2e-6)
    assert not np.any(np.where(keep, 0, out))    # invalid queries: zeros


def _full_grid_kernel(q, pool, table, pos, valid, *, scale, v_width, q_tile,
                      nb):
    """The latent kernel as it was before its grid became a work list
    (PR 29's, kept here as the yardstick): a straight loop over EVERY
    (row, query tile, chunk), a dead chunk skipped in the body, the same
    dots in the same order on the live ones."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    R, P, H, W = q.shape
    bs, M = pool.shape[1], table.shape[1]
    Pt, T, nb, C = ragged_attention._attn_tiling(P, M, q_tile, nb)
    G = Pt * H
    live_tok = jnp.max(jnp.where(valid, pos + 1, 0).reshape(R, T, Pt), axis=2)
    live = ((live_tok + bs - 1) // bs).astype(jnp.int32)

    def kernel(tab_ref, live_ref, pos_ref, val_ref, q_ref, *rest):
        k_refs, (o_ref, acc_ref, m_ref, l_ref) = rest[:nb], rest[nb:]
        r, t, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        nlive = (live_ref[r, t] + nb - 1) // nb

        @pl.when(c == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, ragged_attention._NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(c < nlive)
        def _accumulate():
            k = jnp.concatenate([kr[0] for kr in k_refs], axis=0)
            kpos = c * (nb * bs) + jax.lax.broadcasted_iota(
                jnp.int32, (G, nb * bs), 1)
            vis = (kpos <= pos_ref[0, 0]) & (val_ref[0, 0] != 0)
            s = jax.lax.dot_general(
                q_ref[0, 0], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(vis, s, ragged_attention._NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(vis, jnp.exp(s - m_new), 0.0)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                p.astype(k.dtype), k[:, :v_width],
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        @pl.when(c == jnp.maximum(nlive - 1, 0))
        def _finalize():
            l = l_ref[...]
            o_ref[0, 0] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
                           ).astype(o_ref.dtype)

    def rows(x):
        x = jnp.broadcast_to(x.reshape(R, T, Pt, 1), (R, T, Pt, H))
        return x.reshape(R, T, G, 1)

    def tile_map(r, t, c, tab, live):
        return (r, t, 0, 0)

    def kv_map(b):
        def index(r, t, c, tab, live):
            j = jnp.minimum(c * nb + b, jnp.maximum(live[r, t] - 1, 0))
            return (jnp.maximum(tab[r, j], 0), 0, 0)
        return index

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(R, T, C),
        in_specs=[pl.BlockSpec((1, 1, G, 1), tile_map)] * 2
        + [pl.BlockSpec((1, 1, G, W), tile_map)]
        + [pl.BlockSpec((1, bs, W), kv_map(b)) for b in range(nb)],
        out_specs=pl.BlockSpec((1, 1, G, v_width), tile_map),
        scratch_shapes=[pltpu.VMEM((G, v_width), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32)])
    with jax.enable_x64(False):
        o = pl.pallas_call(
            kernel, grid_spec=spec, interpret=True,
            out_shape=jax.ShapeDtypeStruct((R, T, G, v_width), q.dtype))(
            table, live, rows(pos), rows(valid.astype(jnp.int32)),
            q.reshape(R, T, G, W), *([pool] * nb))
    return o.reshape(R, P, H, v_width)


def _decode_rows(live, ctx):
    """A `[8, 1]` decode call: rows `live` valid, row r at context
    `ctx[r]` (its query sits at position ctx[r] - 1)."""
    valid = np.zeros((8, 1), bool)
    valid[list(live)] = True
    return valid, np.asarray(ctx, np.int32)[:, None] - 1


_CTX = (5, 30, 12, 1, 17, 32, 9, 24)        # of at most M * bs = 32 keys
# a chunk is nb x bs = 2 x 4 = 8 keys: contexts on its boundaries
_AT, _SHORT, _PAST = [[8 * k + d for k in (1, 2, 3, 1, 2, 3, 1, 2)]
                      for d in (0, -1, 1)]
_PREFILL = np.ones((4, 8), bool)
_PREFILL[1] = False                         # a wholly dead row
_PREFILL[2, 6:] = False                     # a half-valid second tile
_PREFILL[3, 2:] = False                     # a second tile with no query
WORK_CASES = {
    # name: (valid [R, P], positions [R, P], q_tile, dtype)
    "live-none": (*_decode_rows((), _CTX), 1, jnp.float32),
    "live-one": (*_decode_rows((3,), _CTX), 1, jnp.float32),
    "live-scattered": (*_decode_rows((0, 2, 5, 6), _CTX), 1, jnp.float32),
    "live-all": (*_decode_rows(range(8), _CTX), 1, jnp.float32),
    "context-at-chunk-end": (*_decode_rows(range(8), _AT), 1, jnp.float32),
    "context-one-short": (*_decode_rows(range(8), _SHORT), 1, jnp.float32),
    "context-one-past": (*_decode_rows(range(8), _PAST), 1, jnp.float32),
    "prefill-dead-row-half-tile": (
        _PREFILL, np.asarray([0, 4, 8, 20])[:, None] + np.arange(8)[None],
        4, jnp.float32),
    "scattered-bfloat16": (*_decode_rows((1, 4, 5, 7), _CTX), 1,
                           jnp.bfloat16),
    "prefill-bfloat16": (
        _PREFILL, np.asarray([3, 4, 8, 21])[:, None] + np.arange(8)[None],
        2, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(WORK_CASES))
def test_latent_kernel_walks_only_live_work(case):
    """The grid is the work list: valid queries equal the gather
    reference, invalid ones are zeros, every live row is bit-equal to the
    full-grid kernel's (same dots, same order), and the list holds
    `ceil(live_blocks / nb)` items for each (row, tile) with a valid
    query and nothing else."""
    valid, pos, q_tile, dtype = WORK_CASES[case]
    R, P = valid.shape
    H, W, V, bs, M, nb = 4, 40, 32, 4, 8, 2
    rng = np.random.default_rng(len(case))
    N = R * M + 3
    q = jnp.asarray(rng.normal(size=(R, P, H, W)), dtype)
    pool = jnp.asarray(rng.normal(size=(N, bs, W)), dtype)
    table = jnp.asarray(rng.permutation(N)[:R * M].reshape(R, M), jnp.int32)
    valid, pos = jnp.asarray(valid), jnp.asarray(pos, jnp.int32)
    kw = dict(scale=0.2, v_width=V, q_tile=q_tile)
    out = np.asarray(mla_paged_attention(
        q, pool, table, pos, valid, blocks_per_step=nb, **kw
    ).astype(jnp.float32))
    keep = np.asarray(valid)[:, :, None, None]
    assert not np.any(np.where(keep, 0, out))    # invalid queries: zeros
    ref = mla.latent_paged_attention_xla(q, pool, table, pos, 0.2, V)
    np.testing.assert_allclose(
        np.where(keep, out, 0), np.where(keep, ref.astype(jnp.float32), 0),
        atol=2e-6 if dtype == jnp.float32 else 2e-2)
    full = np.asarray(_full_grid_kernel(q, pool, table, pos, valid, nb=nb,
                                        **kw).astype(jnp.float32))
    assert np.array_equal(out, full)
    # the list: every (row, tile) with a valid query, its chunks in order
    work = ragged_attention.attn_work_list(
        pos, valid, block_size=bs, table_width=M, q_tile=q_tile,
        blocks_per_step=nb)
    Pt = q_tile
    top = np.where(np.asarray(valid), np.asarray(pos) + 1, 0
                   ).reshape(R, P // Pt, Pt).max(-1)
    want = [(r, t, c) for r in range(R) for t in range(P // Pt)
            for c in range(-(-(-(-int(top[r, t]) // bs)) // nb))]
    n = int(work.count)
    assert n == len(want)
    assert len(work.row) == ragged_attention.attn_grid_steps(
        R, P, M, q_tile, nb) == R * (P // Pt) * (M // nb)
    assert list(zip(*(np.asarray(a)[:n].tolist() for a in
                      (work.row, work.tile, work.chunk)))) == want


def _tokens(n_rows, n, vocab, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, (n_rows, n)
                                                ).astype(np.int32)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("how", ["cold", "cached_suffix", "fused"])
def test_paged_forward_matches_reference_logits(model, how, impl):
    """Prefill, then decode through the latent pool, against the
    reference's full forward over the same tokens."""
    d, cfg, params = model
    B, P, n_dec = 2, 12, 3
    toks = _tokens(B, P + n_dec, d["V"])
    ref = np.asarray(reference.logits(SEED, d, jnp.asarray(toks),
                                      jnp.float32))
    cache = _cache(cfg, B, 24)
    pos = jnp.broadcast_to(jnp.arange(P)[None], (B, P))
    ones = jnp.ones((B, P), bool)
    t = jnp.asarray(toks)
    if how == "cold":
        logits, cache = paged.forward_paged(params, t[:, :P], cache, pos,
                                            ones, cfg, is_prefill=True)
    elif how == "cached_suffix":
        cut = 8         # two blocks cold, the rest through the block table
        _, cache = paged.forward_paged(params, t[:, :cut], cache,
                                       pos[:, :cut], ones[:, :cut], cfg,
                                       is_prefill=True)
        tail, cache = paged.forward_paged(
            params, t[:, cut:P], cache, pos[:, cut:], ones[:, cut:], cfg,
            is_prefill=False, attention_impl=impl)
        np.testing.assert_allclose(np.asarray(tail), ref[:, cut:P], atol=TOL)
        logits = None
    else:
        # row 0 decodes its token P-1 while row 1's whole prompt prefills,
        # as serve_fused_step packs them
        _, c0 = paged.forward_paged(params, t[:1, :P - 1], cache._replace(
            table=cache.table[:1], lengths=cache.lengths[:1]),
            pos[:1, :P - 1], ones[:1, :P - 1], cfg, is_prefill=True)
        groups = (paged._RowGroup(t[:1, P - 1:P], cache.table[:1],
                                  pos[:1, P - 1:P], ones[:1, :1]),
                  paged._RowGroup(t[1:, :P], cache.table[1:], pos[1:],
                                  ones[1:]))
        x, pools, stats = paged._forward_groups(
            params, groups, (c0.k, None, None, None), cfg, False, impl)
        fused = np.asarray(paged._final_head_cached(params, x, cfg))
        np.testing.assert_allclose(fused[0], ref[0, P - 1], atol=TOL)
        np.testing.assert_allclose(fused[1:], ref[1, :P], atol=TOL)
        # P + 1 valid tokens, k choices each, over 2 expert layers
        assert 0 < int(stats["moe_pairs"]) <= (P + 1) * d["k"] * 2
        # one layer's kernel calls: the decode row's one chunk and the
        # prefill row's one tile of one chunk (24 keys fit one chunk);
        # the gather reference walks no grid and counts nothing
        assert ("attn_work_steps" in stats) == (impl == "pallas")
        if impl == "pallas":
            assert int(stats["attn_work_steps"]) == 2
        cache = cache._replace(k=pools[0])
        logits = None
    if logits is not None:
        np.testing.assert_allclose(np.asarray(logits), ref[:, :P], atol=TOL)
    cache = cache._replace(lengths=jnp.full((B,), P, jnp.int32))
    for j in range(n_dec):
        at = jnp.full((B, 1), P + j, jnp.int32)
        step, cache = paged.forward_paged(
            params, t[:, P + j:P + j + 1], cache, at, jnp.ones((B, 1), bool),
            cfg, is_prefill=False, attention_impl=impl)
        np.testing.assert_allclose(np.asarray(step[:, 0]), ref[:, P + j],
                                   atol=TOL)


def test_routing_matches_reference(model):
    d, cfg, params = model
    h = jnp.asarray(np.random.default_rng(1).normal(size=(50, d["D"])),
                    jnp.float32)
    w = params["moe_layers"]["router"][0]
    idx, gates = moe.sigmoid_top_k(h, w, d["k"], d["route_scale"], True)
    ridx, rgates = reference.route(h, w.astype(jnp.float32), d)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(rgates),
                               rtol=1e-6)
    # k distinct experts a token, gates sum to the scaling factor
    assert all(len(set(r)) == d["k"] for r in np.asarray(idx))
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-5)
    assert gates.dtype == jnp.float32


def _experts(rng, n, D, F):
    return {"experts_gate": jnp.asarray(rng.normal(size=(n, D, F)) * 0.2,
                                        jnp.float32),
            "experts_up": jnp.asarray(rng.normal(size=(n, D, F)) * 0.2,
                                      jnp.float32),
            "experts_down": jnp.asarray(rng.normal(size=(n, F, D)) * 0.2,
                                        jnp.float32)}


def _stacked(lp, layers=3, layer=1):
    """The layer's experts as layer `layer` of a stack whose other layers
    hold other weights (the form `expert_share_ffn` takes them in)."""
    out = {"router": lp["router"]}
    for m in ("experts_gate", "experts_up", "experts_down"):
        out[m] = jnp.stack([lp[m] if i == layer else lp[m][::-1] * (i + 2.0)
                            for i in range(layers)])
    return out


def _loop_over_experts(h, lp, k, first, scale):
    """sum_{i in top-k, first <= i < first + n} g_i E_i(h), the plain way."""
    d = {"k": k, "norm_topk": True, "route_scale": scale}
    idx, gates = reference.route(h, lp["router"], d)
    y = jnp.zeros_like(h)
    for j in range(lp["experts_gate"].shape[0]):
        g = jnp.sum(jnp.where(idx == first + j, gates, 0.0), -1)
        y = y + g[:, None] * reference._mlp(
            h, lp["experts_gate"][j], lp["experts_up"][j],
            lp["experts_down"][j], jnp.matmul)
    return y


def test_dropless_when_every_token_picks_one_expert():
    """No capacity: a routing that sends all T tokens to one expert
    computes all T, and the padding rows none."""
    rng = np.random.default_rng(2)
    T, D, F, E, n, k = 40, 16, 8, 16, 4, 3
    h = jnp.asarray(np.abs(rng.normal(size=(T, D))) + 0.1, jnp.float32)
    router = rng.normal(size=(D, E)) * 0.05
    router[:, 5] = 2.0              # every token's first choice: expert 5
    lp = {"router": jnp.asarray(router, jnp.float32),
          **_experts(rng, n, D, F)}
    valid = jnp.asarray(np.arange(T) < 33)
    y, st = moe.expert_share_ffn(h, _stacked(lp), k=k, first=4, scale=2.5,
                                 valid=valid, layer=jnp.int32(1))
    want = _loop_over_experts(h, lp, k, 4, 2.5)
    np.testing.assert_allclose(np.asarray(y)[:33], np.asarray(want)[:33],
                               atol=1e-5)
    assert not np.any(np.asarray(y)[33:])
    assert int(st["moe_load_max"]) == 33          # expert 5 took them all
    assert int(st["moe_full_passes"]) == 0        # 120 pairs: one buffer
    assert 33 <= int(st["moe_pairs"]) <= 33 * k
    # blocked over tokens (the bound on the sorted buffer): same result
    yb, stb = moe.expert_share_ffn(h, _stacked(lp), k=k, first=4, scale=2.5,
                                   valid=valid, layer=1, token_block=16)
    np.testing.assert_allclose(np.asarray(yb), np.asarray(y), atol=1e-6)
    assert int(stb["moe_pairs"]) == int(st["moe_pairs"])


def _dictated(T, E, k, first, n, local_choices, rng):
    """Tokens and a router that route as told: token t's top-k are
    `local_choices[t]` held experts (first + (t + j) % n) and absent ones
    for the rest. h is 3 * one-hot(t), so a token's logits are its own row
    of the router, distinct scores, no ties."""
    absent = [i for i in range(E) if not first <= i < first + n]
    h = 3.0 * np.eye(T, dtype=np.float32)
    router = np.full((T, E), -4.0, np.float32)
    for t in range(T):
        m = int(local_choices[t])
        picks = [first + (t + j) % n for j in range(m)]
        picks += list(rng.permutation(absent)[:k - m])
        router[t, picks] = 2.0 - 0.2 * np.arange(k)
    return jnp.asarray(h), jnp.asarray(router)


# (T, held n, router width E, k, local choices of token t, valid tokens,
#  token_block, token blocks whose local pairs overflow one buffer).
# With k 4 and T 64 a block sorts 256 pairs; n / E = 1 / 8 makes the
# sorted buffer 128 rows (`moe._short_rows`).
SHORT_CASES = {
    "few-local-pairs": (64, 3, 24, 4, lambda t: t % 2, 64, 1024, 0),
    "local-pairs-fill-the-short-buffer":           # n_local == S
        (64, 3, 24, 4, lambda t: 2, 64, 1024, 0),
    "one-pair-over-the-short-buffer":              # n_local == S + 1
        (64, 3, 24, 4, lambda t: 3 if t == 0 else 2, 64, 1024, 1),
    "every-pair-local": (64, 4, 32, 4, lambda t: 4, 64, 1024, 1),
    "masked-rows-bring-it-under":                  # 129 - 8 x 2 = 113
        (64, 3, 24, 4, lambda t: 3 if t == 0 else 2, 56, 1024, 0),
    "wider-block": (96, 3, 24, 4, lambda t: 1, 96, 1024, 0),
    "half-the-experts-held-no-short-buffer":
        (64, 8, 16, 4, lambda t: t % 3, 64, 1024, 0),
    "two-token-blocks-one-overflows":
        (128, 3, 24, 4, lambda t: 3 if t < 64 else 1, 128, 64, 1),
}


@pytest.mark.parametrize("case", list(SHORT_CASES))
def test_short_sorted_buffer_is_exact_and_counted(case, monkeypatch):
    """Passes over a short sorted buffer and one pass over all pairs give
    the same layer and the same counters, whatever the routing;
    `moe_full_passes` counts the overflows; and both equal the plain loop
    over the held experts."""
    T, n, E, k, choices, n_valid, block, full_passes = SHORT_CASES[case]
    rng = np.random.default_rng(len(case))
    first, F = 4, 8
    h, router = _dictated(T, E, k, first, n,
                          [choices(t) for t in range(T)], rng)
    lp = {"router": router, **_experts(rng, n, T, F)}
    valid = jnp.asarray(np.arange(T) < n_valid)
    kw = dict(k=k, first=first, scale=2.5, valid=valid, layer=jnp.int32(1),
              token_block=block)
    n_local = sum(choices(t) for t in range(n_valid))
    pairs = min(T, block) * k
    S = moe._short_rows(pairs, n, E)
    if n != 8:
        assert S == 128 < pairs
    y, st = moe.expert_share_ffn(h, _stacked(lp), **kw)
    assert int(st["moe_full_passes"]) == full_passes
    assert int(st["moe_pairs"]) == n_local
    want = _loop_over_experts(h, lp, k, first, 2.5)
    np.testing.assert_allclose(np.asarray(y)[:n_valid],
                               np.asarray(want)[:n_valid], atol=1e-5)
    assert not np.any(np.asarray(y)[n_valid:])
    # one buffer of all the pairs: nothing can overflow it
    monkeypatch.setattr(moe, "_short_rows", lambda pairs, held, routed: pairs)
    y_full, st_full = moe.expert_share_ffn(h, _stacked(lp), **kw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_full), atol=1e-6)
    assert int(st_full["moe_full_passes"]) == 0
    for name in ("moe_pairs", "moe_experts_hit", "moe_load_max"):
        assert int(st[name]) == int(st_full[name]), name


@pytest.mark.parametrize("pairs, held, routed, rows", [
    (512, 12, 192, 128),        # a decode step of 64 slots, top-8
    (1536, 12, 192, 384),       # a fused step on the 128 bucket
    (4608, 12, 192, 640),       # a fused step on the 512 bucket
    (8192, 12, 192, 1152),      # a 1024-token block of a cold prefill
    (16, 6, 16, 128),           # the tiny model: no short buffer (>= 16)
    (512, 96, 192, 640),        # half the experts held: none (>= 512)
])
def test_short_rows_follow_the_pairs_and_the_held_share(pairs, held, routed,
                                                        rows):
    assert moe._short_rows(pairs, held, routed) == rows
    assert rows % 256 == 128 and rows >= 2 * pairs * held / routed


def test_shares_add_up_to_the_uncut_layer():
    """A 16-expert layer cut into 4 shares of 4: the routed parts all four
    chips compute, plus the shared expert ONCE, equal the uncut layer."""
    rng = np.random.default_rng(4)
    T, D, F, E, k = 24, 16, 8, 16, 4
    h = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    whole = {"router": jnp.asarray(rng.normal(size=(D, E)), jnp.float32),
             **_experts(rng, E, D, F)}
    shared = {n: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
              for n, s in (("gate_proj", (D, F)), ("up_proj", (D, F)),
                           ("down_proj", (F, D)))}
    shared_out = reference._mlp(h, shared["gate_proj"], shared["up_proj"],
                                shared["down_proj"], jnp.matmul)
    uncut = _loop_over_experts(h, whole, k, 0, 2.5) + shared_out
    parts, pairs = jnp.zeros_like(h), 0
    for c in range(4):
        share = {"router": whole["router"],
                 **{n: whole[n][4 * c:4 * c + 4] for n in
                    ("experts_gate", "experts_up", "experts_down")}}
        y, st = moe.expert_share_ffn(h, _stacked(share, 2, 0), k=k,
                                     first=4 * c, scale=2.5, layer=0)
        parts, pairs = parts + y, pairs + int(st["moe_pairs"])
    np.testing.assert_allclose(np.asarray(parts + shared_out),
                               np.asarray(uncut), atol=1e-5)
    assert pairs == T * k           # every pair computed on exactly one chip


def test_latent_pool_is_one_array(model):
    d, cfg, params = model
    b = paged.ContinuousBatcher(params, cfg, max_batch=2, block_size=4,
                                max_total_len=32, max_new_tokens=4)
    W = d["R"] + d["dr"]
    assert b.cache.v is None and b.cache.k_scale is None
    assert b.cache.k.shape == (d["L"], 2 * 8, 4, W)
    assert b.kv_bytes_per_token() == W * 4 * d["L"]        # float32 here
    assert b.kv_pool_bytes() == b.cache.k.nbytes
    real = mla.MlaMoeConfig(num_hidden_layers=7, kv_lora_rank=512,
                            qk_rope_head_dim=64)
    assert mla.kv_block_bytes(real, 16) / 16 == 1152 * 7


def test_engine_serves_mixed_prompts_with_a_prefix_hit(model):
    from paddle_tpu import serving
    d, cfg, params = model
    rng = np.random.default_rng(5)
    shared = rng.integers(1, d["V"], 16).tolist()
    prompts = [shared + rng.integers(1, d["V"], n).tolist()
               for n in (5, 9)] + [rng.integers(1, d["V"], 21).tolist()]
    eng = serving.ServingEngine(
        params, cfg, max_batch=4, block_size=4, max_total_len=64,
        max_new_tokens=6, prefill_buckets=(8, 16), chunk=2,
        max_prefill_group=2, start=False)
    try:
        eng.warmup()
        eng.start()
        first = eng.submit(prompts[0], max_new_tokens=6)
        next(first.stream())            # decoding: the next ones fuse
        rest = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
        outs = [list(h.result(timeout=300)) for h in [first] + rest]
        assert eng.drain(60)
        b = eng.batcher
        assert b.prefix_stats()["hit_tokens"] >= 16
        assert b.fused_steps >= 1 and b.alloc.free_blocks == b.alloc.num_blocks
        recs = [r for r in b.flight.records() if r["mode"] in
                ("decode", "fused") and r.get("closed")]
        assert recs and all(r["moe_pairs"] > 0 and 1 <= r["moe_load_max"]
                            and r["moe_experts_hit"] >= 1 for r in recs)
        # 16 pairs a step fit one sorted buffer: nothing overflows
        assert {r["mode"] for r in recs} == {"decode", "fused"}
        assert all(r["moe_full_passes"] == 0 for r in recs)
    finally:
        eng.shutdown(drain=False, timeout=60)
    # the same greedy tokens as the reference's full forward, token by
    # token (float32 both sides: a near-tie would have to be within 2e-5)
    for p, out in zip(prompts, outs):
        seq = np.asarray([p + out], np.int32)
        ref = np.asarray(reference.logits(SEED, d, jnp.asarray(seq),
                                          jnp.float32))[0]
        at = ref[len(p) - 1:len(p) - 1 + len(out)]
        assert np.all(at.max(-1) - at[np.arange(len(out)), out] < TOL)


def test_flight_records_carry_the_kernels_work_beside_its_full_grid(model):
    """Every decode and fused tick of a batcher whose latent attention is
    the kernel notes `attn_work_steps` (one layer's work items, added up
    on the device) beside `attn_grid_steps` (the full grid of the same
    calls, from shapes); the gather reference walks no grid and notes
    neither."""
    d, cfg, params = model
    rng = np.random.default_rng(7)
    short = rng.integers(1, d["V"], 5).tolist()
    long = rng.integers(1, d["V"], 20).tolist()

    def serve(impl):
        b = paged.ContinuousBatcher(
            params, cfg, max_batch=4, block_size=4, max_total_len=64,
            max_new_tokens=8, chunk=2, max_prefill_bucket=8,
            attention_impl=impl)
        b.submit(short)
        b.step()
        b.submit(long)          # joins mid-decode: its chunks ride fused
        b.run()
        recs = [r for r in b.flight.records()
                if r["mode"] in ("decode", "fused")]
        assert {r["mode"] for r in recs} == {"decode", "fused"}
        return b, recs

    b, recs = serve("pallas")
    # a table of 16 blocks is one chunk a row: a `[4, 1]` decode call
    # spans 4 grid steps, an `[1, 8]` prefill call one
    for r in recs:
        grid = b.chunk * b.B + (r["rows"] if r["mode"] == "fused" else 0)
        assert r["attn_grid_steps"] == grid
        # a live row is one item a decode step; a prefill row one
        live = b.chunk * r["active_slots"] + (r["mode"] == "fused")
        assert 0 < r["attn_work_steps"] <= live
    assert any(r["attn_work_steps"] < r["attn_grid_steps"] for r in recs)
    _, recs = serve("xla")
    assert not any("attn_work_steps" in r or "attn_grid_steps" in r
                   for r in recs)


@pytest.mark.parametrize("option", [
    {"kv_dtype": "int8"}, {"weight_dtype": "int8"}, {"speculative": True},
    {"mesh": object()}])
def test_unsupported_options_are_refused_at_construction(model, option):
    d, cfg, params = model
    with pytest.raises(NotImplementedError, match=next(iter(option))):
        paged.ContinuousBatcher(params, cfg, max_batch=2, block_size=4,
                                max_total_len=32, max_new_tokens=4, **option)


def test_kv_transfer_is_refused():
    from paddle_tpu import serving
    cfg = mla.MlaMoeConfig.tiny(experts_first=4, experts_count=8)
    params = mla.init_params(jax.random.key(0), cfg)
    assert params["moe_layers"]["experts_up"].shape == (2, 8, 64, 32)
    b = paged.ContinuousBatcher(params, cfg, max_batch=2, block_size=4,
                                max_total_len=32, max_new_tokens=4)
    with pytest.raises(NotImplementedError, match="export_kv"):
        b.export_kv(0)
    with pytest.raises(NotImplementedError, match="import_kv"):
        b.import_kv(None)
    with pytest.raises(NotImplementedError, match="prefill"):
        serving.ServingEngine(params, cfg, role="prefill", start=False)


def test_prefill_group_cap_bounds_the_ladder():
    from paddle_tpu.nlp import llama
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    kw = dict(max_batch=8, block_size=8, max_total_len=64, max_new_tokens=4,
              prefill_buckets=(16,))
    wide = paged.ContinuousBatcher(params, cfg, **kw)
    capped = paged.ContinuousBatcher(params, cfg, max_prefill_group=2, **kw)
    assert [wide._group_pad(g) for g in (1, 3, 8)] == [1, 4, 8]
    assert [capped._group_pad(g) for g in (1, 3, 8)] == [1, 2, 2]
    recs = [paged._Admission(i, i, [1] * 9, -1, 4, 2, [], 0, None, [], [],
                             [(0, 9, 16)]) for i in range(5)]
    assert [len(u) for u in capped._units(recs)] == [2, 2, 1]
    assert [len(u) for u in wide._units(recs)] == [5]
