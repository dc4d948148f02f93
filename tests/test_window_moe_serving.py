"""The window + full GQA decoder with softmax experts on the served path
(nlp/window_moe.py, the kinded pool and the window form of the paged
attention in nlp/paged.py and nlp/ragged_attention.py,
moe.expert_share_ffn with softmax scores) at a tiny size on the CPU,
against the benchmark's plain reference
(benchmark/reference/window_moe_decoder.py: float32, no cache, no ring,
every key with a mask, a loop over the experts).

Tolerances: everything here runs in float32 on the CPU, program and
reference alike, so the two differ by the order of float32 sums only (the
online softmax of the kernel and of flash against one softmax over every
key; the grouped GEMM against the loop over experts). On logits of
magnitude 0.3 the largest difference read is 6e-7 through every path;
TOL = 2e-5 leaves thirtyfold room for another backend's sums, and is a
five-hundredth of what the smallest fault here moves them (the window
left out on window layers: 0.012 at 4 x W; bfloat16 in place of float32:
0.004, tested below).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.models import window_moe_decoder as family        # noqa: E402
from benchmark.reference import window_moe_decoder as reference  # noqa: E402
from paddle_tpu.nlp import moe, paged, window_moe                # noqa: E402
from paddle_tpu.nlp.ragged_attention import ragged_paged_attention  # noqa: E402

TOL = 2e-5
W, BS, CHUNK = 16, 4, 16            # window, block, widest prefill chunk

MODEL = {
    "attention_bias": False, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 48, "intermediate_size": 96,
    # two periods of [window, full]: both kinds, the scan over periods,
    # half the body to compile of the published [window x 3, full] (which
    # benchmark/tests/test_window_moe.py runs)
    "layer_types": ["sliding_attention", "full_attention"] * 2,
    "mlp_layer_types": ["sparse"] * 4, "max_position_embeddings": 512,
    "moe_intermediate_size": 24, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 4, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
            "original_max_position_embeddings": 32, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000.0}},
    "sliding_window": W, "tie_word_embeddings": False,
    "use_sliding_window": True, "vocab_size": 128}
CONFIG = {"family": "window_moe_decoder", **MODEL, "served_dtype": "float32"}
SEED = 3


@pytest.fixture(scope="module")
def model():
    d = family.dims(CONFIG)
    cfg = family.program_config(CONFIG)
    params = family.make_params(SEED, d, jnp.float32)
    return d, cfg, params


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 128, n).astype(np.int32)


M_FIX = 20                          # table width of the direct calls
_FWD = {}


def _forward(cfg, lay, impl, is_prefill):
    """`forward_paged` jitted once a (configuration, layout, backend,
    phase): the cases share three programs a backend (a cold chunk, a
    warm chunk, a decode step)."""
    key = (id(cfg), lay, impl, is_prefill)
    if key not in _FWD:
        _FWD[key] = jax.jit(lambda params, t, cache, pos, val:
                            paged.forward_paged(
                                params, t, cache, pos, val, cfg,
                                is_prefill=is_prefill, attention_impl=impl,
                                layout=lay))
    return _FWD[key]


def _served_logits(params, cfg, toks, chunk, steps, impl):
    """Prefill `toks[:-steps]` in pieces of `chunk` tokens (the last one
    padded to the width and masked), then decode `steps` tokens one at a
    time, through `forward_paged` over a kinded pool (one slot: a chain
    for the full layers, a ring for the window layers). Returns the
    logits at every position."""
    P = len(toks) - steps
    M, R = M_FIX, paged.ring_blocks(W, chunk, BS)
    lay = paged.KVLayout(full_layers=2, window_layers=2, full_blocks=M + 3,
                         window_blocks=R + 2, width=M, ring=R)
    k, v, _, _ = paged.init_pool(cfg, 0, BS, layout=lay)
    # not block 0 and not in order: a table that the code must follow
    row = list(range(M + 2, 2, -1))[:M] + list(range(R + 1, 1, -1))[:R]
    cache = paged.PagedKVCache(k, v, jnp.asarray([row], jnp.int32),
                               jnp.zeros((1,), jnp.int32))
    out = []
    spans = [(s, min(s + chunk, P), chunk) for s in range(0, P, chunk)] \
        + [(p, p + 1, 1) for p in range(P, P + steps)]
    for s, e, width in spans:
        pos = np.minimum(np.arange(s, s + width), M * BS - 1)[None]
        t = np.zeros((1, width), np.int32)
        t[0, :e - s] = toks[s:e]
        lg, cache = _forward(cfg, lay, impl, s == 0)(
            params, jnp.asarray(t), cache, jnp.asarray(pos),
            jnp.asarray(np.arange(width)[None] < e - s))
        out.append(np.asarray(lg[0, :e - s]))
    return np.concatenate(out, 0)


# ---- (a) prefill then decode against the reference's full forward --------
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("prompt", [W - 5, W, 4 * W, 4 * W + 3])
def test_served_logits_match_the_reference(model, impl, prompt):
    """Shorter than, equal to and four times the window: one cold chunk,
    then warm chunks that wrap the ring (9 blocks of 4 for a window of 16
    and chunks of 16), the last one padded; then six decode steps."""
    d, cfg, params = model
    toks = _tokens(prompt + 6, seed=prompt)
    got = _served_logits(params, cfg, toks, CHUNK, 6, impl)
    want = np.asarray(reference.logits(SEED, d, jnp.asarray(toks[None]),
                                       jnp.float32))[0]
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_a_cold_chunk_longer_than_the_window_attends_through_the_table(model):
    """Flash sees all of a cold chunk, so a chunk longer than the window
    takes the paged path with the window's bound (the benchmark's buckets
    stay under the window and never need it)."""
    d, cfg, params = model
    toks = _tokens(4 * W + 2, seed=77)
    got = _served_logits(params, cfg, toks, 4 * W, 2, "xla")
    want = np.asarray(reference.logits(SEED, d, jnp.asarray(toks[None]),
                                       jnp.float32))[0]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("impl,fused", [("pallas", True), ("xla", False)])
def test_batcher_tokens_are_the_references(model, impl, fused):
    """Through `ContinuousBatcher`: admissions of every length, alone and
    in groups, plain and fused ticks; every served token is the
    reference's own first at its position (gap under TOL). (XLA with
    fused ticks: the engine's test below.)"""
    d, cfg, params = model
    cb = paged.ContinuousBatcher(
        params, cfg, max_batch=3, block_size=BS, max_total_len=96,
        max_new_tokens=8, prefill_buckets=(CHUNK,), chunk=4,
        attention_impl=impl, fused_prefill=fused, max_prefill_group=2)
    prompts = [_tokens(n, seed=n).tolist() for n in (5, W, 4 * W, 30, 17)]
    rids = [cb.submit(p) for p in prompts]
    out = cb.run()
    gaps = reference.served_gaps(SEED, d, prompts, [out[r] for r in rids],
                                 weight_dtype=jnp.float32, pad=32)
    assert gaps.shape == (40,) and float(gaps.max()) < TOL
    assert (cb.fused_steps > 0) == fused
    # the flight records carry both kinds' blocks and the routing counters
    rec = [r for r in cb.flight.records() if r["mode"] in ("decode", "fused")]
    assert all(r["kv_window_blocks"] <= 3 * cb._layout.ring
               and "kv_full_blocks" in r and r["moe_pairs"] > 0 for r in rec)
    st = cb.alloc_stats()
    assert st["blocks_in_use"] == st["window_blocks_in_use"] == 0


def test_flight_records_carry_both_kinds_of_kernel_work(model):
    """A kinded batcher's decode and fused ticks note `attn_work_steps`,
    the items ONE layer of EACH kind walked (a full layer from block 0, a
    window layer from its window's first block, at most a ring), beside
    `attn_grid_steps`, the full grid of the same calls; both follow the
    schedule. The gather reference notes neither."""
    from attn_work_expect import work_steps
    from paddle_tpu.nlp.ragged_attention import _attn_tiling
    d, cfg, params = model

    def serve(impl):
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=3, block_size=BS, max_total_len=96,
            max_new_tokens=8, prefill_buckets=(CHUNK,), chunk=4,
            attention_impl=impl, max_prefill_group=2)
        cb.submit(_tokens(4 * W, seed=1).tolist())
        while not any(cb.active):
            cb.step()
        cb.submit(_tokens(30, seed=2).tolist())     # two chunks, fused
        cb.run()
        return cb, [r for r in cb.flight.records()
                    if r["mode"] in ("decode", "fused")]

    cb, recs = serve("pallas")
    assert {r["mode"] for r in recs} == {"decode", "fused"}
    lay = cb._layout
    kinds = [(width, window, *(_attn_tiling(P, width, 128, pools=2)[2]
                               for P in (1, CHUNK)))
             for width, window in ((lay.width, None), (lay.ring, W))]
    assert kinds == [(24, None, 8, 16), (9, W, 8, 9)]
    exact = 0
    for r in recs:
        work, grid = work_steps(r, BS, cb.B, kinds)
        assert r["attn_grid_steps"] == grid
        assert 0 < r["attn_work_steps"] <= work < grid
        if r["live_after"] == r["active_slots"]:    # no row retired in it
            assert r["attn_work_steps"] == work
            exact += 1
    assert exact >= 2
    # the long row's window layers walk fewer blocks than its full layers
    full_only = [work_steps(r, BS, cb.B, kinds[:1])[0] for r in recs]
    both = [work_steps(r, BS, cb.B, kinds)[0] for r in recs]
    assert any(b < 2 * f for f, b in zip(full_only, both))
    _, recs = serve("xla")
    assert recs and not any("attn_work_steps" in r or "attn_grid_steps" in r
                            for r in recs)


# ---- (d) what must fail it -----------------------------------------------
def test_dropping_the_window_or_the_precision_fails_the_comparison(model):
    d, cfg, params = model
    toks = _tokens(4 * W + 6, seed=9)
    want = np.asarray(reference.logits(SEED, d, jnp.asarray(toks[None]),
                                       jnp.float32))[0]
    # a program without the bound on window layers (every key kept and
    # seen): as the reference with its window left out
    loose = np.asarray(reference.logits(
        SEED, d, jnp.asarray(toks[None]), jnp.float32, no_window=True))[0]
    assert np.abs(loose[:W] - want[:W]).max() < TOL      # inside the window
    assert np.abs(loose - want).max() > 100 * TOL
    wide = window_moe.WindowMoeConfig(**{
        **family.program_config(CONFIG).__dict__, "sliding_window": 512})
    got = _served_logits(params, wide, toks, CHUNK, 6, "xla")
    assert np.abs(got[:W] - want[:W]).max() < TOL
    assert np.abs(got - want).max() > 100 * TOL
    # the served path in bfloat16 misses the float32 tolerance by far
    half = family.program_config({**CONFIG, "served_dtype": "bfloat16"})
    got = _served_logits(jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                                      params), half, toks, CHUNK, 6, "xla")
    assert np.abs(got - want).max() > 50 * TOL
    # a ring too short for the chunk (keys of the earliest query's window
    # overwritten by the chunk's own writes) fails too: the bound is tight
    assert paged.ring_blocks(W, CHUNK, BS) == 9
    assert paged.ring_blocks(1024, 512, 16) == 97


# ---- (b) the bound, the return of both kinds, a reused ring --------------
def test_window_blocks_are_bounded_and_both_kinds_return(model):
    d, cfg, params = model

    def batcher():
        return paged.ContinuousBatcher(
            params, cfg, max_batch=1, block_size=BS, max_total_len=96,
            max_new_tokens=8, prefill_buckets=(CHUNK,), chunk=4,
            attention_impl="xla")

    cb = batcher()
    lay = cb._layout
    assert (lay.ring, lay.width) == (paged.ring_blocks(W, CHUNK, BS), 24)
    assert lay.ring * BS < 4 * W + 8 <= lay.width * BS
    long, short = _tokens(4 * W, seed=1).tolist(), _tokens(6, seed=2).tolist()
    assert cb.blocks_needed(4 * W, 8) == 18
    assert cb.ring_blocks_needed(4 * W, 8) == lay.ring
    assert cb.ring_blocks_needed(6, 8) == 4
    ra = cb.submit(long)
    seen = []
    while cb.queue or cb._pending or any(cb.active):
        cb.step()
        st = cb.alloc_stats()
        seen.append((st["blocks_in_use"], st["window_blocks_in_use"]))
    assert max(w for _, w in seen) == lay.ring      # never more than a ring
    assert max(f for f, _ in seen) == 18            # the whole chain
    st = cb.alloc_stats()
    assert st["blocks_in_use"] == st["window_blocks_in_use"] == 0
    assert st["window_high_water_blocks"] == lay.ring
    # the same slot and the same ring blocks, reused: what the old request
    # left in them is never read
    rb = cb.submit(short)
    out = cb.run()
    assert cb.alloc_stats()["window_reused_blocks"] >= 4
    fresh = batcher()
    rf = fresh.submit(short)
    assert fresh.run()[rf] == out[rb]
    assert len(out[ra]) == 8
    # bytes: the pool is both kinds' blocks; a token past the ring costs
    # the full layers' rows alone
    row = 2 * 2 * 16 * 4
    assert cb.kv_pool_bytes() == cb.cache.k.nbytes + cb.cache.v.nbytes \
        == (2 * 24 + 2 * lay.ring) * BS * row
    assert cb.kv_bytes_per_token() == 2 * row
    assert cb.kv_ring_bytes() == 2 * lay.ring * BS * row
    # an aborted pending admission returns both kinds too
    cb.submit(long)
    cb._drain_queue()
    assert cb.alloc_stats()["window_blocks_in_use"] == lay.ring
    assert cb.abort(cb._pending[0][0].rid)
    st = cb.alloc_stats()
    assert st["blocks_in_use"] == st["window_blocks_in_use"] == 0


# ---- the kernel and its XLA twin, window and ring ------------------------
@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("P", [1, 8])
def test_window_kernel_agrees_with_its_xla_twin(ring, P):
    rng = np.random.default_rng(P)
    Rr, H, KV, hd, win = 3, 4, 2, 16, 10
    M = 5 if ring else 12
    N = Rr * M + 1
    kp = jnp.asarray(rng.normal(size=(N, BS, KV, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, BS, KV, hd)), jnp.float32)
    table = jnp.asarray(rng.permutation(N - 1)[:Rr * M].reshape(Rr, M) + 1,
                        jnp.int32)
    # rows at different depths, one of them past a ring's first wrap
    last = np.array([P + 2, 29, 44])
    pos = jnp.asarray(last[:, None] - (P - 1) + np.arange(P)[None],
                      jnp.int32)
    valid = jnp.asarray([[True] * P, [True] * P, [True] * (P - 1) + [P == 1]])
    q = jnp.asarray(rng.normal(size=(Rr, P, H, hd)), jnp.float32)
    want = paged._paged_gqa_attention(q, kp, vp, table, pos, valid,
                                      impl="xla", window=win, ring=ring)
    got = ragged_paged_attention(q, kp, vp, table, pos, valid, window=win,
                                 ring=ring, interpret=True)
    ok = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(got)[ok], np.asarray(want)[ok],
                               atol=2e-6, rtol=0)
    if not ring:
        # the window is a bound: without it the result differs
        loose = paged._paged_gqa_attention(q, kp, vp, table, pos, valid)
        assert np.abs(np.asarray(loose) - np.asarray(want))[ok].max() > 1e-3


# the window form's work list (PR 34): case -> (P, q_tile, ring, each
# row's last position; -1 = the row is not live). Window 20 over blocks of
# 4: a ring of 10 blocks (`ring_blocks(20, 16, 4)`), 8 blocks a decode
# step, 16 (the whole ring) a prefill tile's
WINDOW_LIST_CASES = {
    "none-live": (1, 128, True, [-1, -1, -1]),
    "one-row": (1, 128, True, [-1, 30, -1]),
    "all-rows": (1, 128, True, [2, 30, 19]),
    # a chain table 40 wide, a row at its last position: the walk starts
    # at the window's first block, 35, not at block 0
    "full-width": (1, 128, False, [159, 40, -1]),
    # the ring has gone round more than twice: chain block m in m % 10
    "ring-wrapped": (1, 128, True, [97, 36, 5]),
    # 16-query chunks in tiles of 4: row 0's early tiles start and end
    # before its late ones, row 2's first three tiles are padding
    "ragged-tiles": (16, 4, True, [61, 23, 2]),
}


@pytest.mark.parametrize("case", list(WINDOW_LIST_CASES))
def test_window_list_form_matches_its_xla_twin(case):
    """The window + ring form of the kernel over its work list (each
    (row, tile)'s walk starts at its first visible block) against the XLA
    twin; the list against a plain enumeration; handed in or built inside,
    the same output bit for bit."""
    from attn_work_expect import enumerate_work, work_items
    from paddle_tpu.nlp.ragged_attention import _attn_tiling, gqa_work_list
    P, q_tile, ring, last = WINDOW_LIST_CASES[case]
    rng = np.random.default_rng(len(case))
    Rr, H, KV, hd, win = len(last), 4, 2, 16, 20
    M = paged.ring_blocks(win, 16, BS) if ring else 40
    N = Rr * M + 1
    kp = jnp.asarray(rng.normal(size=(N, BS, KV, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, BS, KV, hd)), jnp.float32)
    table = jnp.asarray(rng.permutation(N - 1)[:Rr * M].reshape(Rr, M) + 1,
                        jnp.int32)
    last = np.asarray(last)
    pos = last[:, None] - (P - 1) + np.arange(P)[None]
    valid = jnp.asarray((pos >= 0) & (last >= 0)[:, None])
    pos = jnp.asarray(np.maximum(pos, 0), jnp.int32)
    q = jnp.asarray(rng.normal(size=(Rr, P, H, hd)), jnp.float32)
    want = paged._paged_gqa_attention(q, kp, vp, table, pos, valid,
                                      impl="xla", window=win, ring=ring)
    kw = dict(window=win, ring=ring, q_tile=q_tile, interpret=True)
    got = np.asarray(ragged_paged_attention(q, kp, vp, table, pos, valid,
                                            **kw))
    ok = np.asarray(valid)
    np.testing.assert_allclose(got[ok], np.asarray(want)[ok], atol=2e-6,
                               rtol=0)
    assert not got[~ok].any()
    work = gqa_work_list(pos, valid, M, kp.shape, kp.dtype, window=win,
                         q_tile=q_tile)
    Pt, T, nb, _ = _attn_tiling(P, M, q_tile, pools=2)
    assert nb == (8 if P == 1 else min(16, M))
    items, firsts = enumerate_work(pos, valid, BS, M, Pt, nb, window=win)
    n = int(work.count)
    assert work_items(work) == items
    assert np.array_equal(np.asarray(work.first), firsts)
    if case == "none-live":
        assert n == 0 and not got.any()
    if case == "full-width":
        assert firsts[0, 0] == 35 and items == [(0, 0, 0), (1, 0, 0)]
    if case == "ring-wrapped":
        # the walk's blocks lie past the ring's width: they wrap
        assert firsts[0, 0] * BS > M * BS
    if case == "ragged-tiles":
        assert firsts[0, 0] < firsts[0, -1]
        assert not any(r == 2 and t < 3 for r, t, _ in items)
    again = np.asarray(ragged_paged_attention(q, kp, vp, table, pos, valid,
                                              work=work, **kw))
    assert np.array_equal(got, again)
    # a plain call's list has no starts: refused here by shape
    with pytest.raises(ValueError, match="work list"):
        ragged_paged_attention(
            q, kp, vp, table, pos, valid, **kw,
            work=gqa_work_list(pos, valid, M, kp.shape, kp.dtype,
                               q_tile=q_tile))


# ---- (c) the router ------------------------------------------------------
def test_softmax_router_matches_the_reference_at_a_near_tie():
    rng = np.random.default_rng(4)
    D, E, k = 32, 64, 8
    h = jnp.asarray(rng.normal(size=(12, D)), jnp.float32)
    w = rng.normal(size=(D, E)).astype(np.float32) * 0.3
    # token 0: the eighth and ninth scores a float32 ulp-scale apart
    logit = np.asarray(h[0]) @ w
    order = np.argsort(-logit)
    w[:, order[8]] += np.asarray(h[0]) * (
        (logit[order[7]] - logit[order[8]] - 3e-6) / float(h[0] @ h[0]))
    w = jnp.asarray(w)
    dd = {"k": k, "norm_topk": True}
    idx, gates = moe.softmax_top_k(h, w, k)
    ridx, rgates = reference.route(h, w, dd)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(rgates),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-6)
    p = np.asarray(jax.nn.softmax(h @ w, -1))[0]
    assert abs(np.sort(p)[-8] - np.sort(p)[-9]) < 1e-6 * p.max() * 50
    # not renormalised: the plain probabilities
    _, raw = moe.softmax_top_k(h, w, k, normalize=False)
    assert float(np.asarray(raw).sum(-1).max()) < 1.0


def test_shares_of_sixteen_experts_add_up_to_the_layer():
    """The guide's test of the share: four chips that each hold 16 of the
    64 experts compute parts that add up to what one chip holding all 64
    computes, which is the reference's whole layer."""
    rng = np.random.default_rng(5)
    T, D, F, E, k = 20, 32, 24, 64, 8
    h = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    lp = {"router": jnp.asarray(rng.normal(size=(D, E)) * 0.3, jnp.float32),
          "experts_gate": jnp.asarray(rng.normal(size=(1, E, D, F)) * 0.1,
                                      jnp.float32),
          "experts_up": jnp.asarray(rng.normal(size=(1, E, D, F)) * 0.1,
                                    jnp.float32),
          "experts_down": jnp.asarray(rng.normal(size=(1, E, F, D)) * 0.1,
                                      jnp.float32)}
    whole, st = moe.expert_share_ffn(h, lp, k=k, first=0, score="softmax")
    assert int(st["moe_pairs"]) == T * k and int(st["moe_full_passes"]) == 0
    parts = 0.0
    for c in range(4):
        sub = {"router": lp["router"],
               **{m: lp[m][:, 16 * c:16 * (c + 1)]
                  for m in ("experts_gate", "experts_up", "experts_down")}}
        y, s = moe.expert_share_ffn(h, sub, k=k, first=16 * c,
                                    score="softmax")
        parts = parts + y
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=1e-5)
    idx, gates = reference.route(h, lp["router"], {"k": k, "norm_topk": True})
    want = np.zeros((T, D), np.float32)
    for t in range(T):
        for j in range(k):
            e = int(idx[t, j])
            g = np.asarray(h[t]) @ np.asarray(lp["experts_gate"][0, e])
            u = np.asarray(h[t]) @ np.asarray(lp["experts_up"][0, e])
            want[t] += float(gates[t, j]) * (
                (g / (1 + np.exp(-g)) * u)
                @ np.asarray(lp["experts_down"][0, e]))
    np.testing.assert_allclose(np.asarray(whole), want, atol=1e-5)
    # sigmoid stays the default, and differs
    sig, _ = moe.expert_share_ffn(h, lp, k=k, first=0)
    assert np.abs(np.asarray(sig) - want).max() > 1e-3


# ---- two RoPE tables -----------------------------------------------------
def test_each_kind_rotates_with_its_own_table(model):
    d, cfg, _ = model
    tabs = cfg.rope_tables(64)
    assert set(tabs) == {"full", "window"} == set(cfg.period_kinds)
    assert cfg.period_kinds == ("window", "full")
    assert window_moe.WindowMoeConfig.tiny().period_kinds == \
        ("window",) * 3 + ("full",)
    for kind in tabs:
        rp = d["rope"][kind]
        ang = np.arange(64)[:, None] * reference.inv_freq(16, rp)[None]
        af = reference.attention_factor(rp)
        np.testing.assert_allclose(np.asarray(tabs[kind][0]),
                                   np.cos(ang) * af, atol=2e-6)
        np.testing.assert_allclose(np.asarray(tabs[kind][1]),
                                   np.sin(ang) * af, atol=2e-6)
    assert reference.attention_factor(d["rope"]["window"]) == 1.0
    assert reference.attention_factor(d["rope"]["full"]) == pytest.approx(
        0.1 * np.log(4.0) + 1.0)
    # the published full-layer factor is m(16)
    assert 0.1 * np.log(16.0) + 1.0 == pytest.approx(1.2772588722239782)


# ---- (e) what is refused, by name ----------------------------------------
@pytest.mark.parametrize("kw,name", [
    ({"kv_dtype": "int8"}, "kv_dtype"),
    ({"weight_dtype": "int8"}, "weight_dtype"),
    ({"speculative": True}, "speculative"),
    ({"mesh": object()}, "mesh"),
    ({"prefix_cache": True}, "prefix_cache"),
])
def test_refused_at_construction_by_name(model, kw, name):
    _, cfg, params = model
    with pytest.raises(NotImplementedError, match=name):
        paged.ContinuousBatcher(params, cfg, max_batch=2, block_size=BS,
                                max_total_len=64, max_new_tokens=4,
                                prefill_buckets=(8, CHUNK), **kw)


def test_kv_transfer_and_the_prefill_role_are_refused(model):
    _, cfg, params = model
    cb = paged.ContinuousBatcher(params, cfg, max_batch=2, block_size=BS,
                                 max_total_len=64, max_new_tokens=4,
                                 prefill_buckets=(8, CHUNK))
    with pytest.raises(NotImplementedError, match="export_kv"):
        cb.export_kv(0)
    with pytest.raises(NotImplementedError, match="import_kv"):
        cb.import_kv(None)
    with pytest.raises(ValueError, match="bucket ladder"):
        paged.ContinuousBatcher(params, cfg, max_batch=2, block_size=BS,
                                max_total_len=64, max_new_tokens=4,
                                prefill_buckets=())
    from paddle_tpu import serving
    with pytest.raises(NotImplementedError, match="role='prefill'"):
        serving.ServingEngine(params, cfg, max_batch=2, block_size=BS,
                              max_total_len=64, max_new_tokens=4,
                              prefill_buckets=(8, CHUNK), prefix_cache=False,
                              role="prefill", start=False)
    # the engine's default asks for the prefix cache: refused by name too
    with pytest.raises(NotImplementedError, match="prefix_cache"):
        serving.ServingEngine(params, cfg, max_batch=2, block_size=BS,
                              max_total_len=64, max_new_tokens=4,
                              prefill_buckets=(8, CHUNK), start=False)
    # the window kernel refuses what it was not built with
    q = jnp.zeros((1, 1, 4, 16))
    pool = jnp.zeros((4, BS, 2, 16), jnp.int8)
    with pytest.raises(NotImplementedError, match="window"):
        ragged_paged_attention(q, pool, pool, jnp.zeros((1, 2), jnp.int32),
                               jnp.zeros((1, 1), jnp.int32), window=4,
                               k_scale=jnp.ones((4,)), v_scale=jnp.ones((4,)))


def test_engine_serves_it_through_the_usual_entry_points(model):
    d, cfg, params = model
    from paddle_tpu import serving
    eng = serving.ServingEngine(
        params, cfg, max_batch=2, block_size=BS, max_total_len=96,
        max_new_tokens=6, prefill_buckets=(CHUNK,), chunk=3,
        prefix_cache=False, max_prefill_group=1, start=False)
    try:
        warmed = eng.warmup()
        eng.start()
        prompts = [_tokens(n, seed=40 + n).tolist() for n in (9, 4 * W, 21)]
        hs = [eng.submit(prompts[0], max_new_tokens=6)]
        next(hs[0].stream())            # decoding: the next ones fuse
        hs += [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
        served = [list(h.result(timeout=300)) for h in hs]
        assert eng.drain(60)
        snap = eng.snapshot()
        assert snap["allocator"]["window_blocks_in_use"] == 0
        assert snap["allocator"]["window_capacity_blocks"] == 2 * 9
        assert eng.batcher.compile_count == warmed
        assert eng.batcher.fused_steps > 0
    finally:
        eng.shutdown(drain=False, timeout=30)
    gaps = reference.served_gaps(SEED, d, prompts, served,
                                 weight_dtype=jnp.float32, pad=32)
    assert float(gaps.max()) < TOL
