"""The served expert layer's grouped GEMM (`kernels/grouped_gemm.py`), in
Pallas interpret mode on the CPU: its list of (row tile, hit expert)
items against a NumPy count, the kernel against `lax.ragged_dot` on the
layer's slice of a stack whose other layers hold other weights, and the
layer built on it (`moe.expert_share_ffn`) against a plain loop over the
held experts. The interpreter leaves NaN where the kernel never wrote,
which is what makes "left unread" a test and not a hope."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.kernels import grouped_gemm as gg
from paddle_tpu.nlp import moe

LM, N_EXP, K, N = 3, 5, 32, 256


def _overlaps(sizes, rows, tm):
    """[(tile, expert, first row, row after the last)] by expert, then
    tile: the list the device builds, counted here in plain Python."""
    out, lo = [], 0
    for e, s in enumerate(sizes):
        if s:
            out += [(t, e, lo, lo + s)
                    for t in range(lo // tm, (lo + s - 1) // tm + 1)]
        lo += s
    assert lo <= rows
    return out


# rows of the buffer, rows on each of the 5 experts
SIZES = {
    "one-tile": (48, [3, 0, 17, 9, 0]),
    "no-expert-hit": (300, [0, 0, 0, 0, 0]),
    "one-expert-takes-every-row": (256, [0, 0, 256, 0, 0]),
    "boundary-inside-a-tile": (300, [100, 0, 60, 1, 130]),
    "boundaries-on-the-tiles-edges": (384, [128, 0, 128, 0, 128]),
    "every-expert-one-row": (128, [1, 1, 1, 1, 1]),
    "last-tile-partial-and-full": (300, [40, 50, 60, 70, 80]),
    "an-expert-over-three-tiles": (512, [5, 300, 0, 7, 100]),
}


@pytest.mark.parametrize("case", list(SIZES))
def test_work_list_is_the_tile_expert_overlaps(case):
    rows, sizes = SIZES[case]
    tm = gg._row_tile(rows)
    want = _overlaps(sizes, rows, tm)
    work = gg.gemm_work_list(jnp.asarray(sizes, jnp.int32), jnp.int32(10),
                             rows=rows)
    assert work.items.shape == (4, gg.gemm_items(rows, len(sizes)))
    assert work.items.dtype == jnp.int32 and work.count.dtype == jnp.int32
    assert int(work.count) == len(want)
    got = np.asarray(work.items)[:, :len(want)].T
    assert [tuple(r) for r in got] == [(t, 10 + e, lo, hi)
                                       for t, e, lo, hi in want]
    # what lies past the list still names a tile of the buffer
    assert np.all(np.asarray(work.items)[0] < -(-rows // tm))


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("case", list(SIZES))
def test_kernel_is_ragged_dot_on_the_layers_slice(case, layer):
    """`base` picks the layer inside the stack: the other layers' weights
    differ, so a wrong block shows."""
    rows, sizes = SIZES[case]
    rng = np.random.default_rng(layer)
    w = jnp.asarray(rng.normal(size=(LM, N_EXP, K, N)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(rows, K)), jnp.float32)
    sz = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(gg.grouped_gemm(x, w.reshape(LM * N_EXP, K, N), sz,
                                     jnp.int32(layer * N_EXP)))
    want = np.asarray(jax.lax.ragged_dot(x, w[layer], sz))
    m = sum(sizes)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got[:m], want[:m], rtol=1e-5, atol=1e-5)
    # a tile that no item visits was never written
    tm = gg._row_tile(rows)
    seen = {t for t, *_ in _overlaps(sizes, rows, tm)}
    for t in range(-(-rows // tm)):
        if t not in seen:
            assert np.all(np.isnan(got[t * tm:(t + 1) * tm]))


def test_kernel_takes_bfloat16_rows_and_accumulates_in_float32():
    rows, sizes = SIZES["boundary-inside-a-tile"]
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.normal(size=(N_EXP, K, N)), jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(rows, K)), jnp.bfloat16)
    sz = jnp.asarray(sizes, jnp.int32)
    got = gg.grouped_gemm(x, w, sz, jnp.int32(0))
    assert got.dtype == jnp.bfloat16
    want = jax.lax.ragged_dot(x, w, sz, preferred_element_type=jnp.float32)
    m = sum(sizes)
    np.testing.assert_allclose(np.asarray(got[:m], np.float32),
                               np.asarray(want[:m]), rtol=1e-2, atol=1e-2)


def test_a_list_of_another_shape_is_refused():
    sz = jnp.asarray(SIZES["one-tile"][1], jnp.int32)
    work = gg.gemm_work_list(sz, jnp.int32(0), rows=300)
    with pytest.raises(ValueError, match="work list"):
        gg.grouped_gemm(jnp.zeros((48, K)), jnp.zeros((N_EXP, K, N)), sz,
                        jnp.int32(0), work=work)


@pytest.mark.parametrize("S,Kd,Nd,itemsize,tm,tn", [
    (256, 2304, 896, 2, 128, 896),      # mellum2-l8, a decode step: whole
    (4352, 896, 2304, 2, 128, 2304),    # its fused step's down projection
    (128, 7168, 2048, 2, 128, 512),     # axk1-ep16: column slabs
    (640, 2048, 7168, 2, 128, 1792),
    (40, 64, 96, 4, 40, 96),            # a tiny model: one tile, one slab
])
def test_tiles_follow_the_shapes(S, Kd, Nd, itemsize, tm, tn):
    assert gg._gemm_tiling(S, Kd, Nd, itemsize) == (tm, tn)
    assert Kd * tn * itemsize <= gg._W_BLOCK_BYTES and Nd % tn == 0


def _layer_case(T, n, E, k, D, F, seed, held_first=0, skew=0.0):
    rng = np.random.default_rng(seed)
    router = rng.normal(size=(D, E)) * 0.5
    router[:, held_first:held_first + n] += skew
    lp = {"router": jnp.asarray(router, jnp.float32),
          **{"experts_" + m: jnp.asarray(rng.normal(size=(n,) + s) * 0.3,
                                         jnp.float32)
             for m, s in (("gate", (D, F)), ("up", (D, F)),
                          ("down", (F, D)))}}
    h = jnp.asarray(np.abs(rng.normal(size=(T, D))) + 0.1, jnp.float32)
    return h, lp


def _stack(lp, layer, layers=3):
    """The layer's experts at `layer` of a stack whose other layers hold
    other weights."""
    return {"router": lp["router"], **{
        m: jnp.stack([lp[m] if i == layer else lp[m] * (0.5 + i)
                      for i in range(layers)])
        for m in ("experts_gate", "experts_up", "experts_down")}}


def _loop(h, lp, k, first, scale):
    idx, g = moe.sigmoid_top_k(h, lp["router"], k, scale)
    y = jnp.zeros_like(h)
    for j in range(lp["experts_gate"].shape[0]):
        gj = jnp.sum(jnp.where(idx == first + j, g, 0.0), -1)
        y = y + gj[:, None] * (
            (jax.nn.silu(h @ lp["experts_gate"][j])
             * (h @ lp["experts_up"][j])) @ lp["experts_down"][j])
    return y


# (T, held n, routed E, k, first held, skew towards the held, valid tokens,
#  overflowed buffers)
LAYERS = {
    "every-expert-held": (40, 8, 8, 2, 0, 0.0, 40, 0),
    "few-held-most-rows-past-the-sum": (64, 3, 24, 4, 4, 0.0, 64, 0),
    "no-token-valid": (32, 4, 16, 2, 0, 0.0, 0, 0),
    "held-experts-popular-a-second-pass": (64, 3, 24, 4, 4, 6.0, 64, 1),
    "masked-tokens": (64, 4, 16, 4, 8, 0.0, 37, 0),
}


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("case", list(LAYERS))
def test_layer_on_the_kernel_is_the_loop_over_held_experts(case, layer,
                                                           monkeypatch):
    """Rows past the experts' sum and tiles no item visits hold NaN here;
    the combine reads none of them: the layer is finite and equals the
    loop. Passes over a short buffer give the one-pass answer, and
    `moe_gemm_items` is the NumPy count of the overlaps of every pass."""
    T, n, E, k, first, skew, n_valid, full = LAYERS[case]
    h, lp = _layer_case(T, n, E, k, 16, 8, seed=len(case), held_first=first,
                        skew=skew)
    valid = jnp.arange(T) < n_valid
    kw = dict(k=k, first=first, scale=2.5, valid=valid,
              layer=jnp.int32(layer))
    y, st = jax.jit(lambda h: moe.expert_share_ffn(h, _stack(lp, layer),
                                                   **kw))(h)
    y = np.asarray(y)
    assert np.all(np.isfinite(y)) and not np.any(y[n_valid:])
    want = np.asarray(_loop(h, lp, k, first, 2.5))
    np.testing.assert_allclose(y[:n_valid], want[:n_valid], rtol=1e-5,
                               atol=1e-5)
    assert int(st["moe_full_passes"]) == full
    # the passes' lists, counted from the routing itself
    idx, _ = moe.sigmoid_top_k(h, lp["router"], k, 2.5)
    idx = np.asarray(idx)[:n_valid]
    sizes = [int(np.sum(idx == first + j)) for j in range(n)]
    assert int(st["moe_pairs"]) == sum(sizes)
    S = min(moe._short_rows(T * k, n, E), T * k)
    tm = gg._row_tile(S)
    ends = np.cumsum(sizes)
    items = 0
    for lo in range(0, sum(sizes), S):
        part = (np.clip(ends - lo, 0, S)
                - np.clip(ends - np.asarray(sizes) - lo, 0, S))
        items += len(_overlaps(list(part), S, tm))
    assert int(st["moe_gemm_items"]) == items
    assert items >= int(st["moe_experts_hit"]) or full == 0
    # one buffer of all the pairs: the same layer, one pass
    monkeypatch.setattr(moe, "_short_rows", lambda pairs, held, routed: pairs)
    y1, st1 = jax.jit(lambda h: moe.expert_share_ffn(h, _stack(lp, layer),
                                                     **kw))(h)
    np.testing.assert_allclose(y, np.asarray(y1), rtol=1e-6, atol=1e-6)
    assert int(st1["moe_full_passes"]) == 0
    assert int(st1["moe_gemm_items"]) == len(
        _overlaps(sizes, T * k, gg._row_tile(T * k)))


def test_token_blocks_add_their_items_up():
    T, n, E, k = 70, 4, 8, 2
    h, lp = _layer_case(T, n, E, k, 16, 8, seed=3)
    kw = dict(k=k, first=0, scale=1.0, layer=1)
    y, st = moe.expert_share_ffn(h, _stack(lp, 1), token_block=16, **kw)
    y1, st1 = moe.expert_share_ffn(h, _stack(lp, 1), **kw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y1), rtol=1e-5,
                               atol=1e-5)
    assert int(st["moe_pairs"]) == int(st1["moe_pairs"])
    # five blocks, each with its own list: more items than one list holds
    assert int(st["moe_gemm_items"]) >= int(st1["moe_gemm_items"])
    assert int(st["moe_gemm_items"]) <= 5 * n


def test_flight_records_and_profiler_carry_the_items():
    """A served sparse-expert decoder: every decode and fused tick's
    flight record has `moe_gemm_items` beside the other four counters,
    at least one item a hit expert-layer (its rows lie in one tile or
    more), and the profiler's detail has it too."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark.models import mla_moe_decoder as family
    from paddle_tpu import serving
    config = {
        "family": "mla_moe_decoder", "attention_bias": False,
        "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 64,
        "intermediate_size": 160, "kv_lora_rank": 32,
        "max_position_embeddings": 256, "moe_intermediate_size": 32,
        "moe_layer_freq": 1, "n_routed_experts": 6, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 4,
        "num_experts_per_tok": 4, "num_hidden_layers": 3, "q_lora_rank": 48,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
        "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 64,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_method": "none",
        "v_head_dim": 16, "vocab_size": 128, "served_dtype": "float32",
        "share": {"router_experts": 16, "experts_first": 4}}
    d = family.dims(config)
    cfg = family.program_config(config)
    params = family.make_params(3, d, jnp.float32)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, d["V"], n).tolist() for n in (7, 13)]
    eng = serving.ServingEngine(
        params, cfg, max_batch=4, block_size=4, max_total_len=64,
        max_new_tokens=40, prefill_buckets=(8, 16), chunk=2,
        max_prefill_group=2, profile_sample_every=1, start=False)
    try:
        eng.warmup()
        eng.start()
        # twenty chunks of decoding: the next one arrives inside them
        # however slow this host is, and fuses
        first = eng.submit(prompts[0], max_new_tokens=40)
        next(first.stream())
        rest = eng.submit(prompts[1], max_new_tokens=6)
        for h in (first, rest):
            h.result(timeout=300)
        assert eng.drain(60)
        recs = [r for r in eng.batcher.flight.records()
                if r["mode"] in ("decode", "fused") and r.get("closed")]
        assert {r["mode"] for r in recs} == {"decode", "fused"}
        for r in recs:
            assert r["moe_gemm_items"] >= r["moe_experts_hit"] >= 1, r
            assert r["moe_gemm_items"] <= r["moe_pairs"], r
        steps = eng.batcher.profiler.report()
        assert steps["samples"] >= 1
    finally:
        eng.shutdown(drain=False, timeout=60)
