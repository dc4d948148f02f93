"""The gated window + full GQA decoder on the served path: layers whose
KINDS differ in head count (attention matrices stacked by kind), a
per-head output gate, a rotary share a kind, a leading dense layer (a
group of its own before the periods), a shared expert and a routed scale
on the GQA mixer, a held share of the experts (nlp/window_moe.py, the
kinded pool of nlp/paged.py, nlp/ragged_attention.py at head groups of 6
and 9, kernels/rope.py's rotary share), at a tiny size on the CPU against
the benchmark's plain reference
(benchmark/reference/gated_window_moe_decoder.py: float32, no cache, no
ring, every key with a mask, a loop over the experts).

Tolerances: everything here runs in float32 on the CPU, program and
reference alike, so the two differ by the order of float32 sums only (the
online softmax of the kernel and of flash against one softmax over every
key; the grouped GEMM against the loop over experts). On logits of
magnitude 0.3 the largest difference read is 9e-7 through every path;
TOL = 2e-5 leaves twentyfold room for another backend's sums, and is
under a tenth of what the smallest fault here moves ONE layer's output
(each of the reference's broken-program controls, tested below; the
smallest is the rotary share, 3e-4: at this width the scores are nearly
flat, at the published one they are not).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.models import gated_window_moe_decoder as family        # noqa: E402
from benchmark.reference import gated_window_moe_decoder as reference  # noqa: E402
from paddle_tpu.kernels.rope import apply_rope_half, rope_freqs        # noqa: E402
from paddle_tpu.nlp import paged, window_moe                           # noqa: E402
from paddle_tpu.nlp.ragged_attention import (                          # noqa: E402
    _attn_tiling, gqa_tiling_args, ragged_paged_attention)

TOL = 2e-5
W, BS, CHUNK = 16, 4, 16            # window, block, widest prefill chunk
# the leading layer and two periods of [window, full]: both kinds, the
# scan over periods after a group of its own, half the body to compile of
# the published [window x 3, full] (benchmark/tests/test_gated_window_moe.py
# runs that one)
PERIOD = ["sliding_attention", "full_attention"]
L = 1 + 2 * len(PERIOD)

MODEL = {
    "attention_bias": False, "head_dim": 16, "hidden_size": 48,
    "intermediate_size": 64, "decoder_sparse_step": 1,
    "gating": "per-head", "gating_types": ["per_head"] * L,
    "layer_types": ["full_attention"] + PERIOD * 2,
    "mlp_only_layers": [0],
    "mlp_layer_types": ["dense"] + ["sparse"] * (L - 1),
    "max_position_embeddings": 512, "moe_intermediate_size": 24,
    "shared_expert_intermediate_size": 20,
    "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
    "norm_topk_prob": True, "num_attention_heads": 4,
    "num_attention_heads_per_layer": [4] + [6, 4] * 2,
    "num_experts": 4, "num_experts_per_tok": 3, "num_hidden_layers": L,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
            "original_max_position_embeddings": 32, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.1386294361119891,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0,
                              "partial_rotary_factor": 1}},
    "sliding_window": W, "tie_word_embeddings": False, "vocab_size": 128}
CONFIG = {"family": "gated_window_moe_decoder", **MODEL,
          "served_dtype": "float32",
          "share": {"router_experts": 8, "experts_first": 0}}
SEED = 5


@pytest.fixture(scope="module")
def model():
    d = family.dims(CONFIG)
    cfg = family.program_config(CONFIG)
    params = family.make_params(SEED, d, jnp.float32)
    return d, cfg, params


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 128, n).astype(np.int32)


def test_the_configuration_and_the_parameters_layout(model):
    d, cfg, params = model
    assert cfg.lead_kinds == ("full",)
    assert cfg.period_kinds == ("window", "full")
    assert (cfg.heads("full"), cfg.heads("window")) == (4, 6)
    assert (cfg.rotary_dim("full"), cfg.rotary_dim("window")) == (8, 16)
    assert cfg.heads_by_kind and cfg.experts_count == 4
    # the benchmark's weights lie in the layout the program's own have
    own = jax.eval_shape(lambda k: window_moe.init_params(k, cfg),
                         jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree.map(lambda a: a.shape, t)   # noqa: E731
    assert shapes(own) == shapes(params)
    lay = params["layers"]
    assert lay["attn_window"]["q_proj"].shape == (2, 48, 6 * 16)
    assert lay["attn_full"]["o_proj"].shape == (2, 4 * 16, 48)
    assert lay["attn_window"]["g_proj"].shape == (2, 48, 6)
    assert params["lead_layers"]["gate_proj"].shape == (1, 48, 64)
    assert lay["gate_proj"].shape == (4, 48, 20)        # the shared expert
    assert lay["router"].shape == (4, 48, 8)            # all 8 scored
    assert lay["experts_gate"].shape == (4, 4, 48, 24)  # 4 held


@pytest.mark.parametrize("over,match", [
    ({"mlp_only_layers": (1,)}, "LEADING"),
    ({"num_attention_heads_per_layer": (4, 6, 4, 4, 4)},
     "same within a kind"),
    ({"num_attention_heads_per_layer": (4,) + (5, 4) * 2}, "KV heads"),
    ({"attention_gate": "elementwise"}, "per_head"),
    ({"n_shared_experts": 2}, "one shared"),
])
def test_what_the_configuration_refuses(model, over, match):
    _, cfg, _ = model
    with pytest.raises(ValueError, match=match):
        window_moe.WindowMoeConfig(**{**cfg.__dict__, **over})


# ---- (a) prefill then decode against the reference's full forward --------
_FWD = {}


def _forward(cfg, lay, impl, is_prefill):
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
    padded and masked), then decode `steps` tokens one at a time, through
    `forward_paged` over a kinded pool of 3 full and 2 window layers (one
    slot: a chain a full layer, a ring a window layer). Returns the logits
    at every position."""
    P = len(toks) - steps
    M, R = 20, paged.ring_blocks(W, chunk, BS)
    lay = paged.KVLayout(full_layers=3, window_layers=2, full_blocks=M + 3,
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


@pytest.mark.parametrize("impl,prompt", [
    ("xla", 4 * W + 3), ("pallas", W - 5), ("pallas", 4 * W + 3)])
def test_served_logits_match_the_reference(model, impl, prompt):
    """Shorter than the window (one cold chunk) and four times it (warm
    chunks that wrap the ring of 9 blocks, the last one padded), then six
    decode steps: the LOGITS at every position are the reference's."""
    d, cfg, params = model
    toks = _tokens(prompt + 6, seed=prompt)
    got = _served_logits(params, cfg, toks, CHUNK, 6, impl)
    want = np.asarray(reference.logits(SEED, d, jnp.asarray(toks[None]),
                                       jnp.float32))[0]
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("impl,fused", [("pallas", True), ("xla", False)])
def test_batcher_tokens_are_the_references(model, impl, fused):
    """Through `ContinuousBatcher`: admissions of every length, alone and
    in groups, cold and chunked, plain and fused ticks, rings that wrap;
    every served token is the reference's own first at its position."""
    d, cfg, params = model
    cb = paged.ContinuousBatcher(
        params, cfg, max_batch=3, block_size=BS, max_total_len=96,
        max_new_tokens=8, prefill_buckets=(CHUNK,), chunk=4,
        attention_impl=impl, fused_prefill=fused, max_prefill_group=2)
    lay = cb._layout
    assert (lay.full_layers, lay.window_layers) == (3, 2)
    assert cb.kv_block_bytes() == 3 * BS * 2 * 2 * 16 * 4
    assert cb.weight_bytes() == 4 * family.num_params(d)
    prompts = [_tokens(n, seed=n).tolist() for n in (5, W, 4 * W, 30, 17)]
    rids = [cb.submit(p) for p in prompts]
    out = cb.run()
    gaps = reference.served_gaps(SEED, d, prompts, [out[r] for r in rids],
                                 weight_dtype=jnp.float32, pad=32)
    assert gaps.shape == (40,) and float(gaps.max()) < TOL
    assert (cb.fused_steps > 0) == fused
    rec = [r for r in cb.flight.records() if r["mode"] in ("decode", "fused")]
    # the long row's context (64 + its answer) passes the ring's 36 tokens
    assert all("ring_wrapped_rows" in r and r["moe_pairs"] > 0 for r in rec)
    assert max(r["ring_wrapped_rows"] for r in rec) == 1
    assert all(r["ring_wrapped_rows"] == sum(
        c > lay.ring * BS for c in r["decode_ctx"]) for r in rec)
    st = cb.alloc_stats()
    assert st["blocks_in_use"] == st["window_blocks_in_use"] == 0


# control -> a layer it breaks (1: window, sparse; 2: full, sparse)
BROKEN_AT = {"drop_gate": 1, "no_window": 1, "full_rotary": 2,
             "drop_shared": 2, "route_scale": 1}


@pytest.mark.parametrize("control", sorted(reference.CONTROLS))
def test_each_control_breaks_a_layer(model, control):
    """What the reference can break, the comparison sees: each control
    moves ONE layer's output by over ten tolerances."""
    d, _, _ = model
    assert set(BROKEN_AT) == set(reference.CONTROLS)
    i = BROKEN_AT[control]
    w = family.layer_weights(family.layer_key(family.seed_key(SEED), i), d,
                             jnp.float32, i)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 4 * W, 48)),
                    jnp.float32)
    want = reference.layer(x, w, d, i)
    broken = reference.layer(x, w, d, i, **reference.CONTROLS[control])
    assert np.abs(np.asarray(broken - want)).max() > 10 * TOL


# ---- (b) the shares add up -----------------------------------------------
def test_two_shares_and_the_shared_expert_once_add_up_to_the_layer(model):
    """The guide's test of the share: the routed parts that the two chips
    of a layer compute (experts 0-3 and 4-7 of the router's 8, through
    the program's `_ffn_experts`), plus the shared expert counted ONCE,
    are what the reference gives for the uncut layer."""
    d, cfg, _ = model
    whole = {**d, "n": 8}
    i = 1
    w = family.layer_weights(family.layer_key(family.seed_key(SEED), i),
                             whole, jnp.float32, i)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, 24, 48)), jnp.float32)
    want = reference.ffn(x, w, whole, i) - x
    h = reference._rms(x, w["post_attention_layernorm"], d["eps"])
    group = paged._RowGroup(jnp.zeros((1, 24), jnp.int32), None, None,
                            jnp.ones((1, 24), bool))
    z = jnp.zeros((), jnp.int32)
    parts, pairs = jnp.zeros_like(x), 0
    for c in range(2):
        share = window_moe.WindowMoeConfig(**{
            **cfg.__dict__, "experts_first": 4 * c, "experts_count": 4,
            "n_shared_experts": int(c == 0)})
        stacks = {k: w[k][None, 4 * c:4 * c + 4]
                  for k in paged._EXPERT_STACKS}
        y, st = paged._ffn_experts(
            jnp.zeros_like(x), h, w, share, [group],
            {k: z for k in ("moe_pairs", "moe_experts_hit", "moe_load_max",
                            "moe_full_passes", "moe_gemm_items")}, stacks, 0)
        parts, pairs = parts + y, pairs + int(st["moe_pairs"])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(want),
                               atol=2e-6, rtol=0)
    assert pairs == 24 * 3          # every pair computed on exactly one chip
    # a share of the reference is the reference given the share's stacks
    held = {**w, **{k: w[k][:4] for k in paged._EXPERT_STACKS}}
    np.testing.assert_array_equal(
        np.asarray(reference.ffn(x, w, whole, i, experts=(0, 4))),
        np.asarray(reference.ffn(x, held, d, i)))


# ---- (c) the rotary share -------------------------------------------------
def test_a_rotary_share_by_hand():
    """head_dim 8, r = 4: dims 0-3 rotate (rotate-half inside them: pairs
    (0, 2) and (1, 3)) at the frequencies of a rotary of dim 4, dims 4-7
    pass through."""
    cos, sin = rope_freqs(4, 8, 100.0)
    pos = jnp.asarray([[3]])
    x = jnp.arange(1.0, 9.0).reshape(1, 1, 1, 8)
    got, _ = apply_rope_half(x, x, cos, sin, pos, rotary_dim=4)
    a0, a1 = 3.0, 3.0 / 10.0        # position x theta^(-2i/4), theta 100
    want = [1 * np.cos(a0) - 3 * np.sin(a0), 2 * np.cos(a1) - 4 * np.sin(a1),
            3 * np.cos(a0) + 1 * np.sin(a0), 4 * np.cos(a1) + 2 * np.sin(a1),
            5, 6, 7, 8]
    np.testing.assert_allclose(np.asarray(got)[0, 0, 0], want, rtol=1e-6)


@pytest.mark.parametrize("with_positions", [False, True])
def test_a_whole_rotary_share_is_todays_rotation(with_positions):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 5, 3, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 5, 2, 16)), jnp.float32)
    cos, sin = rope_freqs(16, 32)
    pos = jnp.asarray(rng.integers(0, 32, (2, 5))) if with_positions else None
    for got, want in zip(apply_rope_half(q, k, cos, sin, pos, rotary_dim=16),
                         apply_rope_half(q, k, cos, sin, pos)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---- (d) the ragged kernel at head groups of 6 and 9 ----------------------
@pytest.mark.parametrize("rep,P,win", [(6, 1, None), (9, 1, 10), (6, 8, None),
                                       (9, 8, 10)])
def test_the_kernel_agrees_with_its_xla_twin_at_groups_of_6_and_9(rep, P, win):
    rng = np.random.default_rng(rep + P)
    Rr, KV, hd = 3, 2, 16
    ring = win is not None
    M = 5 if ring else 12
    N = Rr * M + 1
    kp = jnp.asarray(rng.normal(size=(N, BS, KV, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, BS, KV, hd)), jnp.float32)
    table = jnp.asarray(rng.permutation(N - 1)[:Rr * M].reshape(Rr, M) + 1,
                        jnp.int32)
    last = np.array([P + 2, 29, 44])    # one row past a ring's first wrap
    pos = jnp.asarray(last[:, None] - (P - 1) + np.arange(P)[None],
                      jnp.int32)
    valid = jnp.asarray([[True] * P, [True] * P, [True] * (P - 1) + [P == 1]])
    q = jnp.asarray(rng.normal(size=(Rr, P, rep * KV, hd)), jnp.float32)
    want = paged._paged_gqa_attention(q, kp, vp, table, pos, valid,
                                      impl="xla", window=win, ring=ring)
    got = ragged_paged_attention(q, kp, vp, table, pos, valid, window=win,
                                 ring=ring, interpret=True)
    ok = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(got)[ok], np.asarray(want)[ok],
                               atol=2e-6, rtol=0)


def test_a_tile_of_queries_is_held_to_its_rows_of_query_heads():
    """128 queries of 48 heads are 6,144 rows of a tile's q, o,
    accumulator and softmax-state blocks, over what a core's scoped VMEM
    holds beside a chunk inside a step program (the chip's compiler
    refuses it: tests/test_aot_tpu_compile.py); 64 fit, of 72 heads too.
    32 heads keep their 128."""
    pool = ((100, 16, 8, 128), jnp.bfloat16)
    tile = {H: _attn_tiling(512, 65, **gqa_tiling_args(*pool, heads=H))[0]
            for H in (32, 36, 48, 72)}
    assert tile == {32: 128, 36: 128, 48: 64, 72: 64}
    assert gqa_tiling_args(*pool) == gqa_tiling_args(*pool, heads=32)
