"""Rehearsals of the `mla_moe_decoder` family and the `axk1-ep16`
configuration on the CPU: the configuration against the catalog row, the
whole command at a tiny preset, the cost functions on hand-worked cases,
the two new readers on synthetic records, and a broken expert layer coming
out not correct."""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import device, manifest
from benchmark.models import mla_moe_decoder as fam
from benchmark.readers import (mla_attn_roofline, moe_expert_roofline,
                               xstats)
from benchmark.reference import mla_moe_decoder as ref
from benchmark.tests import test_run_cpu, tiny

ROOT = manifest.ROOT
PEAK = device.peaks("TPU v5 lite")

# the catalog row `A.X-K1` (model-configs guide, architectures.jsonl), its
# `config` key for key
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "axk1", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none",
    "v_head_dim": 128, "vocab_size": 163840}
FLOORS = {"num_hidden_layers": 1 + 4, "n_routed_experts": 8,
          "vocab_size": 163840 // 8}

TINY_MODEL = {
    **CATALOG, "hidden_size": 64, "intermediate_size": 160,
    "kv_lora_rank": 32, "max_position_embeddings": 256,
    "moe_intermediate_size": 32, "n_routed_experts": 6,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 3, "num_key_value_heads": 4, "q_lora_rank": 48,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "vocab_size": 256,
    "rope_scaling": {**CATALOG["rope_scaling"], "factor": 4,
                     "original_max_position_embeddings": 64}}
TINY_CONFIG = {"source": "test", "family": "mla_moe_decoder",
               **TINY_MODEL, "served_dtype": "bfloat16",
               "share": {"router_experts": 16, "experts_first": 4},
               "engine": {**tiny.ENGINE, "max_prefill_group": 2}}
# sound runs here (bf16 on the CPU): every served token is the reference's
# own first, gap 0; the factor 2.5 left out: largest 0.0058, mean 4.1e-4;
# the shared expert left out: largest 0.023, mean 0.0017
LIMITS = {"served_gap_max": 0.005, "served_gap_mean": 3e-4}


def test_configuration_keeps_the_catalog_rows_widths():
    man = manifest.manifest(ROOT)
    entry = next(c for c in man["configs"] if c["name"] == "axk1-ep16")
    spec = manifest.config(ROOT, "axk1-ep16")
    assert spec["source"] == entry["source"] == \
        "https://huggingface.co/skt/A.X-K1/blob/main/config.json"
    # the published keys lie at the file's top level, where the driver's
    # check against the catalog row reads them
    assert set(spec) >= set(CATALOG) and "model" not in spec
    changed = {k for k in CATALOG if spec[k] != CATALOG[k]}
    assert changed == set(entry["reduced"]) == \
        {k for k in spec["reduced"] if "." not in k} == set(FLOORS)
    for k in entry["reduced"]:
        # no width: no hidden, intermediate, latent or projection size, no
        # head size, not the experts per token
        assert not k.endswith(("_dim", "_rank")) and k not in (
            "hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "num_attention_heads")
        assert spec[k] >= FLOORS[k]
        assert spec["share"]["published"][k] == CATALOG[k]
    assert spec["share"]["router_experts"] == CATALOG["n_routed_experts"]
    assert spec["share"]["chips_sharing_a_layer"] * \
        spec["n_routed_experts"] == CATALOG["n_routed_experts"]
    assert set(spec["assumed"]) >= {"topk_method", "rope_layout", "weights",
                                    "slots"}
    assert spec["deployment"]
    d = fam.dims(spec)
    assert (d["E"], d["n"], d["k"], d["route_scale"]) == (192, 12, 8, 2.5)
    assert fam.num_params(d) * 2 / 2**30 == pytest.approx(9.02, abs=0.01)
    # the latent pool: 576 columns a token and layer, 1152 bytes in bf16
    pcfg = fam.program_config(spec)
    assert pcfg.kv_row_width == 576 and pcfg.softmax_scale == \
        pytest.approx(0.13086, abs=1e-5)
    cell = manifest.cell(ROOT, "axk1-chat")
    assert cell["rate_per_s"] == pytest.approx(0.8 * cell["knee_per_s"])


def test_parameter_tree_matches_its_shape_and_the_reference_draws_it():
    d = fam.dims(TINY_CONFIG)
    params = fam.make_params(7, d, jnp.bfloat16)
    shapes = fam.params_shape(d, jnp.bfloat16)
    import jax
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), shapes)
    assert params["moe_layers"]["experts_gate"].shape == (2, 6, 64, 32)
    assert params["moe_layers"]["router"].shape == (2, 64, 16)
    assert params["dense_layers"]["gate_proj"].shape == (1, 64, 160)
    # layer 1 (the first expert layer) drawn alone is the stacked tree's row
    one = fam.layer_weights(fam.layer_key(fam.seed_key(7), jnp.int32(1)), d,
                            jnp.bfloat16, True)
    np.testing.assert_array_equal(
        np.asarray(one["experts_up"], np.float32),
        np.asarray(params["moe_layers"]["experts_up"][0], np.float32))


# ---- operations and bytes, on cases worked by hand ----------------------
D = {"H": 64, "R": 512, "dr": 64, "D": 7168, "Fm": 2048, "L": 7}


def test_latent_attention_cost_by_hand():
    # 64 decode rows of 1000 keys: 64,000 rows of 576 bf16 read once
    # (1152 B each), q in 64 x 64 x 576 and o_lat out 64 x 64 x 512, bf16
    c = fam.latent_attention_cost(D, 64000, 64, 64000)
    assert c["bytes"] == 64000 * 1152 + 64 * 64 * (576 + 512) * 2
    # a pair: 64 heads x (576 + 512) columns x 2
    assert c["flops"] == 64000 * 64 * 1088 * 2 == 64000 * 139264
    t, bound = fam.roofline_seconds(c, PEAK)
    assert bound == "bytes"         # 121 FLOP a byte against the chip's 240
    assert 139264 / 1152 == pytest.approx(121, abs=0.2)   # per key alone
    assert fam.latent_attention_cost(D, 1, 1, 1, layers=7)["flops"] \
        == 7 * 139264


def test_expert_ffn_cost_by_hand():
    # one expert: 3 x 7168 x 2048 = 44.04 M parameters, 88.08 MB in bf16
    c = fam.expert_ffn_cost(D, pairs=170, experts_hit=67)
    assert c["bytes"] == 67 * 88_080_384
    assert c["flops"] == 170 * 6 * 7168 * 2048
    assert fam.roofline_seconds(c, PEAK)[1] == "bytes"
    # a standalone 2048-token prefill's pairs on one expert: compute-bound
    assert fam.roofline_seconds(
        fam.expert_ffn_cost(D, pairs=4096, experts_hit=1), PEAK)[1] \
        == "compute"


# ---- the readers, on synthetic records -----------------------------------
def _table(ops, modules, ticks):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ("serve.tick", s, d, {"seq": q, "mode": m})
            for q, s, d, m in ticks]}]}]}


def _spec(name):
    return manifest.load_json(ROOT, f"benchmark/layer_metrics/{name}.json")


def test_readers_on_synthetic_records():
    ms = 1_000_000
    flight = [
        {"seq": 10, "mode": "decode", "synced": True, "chunk": 1,
         "decode_ctx": [100]},
        {"seq": 11, "mode": "decode", "synced": True, "chunk": 2,
         "decode_ctx": [1000, 500], "moe_pairs": 40, "moe_experts_hit": 10},
        {"seq": 12, "mode": "fused", "synced": True, "chunk": 1,
         "decode_ctx": [700], "prefill_spans": [[0, 256]],
         "moe_pairs": 150, "moe_experts_hit": 20},
        {"seq": 13, "mode": "prefill", "cold": True, "synced": True,
         "prefill_spans": [[0, 512]]}]
    path = "ragged-dot-none:"       # the custom call's event has no scope
    ops = [("%mla_paged_attention.1", 20 * ms, 2 * ms, {}),
           ("%mla_paged_attention.2", 30 * ms, 1 * ms, {}),
           ("%mla_paged_attention.9", 2 * ms, 5 * ms, {}),   # before the cut
           ("%ragged-dot.1", 23 * ms, 4 * ms, {"tf_op": path}),
           ("%ragged-dot.2", 33 * ms, 3 * ms, {"tf_op": path}),
           ("%ragged-dot.3", 43 * ms, 9 * ms, {"tf_op": path}),  # prefill
           ("%fusion.5", 28 * ms, 1 * ms, {"tf_op": "jit(f)/mlp/dot:"}),
           ("%fusion.6", 27 * ms, 1 * ms,
            {"tf_op": "jit(f)/while/body/moe_experts/mul:"})]
    modules = [("jit_serve_decode_step(1)", 19 * ms, 10 * ms, {}),
               ("jit_serve_fused_step(2)", 29 * ms, 10 * ms, {}),
               ("jit_serve_prefill_step(3)", 42 * ms, 12 * ms, {})]
    ticks = [(10, 1 * ms, 8 * ms, "decode"), (11, 18 * ms, 10 * ms, "decode"),
             (12, 29 * ms, 11 * ms, "fused"),
             (13, 41 * ms, 14 * ms, "prefill")]
    obs = {"trace_stats": _table(ops, modules, ticks), "flight": flight,
           "dims": D, "device_kind": "TPU v5 lite"}
    # ticks 11-13 (seq 10 synced, so the cut starts after it). Attention:
    # decode 1000+500 then 1001+501 keys; fused 700 keys and one prefill
    # row of 256 queries over its own 256 keys; the cold prefill: nothing
    calls = [fam.latent_attention_cost(D, 1500, 2, 1500),
             fam.latent_attention_cost(D, 1502, 2, 1502),
             fam.latent_attention_cost(D, 700, 1, 700),
             fam.latent_attention_cost(D, 256 * 257 / 2, 256, 256)]
    least = 7 * sum(fam.roofline_seconds(c, PEAK)[0] for c in calls)
    got = mla_attn_roofline.read(_spec("mla_attn_roofline_pct"), obs)
    assert got == pytest.approx(100 * least / 3e-3)
    # experts: the two ticks with counters, 7 ms under the scope in their
    # programs: two grouped GEMMs found by name, one fusion by its scope
    # (the prefill program's 9 ms stay out on both sides)
    least = sum(fam.roofline_seconds(fam.expert_ffn_cost(D, p, h), PEAK)[0]
                for p, h in ((40, 10), (150, 20)))
    got = moe_expert_roofline.read(_spec("moe_expert_roofline_pct"), obs)
    assert got == pytest.approx(100 * least / 8e-3)
    assert 0 < got < 100
    # a program without the spans or counters (the parent): nothing, no raise
    bare = {**obs, "flight": [{k: v for k, v in r.items()
                               if not k.startswith("moe_")} for r in flight],
            "trace_stats": _table([o for o in ops if "mla_" not in o[0]],
                                  modules, ticks)}
    assert mla_attn_roofline.read(_spec("mla_attn_roofline_pct"), bare) \
        is None
    assert moe_expert_roofline.read(_spec("moe_expert_roofline_pct"), bare) \
        is None
    assert mla_attn_roofline.read(
        _spec("mla_attn_roofline_pct"),
        {**obs, "dims": {"H": 32, "KV": 8, "hd": 128, "L": 16}}) is None


# ---- the whole command at a tiny preset ----------------------------------
@pytest.fixture
def root(tiny_root):
    tiny._dump(tiny_root, "benchmark/configs/tiny-mla.json", TINY_CONFIG)
    tiny._dump(tiny_root, "benchmark/cells/tiny-mla-chat.json", {
        "config": "tiny-mla", "traffic": "chat", "chips": 1,
        "rate_per_s": 6.0, "trace_seconds": 0.5, "max_late_share": 5.0,
        "correct": {"sample": 4, "limits": LIMITS}})
    for name in ("mla_attn_roofline_pct", "moe_expert_roofline_pct",
                 "moe_ffn_pct"):
        tiny._dump(tiny_root, f"benchmark/layer_metrics/{name}.json",
                   _spec(name))
    man = manifest.manifest(tiny_root)
    man["configs"].append({"name": "tiny-mla", "source": "test",
                           "file": "benchmark/configs/tiny-mla.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "tiny-mla-chat", "config": "tiny-mla",
                             "traffic": "chat", "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "tiny-chat" in m.get("workloads", []):
            m["workloads"].append("tiny-mla-chat")
    for name in ("mla_attn_roofline_pct", "moe_expert_roofline_pct",
                 "moe_ffn_pct"):
        man["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "t", "moves": "tpot_p90_ms",
            "workloads": ["tiny-mla-chat"]})
    tiny._dump(tiny_root, "BENCHMARK.json", man)
    return tiny_root


def test_the_whole_command_untraced_and_traced(root, cpu_device, capsys,
                                               monkeypatch):
    assert test_run_cpu._run(root, "tiny-mla-chat") == 0
    line, out = test_run_cpu._last(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert set(line["metrics"]) == {"tpot_p90_ms", "setup_s"}
    assert "attention xla" in out and out.count("(limit ") >= 2
    # traced, on the hand-made trace of the dense cell: the metrics that
    # read counters report, the three that read this family's spans find
    # nothing on a trace without them and are left out, not raised
    test_run_cpu._fake_trace(monkeypatch)
    monkeypatch.setattr(xstats, "load", lambda p: _table(
        [("%fusion.1", 10, 5, {"tf_op": "jit(f)/mlp/dot:"})],
        [("jit_serve_decode_step(1)", 5, 20, {})],
        [(10**6, 1, 30, "decode")]))
    assert test_run_cpu._run(root, "tiny-mla-chat", trace=1) == 0
    line, out = test_run_cpu._last(capsys)
    assert {"decode_batch_mean", "warm_programs"} <= set(line["metrics"])
    assert "moe_expert_roofline_pct" not in line["metrics"]
    assert "note: per-layer metric mla_attn_roofline_pct found nothing" in out


def test_a_broken_expert_layer_comes_out_not_correct():
    """What the limits are held against: the reference with the shared
    expert, or the factor 2.5, left out puts other tokens first, and their
    gaps under the sound reference miss the limits by far; so does every
    matmul rounded to float8."""
    d = fam.dims(TINY_CONFIG)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, d["V"], n).tolist() for n in (20, 33, 41)]
    served = []
    for p in prompts:       # the sound reference's own greedy tokens
        seq = list(p)
        for _ in range(6):
            lg = ref.logits(5, d, jnp.asarray([seq], jnp.int32))
            seq.append(int(jnp.argmax(lg[0, -1])))
        served.append(seq[len(p):])
    sound = ref.served_gaps(5, d, prompts, served, pad=16)
    assert sound.shape == (18,) and float(sound.max()) < 1e-5
    for broken in ({"drop_shared": True}, {"route_scale": 1.0},
                   {"act": ref.fp8}):
        gaps = ref.served_gaps(5, d, prompts, served, pad=16, **broken)
        # at these tiny widths a control moves few tokens, but far: each
        # misses the limit on the mean, the largest gap, or both
        assert float(gaps.mean()) > LIMITS["served_gap_mean"] \
            or float(gaps.max()) > LIMITS["served_gap_max"], broken
