"""Rehearsals of the `gated_window_moe_decoder` family, the `laguna-s-ep4`
configuration and the `laguna-s-agent` cell on the CPU: the configuration
against the catalog row, the cost functions on hand-worked cases, the five
new metrics' readers on synthetic records (and finding nothing, without
raising, in a program that lacks the scopes), the whole command at a tiny
preset, the parent failing at once, the reference against the paged path,
and every control coming out not correct."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import device, manifest
from benchmark.models import gated_window_moe_decoder as fam
from benchmark.readers import (flight_mean, moe_expert_roofline,
                               moe_scope_share, window_attn_roofline, xstats)
from benchmark.reference import gated_window_moe_decoder as ref
from benchmark.tests import test_run_cpu, tiny
from benchmark.tests.test_window_moe import _table

ROOT = manifest.ROOT
PEAK = device.peaks("TPU v5 lite")
SOURCE = "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
PERIOD = ["full_attention"] + ["sliding_attention"] * 3

# the catalog row `Laguna-S-2.1` (model-configs guide, architectures.jsonl),
# its `config` key for key
CATALOG = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": PERIOD * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0}

TINY_MODEL = {
    **CATALOG, "head_dim": 16, "hidden_size": 48, "intermediate_size": 64,
    "max_position_embeddings": 512, "moe_intermediate_size": 24,
    "shared_expert_intermediate_size": 20, "num_attention_heads": 4,
    "num_attention_heads_per_layer": [4, 6, 6, 6] * 12, "num_experts": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "sliding_window": 16, "vocab_size": 256,
    "rope_parameters": {
        "full_attention": {**CATALOG["rope_parameters"]["full_attention"],
                           "rope_theta": 10000.0, "factor": 4.0,
                           "original_max_position_embeddings": 32,
                           "attention_factor": 1.1386294361119891},
        "sliding_attention": CATALOG["rope_parameters"]["sliding_attention"]}}
TINY_CONFIG = {"source": "test", "family": "gated_window_moe_decoder",
               **TINY_MODEL, "served_dtype": "bfloat16",
               "share": {"router_experts": 8, "experts_first": 0},
               "engine": {**tiny.ENGINE, "max_prefill_group": 2,
                          "prefix_cache": False}}
# sound runs here (bf16 on the CPU): every served token is the reference's
# own first or within 0.003 of it
LIMITS = {"served_gap_max": 0.006, "served_gap_mean": 5e-4}


def test_configuration_keeps_the_catalog_rows_widths():
    man = manifest.manifest(ROOT)
    entry = next(c for c in man["configs"] if c["name"] == "laguna-s-ep4")
    spec = manifest.config(ROOT, "laguna-s-ep4")
    assert spec["source"] == entry["source"] == SOURCE
    # the published keys lie at the file's top level, where the driver's
    # check against the catalog row reads them
    assert set(spec) >= set(CATALOG) and "model" not in spec
    changed = {k for k in CATALOG if spec[k] != CATALOG[k]}
    assert changed == set(entry["reduced"]) == set(spec["reduced"]) == \
        {"num_hidden_layers", "num_experts", "vocab_size"}
    assert (spec["num_hidden_layers"], spec["num_experts"],
            spec["vocab_size"]) == (5, 64, 25088)
    sh = spec["share"]
    assert sh["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                               "vocab_size": 100352}
    assert (sh["chips_sharing_a_layer"], sh["router_experts"],
            sh["experts_first"]) == (4, 256, 0)
    assert set(spec["assumed"]) >= {"gate", "router", "routed_scale",
                                    "shared_expert", "qk_norm",
                                    "rope_layout", "window", "weights",
                                    "slots"}
    assert "4 chips" in spec["deployment"]
    d = fam.dims(spec)
    # the leading layer and one whole period, every width as published
    assert d["kinds"] == ["full", "window", "window", "window", "full"]
    assert d["H"] == {"full": 48, "window": 72} and d["Ld"] == 1
    assert (d["D"], d["KV"], d["hd"], d["E"], d["n"], d["k"], d["Fm"],
            d["Fs"], d["F"], d["W"], d["V"], d["route_scale"]) == (
        3072, 8, 128, 256, 64, 10, 1024, 1024, 12288, 512, 25088, 2.5)
    assert d["rope"]["full"]["partial_rotary_factor"] == 0.5
    assert d["rope"]["full"]["factor"] == 128
    assert d["rope"]["window"]["rope_theta"] == 10000
    assert fam.num_params(d) == pytest.approx(3.00e9, rel=2e-3)
    assert fam.num_params(d) * 2 / 2**30 == pytest.approx(5.59, abs=0.01)
    pcfg = fam.program_config(spec)
    assert pcfg.lead_kinds == ("full",)
    assert pcfg.period_kinds == ("window", "window", "window", "full")
    assert (pcfg.heads("full"), pcfg.heads("window")) == (48, 72)
    assert (pcfg.rotary_dim("full"), pcfg.rotary_dim("window")) == (64, 128)
    assert pcfg.attention_gate == "per_head" and pcfg.n_shared_experts == 1
    assert pcfg.scoring_func == "softmax" and pcfg.num_experts == 256
    assert (pcfg.experts_first, pcfg.experts_count) == (0, 64)
    assert pcfg.routed_scaling_factor == 2.5 and pcfg.intermediate_size == 12288
    # the pools: full layers 32 x 304 blocks x 2 layers, window layers 32
    # rings of 65 x 3 layers, 16 tokens of 4 KB a block and layer
    from paddle_tpu.nlp import paged
    eng = spec["engine"]
    assert eng["max_total_len"] == 4864 and eng["max_batch"] == 32
    assert "num_blocks" not in eng          # 32 rows at full length
    ring = paged.ring_blocks(512, max(eng["prefill_buckets"]),
                             eng["block_size"])
    blocks = 2 * 32 * (4864 // 16) + 3 * 32 * ring
    assert ring == 65 and blocks * 16 * 4096 / 2**30 == \
        pytest.approx(1.57, abs=0.01)
    mix = manifest.traffic(ROOT, "agent-turns")
    assert mix == {**mix, "kind": "serve_open", "order_block": 8,
                   "prompt": {"dist": "lognormal", "median": 768,
                              "sigma": 0.7, "min": 128, "max": 4096},
                   "output": {"dist": "lognormal", "median": 256,
                              "sigma": 0.5, "min": 64, "max": 768}}
    assert mix["prompt"]["max"] + mix["output"]["max"] <= eng["max_total_len"]
    assert mix["output"]["max"] == eng["max_new_tokens"]


def test_the_cell_runs_at_four_fifths_of_its_knee_and_reports_its_metrics():
    cell = manifest.cell(ROOT, "laguna-s-agent")
    assert cell["rate_per_s"] == pytest.approx(0.8 * cell["knee_per_s"],
                                               rel=0.01)
    assert cell["chips"] == 1 and cell["trace_seconds"] == 8.0
    assert cell["max_late_share"] == 0.1 and cell["correct"]["sample"] == 24
    assert set(cell["correct"]["limits"]) == {"served_gap_max",
                                              "served_gap_mean"}
    names = {m["name"] for m in manifest.per_layer(ROOT, "laguna-s-agent")}
    assert names >= {"decode_batch_mean", "step_program_p90_ms",
                     "warm_programs", "device_idle_pct.chat",
                     "host_gap_pct.chat", "kv_pool_copy_pct", "moe_ffn_pct",
                     "window_attn_pct", "setup_lower_s", "setup_executable_s",
                     "setup_cache_miss", "window_compiles", *NEW}
    assert not names & {"ragged_attn_roofline_pct", "mla_attn_roofline_pct",
                        "moe_expert_roofline_pct", "window_attn_roofline_pct",
                        "gqa_moe_expert_roofline_pct"}
    assert {m["name"] for m in manifest.end_to_end(ROOT, "laguna-s-agent")} \
        == {"tpot_p90_ms", "setup_s"}


def test_parameter_tree_matches_its_shape_and_the_reference_draws_it():
    d = fam.dims(TINY_CONFIG)
    params = fam.make_params(7, d, jnp.bfloat16)
    shapes = fam.params_shape(d, jnp.bfloat16)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), shapes)
    lay = params["layers"]
    assert lay["experts_gate"].shape == (4, 4, 48, 24)
    assert lay["attn_window"]["q_proj"].shape == (3, 48, 96)
    assert lay["attn_full"]["q_proj"].shape == (1, 48, 64)
    assert params["lead_layers"]["attn_full"]["g_proj"].shape == (1, 48, 4)
    assert params["lead_layers"]["up_proj"].shape == (1, 48, 64)
    # layer 3 is the period's third window layer, layer 4 its full one
    one = fam.layer_weights(fam.layer_key(fam.seed_key(7), jnp.int32(3)), d,
                            jnp.bfloat16, 3)
    np.testing.assert_array_equal(
        np.asarray(one["q_proj"], np.float32),
        np.asarray(lay["attn_window"]["q_proj"][2], np.float32))
    np.testing.assert_array_equal(
        np.asarray(one["experts_up"], np.float32),
        np.asarray(lay["experts_up"][2], np.float32))
    assert sum(a.size for a in jax.tree.leaves(params)) == fam.num_params(d)


# ---- operations and bytes, on cases worked by hand ----------------------
D = {"H": {"full": 48, "window": 72}, "KV": 8, "hd": 128, "W": 512,
     "D": 3072, "Fm": 1024,
     "kinds": ["full", "window", "window", "window", "full"]}


def test_attention_cost_by_hand():
    # a decode row of context 700: a window layer reads 512 keys at 72
    # heads, a full layer 700 at 48; K and V of 8 heads x 128 in bf16 =
    # 4 KB a key and layer
    full = fam.attention_cost(D, "full", [700])
    win = fam.attention_cost(D, "window", [700])
    assert full["bytes"] == 700 * 4096 + 2 * 48 * 128 * 2
    assert win["bytes"] == 512 * 4096 + 2 * 72 * 128 * 2
    assert full["flops"] == 4 * 700 * 48 * 128
    assert win["flops"] == 4 * 512 * 72 * 128
    # a row shorter than the window: the kinds differ by their heads alone
    a, b = (fam.attention_cost(D, k, [300]) for k in ("window", "full"))
    assert a["flops"] * 48 == b["flops"] * 72
    assert a["bytes"] - b["bytes"] == 2 * (72 - 48) * 128 * 2
    # a prefill row [1024, 1536): a full layer reads 1,536 keys, a window
    # layer those from 1024 - 511 on: 1,023; every query of a window layer
    # sees 512 pairs, of a full one p + 1
    full = fam.attention_cost(D, "full", (), [[1024, 1536]])
    win = fam.attention_cost(D, "window", (), [[1024, 1536]])
    assert full["bytes"] == 1536 * 4096 + 2 * 512 * 48 * 128 * 2
    assert win["bytes"] == 1023 * 4096 + 2 * 512 * 72 * 128 * 2
    assert win["flops"] == 4 * 512 * 512 * 72 * 128
    assert full["flops"] == 4 * (512 * 1024 + 512 * 513 / 2) * 48 * 128
    assert fam.roofline_seconds(win, PEAK)[1] == "compute"
    assert fam.roofline_seconds(
        fam.attention_cost(D, "window", [5000]), PEAK)[1] == "bytes"


def test_expert_ffn_cost_by_hand():
    # one expert: 3 x 3072 x 1024 = 9.44 M parameters, 18.87 MB in bf16
    c = fam.expert_ffn_cost(D, pairs=30, experts_hit=24)
    assert c["bytes"] == 24 * 3 * 3072 * 1024 * 2 == 24 * 18_874_368
    assert c["flops"] == 30 * 6 * 3072 * 1024
    assert fam.roofline_seconds(c, PEAK)[1] == "bytes"
    assert fam.roofline_seconds(
        fam.expert_ffn_cost(D, pairs=20000, experts_hit=64), PEAK)[1] \
        == "compute"


# ---- the readers of the five new metrics, on synthetic records -----------
NEW = ("gated_attn_roofline_pct", "gated_moe_expert_roofline_pct",
       "full_attn_pct", "moe_shared_pct", "moe_experts_hit_mean")


def _spec(name):
    return manifest.load_json(ROOT, f"benchmark/layer_metrics/{name}.json")


def test_readers_on_synthetic_records():
    ms = 1_000_000
    flight = [
        {"seq": 10, "mode": "decode", "synced": True, "chunk": 1,
         "decode_ctx": [100]},
        {"seq": 11, "mode": "decode", "synced": True, "chunk": 2,
         "decode_ctx": [1500, 300], "moe_pairs": 40, "moe_experts_hit": 10},
        {"seq": 12, "mode": "fused", "synced": True, "chunk": 1,
         "decode_ctx": [3000], "prefill_spans": [[1024, 1536]],
         "moe_pairs": 150, "moe_experts_hit": 20}]
    path = "jit(serve_decode_step)/while/body/closed_call/"
    ops = [("%ragged_window_attention.1", 20 * ms, 2 * ms,
            {"tf_op": path + "attn_window/attn_kernel/pallas_call:"}),
           ("%ragged_paged_attention.2", 30 * ms, 1 * ms,
            {"tf_op": path + "attn_full/attn_kernel/pallas_call:"}),
           ("%grouped_gemm.1", 23 * ms, 4 * ms,
            {"tf_op": path + "moe_experts/pallas_call:"}),
           ("%fusion.5", 33 * ms, 1 * ms,
            {"tf_op": path + "attn_full/attn_gate/mul:"}),
           ("%fusion.6", 27 * ms, 2 * ms,
            {"tf_op": path + "moe_shared/dot:"})]
    modules = [("jit_serve_decode_step(1)", 19 * ms, 10 * ms, {}),
               ("jit_serve_fused_step(2)", 29 * ms, 10 * ms, {})]
    ticks = [(10, 1 * ms, 8 * ms, "decode"), (11, 18 * ms, 10 * ms, "decode"),
             (12, 29 * ms, 11 * ms, "fused")]
    obs = {"trace_stats": _table(ops, modules, ticks), "flight": flight,
           "dims": D, "device_kind": "TPU v5 lite"}
    calls = [([1500, 300], ()), ([1501, 301], ()), ([3000], ()),
             ((), [[1024, 1536]])]
    least = sum(n * fam.roofline_seconds(fam.attention_cost(D, k, c, s),
                                         PEAK)[0]
                for k, n in (("full", 2), ("window", 3)) for c, s in calls)
    got = window_attn_roofline.read(_spec("gated_attn_roofline_pct"), obs)
    assert got == pytest.approx(100 * least / 3e-3) and 0 < got < 100
    least = sum(fam.roofline_seconds(fam.expert_ffn_cost(D, p, h), PEAK)[0]
                for p, h in ((40, 10), (150, 20)))
    got = moe_expert_roofline.read(_spec("gated_moe_expert_roofline_pct"),
                                   obs)
    assert got == pytest.approx(100 * least / 4e-3)
    # the full layers' share: their kernel call and their gate, 2 of 20 ms
    assert moe_scope_share.read(_spec("full_attn_pct"), obs) == \
        pytest.approx(100 * 2 / 20)
    assert moe_scope_share.read(_spec("moe_shared_pct"), obs) == \
        pytest.approx(100 * 2 / 20)
    assert flight_mean.read(_spec("moe_experts_hit_mean"), obs) == 15
    # a program without the scopes, kinds or counters (the parent, or
    # another family): nothing, no raise
    bare = {**obs, "dims": {"H": 32, "KV": 8, "hd": 128, "L": 16},
            "flight": [{k: v for k, v in r.items()
                        if not k.startswith("moe_")} for r in flight],
            "trace_stats": _table(
                [(n, s, dur, {"tf_op": "jit(f)/attn_kernel/x:"})
                 for n, s, dur, _ in ops], modules, ticks)}
    for name in NEW:
        spec = _spec(name)
        reader = manifest.plugin("readers", spec["reader"])
        assert reader.read(spec, bare) is None, name


# ---- the whole command at a tiny preset ----------------------------------
@pytest.fixture
def root(tiny_root):
    tiny._dump(tiny_root, "benchmark/configs/tiny-gated.json", TINY_CONFIG)
    tiny._dump(tiny_root, "benchmark/cells/tiny-gated-chat.json", {
        "config": "tiny-gated", "traffic": "chat", "chips": 1,
        "rate_per_s": 6.0, "trace_seconds": 0.5, "max_late_share": 5.0,
        "correct": {"sample": 4, "limits": LIMITS}})
    for name in NEW:
        tiny._dump(tiny_root, f"benchmark/layer_metrics/{name}.json",
                   _spec(name))
    man = manifest.manifest(tiny_root)
    man["configs"].append({"name": "tiny-gated", "source": "test",
                           "file": "benchmark/configs/tiny-gated.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "tiny-gated-chat",
                             "config": "tiny-gated", "traffic": "chat",
                             "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "tiny-chat" in m.get("workloads", []):
            m["workloads"].append("tiny-gated-chat")
    for name in NEW:
        man["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "t", "moves": "tpot_p90_ms",
            "workloads": ["tiny-gated-chat"]})
    tiny._dump(tiny_root, "BENCHMARK.json", man)
    return tiny_root


def test_the_whole_command_untraced_and_traced(root, cpu_device, capsys,
                                               monkeypatch):
    assert test_run_cpu._run(root, "tiny-gated-chat") == 0
    line, out = test_run_cpu._last(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert set(line["metrics"]) == {"tpot_p90_ms", "setup_s"}
    assert "attention xla" in out and out.count("(limit ") >= 2
    # traced, on a hand-made trace without this family's spans: the
    # metrics that read counters report (the new one among them), the
    # trace's four find nothing and are left out, not raised
    test_run_cpu._fake_trace(monkeypatch)
    monkeypatch.setattr(xstats, "load", lambda p: _table(
        [("%fusion.1", 10, 5, {"tf_op": "jit(f)/mlp/dot:"})],
        [("jit_serve_decode_step(1)", 5, 20, {})],
        [(10**6, 1, 30, "decode")]))
    assert test_run_cpu._run(root, "tiny-gated-chat", trace=1) == 0
    line, out = test_run_cpu._last(capsys)
    assert {"decode_batch_mean", "warm_programs", "moe_experts_hit_mean"} \
        <= set(line["metrics"])
    assert line["metrics"]["moe_experts_hit_mean"]["value"] > 0
    assert not set(NEW[:4]) & set(line["metrics"])
    assert "note: per-layer metric gated_attn_roofline_pct found nothing" \
        in out


def test_the_parent_fails_at_once_on_the_new_family(root, cpu_device,
                                                    monkeypatch):
    """A program without models/gated_window_moe_decoder.py (the parent,
    given this PR's BENCHMARK.json and data files) fails loudly before it
    touches a device, and does not hang."""
    real = manifest.plugin

    def parent(kind, name):
        if name == "gated_window_moe_decoder":
            raise manifest.ManifestError(f"no benchmark/{kind}/{name}.py")
        return real(kind, name)

    monkeypatch.setattr(manifest, "plugin", parent)
    with pytest.raises(manifest.ManifestError,
                       match="gated_window_moe_decoder"):
        test_run_cpu._run(root, "tiny-gated-chat")


def test_every_control_comes_out_not_correct():
    """What the limits are held against: each broken forward of the
    reference (`CONTROLS`) and every matmul rounded to float8 put other
    tokens first, whose gaps under the sound reference miss the limits,
    or, where attention is all but uniform at these widths and no first
    token moves (the rotary share), move the logits by far more than the
    sound program's distance; the chip run reads them at the published
    widths (PERF.md)."""
    d = fam.dims(TINY_CONFIG)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, d["V"], n).tolist() for n in (40, 53, 61)]
    served = []
    for p in prompts:       # the sound reference's own greedy tokens
        seq = list(p)
        for _ in range(6):
            lg = ref.logits(5, d, jnp.asarray([seq], jnp.int32))
            seq.append(int(jnp.argmax(lg[0, -1])))
        served.append(seq[len(p):])
    sound = ref.served_gaps(5, d, prompts, served, pad=16)
    assert sound.shape == (18,) and float(sound.max()) < 1e-5
    toks = jnp.asarray([prompts[2] + served[2]], jnp.int32)
    base = np.asarray(ref.logits(5, d, toks))
    assert set(ref.CONTROLS) >= {"drop_gate", "full_rotary", "drop_shared",
                                 "route_scale"}
    for name, broken in {"fp8": {"act": ref.fp8}, **ref.CONTROLS}.items():
        gaps = ref.served_gaps(5, d, prompts, served, pad=16, **broken)
        missed = float(gaps.mean()) > LIMITS["served_gap_mean"] \
            or float(gaps.max()) > LIMITS["served_gap_max"]
        moved = np.abs(np.asarray(ref.logits(5, d, toks, **broken)) - base)
        assert missed or moved.max() > 1e-3, (name, gaps.max(), moved.max())
