"""Rehearsals of the `window_moe_decoder` family and the `mellum2-l8`
configuration on the CPU: the configuration against the catalog row, the
cost functions on hand-worked cases, the new reader on synthetic records
and on a recorded trace table, the whole command at a tiny preset, the
reference against the paged path, and a broken program coming out not
correct."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import device, manifest
from benchmark.models import window_moe_decoder as fam
from benchmark.readers import (moe_expert_roofline, moe_scope_share,
                               window_attn_roofline, xstats)
from benchmark.reference import window_moe_decoder as ref
from benchmark.tests import test_run_cpu, tiny

ROOT = manifest.ROOT
PEAK = device.peaks("TPU v5 lite")
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
          "blob/main/config.json")

# the catalog row `Mellum2-12B-A2.5B-Instruct` (model-configs guide,
# architectures.jsonl), its `config` key for key
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}

TINY_MODEL = {
    **CATALOG, "head_dim": 16, "hidden_size": 48, "intermediate_size": 96,
    "max_position_embeddings": 512, "moe_intermediate_size": 24,
    "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 8, "num_key_value_heads": 2, "sliding_window": 16,
    "vocab_size": 256,
    "rope_parameters": {
        "full_attention": {**CATALOG["rope_parameters"]["full_attention"],
                           "rope_theta": 10000.0, "factor": 4.0,
                           "original_max_position_embeddings": 32,
                           "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000.0}}}
TINY_CONFIG = {"source": "test", "family": "window_moe_decoder",
               **TINY_MODEL, "served_dtype": "bfloat16",
               "engine": {**tiny.ENGINE, "max_prefill_group": 2,
                          "prefix_cache": False}}
# sound runs here (bf16 on the CPU): every served token is the reference's
# own first or within 0.002 of it; the window left out: largest 0.06+; the
# renormalisation left out: largest 0.01+
LIMITS = {"served_gap_max": 0.005, "served_gap_mean": 5e-4}


def test_configuration_keeps_the_catalog_rows_widths():
    man = manifest.manifest(ROOT)
    entry = next(c for c in man["configs"] if c["name"] == "mellum2-l8")
    spec = manifest.config(ROOT, "mellum2-l8")
    assert spec["source"] == entry["source"] == SOURCE
    # the published keys lie at the file's top level, where the driver's
    # check against the catalog row reads them
    assert set(spec) >= set(CATALOG) and "model" not in spec
    changed = {k for k in CATALOG if spec[k] != CATALOG[k]}
    assert changed == set(entry["reduced"]) == set(spec["reduced"]) == \
        {"num_hidden_layers"}
    assert spec["num_hidden_layers"] == 8 and \
        spec["published"]["num_hidden_layers"] == 28
    assert set(spec["assumed"]) >= {"router", "qk_norm", "window",
                                    "rope_layout", "mtp_head", "weights",
                                    "slots"}
    assert spec["deployment"]
    d = fam.dims(spec)
    # two whole periods of the published pattern, every width as published
    assert d["kinds"] == ["window", "window", "window", "full"] * 2
    assert (d["D"], d["H"], d["KV"], d["hd"], d["E"], d["n"], d["k"],
            d["Fm"], d["W"], d["V"]) == (2304, 32, 4, 128, 64, 64, 8, 896,
                                         1024, 98304)
    assert d["H"] * d["hd"] == 4096 != d["D"]
    assert fam.num_params(d) == pytest.approx(3.80e9, rel=2e-3)
    assert fam.num_params(d) * 2 / 2**30 == pytest.approx(7.07, abs=0.01)
    pcfg = fam.program_config(spec)
    assert pcfg.period_kinds == ("window", "window", "window", "full")
    assert pcfg.head_dim == 128 and pcfg.sliding_window == 1024
    assert pcfg.scoring_func == "softmax" and not pcfg.n_shared_experts
    assert pcfg.experts_first == 0 and pcfg.experts_count == 64
    # the pools: full layers 32 x 800 blocks x 2 layers, window layers 32
    # rings of 97 x 6 layers, 16 tokens of 2 KB a block and layer
    from paddle_tpu.nlp import paged
    eng = spec["engine"]
    assert eng["max_total_len"] == 12800 and eng["max_batch"] == 32
    ring = paged.ring_blocks(1024, max(eng["prefill_buckets"]),
                             eng["block_size"])
    # the full layers' pool is sized by memory (no program donates it
    # yet), not by slots x the longest sequence, which would be 2.13 GiB
    assert eng["num_blocks"] == 16384 < 32 * 800
    blocks = 2 * eng["num_blocks"] + 6 * 32 * ring
    assert ring == 97 and blocks * 16 * 2048 / 2**30 == \
        pytest.approx(1.57, abs=0.01)
    assert (2 * 32 * 800 + 6 * 32 * ring) * 16 * 2048 / 2**30 == \
        pytest.approx(2.13, abs=0.01)
    cell = manifest.cell(ROOT, "mellum2-code")
    assert cell["rate_per_s"] == pytest.approx(0.8 * cell["knee_per_s"])
    mix = manifest.traffic(ROOT, "code-context")
    assert mix["prompt"]["max"] + mix["output"]["max"] <= eng["max_total_len"]
    assert mix["output"]["max"] == eng["max_new_tokens"]
    # the cell reports every accepted metric it should (later PRs list it
    # under theirs too: the set CONTAINS these), and not the dense or the
    # latent kernel's roofline, whose count would be wrong for it
    names = {m["name"] for m in manifest.per_layer(ROOT, "mellum2-code")}
    assert names >= {"decode_batch_mean", "step_program_p90_ms",
                     "warm_programs", "device_idle_pct.chat",
                     "host_gap_pct.chat", "kv_pool_copy_pct", "moe_ffn_pct",
                     "window_attn_roofline_pct",
                     "gqa_moe_expert_roofline_pct", "window_attn_pct"}
    assert not names & {"ragged_attn_roofline_pct", "mla_attn_roofline_pct",
                        "moe_expert_roofline_pct"}


def test_parameter_tree_matches_its_shape_and_the_reference_draws_it():
    d = fam.dims(TINY_CONFIG)
    params = fam.make_params(7, d, jnp.bfloat16)
    shapes = fam.params_shape(d, jnp.bfloat16)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), shapes)
    assert params["layers"]["experts_gate"].shape == (8, 8, 48, 24)
    assert params["layers"]["q_proj"].shape == (8, 48, 64)
    one = fam.layer_weights(fam.layer_key(fam.seed_key(7), jnp.int32(5)), d,
                            jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(one["experts_up"], np.float32),
        np.asarray(params["layers"]["experts_up"][5], np.float32))


# ---- operations and bytes, on cases worked by hand ----------------------
D = {"H": 32, "KV": 4, "hd": 128, "W": 1024, "D": 2304, "Fm": 896,
     "kinds": ["window", "window", "window", "full"] * 2}


def test_attention_cost_by_hand():
    # a decode row of ctx 5,000: a full layer reads 5,000 keys, a window
    # layer 1,024; K and V of 4 heads x 128 in bf16 = 2 KB a key and layer
    full = fam.attention_cost(D, "full", [5000])
    win = fam.attention_cost(D, "window", [5000])
    qo = 2 * 32 * 128 * 2
    assert full["bytes"] == 5000 * 2048 + qo
    assert win["bytes"] == 1024 * 2048 + qo
    assert full["flops"] == 4 * 5000 * 32 * 128
    assert win["flops"] == 4 * 1024 * 32 * 128
    # over the model's 2 full + 6 window layers: 2 x 5,000 + 6 x 1,024 keys
    keys = sum(fam.attention_cost(D, k, [5000])["bytes"] - qo
               for k in D["kinds"]) / 2048
    assert keys == 2 * 5000 + 6 * 1024 == 16144
    # a row shorter than the window: both kinds alike
    assert fam.attention_cost(D, "window", [700]) == \
        fam.attention_cost(D, "full", [700])
    # a prefill row [2048, 2560): a full layer reads 2,560 keys, a window
    # layer those from 2048 - 1023 on: 1,535; pairs: every query sees
    # 1,024 in a window layer, p + 1 in a full one
    full = fam.attention_cost(D, "full", (), [[2048, 2560]])
    win = fam.attention_cost(D, "window", (), [[2048, 2560]])
    q = 2 * 512 * 32 * 128 * 2
    assert full["bytes"] == 2560 * 2048 + q
    assert win["bytes"] == 1535 * 2048 + q
    assert win["flops"] == 4 * 512 * 1024 * 32 * 128
    assert full["flops"] == 4 * (512 * 2048 + 512 * 513 / 2) * 32 * 128
    # a cold chunk's first row [0, 512): the window hides nothing
    assert fam.attention_cost(D, "window", (), [[0, 512]]) == \
        fam.attention_cost(D, "full", (), [[0, 512]])
    assert fam.roofline_seconds(win, PEAK)[1] == "compute"
    assert fam.roofline_seconds(
        fam.attention_cost(D, "window", [5000]), PEAK)[1] == "bytes"


def test_expert_ffn_cost_by_hand():
    # one expert: 3 x 2304 x 896 = 6.19 M parameters, 12.39 MB in bf16
    c = fam.expert_ffn_cost(D, pairs=56, experts_hit=39)
    assert c["bytes"] == 39 * 3 * 2304 * 896 * 2 == 39 * 12_386_304
    assert c["flops"] == 56 * 6 * 2304 * 896
    assert fam.roofline_seconds(c, PEAK)[1] == "bytes"
    assert fam.roofline_seconds(
        fam.expert_ffn_cost(D, pairs=16640, experts_hit=64), PEAK)[1] \
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
         "decode_ctx": [5000, 500], "moe_pairs": 40, "moe_experts_hit": 10},
        {"seq": 12, "mode": "fused", "synced": True, "chunk": 1,
         "decode_ctx": [3000], "prefill_spans": [[2048, 2560]],
         "moe_pairs": 150, "moe_experts_hit": 20},
        {"seq": 13, "mode": "prefill", "cold": True, "synced": True,
         "prefill_spans": [[0, 512]]}]
    # a kernel's event carries its form's name and its layer's scope path
    win = ("jit(serve_decode_step)/while/body/closed_call/attn_window/"
           "attn_kernel/jit(ragged_paged_attention)/ragged_window_attention/"
           "pallas_call:")
    ops = [("%ragged_window_attention.1", 20 * ms, 2 * ms, {"tf_op": win}),
           ("%ragged_paged_attention.2", 30 * ms, 1 * ms,
            {"tf_op": win.replace("window", "full")}),
           ("%ragged_window_attention.9", 2 * ms, 5 * ms, {"tf_op": win}),
           ("%ragged-dot.1", 23 * ms, 4 * ms, {"tf_op": "ragged-dot-none:"}),
           ("%fusion.5", 33 * ms, 1 * ms,
            {"tf_op": "jit(f)/while/body/attn_window/attn_qkv/dot:"}),
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
    # ticks 11-13 (seq 10 synced, so the cut starts after it): two decode
    # calls of rows 5000+500 then 5001+501, a fused tick's decode call
    # (3000) and prefill call ([2048, 2560)); the cold prefill: nothing.
    # Each call per kind, 2 full + 6 window layers
    calls = [([5000, 500], ()), ([5001, 501], ()), ([3000], ()),
             ((), [[2048, 2560]])]
    least = sum(n * fam.roofline_seconds(fam.attention_cost(D, k, c, s),
                                         PEAK)[0]
                for k, n in (("full", 2), ("window", 6)) for c, s in calls)
    got = window_attn_roofline.read(_spec("window_attn_roofline_pct"), obs)
    assert got == pytest.approx(100 * least / 3e-3) and 0 < got < 100
    # the uniform count (every layer a row's whole context) would read
    # higher: the window's bound is in the least seconds
    uniform = 8 * sum(fam.roofline_seconds(
        fam.attention_cost(D, "full", c, s), PEAK)[0] for c, s in calls)
    assert uniform > 1.5 * least
    least = sum(fam.roofline_seconds(fam.expert_ffn_cost(D, p, h), PEAK)[0]
                for p, h in ((40, 10), (150, 20)))
    got = moe_expert_roofline.read(_spec("gqa_moe_expert_roofline_pct"), obs)
    assert got == pytest.approx(100 * least / 5e-3)
    # the window layers' share of the decoding programs: the window
    # kernel's event and the projection, both under attn_window, 3 of
    # 20 ms; the full layers' kernel call is not theirs
    got = moe_scope_share.read(_spec("window_attn_pct"), obs)
    assert got == pytest.approx(100 * 3 / 20)
    # a program without the spans or counters (the parent): nothing, no raise
    bare = {**obs, "dims": {"H": 32, "KV": 8, "hd": 128, "L": 16},
            "flight": [{k: v for k, v in r.items()
                        if not k.startswith("moe_")} for r in flight],
            "trace_stats": _table(
                [(n.replace("_window_", "_paged_"), s, dur,
                  {"tf_op": "jit(f)/attn_kernel/x:"})
                 for n, s, dur, _ in ops], modules, ticks)}
    assert window_attn_roofline.read(_spec("window_attn_roofline_pct"),
                                     bare) is None
    assert moe_expert_roofline.read(_spec("gqa_moe_expert_roofline_pct"),
                                    bare) is None
    assert moe_scope_share.read(_spec("window_attn_pct"), bare) is None


def test_new_readers_on_a_recorded_trace_find_nothing_and_do_not_raise():
    """The recorded chip trace of the dense cell (tests/data/
    chat_tick_cut.json.gz: the parent's program, four ticks with their
    flight records): no window scope, no kinds, no counters."""
    import gzip
    import json
    import os
    with gzip.open(os.path.join(os.path.dirname(__file__), "data",
                                "chat_tick_cut.json.gz"), "rt") as f:
        recorded = json.load(f)
    obs = {"trace_stats": {"planes": recorded["planes"]},
           "flight": recorded["flight"],
           "dims": {"H": 32, "KV": 8, "hd": 128, "L": 16},
           "device_kind": "TPU v5 lite"}
    for name in ("window_attn_roofline_pct", "gqa_moe_expert_roofline_pct",
                 "window_attn_pct"):
        spec = _spec(name)
        reader = manifest.plugin("readers", spec["reader"])
        assert reader.read(spec, obs) is None


# ---- the whole command at a tiny preset ----------------------------------
NEW = ("window_attn_roofline_pct", "gqa_moe_expert_roofline_pct",
       "window_attn_pct")


@pytest.fixture
def root(tiny_root):
    tiny._dump(tiny_root, "benchmark/configs/tiny-window.json", TINY_CONFIG)
    tiny._dump(tiny_root, "benchmark/cells/tiny-window-chat.json", {
        "config": "tiny-window", "traffic": "chat", "chips": 1,
        "rate_per_s": 6.0, "trace_seconds": 0.5, "max_late_share": 5.0,
        "correct": {"sample": 4, "limits": LIMITS}})
    for name in NEW:
        tiny._dump(tiny_root, f"benchmark/layer_metrics/{name}.json",
                   _spec(name))
    man = manifest.manifest(tiny_root)
    man["configs"].append({"name": "tiny-window", "source": "test",
                           "file": "benchmark/configs/tiny-window.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "tiny-window-chat",
                             "config": "tiny-window", "traffic": "chat",
                             "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "tiny-chat" in m.get("workloads", []):
            m["workloads"].append("tiny-window-chat")
    for name in NEW:
        man["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "t", "moves": "tpot_p90_ms",
            "workloads": ["tiny-window-chat"]})
    tiny._dump(tiny_root, "BENCHMARK.json", man)
    return tiny_root


def test_the_whole_command_untraced_and_traced(root, cpu_device, capsys,
                                               monkeypatch):
    assert test_run_cpu._run(root, "tiny-window-chat") == 0
    line, out = test_run_cpu._last(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert set(line["metrics"]) == {"tpot_p90_ms", "setup_s"}
    assert "attention xla" in out and out.count("(limit ") >= 2
    # traced, on a hand-made trace without this family's spans: the
    # metrics that read counters report, the new three find nothing and
    # are left out, not raised
    test_run_cpu._fake_trace(monkeypatch)
    monkeypatch.setattr(xstats, "load", lambda p: _table(
        [("%fusion.1", 10, 5, {"tf_op": "jit(f)/mlp/dot:"})],
        [("jit_serve_decode_step(1)", 5, 20, {})],
        [(10**6, 1, 30, "decode")]))
    assert test_run_cpu._run(root, "tiny-window-chat", trace=1) == 0
    line, out = test_run_cpu._last(capsys)
    assert {"decode_batch_mean", "warm_programs"} <= set(line["metrics"])
    assert not set(NEW) & set(line["metrics"])
    assert "note: per-layer metric window_attn_roofline_pct found nothing" \
        in out


def test_the_parent_fails_at_once_on_the_new_family(root, cpu_device,
                                                    monkeypatch):
    """A program without models/window_moe_decoder.py (the parent, given
    this PR's BENCHMARK.json and data files) fails loudly before it
    touches a device, and does not hang."""
    real = manifest.plugin

    def parent(kind, name):
        if name == "window_moe_decoder":
            raise manifest.ManifestError(f"no benchmark/{kind}/{name}.py")
        return real(kind, name)

    monkeypatch.setattr(manifest, "plugin", parent)
    with pytest.raises(manifest.ManifestError, match="window_moe_decoder"):
        test_run_cpu._run(root, "tiny-window-chat")


def test_a_broken_program_comes_out_not_correct():
    """What the limits are held against: the reference with the window
    left out on window layers puts other tokens first, and their gaps under the sound reference
    miss the limits; so does every matmul rounded to float8. (YaRN's
    attention factor or the gates' renormalisation left out move the
    logits but no first token at these widths, where attention is all but
    uniform and two of eight experts carry a token: the chip run reads
    them, PERF.md.)"""
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
    for broken in ({"no_window": True}, {"act": ref.fp8}):
        gaps = ref.served_gaps(5, d, prompts, served, pad=16, **broken)
        assert float(gaps.mean()) > LIMITS["served_gap_mean"] \
            or float(gaps.max()) > LIMITS["served_gap_max"], broken
    # the other two move the logits and no first token at these widths
    toks = jnp.asarray([prompts[2] + served[2]], jnp.int32)
    sound = np.asarray(ref.logits(5, d, toks))
    for broken in ({"no_renorm": True}, {"no_attention_factor": True}):
        moved = np.abs(np.asarray(ref.logits(5, d, toks, **broken)) - sound)
        assert moved.max() > 2e-3, broken
    assert set(ref.CONTROLS) == {"no_window", "no_attention_factor",
                                 "no_renorm"}
