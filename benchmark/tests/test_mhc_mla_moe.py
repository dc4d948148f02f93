"""Rehearsals of the `mhc_mla_moe_decoder` family and the `xing4-ep4-train`
configuration on the CPU: the configuration against the catalog row, the
whole command at a tiny preset (untraced and traced), the cost functions
on hand-worked cases, the new reader on a small made-up table with
forward, `jvp` and `transpose` components, and the broken programs coming
out not correct."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import device, manifest
from benchmark.models import mhc_mla_moe_decoder as fam
from benchmark.readers import train_scope_share, xstats
from benchmark.reference import mhc_mla_moe_decoder as ref
from benchmark.runners import train as runner
from benchmark.tests import test_run_cpu, tiny

ROOT = manifest.ROOT
PEAK = device.peaks("TPU v5 lite")

# the catalog row `Xing4.0-29B-A4B` (model-configs guide,
# architectures.jsonl), its `config` key for key
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
# the floors of a cut: a dense layer (they count once) + 4 expert layers,
# 8 routed experts held, an eighth of the vocabulary
FLOORS = {"num_hidden_layers": 1 + 4, "first_k_dense_replace": 1,
          "n_routed_experts": 8, "vocab_size": 131072 // 8}

TINY_MODEL = {
    **CATALOG, "first_k_dense_replace": 1, "hidden_size": 64,
    "intermediate_size": 160, "kv_lora_rank": 32,
    "max_position_embeddings": 256, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "num_attention_heads": 4,
    "num_hidden_layers": 3, "num_key_value_heads": 4, "q_lora_rank": 48,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "vocab_size": 256,
    "rope_scaling": {**CATALOG["rope_scaling"], "factor": 4,
                     "original_max_position_embeddings": 64}}
TINY_CONFIG = {"source": "test", "family": "mhc_mla_moe_decoder",
               **TINY_MODEL, "trained_dtype": "bfloat16",
               "share": {"router_experts": 16, "experts_first": 4},
               "trainer": tiny.TRAINER}
# sound runs here (bf16 on the CPU, 4 x 32 tokens): loss gap 5e-5,
# gradient-norm gap 0.010, change-norm gap 0.72 (the norm scales, 1 +- 0.1
# in bfloat16, do not move by 1e-4 a step, and at these sizes the median
# leaf is a small one; a step that changes nothing reads 1.0); every
# broken program misses the loss or the gradient norms by far (below)
LIMITS = {"loss_gap": 0.002, "grad_norm_gap": 0.025, "delta_norm_gap": 0.85}


def test_configuration_keeps_the_catalog_rows_widths():
    man = manifest.manifest(ROOT)
    entry = next(c for c in man["configs"] if c["name"] == "xing4-ep4-train")
    spec = manifest.config(ROOT, "xing4-ep4-train")
    assert spec["source"] == entry["source"] == (
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
        "config.json")
    # the published keys lie at the file's top level, where the driver's
    # check against the catalog row reads them
    assert set(spec) >= set(CATALOG) and "model" not in spec
    changed = {k for k in CATALOG if spec[k] != CATALOG[k]}
    assert changed == set(entry["reduced"]) == \
        {k for k in spec["reduced"] if "." not in k} == set(FLOORS)
    for k in entry["reduced"]:
        # no width: no hidden, intermediate, latent or projection size, no
        # head size, not the experts per token, not the streams
        assert not k.endswith(("_dim", "_rank", "_size")) or k == "vocab_size"
        assert k not in ("num_experts_per_tok", "num_attention_heads",
                         "hc_mult")
        assert spec[k] >= FLOORS[k]
        assert spec["share"]["published"][k] == CATALOG[k]
    assert spec["share"]["router_experts"] == CATALOG["n_routed_experts"]
    assert spec["share"]["chips_sharing_a_layer"] * \
        spec["n_routed_experts"] == CATALOG["n_routed_experts"]
    assert set(spec["assumed"]) >= {
        "hc_order", "hc_eps", "hc_norm_scale", "hc_entry_exit",
        "mtp_loss_weight", "rope_layout", "weights", "trainer"}
    assert spec["deployment"] and spec["trainer"]["state_quant"] == "8bit"
    d = fam.dims(spec)
    assert (d["E"], d["n"], d["k"], d["route_scale"], d["hc"]) == \
        (64, 16, 4, 2.0, 4)
    assert (d["Ld"], d["L"]) == (1, spec["num_hidden_layers"] - 1)
    # the issue's count a layer: attention 28.4 M, mHC 0.72 M, a dense
    # layer 128.2 M, an expert layer 216.5 M
    assert fam._layer_params(d, False)["params"] / 1e6 == \
        pytest.approx(128.2, abs=0.1)
    assert fam._layer_params(d, True)["params"] / 1e6 == \
        pytest.approx(216.5, abs=0.1)
    shapes = fam.params_shape(d)
    assert fam.num_params(d) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    pcfg = fam.program_config(spec)
    assert pcfg.router == "sigmoid_bias" and pcfg.hc_mult == 4
    # (192)^-0.5 x yarn_mscale(64, 1)^2
    assert pcfg.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2, rel=1e-6)
    cell = manifest.cell(ROOT, "xing4-train-4k")
    mix = manifest.traffic(ROOT, cell["traffic"])
    assert (mix["kind"], mix["batch"], mix["seq_len"]) == ("train", 4, 4096)
    assert mix["seq_len"] == \
        CATALOG["rope_scaling"]["original_max_position_embeddings"]


def test_parameter_tree_is_the_one_the_runner_walks():
    d = fam.dims(TINY_CONFIG)
    params = fam.make_params(7, d, jnp.bfloat16)
    shapes = fam.params_shape(d, jnp.bfloat16)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), shapes)
    # one stacked group, every other leaf an array beside it
    assert all(not isinstance(v, dict) for k, v in params.items()
               if k != "layers")
    assert params["layers"]["experts_gate"].shape == (2, 4, 64, 32)
    assert params["layers"]["e_bias"].shape == (2, 16)
    # every chip's block of 4 experts draws the same bias values, in an
    # order of its own: each chip is routed about a quarter of the pairs
    blocks = np.sort(np.asarray(params["layers"]["e_bias"]).reshape(2, 4, 4))
    np.testing.assert_array_equal(blocks, blocks[:, :1].repeat(4, 1))
    assert len({tuple(b) for b in np.asarray(
        params["layers"]["e_bias"][0]).reshape(4, 4)}) > 1
    assert params["dense_gate_proj"].shape == (1, 64, 160)
    assert params["mtp_eh_proj"].shape == (128, 64)
    assert params["layers"]["hc_attn_phi"].shape == (2, 256, 24)
    assert not set(params["layers"]) & set(params)
    # expert layer 1 drawn alone is the stacked tree's row, and the outer
    # leaves drawn alone are the tree's
    key = fam.seed_key(7)
    one = fam.layer_weights(fam.layer_key(key, jnp.int32(1)), d, jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(one["experts_up"], np.float32),
        np.asarray(params["layers"]["experts_up"][1], np.float32))
    outer = fam.outer_weights(key, d, jnp.bfloat16)
    assert set(outer) == set(params) - {"layers"}
    np.testing.assert_array_equal(
        np.asarray(outer["mtp_hc_ffn_b"], np.float32),
        np.asarray(params["mtp_hc_ffn_b"], np.float32))
    # the program's own tree has the same names, shapes and types: the
    # matrices in the trained type, every 1-D leaf float32
    from paddle_tpu.nlp import mla_train
    own = jax.eval_shape(lambda: mla_train.init_params(
        jax.random.key(0), fam.program_config(TINY_CONFIG)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), own) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), shapes)
    assert params["layers"]["hc_attn_a"].dtype == jnp.float32
    assert params["norm"].dtype == jnp.float32
    assert params["layers"]["router"].dtype == jnp.bfloat16


# ---- operations and bytes, on cases worked by hand ----------------------
D = {"H": 32, "dn": 128, "dr": 64, "dv": 128, "D": 3584, "Q": 768, "R": 512,
     "F": 9216, "Fm": 1024, "E": 64, "n": 16, "k": 4, "shared": 1, "hc": 4,
     "V": 16384, "L": 4, "Ld": 1}


def test_causal_attention_cost_by_hand():
    # 4 x 4096, 32 heads: the causal half of QK^T over 192 columns and of
    # PV over 128, 2 FLOPs a multiply-add: 4 x 32 x (4096^2 / 2) x 320 x 2
    c = fam.causal_attention_cost(D, 4, 4096)
    assert c["flops"] == 4 * 32 * (4096 ** 2 // 2) * 320 * 2
    # q, k 192 wide and v, o 128 wide, bf16, once each
    assert c["bytes"] == 4 * 4096 * 32 * (192 + 192 + 128 + 128) * 2
    t, bound = fam.roofline_seconds(c, PEAK)
    assert bound == "compute" and t == pytest.approx(3.488e-3, rel=1e-3)
    b = fam.causal_attention_cost(D, 4, 4096, backward=True)
    assert b["flops"] == 2.5 * c["flops"] and b["bytes"] == 2 * c["bytes"]
    # a padded value (192 wide) would read 6/5 of this: not credited
    assert c["flops"] / (4 * 32 * (4096 ** 2 // 2) * 384 * 2) == \
        pytest.approx(5 / 6)


def test_train_flops_per_token_by_hand():
    attn_proj = (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256
                 + 32 * 128 * 3584)
    assert attn_proj == 28_409_856           # the 28.4 M of the issue
    hc = 2 * (4 * 3584 * 24 + 24 * 3584)            # phi and the mixes
    attn = 32 * 320 * 4096 // 2                     # the causal half
    dense = attn_proj + hc + attn + 3 * 3584 * 9216
    # the router, the shared expert, ONE routed pair a token in expectation
    moe = attn_proj + hc + attn + 3584 * 64 + 3 * 3584 * 1024 * (1 + 1)
    mtp = 2 * 3584 * 3584 + moe
    want = 6 * (dense + 4 * moe + mtp + 2 * 16384 * 3584)
    assert fam.train_flops_per_token(D, 4096) == pytest.approx(want)
    # 16 of 64 held: k n / E = 1 pair; all 64 held would be 4
    assert fam.train_flops_per_token({**D, "n": 64}, 4096) - want == \
        pytest.approx(6 * 5 * 3 * 3 * 3584 * 1024)
    spec = manifest.config(ROOT, "xing4-ep4-train")
    fwd = fam.train_flops_per_token(fam.dims(spec), 4096) / 3
    assert 1.3e9 < fwd < 1.6e9          # the issue's 1.53 GFLOP at V 32768


# ---- the new reader, on a small made-up table -----------------------------
def _table(ops, modules):
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]}]}


def _spec(name):
    return manifest.load_json(ROOT, f"benchmark/layer_metrics/{name}.json")


def test_train_scope_share_reads_forward_recomputed_and_backward(
        tmp_path, monkeypatch):
    us = 1000
    step = "jit(train_step)/"
    ops = [
        ("%fusion.1", 10 * us, 2 * us, {"tf_op": step + "jvp(hc_pre)/mul:"}),
        ("%fusion.2", 13 * us, 3 * us, {
            "tf_op": step + "transpose(jvp())/while/body/closed_call/"
            "checkpoint/rematted_computation/hc_post/add:"}),
        ("%fusion.3", 17 * us, 4 * us, {
            "tf_op": step + "transpose(jvp(hc_sinkhorn))/div:"}),
        ("%fusion.4", 22 * us, 4 * us, {
            "tf_op": step + "transpose(jvp(mtp))/lm_head/dot_general:"}),
        # no scope path, between two operations of the module: the module's
        ("%ragged-dot.6", 26 * us, 1 * us, {"tf_op": "ragged-dot-none:"}),
        ("%copy.3", 27 * us, 1 * us, {}),
        ("%fusion.5", 28 * us, 6 * us, {
            "tf_op": step + "jvp(mtp)/while/body/jvp(moe_router)/dot:"}),
        # between the module and another layer: an expert GEMM, not mtp's
        ("%ragged-dot.7", 35 * us, 7 * us, {"tf_op": "ragged-dot-none:"}),
        ("%fusion.8", 43 * us, 8 * us, {"tf_op": step + "jvp(mlp)/dot:"}),
        ("%fusion.9", 52 * us, 9 * us, {
            "tf_op": step + "jit(hc_prefetch)/mul:"}),      # no scope of ours
        ("%fusion.10", 300 * us, 9 * us, {
            "tf_op": step + "jvp(hc_pre)/mul:"})]           # outside a step
    modules = [("jit_train_step(1)", 5 * us, 100 * us, {}),
               ("jit_tokens(2)", 290 * us, 30 * us, {})]
    obs = {"trace_stats": _table(ops, modules)}
    got = {n: train_scope_share.read(_spec(n), obs)
           for n in ("mhc_pct", "moe_train_pct", "mtp_pct")}
    assert got["mhc_pct"] == pytest.approx(100 * (2 + 3 + 4) / 100)
    assert got["moe_train_pct"] == pytest.approx(100 * (1 + 6 + 7) / 100)
    assert got["mtp_pct"] == pytest.approx(100 * (4 + 1 + 1 + 6) / 100)
    assert train_scope_share.scope_under(
        "jit(f)/transpose(jvp(hc_coef))/mul:", {"hc_coef"}) == "hc_coef"
    assert train_scope_share.scope_under(
        "jit(f)/jit(hc_coef_like)/mul:", {"hc_coef"}) == ""
    # a program without the scopes (the parent), or a run without a
    # trace: nothing, no raise
    bare = {"trace_stats": _table([ops[8]], modules)}
    assert train_scope_share.read(_spec("mhc_pct"), bare) is None
    assert train_scope_share.read(_spec("mhc_pct"), {"trace": None}) is None
    # the train runner keeps no trace_dir: the newest directory under the
    # temporary one is read only if it holds THIS run's programs
    os.mkdir(tmp_path / "bench_trace_x")
    monkeypatch.setattr(train_scope_share.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    monkeypatch.setattr(train_scope_share.xplane, "find_xplane", lambda d: d)
    monkeypatch.setattr(xstats, "load", lambda p: _table(ops, modules))
    mine = {"trace": _table([], modules)}
    assert train_scope_share.read(_spec("mhc_pct"), mine) == got["mhc_pct"]
    assert mine["trace_dir"] == str(tmp_path / "bench_trace_x")
    other = {"trace": _table([], [("jit_train_step(1)", 6 * us, 100 * us,
                                   {})])}
    assert train_scope_share.read(_spec("mhc_pct"), other) is None
    assert "trace_dir" not in other


# ---- the whole command at a tiny preset ----------------------------------
# the four that read the trace (the share of the peak needs a chip's
# published peak: below, on made-up values)
NEW_METRICS = ("mla_flash_roofline_pct", "mhc_pct", "moe_train_pct",
               "mtp_pct")


@pytest.fixture
def root(tiny_root):
    tiny._dump(tiny_root, "benchmark/configs/tiny-mhc.json", TINY_CONFIG)
    tiny._dump(tiny_root, "benchmark/cells/tiny-mhc-train.json", {
        "config": "tiny-mhc", "traffic": "pretrain", "chips": 1,
        "trace_seconds": 0.5,
        "correct": {"steps": 2, "rows": 2, "limits": LIMITS}})
    for name in NEW_METRICS:
        tiny._dump(tiny_root, f"benchmark/layer_metrics/{name}.json",
                   _spec(name))
    man = manifest.manifest(tiny_root)
    man["configs"].append({"name": "tiny-mhc", "source": "test",
                           "file": "benchmark/configs/tiny-mhc.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "tiny-mhc-train", "config": "tiny-mhc",
                             "traffic": "pretrain", "chips": 1,
                             "why": "test"})
    for m in man["end_to_end"]:
        if "tiny-train" in m.get("workloads", []):
            m["workloads"].append("tiny-mhc-train")
    for name in NEW_METRICS:
        man["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "t", "moves": "train_tok_s",
            "workloads": ["tiny-mhc-train"]})
    tiny._dump(tiny_root, "BENCHMARK.json", man)
    return tiny_root


def test_the_whole_command_untraced_and_traced(root, cpu_device, capsys,
                                               monkeypatch):
    assert test_run_cpu._run(root, "tiny-mhc-train") == 0
    line, out = test_run_cpu._last(capsys)
    assert line["correct"] is True and line["failed"] == 0, out
    assert line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_tok_s", "setup_s"}
    assert out.count("(limit ") == 3 and "FAILED" not in out
    # traced, on the hand-made trace of the dense cell: the four that read
    # this family's spans and kernels find nothing on a trace without
    # them and are left out, not raised
    test_run_cpu._fake_trace(monkeypatch)
    monkeypatch.setattr(xstats, "load", lambda p: _table(
        [("%fusion.1", 10, 5, {"tf_op": "jit(train_step)/jvp(mlp)/dot:"})],
        [("jit_train_step(1)", 5, 20, {})]))
    assert test_run_cpu._run(root, "tiny-mhc-train", trace=1) == 0
    line, out = test_run_cpu._last(capsys)
    assert not set(line["metrics"]) & set(NEW_METRICS)
    for name in NEW_METRICS:
        assert f"note: per-layer metric {name} found nothing" in out


def test_share_of_the_peak_and_flash_roofline_on_made_up_values():
    from benchmark.readers import flash_roofline, mfu
    spec = manifest.config(ROOT, "xing4-ep4-train")
    d = fam.dims(spec)
    obs = {"values": {"train_tok_s": 15000.0}, "dims": d, "seq_len": 4096,
           "batch": 4, "device_kind": "TPU v5 lite", "chips": 1}
    got = mfu.read(_spec("mfu_pct.mhc_moe"), obs)
    assert got == pytest.approx(
        100 * 15000 * fam.train_flops_per_token(d, 4096) / 197e12)
    assert 25 < got < 35
    # 6 backward calls a step (5 layers and the module's), 2 steps traced:
    # forward, recomputed forward and backward events, 400 ms in all
    ms = 1_000_000
    ops = [(f"%flash_attention_pallas.{i}", i * 40 * ms, 10 * ms)
           for i in range(24)] + [
        (f"%flash_attention_pallas_bwd.{i}", (i * 40 + 20) * ms,
         160 * ms // 12) for i in range(12)]
    obs["trace"] = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops}]}]}
    least = sum(fam.roofline_seconds(fam.causal_attention_cost(
        d, 4, 4096, backward=b), PEAK)[0] for b in (False, True))
    got = flash_roofline.read(_spec("mla_flash_roofline_pct"), obs)
    assert got == pytest.approx(100 * least * 12 / 0.4, rel=1e-3)
    assert 0 < got < 100


def _gaps(got, want):
    return {"loss_gap": max(abs(a - b) for a, b in zip(got["loss"],
                                                       want["loss"])),
            "grad_norm_gap": runner.worst_leaf_gap(got["grad_norm"],
                                                   want["grad_norm"]),
            "delta_norm_gap": runner.worst_leaf_gap(got["delta_norm"],
                                                    want["delta_norm"])}


def test_every_broken_program_comes_out_not_correct():
    """What the limits are held against: the reference with each of
    CONTROLS' breaks, and with every matmul rounded to float8, followed
    beside the sound one; each misses one of the tiny cell's limits."""
    assert set(ref.CONTROLS) == {"hres_identity", "sinkhorn_one_round",
                                 "no_mtp_loss", "no_selection_bias",
                                 "gates_unscaled"}
    d = fam.dims(TINY_CONFIG)
    hp = runner.hyper(TINY_CONFIG)
    key = fam.seed_key(3)
    toks = lambda k: np.asarray(                            # noqa: E731
        fam.train_tokens(key, k, 4, 32, d["V"]))
    want = ref.train_follow(3, d, toks, 2, hp, jnp.bfloat16, 2)
    assert want["grad_norm"]["e_bias"] == 0.0
    assert want["grad_norm"]["mtp_e_bias"] == 0.0
    assert 0.0 < want["delta_norm"]["e_bias"] < 1e-4    # weight decay alone
    for name in [*ref.CONTROLS, "fp8"]:
        low = ref.train_follow(3, d, toks, 2, hp, jnp.bfloat16, 2,
                               lower=getattr(ref, name))
        gaps = _gaps(low, want)
        print(name, gaps)
        assert any(gaps[k] > LIMITS[k] for k in LIMITS), (name, gaps)
