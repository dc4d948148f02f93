"""A tiny copy of the benchmark's data files in a scratch root, for the CPU
rehearsals: the same command, runner, generator, readers and reference at
sizes a test run can hold. Only data is made here; the code is the real
package's."""
from __future__ import annotations

import json
import os

MODEL = {
    "model_type": "mistral", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "hidden_act": "silu", "max_position_embeddings": 256,
    "rms_norm_eps": 1e-05, "rope_theta": 10000.0, "sliding_window": None,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16"}
ENGINE = {"max_batch": 4, "block_size": 8, "max_total_len": 128,
          "max_new_tokens": 16, "prefill_buckets": [16, 32], "chunk": 4}
TRAINER = {"learning_rate": 1e-4, "weight_decay": 0.1, "b1": 0.9, "b2": 0.95,
           "eps": 1e-8, "grad_clip": 1.0, "state_quant": "8bit"}
# sound runs here: loss gap 7e-5, gradient-norm gap 0.0013, change-norm gap
# 0.13; the float8 control: 7e-4 and 0.005 at the least; a step that
# changes nothing: 1.0
TRAIN_LIMITS = {"loss_gap": 3e-4, "grad_norm_gap": 0.004,
                "delta_norm_gap": 0.4}
# sound runs here: largest gap 0.0017, mean 6e-5; the float8 control's
# largest gap 0.008 at the least over 180 tokens; an altered token: 0.05+
LIMITS = {"served_gap_max": 0.005, "served_gap_mean": 0.0005}
# A size at which the precision of the KV cache shows in the served tokens
# (at MODEL's widths attention is all but uniform and int8 KV changes no
# token): 2 layers at hidden 2048, heads of 128. Over 24 requests of 48
# served tokens on the CPU, six seeds each: the program as configured reads
# a mean gap of 1.5e-4 to 2.8e-4, the program with kv_dtype int8 5.9e-4 to
# 7.8e-4 (int8 with one scale a block is about twice bf16's rounding, and
# the mean gap goes with its square)
WIDE_MODEL = {**MODEL, "vocab_size": 1024, "hidden_size": 2048,
              "intermediate_size": 4096, "num_attention_heads": 16,
              "num_key_value_heads": 4, "head_dim": 128}
WIDE_ENGINE = {"max_total_len": 208, "max_new_tokens": 48,
               "prefill_buckets": [32, 64]}
WIDE_MIX = {"kind": "serve_open",
            "prompt": {"dist": "lognormal", "median": 60, "sigma": 0.6,
                       "min": 16, "max": 150},
            "output": {"dist": "constant", "value": 48}}
WIDE_LIMIT = 4.0e-4


def _dump(root, rel, obj):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(root: str, mesh=None, engine=None, model=None) -> str:
    """BENCHMARK.json and benchmark/{configs,traffic,cells,layer_metrics}
    for two tiny cells: `tiny-chat` (open loop) and `tiny-docs` (closed)."""
    cfg = {"source": "test", "family": "dense_decoder",
           "model": model or MODEL, "engine": {**ENGINE, **(engine or {})}}
    if mesh:
        cfg["mesh"] = mesh
    _dump(root, "benchmark/configs/tiny.json", cfg)
    _dump(root, "benchmark/traffic/chat.json", {
        "kind": "serve_open",
        "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                   "min": 4, "max": 90},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.4,
                   "min": 2, "max": 16}})
    _dump(root, "benchmark/traffic/docs.json", {
        "kind": "serve_closed",
        "prompt": {"dist": "lognormal", "median": 60, "sigma": 0.3,
                   "min": 40, "max": 100},
        "output": {"dist": "uniform", "min": 2, "max": 6}})
    chips = int(mesh["tp"]) if mesh else 1
    _dump(root, "benchmark/cells/tiny-chat.json", {
        "config": "tiny", "traffic": "chat", "chips": chips,
        "rate_per_s": 6.0, "trace_seconds": 0.5, "max_late_share": 5.0,
        "correct": {"sample": 3, "limits": LIMITS}})
    _dump(root, "benchmark/cells/tiny-docs.json", {
        "config": "tiny", "traffic": "docs", "chips": chips, "clients": 4,
        "requests_per_s_max": 400, "trace_seconds": 0.5,
        "correct": {"sample": 3, "limits": LIMITS}})
    layer = {
        "queue_wait_p90_ms": {"reader": "request_wait", "percentile": 90},
        "decode_batch_mean": {"reader": "flight_mean",
                              "field": "active_slots",
                              "modes": ["decode", "fused"]},
        "prefill_pad_pct": {"reader": "prefill_pad"},
        "warm_programs": {"reader": "counter", "counter": "warm_programs"},
    }
    for name, spec in layer.items():
        _dump(root, f"benchmark/layer_metrics/{name}.json", spec)
    _dump(root, "benchmark/configs/tiny-train.json", {
        "source": "test", "family": "dense_decoder", "model": MODEL,
        "trainer": TRAINER})
    _dump(root, "benchmark/traffic/pretrain.json", {
        "kind": "train", "batch": 4, "seq_len": 32})
    _dump(root, "benchmark/cells/tiny-train.json", {
        "config": "tiny-train", "traffic": "pretrain", "chips": 1,
        "trace_seconds": 0.5,
        "correct": {"steps": 2, "rows": 2, "limits": TRAIN_LIMITS}})
    layer["mfu_pct"] = {"reader": "mfu", "family": "dense_decoder"}
    _dump(root, "benchmark/layer_metrics/mfu_pct.json", layer["mfu_pct"])
    _dump(root, "BENCHMARK.json", {
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 3,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "benchmark/configs/tiny.json", "reduced": [],
                     "why": "test"},
                    {"name": "tiny-train", "source": "test",
                     "file": "benchmark/configs/tiny-train.json",
                     "reduced": [], "why": "test"}],
        "workloads": [
            {"name": "tiny-chat", "config": "tiny", "traffic": "chat",
             "chips": chips, "why": "test"},
            {"name": "tiny-docs", "config": "tiny", "traffic": "docs",
             "chips": chips, "why": "test"},
            {"name": "tiny-train", "config": "tiny-train",
             "traffic": "pretrain", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "tpot_p90_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny-chat"]},
            {"name": "serve_tok_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny-docs"]},
            {"name": "train_tok_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny-train"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        "per_layer": [
            {"name": "queue_wait_p90_ms", "unit": "ms", "better": "lower",
             "source": "program_counter", "layer": "Engine",
             "moves": "tpot_p90_ms", "workloads": ["tiny-chat"]},
            {"name": "decode_batch_mean", "unit": "seqs",
             "better": "higher", "source": "program_counter",
             "layer": "Batcher", "moves": "tpot_p90_ms",
             "workloads": ["tiny-chat"]},
            {"name": "prefill_pad_pct", "unit": "%", "better": "lower",
             "source": "program_counter", "layer": "Batcher",
             "moves": "serve_tok_s", "workloads": ["tiny-docs"]},
            {"name": "mfu_pct", "unit": "%", "better": "higher",
             "source": "host_clock", "layer": "Trainer",
             "moves": "train_tok_s", "workloads": ["tiny-train"]},
            {"name": "warm_programs", "unit": "programs",
             "better": "lower", "source": "program_counter",
             "layer": "Compile", "moves": "setup_s"}]})
    return root
