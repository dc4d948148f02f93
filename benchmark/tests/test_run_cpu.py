"""The whole command at a tiny size on the CPU, steered from here: each
kind of run, the last line, a traced run on a hand-made trace, cells added
as files only, a broken timed path, and the refusals."""
import json
import os
import subprocess
import sys
import threading

import pytest

from benchmark import run
from benchmark.harness import manifest, window, xplane
from benchmark.runners import serve
from benchmark.tests import test_xplane, tiny

ROOT = manifest.ROOT
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _last(capsys):
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def _run(root, workload, trace=0, seed=2**31 + 99, overrides=None):
    return run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", "2", "--trace", str(trace)], root=root,
                    overrides=overrides)


def _fake_trace(monkeypatch):
    """No profiler on the CPU: the traced run reads a hand-made table."""
    def thread(_after, _length, out):
        out["dir"] = "unused"
        th = threading.Thread(target=lambda: None)
        th.start()
        return th
    monkeypatch.setattr(window, "trace_thread", thread)
    monkeypatch.setattr(xplane, "find_xplane", lambda d: d)
    monkeypatch.setattr(xplane, "load", lambda p: test_xplane.table())


@pytest.mark.parametrize("workload,metrics", [
    ("tiny-chat", {"tpot_p90_ms", "setup_s"}),
    ("tiny-docs", {"serve_tok_s", "setup_s"}),
    ("tiny-train", {"train_tok_s", "setup_s"})])
def test_each_kind_of_run(tiny_root, cpu_device, capsys, workload, metrics):
    assert _run(tiny_root, workload) == 0
    line, out = _last(capsys)
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert set(line["metrics"]) == metrics
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # every number compared is printed beside its limit
    assert out.count("(limit ") >= 2 and "FAILED" not in out


def test_a_cell_lays_its_own_keys_over_its_mix(tiny_root, cpu_device, capsys,
                                               monkeypatch):
    """`mix` in a cell's file: keys of the traffic mix that belong to this
    cell alone (its `order_block`), so that a mix two cells share stays as
    it is for the other."""
    path = os.path.join(tiny_root, "benchmark/cells/tiny-chat.json")
    with open(path) as f:
        cell = json.load(f)
    tiny._dump(tiny_root, "benchmark/cells/tiny-chat.json",
               {**cell, "mix": {"order_block": 4}})
    seen = []
    generate = serve.traffic.generate

    def spy(mix, *a, **k):
        seen.append(mix)
        return generate(mix, *a, **k)
    monkeypatch.setattr(serve.traffic, "generate", spy)
    assert _run(tiny_root, "tiny-chat") == 0
    assert _last(capsys)[0]["correct"] is True
    assert seen[0]["order_block"] == 4 and seen[0]["kind"] == "serve_open"
    assert "order_block" not in manifest.traffic(tiny_root, "chat")


def test_traced_run_reports_the_per_layer_metrics(tiny_root, cpu_device,
                                                  capsys, monkeypatch):
    _fake_trace(monkeypatch)
    assert _run(tiny_root, "tiny-chat", trace=1) == 0
    line, _ = _last(capsys)
    assert set(line) == KEYS | {"breakdown"}
    assert set(line["metrics"]) == {"queue_wait_p90_ms", "decode_batch_mean",
                                    "warm_programs"}
    assert line["metrics"]["decode_batch_mean"]["value"] >= 1
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert len(line["breakdown"]["device_ops"]) <= 10
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_cell_a_mix_and_a_metric_added_as_files_only(
        tiny_root, cpu_device, capsys, monkeypatch):
    """What a later PR does: new files, new manifest entries, no edit to a
    file that is there (BENCHMARK.json is the manifest the PR extends)."""
    _fake_trace(monkeypatch)
    before = {os.path.join(base, p): open(os.path.join(base, p)).read()
              for base, _, files in os.walk(tiny_root) for p in files
              if p != "BENCHMARK.json"}
    tiny._dump(tiny_root, "benchmark/traffic/made-up.json", {
        "kind": "serve_closed",
        "prompt": {"dist": "constant", "value": 20},
        "output": {"dist": "constant", "value": 5}})
    tiny._dump(tiny_root, "benchmark/cells/made-up-cell.json", {
        "config": "tiny", "traffic": "made-up", "chips": 1, "clients": 2,
        "requests_per_s_max": 2000, "correct": {"sample": 2,
                                               "limits": tiny.LIMITS}})
    tiny._dump(tiny_root, "benchmark/layer_metrics/free_slots_mean.json", {
        "reader": "flight_mean", "field": "free_slots",
        "modes": ["decode", "fused", "prefill"]})
    man = manifest.manifest(tiny_root)
    man["workloads"].append({"name": "made-up-cell", "config": "tiny",
                             "traffic": "made-up", "chips": 1, "why": "t"})
    next(m for m in man["end_to_end"] if m["name"] == "serve_tok_s")[
        "workloads"].append("made-up-cell")
    man["per_layer"].append({
        "name": "free_slots_mean", "unit": "slots", "better": "higher",
        "source": "program_counter", "layer": "Batcher",
        "moves": "serve_tok_s", "workloads": ["made-up-cell"]})
    tiny._dump(tiny_root, "BENCHMARK.json", man)
    assert _run(tiny_root, "made-up-cell", trace=1) == 0
    line, _ = _last(capsys)
    assert line["correct"] is True
    assert 0 <= line["metrics"]["free_slots_mean"]["value"] <= 4
    assert "warm_programs" in line["metrics"]
    assert _run(tiny_root, "made-up-cell") == 0
    assert set(_last(capsys)[0]["metrics"]) == {"serve_tok_s", "setup_s"}
    for path, text in before.items():
        assert open(path).read() == text, path


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tiny_root, cpu_device, capsys, monkeypatch):
    """The timed path broken underneath: the engine hands out a token it
    did not compute. The rest of the run is the real one."""
    from paddle_tpu.serving.request import GenerationRequest
    deliver = GenerationRequest._deliver
    seen = {"n": 0}

    def altered(self, tok):
        seen["n"] += 1
        deliver(self, (tok + 1) % 256 if seen["n"] % 3 == 0 else tok)

    monkeypatch.setattr(GenerationRequest, "_deliver", altered)
    assert _run(tiny_root, "tiny-chat") == 0
    line, out = _last(capsys)
    assert line["correct"] is False and "FAILED" in out


def test_a_step_that_changes_nothing_is_not_correct(tiny_root, cpu_device,
                                                    capsys):
    """The training path broken underneath: the step hands its state back
    unchanged (the loss it reports is the real one)."""
    def wrap(step):
        def unchanged(state, tokens):
            import jax
            kept = jax.tree.map(lambda a: a.copy(), state)  # step donates
            _, metrics = step(state, tokens)
            return kept, metrics
        return unchanged

    assert _run(tiny_root, "tiny-train", overrides={"wrap_step": wrap}) == 0
    line, out = _last(capsys)
    assert line["correct"] is False
    assert "delta_norm_gap 1 " in out and "FAILED" in out


def test_the_serving_control_is_not_correct():
    """The reference in float8 in the program's place (the operands of
    every matmul to e4m3): at each position of the same prompts and tokens,
    the token it puts first lies further below the float32 reference's best
    than the cell's limit allows, on three seeds. The sound program keeps
    the same limits (test_each_kind_of_run)."""
    import numpy as np
    from benchmark.models import dense_decoder as fam
    from benchmark.reference import dense_decoder as ref
    d = fam.dims({"model": tiny.MODEL})
    for seed in (1, 2, 2**31 + 3):
        rng = np.random.default_rng(seed % 1000)
        prompts = [rng.integers(1, d["V"], n).tolist() for n in (20, 40, 60)]
        served = [rng.integers(1, d["V"], 60).tolist() for _ in prompts]
        g = ref.served_gaps(seed, d, prompts, served, pad=128,
                            act=ref.fp8)
        assert float(g.max()) > tiny.LIMITS["served_gap_max"] \
            or float(g.mean()) > tiny.LIMITS["served_gap_mean"], g.max()


def test_the_int8_kv_engine_is_not_correct(tmp_path, cpu_device,
                                            monkeypatch):
    """The program's own lower-precision path in the program's place: the
    engine with kv_dtype int8 serves the same prompts as the engine as
    configured, at a size where the cache's precision reaches the tokens
    (tiny.WIDE_MODEL). On three seeds its served tokens lie further below
    the reference's best, on average, than the limit between the two
    allows; the engine as configured keeps it."""
    import time
    import numpy as np
    from benchmark.harness import traffic
    from benchmark.reference import dense_decoder as ref
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    root = tiny.make_root(str(tmp_path / "root"), engine=tiny.WIDE_ENGINE,
                          model=tiny.WIDE_MODEL)
    cell, config = manifest.cell(root, "tiny-chat"), manifest.config(
        root, "tiny")
    seeds = (1, 2, 2**31 + 6)
    mean = {}
    for kv in ("bf16", "int8"):
        over = {} if kv == "bf16" else {"kv_dtype": "int8"}
        ctx = run.Context(root, "tiny-chat", cell, config, tiny.WIDE_MIX, 1,
                          1.0, False, time.time(), {"engine": over}, "cpu")
        sv = serve.setup(ctx)
        try:
            assert sv["eng"].kv_dtype == ("int8" if over else "fp")
            for seed in seeds:
                sv["eng"].batcher.params = serve.make_params(
                    sv["fam"], config, ctx.pcfg, sv["d"], seed)
                reqs = traffic.generate(tiny.WIDE_MIX, seed, 24,
                                        sv["d"]["V"])
                handles = [sv["eng"].submit(r.prompt, max_new_tokens=r.n_out)
                           for r in reqs]
                for h in handles:
                    h.result(timeout=600)
                gaps = ref.served_gaps(
                    seed, sv["d"], [r.prompt for r in reqs],
                    [list(h.tokens)[:r.n_out] for h, r in
                     zip(handles, reqs)], pad=64)
                assert gaps.size == 24 * 48
                mean[kv, seed] = float(np.mean(gaps))
        finally:
            sv["eng"].shutdown(drain=False, timeout=60)
    for seed in seeds:
        assert mean["bf16", seed] < tiny.WIDE_LIMIT < mean["int8", seed], mean


def test_the_training_control_is_not_correct():
    """The reference in float8 in the program's place (forward operands to
    e4m3, cotangents to e5m2, scaled): on three seeds it misses at least
    one of the cell's limits, which the sound program keeps (first test)."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark.models import dense_decoder as fam
    from benchmark.reference import dense_decoder as ref
    from benchmark.runners import train as runner
    config = {"model": tiny.MODEL, "trainer": tiny.TRAINER}
    d, hp = fam.dims(config), runner.hyper(config)
    for seed in (1, 2, 2**31 + 3):
        key = fam.seed_key(seed)
        toks = lambda k: np.asarray(                     # noqa: E731
            fam.train_tokens(key, k, 4, 32, d["V"]))
        want = ref.train_follow(seed, d, toks, 2, hp, jnp.bfloat16, 2)
        low = ref.train_follow(seed, d, toks, 2, hp, jnp.bfloat16, 2,
                               lower=ref.fp8)
        gaps = {"loss_gap": max(abs(a - b) for a, b in
                                zip(low["loss"], want["loss"])),
                "grad_norm_gap": runner.worst_leaf_gap(
                    low["grad_norm"], want["grad_norm"]),
                "delta_norm_gap": runner.worst_leaf_gap(
                    low["delta_norm"], want["delta_norm"])}
        assert any(gaps[k] > tiny.TRAIN_LIMITS[k] for k in gaps), gaps


def test_a_compile_inside_the_window_fails_the_run(tiny_root, cpu_device,
                                                   monkeypatch):
    """An engine that was not warmed compiles on its first requests."""
    monkeypatch.setattr(serve, "preroll", lambda *a, **k: 0)
    from paddle_tpu import serving
    monkeypatch.setattr(serving.ServingEngine, "warmup", lambda self: 0)
    with pytest.raises(serve.CompileInWindow, match="inside the measured"):
        _run(tiny_root, "tiny-chat")


def test_a_late_generator_fails_the_run(tiny_root, cpu_device):
    """No generator keeps to a billionth of its mean gap: the run ends with
    a message and no result."""
    path = os.path.join(tiny_root, "benchmark", "cells", "tiny-chat.json")
    with open(path) as f:
        cell = json.load(f)
    tiny._dump(tiny_root, "benchmark/cells/tiny-chat.json",
               {**cell, "max_late_share": 1e-9})
    with pytest.raises(serve.LateGenerator, match="ran late"):
        _run(tiny_root, "tiny-chat")


def _cli(cwd, *args, env=None):
    e = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *args],
        cwd=cwd, env=e, capture_output=True, text=True, timeout=600)


def test_no_tpu_no_result():
    """Unsteered, on the CPU: non-zero, the platform named, no result."""
    p = _cli(ROOT, "--workload", "mistral7b-chat", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_bare_directory_no_result(tmp_path):
    """Only BENCHMARK.json and the files under paths: non-zero, no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path), "--workload", "mistral7b-chat", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and '"correct"' not in p.stdout


TP_SCRIPT = """
import json, sys, tempfile
sys.path.insert(0, {root!r})
import jax
from benchmark.tests import tiny
from benchmark.harness import device
from benchmark import run
assert len(jax.devices()) == 4
device.require = lambda chips: {{"platform": "cpu", "kind": "cpu",
                                "count": len(jax.devices())}}
root = tiny.make_root(tempfile.mkdtemp(), mesh={{"tp": 4}})
sys.exit(run.main(["--workload", "tiny-chat", "--seed", "7", "--seconds",
                   "2", "--trace", "0"], root=root))
"""


def test_tp_cell_on_four_forced_host_devices(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}
    p = subprocess.run([sys.executable, "-c", TP_SCRIPT.format(root=ROOT)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4
    assert "tpot_p90_ms" in line["metrics"]
