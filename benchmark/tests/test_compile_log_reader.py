"""The four `Compile` metrics (PR 38) and their one reader: over a
synthetic log, by name whatever the order and by `obs["window"]`; over
nothing; and the whole command on the CPU, a serve cell and a train cell,
with the four entries in a tiny manifest."""
import json
import os

import pytest

from benchmark.harness import manifest
from benchmark.readers import compile_log as reader
from benchmark.tests.test_run_cpu import _fake_trace, _last, _run

NEW = ("setup_lower_s", "setup_executable_s", "setup_cache_miss",
       "window_compiles")
SERVE = ["mistral7b-chat", "axk1-chat", "mellum2-code"]
TRAIN = ["mistral7b-train-2k", "xing4-train-4k"]
WINDOW = (100.0, 151.0)
# name, t, trace, lower, executable, cache: a warm-up, two small programs
# of the host's inside the window, the reference's after it
LOG = [("jit_serve_prefill_step", 10.0, 1.0, 2.0, 4.0, "hit"),
       ("jit__build", 2.0, 9.0, 9.0, 9.0, "miss"),
       ("jit_serve_fused_step", 20.0, 1.5, 2.5, 8.0, "miss"),
       ("jit_serve_decode_step", 30.0, 0.5, 1.5, 2.0, "hit"),
       ("jit_gather", 120.0, 0.1, 0.1, 0.1, "miss"),
       ("jit_argmax", 151.0, 0.1, 0.1, 0.1, "hit"),
       ("jit_layer", 160.0, 7.0, 7.0, 7.0, "miss"),
       ("jit_serve_decode_step_of_the_reference", 170.0, 5.0, 5.0, 5.0,
        "miss")]


def _spec(name):
    man = manifest.manifest(manifest.ROOT)
    entry, = [m for m in man["per_layer"] if m["name"] == name]
    return {**manifest.load_json(
        manifest.ROOT, f"benchmark/layer_metrics/{name}.json"), **entry}


def _log(rows, monkeypatch):
    from paddle_tpu.core import compile_cache
    log = compile_cache.CompileLog()
    for name, t, tr, lo, ex, cache in rows:
        with log.program(name) as rec:
            rec.update(t=t, trace_s=tr, lower_s=lo, executable_s=ex,
                       cache=cache)
    monkeypatch.setattr(compile_cache, "compile_log", log)


@pytest.mark.parametrize("order", [1, -1])
@pytest.mark.parametrize("name, want", [
    ("setup_lower_s", 1.0 + 2.0 + 1.5 + 2.5 + 0.5 + 1.5),
    ("setup_executable_s", 4.0 + 8.0 + 2.0),
    ("setup_cache_miss", 1),
    ("window_compiles", 2)])
def test_each_metric_over_a_synthetic_log(monkeypatch, capsys, name, want,
                                          order):
    _log(LOG[::order], monkeypatch)
    got = reader.read(_spec(name), {"window": WINDOW})
    assert got == pytest.approx(want)
    out = capsys.readouterr().out
    if name == "window_compiles":
        assert "jit_gather" in out and "jit_argmax" in out \
            and "jit_layer" not in out
    else:
        assert out == ""


def test_the_train_step_is_a_step_program_and_a_train_run_has_no_window(
        monkeypatch):
    _log([("jit_train_step", 50.0, 3.0, 1.0, 90.0, "miss"),
          ("jit_init", 5.0, 1.0, 1.0, 1.0, "miss")], monkeypatch)
    obs = {"values": {}, "counters": {}}
    assert reader.read(_spec("setup_lower_s"), obs) == 4.0
    assert reader.read(_spec("setup_executable_s"), obs) == 90.0
    assert reader.read(_spec("setup_cache_miss"), obs) == 1
    assert reader.read(_spec("window_compiles"), obs) is None


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_is_none_and_no_error(monkeypatch, name):
    _log([], monkeypatch)                   # no listener was installed
    assert reader.read(_spec(name), {"window": WINDOW}) is None
    _log([("jit_other", 1.0, 1.0, 1.0, 1.0, "miss")], monkeypatch)
    want = 0 if name == "window_compiles" else None     # no step program
    assert reader.read(_spec(name), {"window": WINDOW}) == want
    from paddle_tpu.core import compile_cache
    monkeypatch.delattr(compile_cache, "compile_log")   # the parent's tree
    assert reader.read(_spec(name), {"window": WINDOW}) is None


@pytest.mark.parametrize("name", NEW)
def test_new_entry_names_a_reader_and_its_cell(name):
    man = manifest.manifest(manifest.ROOT)
    entry, = [m for m in man["per_layer"] if m["name"] == name]
    assert entry == man["per_layer"][-4 + NEW.index(name)]  # at the end
    window = name == "window_compiles"
    assert sorted(entry["workloads"]) == sorted(
        SERVE if window else SERVE + TRAIN)
    assert entry["moves"] == ("tpot_p90_ms" if window else "setup_s")
    assert entry["layer"] == "Compile"
    assert entry["unit"] == ("s" if name.endswith("_s") else "programs")
    spec = manifest.load_json(manifest.ROOT,
                              f"benchmark/layer_metrics/{name}.json")
    assert spec["reader"] == "compile_log"
    assert callable(manifest.plugin("readers", spec["reader"]).read)
    assert len(spec["source_detail"]) > 80
    for cell in entry["workloads"]:
        merged, = [m for m in manifest.per_layer(manifest.ROOT, cell)
                   if m["name"] == name]
        assert merged["reader"] == "compile_log"
        assert merged["when"] == ("window" if window else "all")


@pytest.mark.parametrize("workload, names", [
    ("tiny-chat", NEW), ("tiny-train", NEW[:3])])
def test_traced_cpu_run_prints_the_compile_metrics(
        tiny_root, cpu_device, monkeypatch, capsys, workload, names):
    path = os.path.join(tiny_root, "BENCHMARK.json")
    man = json.load(open(path))
    # (`mfu_pct` has no peak to read against on the CPU)
    man["per_layer"] = [m for m in man["per_layer"] if m["name"] != "mfu_pct"]
    for name in names:
        spec = _spec(name)
        man["per_layer"].append({k: spec[k] for k in (
            "name", "unit", "better", "source", "layer", "moves")}
            | {"workloads": [workload]})
        with open(os.path.join(tiny_root, "benchmark", "layer_metrics",
                               name + ".json"), "w") as f:
            json.dump({k: spec[k] for k in ("reader", "field", "programs",
                                            "when") if k in spec}, f)
    json.dump(man, open(path, "w"))
    _fake_trace(monkeypatch)
    from paddle_tpu.core.compile_cache import compile_log
    compile_log.clear()         # a run is a process: nothing before it
    assert _run(tiny_root, workload, trace=1) == 0
    line, out = _last(capsys)
    got = {n: line["metrics"][n]["value"] for n in names}
    steps = compile_log.records(_spec("setup_lower_s")["programs"])
    assert got["setup_lower_s"] > 0 and got["setup_executable_s"] > 0
    # no persistent cache in a rehearsal: every step program is a miss
    assert got["setup_cache_miss"] == len(steps)
    if workload == "tiny-chat":
        assert len(steps) == line["metrics"]["warm_programs"]["value"]
        assert got["window_compiles"] >= 0
    else:
        assert [r["name"] for r in steps] == ["jit_train_step"]
    assert not any(f"metric {n} found nothing" in out for n in names)
