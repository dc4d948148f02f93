"""BENCHMARK.json against the contract's schema, and the data files it
names against each other."""
import json
import os
import re

import pytest

from benchmark.harness import manifest

ROOT = manifest.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
_HF = "https://huggingface.co/"
# each source's published widths (hidden, intermediate, latent and head
# sizes, the heads, the experts a token): no configuration of that source
# may differ from them, whatever else it cuts
WIDTHS = {
    _HF + "mistralai/Mistral-7B-v0.3/blob/main/config.json": {
        "vocab_size": 32768, "hidden_size": 4096,
        "intermediate_size": 14336, "num_attention_heads": 32,
        "num_key_value_heads": 8, "head_dim": 128,
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-05},
    _HF + "skt/A.X-K1/blob/main/config.json": {
        "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "q_lora_rank": 1536,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_attention_heads": 64, "num_experts_per_tok": 8},
    _HF + "JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json": {
        "vocab_size": 98304, "hidden_size": 2304, "head_dim": 128,
        "num_attention_heads": 32, "num_key_value_heads": 4,
        "moe_intermediate_size": 896, "num_experts": 64,
        "num_experts_per_tok": 8, "sliding_window": 1024},
    _HF + "XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json": {
        "hidden_size": 3584, "intermediate_size": 9216,
        "moe_intermediate_size": 1024, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_attention_heads": 32, "num_experts_per_tok": 4, "hc_mult": 4},
}


def _is_width(key):
    """What `reduced` may never name (the contract): a hidden,
    intermediate, latent, state or projection size, a `_dim` or `_rank`,
    a head size, the experts a token. The vocabulary may be sliced."""
    return key.endswith(("_dim", "_rank")) or key == "num_experts_per_tok" \
        or (key.endswith("_size") and key != "vocab_size")


@pytest.fixture(scope="module")
def man():
    return manifest.manifest(ROOT)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(man["command"]) <= 32 and all(map(_line, man["command"]))
    assert man["paths"] == ["benchmark"]
    assert all(PATH.match(p) for p in man["paths"])
    assert isinstance(man["run_seconds"], int) and 1 <= man["run_seconds"] <= 51
    # the full check with 24 cells fits the driver's limit
    cells, rs = 24, man["run_seconds"]
    assert (2 + 14 * cells) * (rs + 60) + cells * 180 + 1200 <= 43200


def test_configs(man):
    assert 1 <= len(man["configs"]) <= 24
    names = [c["name"] for c in man["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in man["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in man["workloads"]}
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmark/") and PATH.match(c["file"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
        spec = manifest.config(ROOT, c["name"])
        assert spec["source"] == c["source"]
        # the file also records cuts to the deployment (`engine.<key>`),
        # which are not keys of the source
        assert {k for k in spec["reduced"] if "." not in k} == \
            set(c["reduced"])
        # a catalogued configuration's published keys lie at the file's
        # top level, where the driver's check reads them; the benchmark's
        # own (in no catalog) keep them in a `model` group
        published = spec.get("model", spec)
        for k, v in WIDTHS[c["source"]].items():
            assert published[k] == v, (c["name"], k)
            assert k not in c["reduced"]
        assert spec["assumed"] and spec["deployment"]
        for k in c["reduced"]:
            assert not _is_width(k), (c["name"], k)


def test_workloads(man):
    assert 1 <= len(man["workloads"]) <= 24
    names = [w["name"] for w in man["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(names) // 4)
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _line(w["why"])
        cell = manifest.cell(ROOT, w["name"])          # agrees, or raises
        mix = manifest.traffic(ROOT, w["traffic"])
        if mix["kind"] == "serve_open":
            # the fixed rate is a number in the cell's file, under the knee
            assert isinstance(cell["rate_per_s"], (int, float))
            assert isinstance(cell["knee_per_s"], (int, float))
            # four fifths of the knee the last sweep read, to rounding
            # (README.md, "How the knee is read"): a PR that moves one
            # without the other has not re-founded the cell
            assert cell["rate_per_s"] == pytest.approx(
                0.8 * cell["knee_per_s"], rel=0.01), w["name"]
        elif mix["kind"] == "serve_closed":
            assert isinstance(cell["clients"], int)
        for name, limit in cell["correct"]["limits"].items():
            assert isinstance(limit, (int, float)), name


def _metric_ok(m, extra):
    assert set(m) - {"workloads"} == {"name", "unit", "better",
                                      "source"} | extra, m
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES


def test_metrics(man):
    cells = [w["name"] for w in man["workloads"]]
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert 1 <= len(e2e) == len(man["end_to_end"]) <= 16
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in man["end_to_end"]:
        _metric_ok(m, {"bound"})
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", cells)) <= set(cells)
    names = [m["name"] for m in man["per_layer"]]
    assert 1 <= len(set(names)) == len(names) <= 128
    assert not set(names) & set(e2e)
    for m in man["per_layer"]:
        _metric_ok(m, {"layer", "moves"})
        assert _line(m["layer"])
        moved = e2e[m["moves"]]                        # names an e2e metric
        reporting = moved.get("workloads", cells)
        # ... which every cell that reports this metric also reports
        assert set(m.get("workloads", reporting)) <= set(reporting), m
        spec = manifest.load_json(
            ROOT, f"benchmark/layer_metrics/{m['name']}.json")
        manifest.plugin("readers", spec["reader"])
    for c in cells:
        mine = [m for m in man["end_to_end"] if manifest.reported(m, c)]
        assert len(mine) >= 2, f"{c}: setup_s and one more"
        assert manifest.per_layer(ROOT, c), f"{c}: no per-layer metric"


def test_only_benchmark_files_under_paths():
    """Every file under paths is named from the characters of a name."""
    for base, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert PATH.match(rel), rel


def test_missing_names_fail_loudly(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(
        {"workloads": [{"name": "a", "config": "c", "traffic": "t",
                        "chips": 1}], "configs": []}))
    with pytest.raises(manifest.ManifestError, match="no workload named"):
        manifest.cell(str(tmp_path), "nope")
    with pytest.raises(manifest.ManifestError, match="missing benchmark file"):
        manifest.cell(str(tmp_path), "a")
    with pytest.raises(manifest.ManifestError, match="one new file"):
        manifest.plugin("readers", "no_such_reader")
