"""Finds every piece of the benchmark by the name BENCHMARK.json gives it.

Nothing here knows a cell, a model or a metric by name: a later PR adds
files and manifest entries, and edits no file that is there.
"""
from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict, List

# <checkout>/benchmark/harness/manifest.py -> <checkout>
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = "benchmark"


class ManifestError(Exception):
    """A name in BENCHMARK.json (or a file it points at) does not resolve."""


def load_json(root: str, rel: str) -> Dict[str, Any]:
    path = os.path.join(root, rel)
    if not os.path.isfile(path):
        raise ManifestError(f"missing benchmark file {rel} (under {root})")
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> Dict[str, Any]:
    return load_json(root, "BENCHMARK.json")


def _entry(entries: List[Dict[str, Any]], name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(
        f"no {what} named {name!r} in BENCHMARK.json (it has: "
        f"{', '.join(e['name'] for e in entries)})")


def cell(root: str, workload: str) -> Dict[str, Any]:
    """The workload's manifest entry merged over its cell file
    (benchmark/cells/<workload>.json: fixed rate or clients, window
    details). The two must agree on config, traffic and chips."""
    entry = _entry(manifest(root)["workloads"], workload, "workload")
    spec = load_json(root, f"{BENCH_DIR}/cells/{workload}.json")
    for k in ("config", "traffic", "chips"):
        if spec.get(k) != entry[k]:
            raise ManifestError(
                f"cells/{workload}.json says {k}={spec.get(k)!r}, "
                f"BENCHMARK.json says {entry[k]!r}")
    return {**spec, "name": workload}


def config(root: str, name: str) -> Dict[str, Any]:
    entry = _entry(manifest(root)["configs"], name, "configuration")
    return {**load_json(root, entry["file"]), "name": name}


def traffic(root: str, name: str) -> Dict[str, Any]:
    return {**load_json(root, f"{BENCH_DIR}/traffic/{name}.json"),
            "name": name}


def reported(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def end_to_end(root: str, workload: str) -> List[Dict[str, Any]]:
    return [m for m in manifest(root)["end_to_end"] if reported(m, workload)]


def per_layer(root: str, workload: str) -> List[Dict[str, Any]]:
    """The cell's per-layer metrics, each merged with its own file
    (benchmark/layer_metrics/<name>.json: reader, patterns, parameters)."""
    out = []
    for m in manifest(root)["per_layer"]:
        if reported(m, workload):
            spec = load_json(
                root, f"{BENCH_DIR}/layer_metrics/{m['name']}.json")
            out.append({**spec, **m})
    return out


def plugin(kind: str, name: str):
    """benchmark/<kind>/<name>.py, imported by name (runners, models,
    reference, readers). A missing one fails loudly."""
    try:
        return importlib.import_module(f"{BENCH_DIR}.{kind}.{name}")
    except ModuleNotFoundError as e:
        if e.name == f"{BENCH_DIR}.{kind}.{name}":
            raise ManifestError(
                f"no {BENCH_DIR}/{kind}/{name}.py: a new {kind[:-1]} is "
                f"one new file there") from e
        raise
