"""The reduction from a trace table to numbers: on a hand-made table whose
answers are known, and on a small table cut from a recorded chip trace."""
import gzip
import json
import os

import pytest

from benchmark.harness import xplane

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000


def table():
    """Two chips, 10 ms. Chip 0: a program `jit_run_chunk` of 4 ms whose
    while-loop event nests two kernels and a matmul; then idle 2 ms; then
    `jit_prefill` 3 ms with an all-gather of 1 ms, half under a fusion."""
    ops0 = [("while.1", 0, 4 * MS),
            ("ragged_attn.1", 0, 1 * MS), ("fusion.7", 1 * MS, 2 * MS),
            ("ragged_attn.1", 3 * MS, 1 * MS),
            ("all-gather.3", 6 * MS, 1 * MS),
            ("fusion.9", 6 * MS + MS // 2, 2 * MS + MS // 2)]
    mods0 = [("jit_run_chunk(123)", 0, 4 * MS), ("jit_prefill(9)", 6 * MS, 3 * MS),
             ("jit_run_chunk(123)", 9 * MS, 1 * MS)]
    ops1 = [("fusion.7", 0, 5 * MS), ("all-gather.3", 5 * MS, 5 * MS)]
    host = [("thread-long wrapper", 0, 10 * MS),
            ("np.asarray sync", 4 * MS, 2 * MS - 1000)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods0},
            {"name": "XLA Ops", "events": ops0}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": ops1}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]}]}


def test_busy_union_and_idle_share():
    assert xplane.union_ns([(0, 4), (1, 2), (3, 6), (8, 9)]) == 7
    busy_s, window_s = xplane.busy_seconds(table())
    assert window_s == pytest.approx(10e-3)
    # chip 0: [0,4) + [6,9) = 7 ms; chip 1: 10 ms; mean 8.5
    assert busy_s == pytest.approx(8.5e-3)


def test_program_durations_by_pattern():
    t = table()
    assert xplane.program_durations_ms(t, "^jit_run_chunk|serve_decode_step") \
        == [4.0, 1.0]
    assert xplane.program_durations_ms(t, "^jit_prefill") == [3.0]
    assert xplane.program_durations_ms(t, "serve_prefill_step") == []


def test_kernel_time_by_pattern_and_exclusive_time():
    t = table()
    secs, calls = xplane.kernel_seconds(t, "ragged_attn|ragged_paged_attention")
    assert calls == 2 and secs == pytest.approx(2e-3)
    ex = {(n, s): d for n, s, d in xplane.leaf_exclusive(
        xplane.line_events(t["planes"][0], "XLA Ops"))}
    assert ex[("while.1", 0)] == 0          # its body covers it
    top = dict(map(tuple, xplane.top_ops(t)))
    assert top["fusion.7"] == pytest.approx(2e-3)
    assert top["fusion.9"] == pytest.approx(2.5e-3)


def test_exposed_collective_time():
    # chip 0: all-gather [6,7) with a fusion from 6.5: 0.5 ms exposed;
    # chip 1: 5 ms exposed; mean 2.75 ms
    assert xplane.exposed_seconds(table(), "all-gather|all-reduce") \
        == pytest.approx(2.75e-3)


def test_idle_gaps_name_the_host_event_of_their_size():
    gaps = xplane.idle_gaps(table())
    assert gaps[0][0] == "np.asarray sync" and gaps[0][1] == pytest.approx(2e-3)


def test_cut_keeps_whole_events_only():
    part = xplane.cut(table(), 0, 5 * MS)
    names = [e[0] for p in part["planes"] for line in p["lines"]
             for e in line["events"]]
    assert "jit_prefill(9)" not in names and "while.1" in names


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(DATA, "chat_trace_cut.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_chip_trace(recorded):
    """A cut of the first traced chat run on the chip (PR 23): what the
    planes and lines are called there, and that every reduction reads it."""
    dev = xplane.device_planes(recorded)
    assert [p["name"] for p in dev] == ["/device:TPU:0"]
    lines = {line["name"] for line in dev[0]["lines"]}
    assert {"XLA Ops", "XLA Modules"} <= lines
    busy_s, window_s = xplane.busy_seconds(recorded)
    assert 0 < busy_s <= window_s
    decode = xplane.program_durations_ms(recorded, "^jit_run_chunk")
    assert decode and all(d > 0 for d in decode)
    assert xplane.top_ops(recorded, 5)
    assert isinstance(xplane.idle_gaps(recorded, 3), list)


def test_flash_roofline_reader_on_a_hand_made_trace():
    """One layer-step: forward 2 ms, recomputed forward 2 ms, backward
    6 ms of flash events; the least at 197 TFLOP/s is 1.395 + 3.488 ms."""
    from benchmark.readers import flash_roofline
    ops = [("%flash_attention_pallas.20 = x", 0, 2 * MS),
           ("%fusion.1", 2 * MS, MS),
           ("%flash_attention_pallas.21 = x", 3 * MS, 2 * MS),
           ("%flash_attention_pallas_bwd.13 = x", 5 * MS, 6 * MS)]
    t = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops}]}]}
    spec = {"patterns": ["flash_attention_pallas", "flash_attention_fwd"],
            "backward_patterns": ["flash_attention_pallas_bwd"]}
    d = {"H": 32, "KV": 8, "hd": 128}
    obs = {"trace": t, "dims": d, "batch": 8, "seq_len": 2048,
           "device_kind": "TPU v5 lite"}
    flops = 4 * 8 * 32 * 128 * 2048 * 2048 / 2
    want = 100 * (flops / 197e12 * 3.5) / 10e-3
    assert flash_roofline.read(spec, obs) == pytest.approx(want)
    assert 48 < want < 49
    assert flash_roofline.read(spec, {**obs, "trace": None}) is None


def test_a_span_of_fused_steps_still_gives_the_step_metric():
    """While prompts wait, every step of the server is fused: a traced
    span can hold no plain decode chunk (a driver's run met one). The
    metric the chat cell ships reads every step program that decodes, so
    it finds a value there; the plain chunk's median finds none."""
    from benchmark.harness import manifest
    from benchmark.readers import program_ms
    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    shipped = manifest.load_json(
        root, "benchmark/layer_metrics/step_program_p90_ms.json")
    plain = manifest.load_json(
        root, "benchmark/layer_metrics/decode_step_ms.json")

    def trace(mods):
        return {"trace": {"planes": [{"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods}]}]}}

    fused = [(f"jit_run_fused({7 if k % 3 else 8})", 700 * k * MS,
              (270 if k == 0 else 600 + k) * MS) for k in range(12)]
    assert program_ms.read(plain, trace(fused)) is None
    # nearest rank: the 11th of 12, and never the cut first event
    assert program_ms.read(shipped, trace(fused)) == 610.0
    mixed = fused[1:4] + [("jit_run_chunk(1)", (9000 + 200 * k) * MS, 190 * MS)
                         for k in range(17)]
    assert program_ms.read(plain, trace(mixed)) == 190.0
    assert program_ms.read(shipped, trace(mixed)) == 601.0     # 18th of 20
    assert program_ms.read(shipped, {"trace": None}) is None
    assert xplane.program_counts(trace(mixed)["trace"]).startswith(
        "17 x jit_run_chunk (median 190.0 ms), 3 x jit_run_fused")
