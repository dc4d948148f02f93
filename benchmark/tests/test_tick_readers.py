"""The three metrics that read the program's own names (PR 24): the
stats-keeping trace decoder, the ops-and-bytes of a prefill row, and the
readers on a cut recorded from a chip trace with its flight records."""
import gzip
import json
import os
import struct

import pytest

from benchmark.harness import device, manifest, xplane
from benchmark.models import dense_decoder as fam
from benchmark.readers import host_gap, ragged_attn_roofline, xstats

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("ragged_attn_roofline_pct", "kv_pool_copy_pct", "host_gap_pct.chat")
D = {"H": 32, "KV": 8, "hd": 128, "L": 16}
PEAK = device.peaks("TPU v5 lite")


def _spec(name):
    return manifest.load_json(manifest.ROOT,
                              f"benchmark/layer_metrics/{name}.json")


# ---- the decoder, on a message encoded here by hand ---------------------
def _varint(v):
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        b, v = v & 0x7F, v >> 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _stat(meta_id, **kind):
    (key, value), = kind.items()
    num = {"double": 2, "uint": 3, "int": 4, "str": 5, "ref": 7}[key]
    return _field(1, meta_id) + _field(num, value)


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def _xspace():
    """A device plane with a module and an operation whose METADATA holds
    tf_op, a line the decoder skips, and a host plane whose span holds
    its own stats, one of them a reference to a stat's name."""
    stat_names = {1: "tf_op", 2: "seq", 3: "mode", 4: "fused", 5: "flops"}
    smeta = b"".join(_field(5, _entry(k, _field(1, k) + _field(2, n)))
                     for k, n in stat_names.items())
    op_meta = _field(1, 7) + _field(2, "%fusion.1 = bf16[4] fusion()") \
        + _field(5, _stat(1, str="jit(f)/while/body/kv_pool_read/slice:")) \
        + _field(5, _stat(5, uint=99))
    mod_meta = _field(1, 8) + _field(2, "jit_serve_decode_step(123)")
    ops = _field(2, "XLA Ops") + _field(3, 1000) + _field(4, (
        _field(1, 7) + _field(2, 5_000_000) + _field(3, 2_000_000)
        + _field(4, _stat(5, uint=1))))
    mods = _field(2, "XLA Modules") + _field(3, 1000) + _field(4, (
        _field(1, 8) + _field(2, 4_000_000) + _field(3, 9_000_000)))
    other = _field(2, "Async XLA Ops") + _field(3, 1000) + _field(4, (
        _field(1, 7) + _field(2, 0) + _field(3, 1)))
    dev = _field(2, "/device:TPU:0") + _field(3, ops) + _field(3, mods) \
        + _field(3, other) + _field(4, _entry(7, op_meta)) \
        + _field(4, _entry(8, mod_meta)) + smeta
    tick_meta = _field(1, 1) + _field(2, "serve.tick")
    host_line = _field(2, "python3") + _field(3, 2000) + _field(4, (
        _field(1, 1) + _field(2, 3_000_000) + _field(3, 12_000_000)
        + _field(4, _stat(2, int=41)) + _field(4, _stat(3, ref=4))))
    host = _field(2, "/host:CPU") + _field(3, host_line) \
        + _field(4, _entry(1, tick_meta)) + smeta
    skipped = _field(2, "Task Environment") + _field(3, host_line)
    return _field(1, dev) + _field(1, host) + _field(1, skipped) \
        + _field(4, "hostname")


def test_decoder_keeps_metadata_and_event_stats(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    table = xstats.load(str(path))
    assert [p["name"] for p in table["planes"]] == \
        ["/device:TPU:0", "/host:CPU"]
    dev = xplane.device_planes(table)[0]
    assert [ln["name"] for ln in dev["lines"]] == ["XLA Ops", "XLA Modules"]
    # start: the line's timestamp plus the event's offset, in ns
    assert xplane.line_events(dev, "XLA Ops") == [
        ("%fusion.1 = bf16[4] fusion()", 6000, 2000,
         {"tf_op": "jit(f)/while/body/kv_pool_read/slice:"})]
    assert xplane.line_events(dev, "XLA Modules") == [
        ("jit_serve_decode_step(123)", 5000, 9000, {})]
    assert xstats.host_events(table, "serve.") == [
        ("serve.tick", 5000, 12000, {"seq": 41, "mode": "fused"})]
    op = xplane.line_events(dev, "XLA Ops")[0]
    assert xstats.scope_of(op[3], ["kv_pool_write", "kv_pool_read"]) \
        == "kv_pool_read"
    assert xstats.scope_of(op[3], ["mlp"]) is None
    assert xstats.scope_of({}, ["mlp"]) is None
    # the same table as the name-only loader's, where both can read
    assert xstats.load(str(path), keep=())["planes"][0]["lines"][0][
        "events"][0][3] == {}


# ---- operations and bytes, on cases worked by hand ----------------------
def test_prefill_attention_cost_by_hand():
    d = {"H": 2, "KV": 1, "hd": 4}
    # one row, queries 2..3 of a sequence of 4 cached keys: the query at
    # 2 sees 3 keys, the one at 3 sees 4: 7 key visits, 4 FLOPs per visit
    # and head element: 4 * 7 * 2 * 4 = 224. K and V of 4 keys read once
    # (2 * 4 * 1 * 4 = 32 elements), q read and o written for 2 queries
    # (2 * 2 * 2 * 4 = 32): 64 elements of 2 bytes
    assert ragged_attn_roofline.prefill_attention_cost(d, [(2, 4)]) == \
        {"flops": 224.0, "bytes": 128.0}
    # a cold row of 3 sees 1 + 2 + 3 keys; rows add up
    one = ragged_attn_roofline.prefill_attention_cost(d, [(0, 3)])
    assert one == {"flops": 4.0 * 6 * 8, "bytes": (2 * 3 * 4 + 2 * 3 * 8) * 2}
    both = ragged_attn_roofline.prefill_attention_cost(d, [(0, 3), (2, 4)])
    assert both == {"flops": one["flops"] + 224.0,
                    "bytes": one["bytes"] + 128.0}
    # at the real widths one 512-token chunk behind 512 cached tokens is
    # bound by FLOPs, a decode row by bytes
    cost = ragged_attn_roofline.prefill_attention_cost(D, [(512, 1024)])
    assert cost["flops"] == 4.0 * (512 * 512 + 512 * 513 / 2) * 32 * 128
    assert fam.roofline_seconds(cost, PEAK)[1] == "compute"
    assert fam.roofline_seconds(
        fam.decode_attention_cost(D, [550] * 4), PEAK)[1] == "bytes"


def test_tick_least_seconds_by_kind_of_tick():
    least = ragged_attn_roofline.tick_least_seconds

    def secs(cost):
        return fam.roofline_seconds(cost, PEAK)[0]

    ctx, spans = [100, 700], [[512, 1024]]
    dec = {"mode": "decode", "chunk": 3, "decode_ctx": ctx}
    want = D["L"] * sum(secs(fam.decode_attention_cost(
        D, [c + i for c in ctx])) for i in range(3))
    assert least(fam, D, PEAK, dec) == pytest.approx(want, rel=1e-12)
    # fused: the first call carries the prefill rows too, and one call is
    # bound by the larger of ITS bytes and ITS FLOPs
    pre = ragged_attn_roofline.prefill_attention_cost(D, [(512, 1024)])
    first = fam.decode_attention_cost(D, ctx)
    mixed = {k: first[k] + pre[k] for k in first}
    fused = {"mode": "fused", "chunk": 3, "decode_ctx": ctx,
             "prefill_spans": spans}
    assert least(fam, D, PEAK, fused) == pytest.approx(
        want + D["L"] * (secs(mixed) - secs(first)), rel=1e-12)
    assert secs(mixed) < secs(first) + secs(pre)
    # a standalone prefill runs this kernel unless it is cold (flash)
    warm = {"mode": "prefill", "cold": False, "prefill_spans": spans}
    assert least(fam, D, PEAK, warm) == pytest.approx(D["L"] * secs(pre))
    assert least(fam, D, PEAK, {**warm, "cold": True}) == 0.0
    # a record of the parent program has none of the fields: nothing
    assert least(fam, D, PEAK, {"mode": "decode"}) == 0.0
    assert least(fam, D, PEAK, {"mode": "spec_verify", "decode_ctx": ctx}) \
        == 0.0


def _tick(seq, t_dispatch, dispatch_s, wait_s, live_after, synced=True,
          **more):
    return {"seq": seq, "mode": "decode", "closed": True, "synced": synced,
            "t_dispatch": t_dispatch, "dispatch_s": dispatch_s,
            "wait_s": wait_s, "live_after": live_after,
            "t_synced": t_dispatch + dispatch_s + wait_s if synced else None,
            **more}


def test_host_gap_by_hand():
    # tick 0 busy 0.9 s, then 0.1 s of host before tick 1 is issued;
    # tick 1 busy 0.4 s and leaves nothing decoding: the 5 s before tick 2
    # are no gap (no work waited); tick 2 did not sync: no gap after it
    # can be seen; tick 4 does not follow tick 3's seq... it does, 0.05 s
    flight = [_tick(0, 10.0, 0.1, 0.8, 2), _tick(1, 11.0, 0.1, 0.3, 0),
              _tick(2, 16.4, 0.2, 0.0, 1, synced=False),
              _tick(3, 16.7, 0.1, 0.4, 3), _tick(4, 17.25, 0.1, 0.2, 3)]
    value = host_gap.read({}, {"flight": flight})
    assert value == pytest.approx(100 * 0.15 / (0.15 + 0.9 + 0.5))
    # a missing record breaks the pair; an unclosed one (a tick that
    # raised) is no tick; records of the parent program read nothing
    assert host_gap.read({}, {"flight": [flight[0], flight[3]]}) is None
    raised = {"seq": 5, "mode": "decode", "t": 18.0}
    assert host_gap.read({}, {"flight": flight + [raised]}) == value
    assert host_gap.read({}, {"flight": [{"seq": 0, "mode": "decode"},
                                        {"seq": 1, "mode": "fused"}]}) is None
    assert host_gap.read({}, {}) is None


# ---- the manifest's new entries -----------------------------------------
@pytest.mark.parametrize("name", NEW)
def test_new_entry_names_a_reader_and_its_cell(name):
    man = manifest.manifest(manifest.ROOT)
    entry, = [m for m in man["per_layer"] if m["name"] == name]
    # later PRs append their cells: the list CONTAINS this one
    assert "mistral7b-chat" in entry["workloads"]
    assert entry["moves"] == "tpot_p90_ms" and entry["unit"] == "%"
    spec = _spec(name)
    reader = manifest.plugin("readers", spec["reader"])
    assert callable(reader.read) and len(spec["source_detail"]) > 80
    merged, = [m for m in manifest.per_layer(manifest.ROOT, "mistral7b-chat")
               if m["name"] == name]
    assert merged["reader"] == spec["reader"]
    # nothing to read (a run without a trace, the parent's records):
    # no value and no error
    assert reader.read(merged, {"flight": [{"seq": 0, "mode": "decode"}],
                                "trace": None, "trace_dir": None,
                                "dims": D, "device_kind": "TPU v5 lite"}) \
        is None


# ---- the readers on a cut recorded from a chip trace of PR 24 -----------
@pytest.fixture(scope="module")
def recorded():
    """chat_tick_cut.json.gz: two fused and two plain step programs side
    by side (ticks 90-93 of seed 3000001095's traced 8 s, TPU v5 lite),
    every device event with the `tf_op` of its metadata, the program's
    own host spans with their stats, and the whole window's flight
    records; made by benchmark/tools/dump_stats.py."""
    with gzip.open(os.path.join(HERE, "data", "chat_tick_cut.json.gz"),
                   "rt") as f:
        return json.load(f)


def _obs(recorded, table=None):
    return {"trace_stats": table or {"planes": recorded["planes"]},
            "flight": recorded["flight"], "dims": D,
            "device_kind": "TPU v5 lite"}


def _within(table, t0, t1):
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [
                e for e in ln["events"] if e[1] >= t0 and e[1] + e[2] <= t1]}
            for ln in p["lines"]]} for p in table["planes"]]}


def _read(name, obs):
    spec, = [m for m in manifest.per_layer(manifest.ROOT, "mistral7b-chat")
             if m["name"] == name]
    if name == "kv_pool_copy_pct":
        # the cut was recorded from PR 24's program, whose layers still
        # sliced their blocks out of the pool under a scope of its own
        spec = {**spec, "scopes": spec["scopes"] + ["kv_pool_read"]}
    return manifest.plugin("readers", spec["reader"]).read(spec, obs)


def test_recorded_cut_holds_what_the_readers_need(recorded):
    table = {"planes": recorded["planes"]}
    ticks = xstats.host_events(table, "serve.tick")
    assert [t[3] for t in ticks] == [
        {"seq": 90, "mode": "fused"}, {"seq": 91, "mode": "fused"},
        {"seq": 92, "mode": "decode"}, {"seq": 93, "mode": "decode"}]
    dev = xplane.device_planes(table)[0]
    progs = [e[0].split("(")[0] for e in xplane.line_events(dev, "XLA Modules")
             if e[2] > 1e6]
    assert progs == ["jit_serve_fused_step"] * 2 + ["jit_serve_decode_step"] * 2
    by_seq = {r["seq"]: r for r in recorded["flight"]}
    for name, start, dur, st in ticks:
        rec = by_seq[st["seq"]]
        assert rec["mode"] == st["mode"] and rec["closed"] and rec["synced"]
        # span and stamp come from one pair of clock reads each: the
        # phases add up to the span, on either clock
        phases = sum(rec[k] for k in ("pack_s", "dispatch_s", "wait_s",
                                      "commit_s"))
        assert phases <= dur / 1e9 <= phases + 2e-3
    names = {e[0] for e in xstats.host_events(table, "")}
    assert {"serve.tick", "serve.pack", "serve.dispatch", "serve.wait",
            "serve.commit", "serve.admit", "engine.deliver",
            "engine.housekeeping", "serving.step_s"} <= names
    # one event a kernel call: 16 layers x (1 mixed + 7 decode) calls a
    # fused tick, 16 x 8 a plain one
    t0, t1 = ticks[0][1], ticks[-1][1] + ticks[-1][2]
    kernel = [e for e in xplane.line_events(dev, "XLA Ops")
              if "ragged_paged_attention" in e[0] and t0 <= e[1] < t1]
    assert len(kernel) == 4 * 16 * 8


def test_three_metrics_read_the_recorded_cut(recorded):
    obs = _obs(recorded)
    ragged = _read("ragged_attn_roofline_pct", obs)
    pool = _read("kv_pool_copy_pct", obs)
    gap = _read("host_gap_pct.chat", obs)
    assert 1.0 < ragged < 10.0         # chip runs of PR 24: 3.0 - 4.0
    assert 10.0 < pool < 30.0          # 14.5 - 18.7
    assert 0.3 < gap < 3.0             # 1.05 - 1.11
    # by hand: the kernel's seconds in ticks 90-93 against their records
    table = obs["trace_stats"]
    ticks = xstats.host_events(table, "serve.tick")
    t0, t1 = ticks[0][1], ticks[-1][1] + ticks[-1][2]
    dev = xplane.device_planes(table)[0]
    secs = sum(e[2] for e in xplane.line_events(dev, "XLA Ops")
               if "ragged_paged_attention" in e[0] and t0 <= e[1] < t1) / 1e9
    by_seq = {r["seq"]: r for r in recorded["flight"]}
    least = sum(ragged_attn_roofline.tick_least_seconds(
        fam, D, PEAK, by_seq[q]) for q in (90, 91, 92, 93))
    assert ragged == pytest.approx(100 * least / secs)


def test_three_metrics_read_a_span_of_fused_programs_only(recorded):
    table = {"planes": recorded["planes"]}
    ticks = xstats.host_events(table, "serve.tick")
    fused = _within(table, ticks[0][1] - 10**6,
                    ticks[1][1] + ticks[1][2] + 10**6)
    dev = xplane.device_planes(fused)[0]
    assert {e[0].split("(")[0] for e in xplane.line_events(dev, "XLA Modules")
            if e[2] > 1e6} == {"jit_serve_fused_step"}
    obs = _obs(recorded, fused)
    values = {n: _read(n, obs) for n in NEW}
    assert all(v is not None and v > 0 for v in values.values()), values
    # the plain chunks spend more of their time on the pool than the
    # fused steps do (PERF.md section 5), and the whole cut lies between
    plain = _within(table, ticks[2][1] - 10**6,
                    ticks[3][1] + ticks[3][2] + 10**6)
    only_plain = _read("kv_pool_copy_pct", _obs(recorded, plain))
    whole = _read("kv_pool_copy_pct", _obs(recorded))
    assert values["kv_pool_copy_pct"] < whole < only_plain


def test_a_trace_of_the_parent_program_reads_nothing(recorded):
    """No span, no scope, no closed record: every reader returns None and
    none raises (the driver runs the new readers on the parent too)."""
    bare = {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [
                [e[0].replace("ragged_paged_attention", "closed_call")
                 .replace("serve_", "run_"), e[1], e[2], {}]
                for e in ln["events"] if not e[0].startswith(
                    ("serve.", "engine."))]}
            for ln in p["lines"]]} for p in recorded["planes"]]}
    old = [{k: r[k] for k in ("seq", "t", "mode", "active_slots")}
           for r in recorded["flight"]]
    obs = {"trace_stats": bare, "flight": old, "dims": D,
           "device_kind": "TPU v5 lite"}
    assert [_read(n, obs) for n in NEW] == [None, None, None]


def test_traced_cpu_run_prints_the_window_metric(tiny_root, cpu_device,
                                                 monkeypatch, capsys,
                                                 recorded):
    """The whole command on the CPU with the new entries in a tiny
    manifest: `host_gap_pct.chat` comes from the records that THIS run's
    batcher closed; the two device metrics from the recorded table."""
    from benchmark import run
    from benchmark.harness import window
    from benchmark.tests import test_xplane
    path = os.path.join(tiny_root, "BENCHMARK.json")
    man = json.load(open(path))
    for entry in manifest.manifest(manifest.ROOT)["per_layer"]:
        if entry["name"] in NEW:
            man["per_layer"].append({**entry, "workloads": ["tiny-chat"]})
            with open(os.path.join(tiny_root, "benchmark", "layer_metrics",
                                   entry["name"] + ".json"), "w") as f:
                json.dump(_spec(entry["name"]), f)
    json.dump(man, open(path, "w"))

    def no_trace(_after, _length, out):
        import threading
        out["dir"] = "unused"
        th = threading.Thread(target=lambda: None)
        th.start()
        return th

    monkeypatch.setattr(window, "trace_thread", no_trace)
    monkeypatch.setattr(xplane, "find_xplane", lambda d: d)
    monkeypatch.setattr(xplane, "load", lambda p: test_xplane.table())
    monkeypatch.setattr(xstats, "load",
                        lambda p: {"planes": recorded["planes"]})
    assert run.main(["--workload", "tiny-chat", "--seed", "7", "--seconds",
                     "2", "--trace", "1"], root=tiny_root) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert 0.0 < line["metrics"]["host_gap_pct.chat"]["value"] < 100.0
    assert line["metrics"]["kv_pool_copy_pct"]["value"] > 0.0
