"""Serving observability: per-request trace timelines
(paddle_tpu.serving.trace), the step flight recorder, and Prometheus
export.

Coverage per the PR's acceptance criteria: every terminal request
state (FINISHED / CANCELLED / TIMED_OUT / FAILED) yields a complete,
ordered timeline; fused prefill chunks are attributed to the RIGHT
request (with bucket / pad / cached-token annotations); an injected
step failure dumps the flight recorder — naming the failing step's
mode and unit composition — and the dump round-trips through
json.loads; the Chrome-trace export is schema-valid with monotonic
timestamps; Histogram.summary() separates windowed from lifetime
stats once the ring wraps; MetricsRegistry.to_prometheus() renders
the text exposition format.
"""
import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest
import jax

from paddle_tpu.nlp import llama, paged
from paddle_tpu import serving
from paddle_tpu.serving import (FlightRecorder, MetricsRegistry,
                                RequestState, TraceSink)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


_RNG = np.random.RandomState(7)
PROMPT = list(map(int, _RNG.randint(1, 200, 5)))
PROMPT2 = list(map(int, _RNG.randint(1, 200, 7)))


def _kinds(tl):
    return [e["kind"] for e in tl["events"]]


def _assert_ordered(tl, *subsequence):
    """Each kind's FIRST occurrence appears in the given order, and
    timestamps never go backwards."""
    ks = _kinds(tl)
    idx = []
    for kind in subsequence:
        assert kind in ks, f"{kind} missing from timeline {ks}"
        idx.append(ks.index(kind))
    assert idx == sorted(idx), f"{subsequence} out of order in {ks}"
    ts = [e["t"] for e in tl["events"]]
    assert ts == sorted(ts), "timeline timestamps are not monotonic"


# ---- metrics: windowed histogram + prometheus --------------------------
class TestMetricsObservability:
    def test_histogram_window_wrap_regression(self):
        """Once the ring wraps past cap, lifetime min/max/mean must NOT
        leak into the windowed view the percentiles rank — the window
        gets its own explicit keys (the satellite bugfix)."""
        m = MetricsRegistry()
        h = m.histogram("lat", cap=4)
        for v in range(1, 11):          # 1..10; ring keeps 7, 8, 9, 10
            h.observe(float(v))
        s = h.summary()
        assert s["count"] == 10
        assert s["min"] == 1.0 and s["max"] == 10.0      # lifetime
        assert s["mean"] == pytest.approx(5.5)
        assert s["window_count"] == 4
        assert s["window_min"] == 7.0 and s["window_max"] == 10.0
        # percentiles rank ONLY the window — p50 can't be the lifetime
        # median once early observations fell off the ring
        assert s["p50"] >= 7.0
        assert s["p99"] == 10.0

    def test_histogram_window_matches_lifetime_before_wrap(self):
        m = MetricsRegistry()
        h = m.histogram("lat2", cap=8)
        for v in (3.0, 1.0, 2.0):
            h.observe(v)
        s = h.summary()
        assert s["window_count"] == s["count"] == 3
        assert s["window_min"] == s["min"] == 1.0
        assert s["window_max"] == s["max"] == 3.0

    def test_to_prometheus_text_format(self):
        m = MetricsRegistry()
        m.counter("requests_done").inc(3)
        m.gauge("queue_depth").set(2.0)
        h = m.histogram("serving.step_s")
        for v in (0.1, 0.2, 0.3):
            h.observe(v)
        text = m.to_prometheus()
        lines = text.strip().splitlines()
        # the TYPE family must name the _total sample exactly, or the
        # scraper types every counter "unknown"
        assert "# TYPE paddle_tpu_requests_done_total counter" in lines
        assert "paddle_tpu_requests_done_total 3.0" in lines
        assert "# TYPE paddle_tpu_queue_depth gauge" in lines
        assert "paddle_tpu_queue_depth 2.0" in lines
        # dotted names sanitize to the prometheus charset
        assert "# TYPE paddle_tpu_serving_step_s summary" in lines
        assert any(l.startswith('paddle_tpu_serving_step_s{quantile="0.5"}')
                   for l in lines)
        assert "paddle_tpu_serving_step_s_count 3.0" in lines
        # every sample line is "name{labels} value" — two fields
        for l in lines:
            if not l.startswith("#"):
                assert len(l.split()) == 2, l

    def test_empty_histogram_renders(self):
        m = MetricsRegistry()
        m.histogram("never_observed")
        text = m.to_prometheus()
        assert "paddle_tpu_never_observed_count 0.0" in text


# ---- trace sink units --------------------------------------------------
class TestTraceSink:
    def test_start_emit_finish_roundtrip(self):
        s = TraceSink()
        tid = s.start()
        s.emit(tid, "enqueued", prompt_len=4)
        s.alias(17, tid)
        s.emit(17, "prepared", slot=1)          # resolves via alias
        assert s.timeline(17)["trace_id"] == tid
        s.finish(tid, "finished", reason="length")
        tl = s.timeline(tid)
        assert tl["done"] is True
        assert _kinds(tl) == ["enqueued", "prepared", "finished"]
        assert tl["slot"] == 1                  # slot attr tracked
        assert s.timeline(17) is None           # alias released on finish
        # finish is idempotent
        s.finish(tid, "finished")
        assert len(_kinds(s.timeline(tid))) == 3

    def test_unaliased_rid_autocreates_timeline(self):
        """A standalone batcher traces without an engine: rid refs
        auto-open rid<n> timelines."""
        s = TraceSink()
        s.emit(5, "prepared", slot=0)
        tl = s.timeline(5)
        assert tl["trace_id"] == "rid5"
        assert _kinds(tl) == ["prepared"]

    def test_event_bound_drops_but_terminal_lands(self):
        s = TraceSink(max_events=3)
        tid = s.start()
        for i in range(10):
            s.emit(tid, "decode_emit", n=1)
        s.finish(tid, "finished")
        tl = s.timeline(tid)
        assert len(tl["events"]) == 4           # 3 kept + forced terminal
        assert tl["events"][-1]["kind"] == "finished"
        assert s.dropped_events == 7

    def test_done_ring_bounded(self):
        s = TraceSink(max_requests=2)
        tids = []
        for _ in range(5):
            tid = s.start()
            s.finish(tid, "finished")
            tids.append(tid)
        assert len(s.timelines()) == 2
        assert s.timeline(tids[0]) is None      # oldest evicted
        assert s.timeline(tids[-1]) is not None

    def test_emit_after_finish_is_dropped(self):
        s = TraceSink()
        tid = s.start()
        s.finish(tid, "cancelled")
        s.emit(tid, "decode_emit", n=1)
        assert _kinds(s.timeline(tid)) == ["cancelled"]
        assert s.dropped_events == 1            # lost, but never silently

    def test_chrome_trace_schema(self):
        s = TraceSink()
        tid = s.start()
        s.emit(tid, "enqueued", prompt_len=4)
        s.emit(tid, "prefill_chunk", dur=0.01, slot=1, bucket=8, pad=3)
        s.span("engine.step", dur=0.005, tokens=2)
        s.finish(tid, "finished")
        ct = s.to_chrome_trace()
        assert set(ct) == {"traceEvents", "displayTimeUnit"}
        evs = ct["traceEvents"]
        json.loads(json.dumps(ct))              # JSON-serializable
        meta = [e for e in evs if e["ph"] == "M"]
        body = [e for e in evs if e["ph"] != "M"]
        assert {m["name"] for m in meta} >= {"process_name", "thread_name"}
        for e in body:
            assert e["ph"] in ("X", "i")
            assert isinstance(e["ts"], float) and e["ts"] >= 0.0
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
            if e["ph"] == "X":
                assert e["dur"] >= 0.0
        # monotonic timestamps (the Perfetto-validity acceptance bar)
        ts = [e["ts"] for e in body]
        assert ts == sorted(ts)
        # pid = engine, tid = slot for slot-anchored events
        chunk = next(e for e in body if e["name"] == "prefill_chunk")
        assert chunk["tid"] == 1 and chunk["args"]["bucket"] == 8
        step = next(e for e in body if e["name"] == "engine.step")
        assert step["ph"] == "X"

    def test_chrome_span_renders_at_start_not_emission(self):
        """A dur-carrying event is emitted AFTER the measured call, so
        its chrome ts must be (emission - dur) — rendering at emission
        time would shift every chunk span right by its own duration,
        outside the engine.step span that contained it."""
        t = {"v": 100.0}

        def clock():
            return t["v"]

        s = TraceSink(clock=clock)              # origin = 100.0
        t["v"] = 105.0
        tid = s.start()
        s.emit(tid, "prefill_chunk", dur=2.0)   # ran [103, 105]
        s.finish(tid, "finished")
        body = [e for e in s.to_chrome_trace()["traceEvents"]
                if e["ph"] != "M"]
        chunk = next(e for e in body if e["name"] == "prefill_chunk")
        assert chunk["ts"] == pytest.approx(3.0 * 1e6)   # 103 - origin
        assert chunk["dur"] == pytest.approx(2.0 * 1e6)

    def test_live_timelines_bounded_without_finish(self):
        """A producer that never finishes (standalone batcher rid
        timelines) must not grow the live set unboundedly: the oldest
        displaces onto the completed ring, aliases dropped."""
        s = TraceSink(max_requests=2)
        for rid in range(5):
            s.emit(rid, "prepared", slot=0)
        assert len(s._live) <= 2
        assert len(s.timelines()) <= 4          # live + done ring
        assert s.displaced_live == 3            # loss is accounted
        # a late emit for a displaced-but-retained rid neither
        # resurrects nor splits its timeline — it drops, visibly
        # (rid2 still sits on the done ring; rid0 fell off entirely)
        s.emit(2, "retired", slot=0)
        assert s.timeline(2)["trace_id"] == "rid2"   # the displaced one
        assert _kinds(s.timeline(2)) == ["prepared"]
        assert s.dropped_events == 1
        s.alias(99, s.start())
        for _ in range(3):
            s.start()
        assert 99 not in s._alias               # displaced with its tl

    def test_flight_recorder_ring(self):
        fr = FlightRecorder(cap=3)
        for i in range(7):
            fr.record("decode", free_slots=i)
        recs = fr.records()
        assert len(recs) == len(fr) == 3
        assert [r["seq"] for r in recs] == [4, 5, 6]
        assert all(r["mode"] == "decode" for r in recs)
        json.loads(json.dumps(recs))

    def test_sync_rule_covers_trace_emission(self):
        """The SYNC001 hot-path set extends to the trace emission
        helpers — a device sync hiding in an event attr would tax
        every step. Since the call-graph closure replaced the hand
        list, coverage is asserted on the DERIVED set of the real
        tree (the sink's emit is reached through the batcher's typed
        `_trace` attr, not a hand entry)."""
        import os
        from paddle_tpu.analysis.core import load_project
        from paddle_tpu.analysis.rules.sync import derive_hot_paths
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        # the decode hot path's roots all live in these three subtrees
        # — loading just them keeps this assertion cheap in tier-1
        project, errs = load_project(
            [os.path.join(repo, "paddle_tpu", d)
             for d in ("nlp", "serving", "quantization")], repo)
        assert errs == []
        hot, _dead = derive_hot_paths(project)
        names = {(ctx.relpath, node.name) for ctx, node, _ in hot.values()}
        assert ("paddle_tpu/serving/trace.py", "emit") in names
        assert ("paddle_tpu/nlp/paged.py", "_trace_emit") in names


# ---- batcher-level: chunk attribution + flight records -----------------
class TestBatcherTracing:
    def test_fused_chunks_attributed_to_right_request(self, setup):
        """A long prompt admitted mid-decode streams its chunks FUSED;
        every chunk event lands on that request's timeline (contiguous
        spans covering exactly its suffix), never the decoding
        neighbor's."""
        cfg, params = setup
        sink = TraceSink()
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=8, chunk=2, max_prefill_bucket=8, trace=sink)
        r1 = cb.submit(PROMPT)
        cb.step()                                # r1 prefills + decodes
        long_prompt = list(range(1, 21))         # 20 toks -> 3 chunks @ 8
        r2 = cb.submit(long_prompt)
        while cb.queue or cb._pending or any(cb.active):
            cb.step()

        tl2 = sink.timeline(r2)
        chunks = [e["attrs"] for e in tl2["events"]
                  if e["kind"] == "prefill_chunk"]
        assert [c["fused"] for c in chunks] == [True, True, True]
        assert [(c["start"], c["end"]) for c in chunks] == \
            [(0, 8), (8, 16), (16, 20)]
        assert chunks[-1]["pad"] == 4            # 20 pads to 3 x bucket 8
        assert all(c["bucket"] == 8 for c in chunks)
        # the decoding neighbor's prefill was standalone, not fused
        tl1 = sink.timeline(r1)
        assert [e["attrs"]["fused"] for e in tl1["events"]
                if e["kind"] == "prefill_chunk"] == [False]
        # ... and the fused flight record names exactly r2's unit
        fused = [r for r in cb.flight.records() if r["mode"] == "fused"]
        assert len(fused) == 3                   # one per streamed chunk
        assert all(r["units"] == [[r2]] for r in fused)
        assert all(r["bucket"] == 8 for r in fused)

    def test_flight_records_have_tick_state(self, setup):
        cfg, params = setup
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=4, chunk=2)
        cb.submit(PROMPT)
        cb.run()
        recs = cb.flight.records()
        assert recs, "step ticks must record"
        assert {r["mode"] for r in recs} <= {"prefill", "decode", "fused"}
        for r in recs:
            for key in ("seq", "t", "free_slots", "free_blocks",
                        "active_slots", "queue_depth", "pending",
                        "compile_hit"):
                assert key in r, f"{key} missing from {r}"
        # the first prefill/decode of a cold batcher are compile misses
        assert recs[0]["compile_hit"] is False
        # steady-state decode hits the memo
        assert recs[-1]["mode"] == "decode" and recs[-1]["compile_hit"]

    def test_trace_off_is_default_and_silent(self, setup):
        cfg, params = setup
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=1, block_size=4, max_total_len=16,
            max_new_tokens=2, chunk=2)
        cb.submit(PROMPT)
        out = cb.run()
        assert len(out[0]) == 2                  # serves fine untraced
        assert cb._trace is None

    def test_batcher_trace_bool_mirrors_engine_api(self, setup):
        """trace=True on the batcher builds a default sink (the engine's
        bool API, mirrored) instead of crashing mid-step; a non-sink
        value is rejected at construction, not as a device failure."""
        cfg, params = setup
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=1, block_size=4, max_total_len=16,
            max_new_tokens=2, chunk=2, trace=True)
        rid = cb.submit(PROMPT)
        cb.run()
        assert _kinds(cb._trace.timeline(rid))[0] == "prepared"
        assert paged.ContinuousBatcher(
            params, cfg, max_batch=1, block_size=4, max_total_len=16,
            max_new_tokens=2, chunk=2, trace=False)._trace is None
        with pytest.raises(TypeError):
            paged.ContinuousBatcher(
                params, cfg, max_batch=1, block_size=4, max_total_len=16,
                max_new_tokens=2, chunk=2, trace=42)


# ---- engine-level: terminal timelines ----------------------------------
class TestEngineTimelines:
    def test_finished_timeline_complete_and_ordered(self, setup):
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=4, chunk=2, start=False)
        # the engine sizes the sink's live bound above everything it
        # can hold open at once, so a deep queued burst can never
        # displace a running request's timeline
        assert eng.trace._max_live > eng.queue.max_depth + 2
        r1 = eng.submit(PROMPT)
        r2 = eng.submit(PROMPT2)
        eng.start()
        eng.shutdown(drain=True, timeout=300)
        assert r1.result() and r2.result()
        for req in (r1, r2):
            tl = eng.trace.timeline(req.trace_id)
            assert tl is not None and tl["done"]
            _assert_ordered(tl, "enqueued", "admitted", "prepared",
                            "prefill_chunk", "first_token",
                            "decode_emit", "retired", "finished")
            assert _kinds(tl)[-1] == "finished"
            ev = tl["events"]
            enq = next(e for e in ev if e["kind"] == "enqueued")
            assert enq["attrs"]["prompt_len"] == len(req.prompt)
            fin = ev[-1]
            assert fin["attrs"]["reason"] == "length"

    def test_cancelled_and_timed_out_timelines(self, setup):
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=4, chunk=2, start=False)
        r_cancel = eng.submit(PROMPT)
        r_cancel.cancel()
        r_timeout = eng.submit(PROMPT2, timeout_s=0.0)
        eng.start()
        eng.shutdown(drain=True, timeout=300)
        assert r_cancel.state is RequestState.CANCELLED
        assert r_timeout.state is RequestState.TIMED_OUT
        tl_c = eng.trace.timeline(r_cancel.trace_id)
        _assert_ordered(tl_c, "enqueued", "cancelled")
        assert _kinds(tl_c)[-1] == "cancelled"
        tl_t = eng.trace.timeline(r_timeout.trace_id)
        _assert_ordered(tl_t, "enqueued", "timed_out")
        assert _kinds(tl_t)[-1] == "timed_out"

    def test_failed_timeline_on_token_boundary(self, setup):
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=4, chunk=2, start=False)

        def boom(tok):
            raise RuntimeError("consumer exploded")

        r_bad = eng.submit(PROMPT, on_token=boom)
        r_ok = eng.submit(PROMPT2)
        eng.start()
        eng.shutdown(drain=True, timeout=300)
        assert r_bad.state is RequestState.FAILED
        assert r_ok.state is RequestState.FINISHED
        tl = eng.trace.timeline(r_bad.trace_id)
        _assert_ordered(tl, "enqueued", "admitted", "prepared",
                        "prefill_chunk", "first_token", "decode_emit",
                        "failed")
        assert _kinds(tl)[-1] == "failed"
        assert "consumer exploded" in tl["events"][-1]["attrs"]["error"]
        # the delivered-before-failure tokens stay on the timeline, so
        # it agrees with the ttft histogram and req.tokens
        emit = next(e for e in tl["events"] if e["kind"] == "decode_emit")
        assert emit["attrs"]["n"] == len(r_bad.tokens) >= 1

    def test_cached_prefix_skip_visible(self, setup):
        """The acceptance bar's shared-prefix story: a repeat prompt's
        timeline shows the prefix cache skipping cached tokens."""
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=4, chunk=2)
        warm = PROMPT + PROMPT2                  # 12 toks = 3 full blocks
        r1 = eng.submit(warm)
        r1.result(timeout=300)
        r2 = eng.submit(warm)
        r2.result(timeout=300)
        eng.shutdown()
        tl1 = eng.trace.timeline(r1.trace_id)
        tl2 = eng.trace.timeline(r2.trace_id)
        prep1 = next(e for e in tl1["events"] if e["kind"] == "prepared")
        prep2 = next(e for e in tl2["events"] if e["kind"] == "prepared")
        assert prep1["attrs"]["cached_tokens"] == 0
        assert prep2["attrs"]["cached_tokens"] > 0
        chunk2 = next(e for e in tl2["events"]
                      if e["kind"] == "prefill_chunk")
        assert chunk2["attrs"]["cached_tokens"] == \
            prep2["attrs"]["cached_tokens"]
        assert chunk2["attrs"]["cold"] is False  # suffix-only prefill

    def test_trace_disabled_engine(self, setup):
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=1, block_size=4, max_total_len=16,
            max_new_tokens=2, chunk=2, trace=False)
        assert eng.trace is None
        assert eng.generate(PROMPT, timeout=300)
        # the flight recorder stays on even with timelines off
        dump = eng.dump_flight_recorder()
        assert dump["records"]
        eng.shutdown()


# ---- flight recorder dumps --------------------------------------------
class TestFlightRecorderDump:
    def test_injected_decode_fault_dumps_and_roundtrips(self, setup,
                                                        tmp_path):
        """A device-step failure mid-decode leaves a JSON dump naming
        the failing step's mode, with allocator/queue state attached —
        and the engine keeps serving afterwards."""
        cfg, params = setup
        dump_path = tmp_path / "flight.json"
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=4, chunk=2, flight_dump_path=str(dump_path))
        assert eng.generate(PROMPT, timeout=300)     # healthy first

        real = eng.batcher._chunk_exe

        def faulty():
            raise RuntimeError("injected device fault")

        eng.batcher._chunk_exe = faulty
        r = eng.submit(PROMPT2)
        with pytest.raises(serving.RequestFailed):
            r.result(timeout=300)
        # the dump round-trips through json.loads and names the step
        dump = json.loads(eng.last_flight_dump_json)
        assert "injected device fault" in dump["error"]
        assert dump["failing_record"]["mode"] == "decode"
        assert dump["records"][-1] == dump["failing_record"]
        assert dump["allocator"]["capacity_blocks"] > 0
        assert isinstance(dump["running_rids"], list)
        # ... and hit the configured path too
        on_disk = json.loads(dump_path.read_text())
        assert on_disk["failing_record"]["mode"] == "decode"
        # engine survives: heal the batcher and serve again
        eng.batcher._chunk_exe = real
        assert eng.generate(PROMPT, timeout=300)
        eng.shutdown()

    def test_injected_fused_fault_names_unit_composition(self, setup):
        """The acceptance bar: a fault in the FUSED step's device call
        dumps a record naming mode='fused' and the unit composition
        (which pending rids rode the failing call)."""
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=64,
            max_new_tokens=24, chunk=2, start=False)

        def faulty(Gp, Pb):
            raise RuntimeError("injected fused fault")

        eng.batcher._fused_exe = faulty
        got_first = threading.Event()
        r1 = eng.submit(PROMPT, on_token=lambda t: got_first.set())
        eng.start()
        assert got_first.wait(timeout=300)       # r1 is mid-decode
        r2 = eng.submit(PROMPT2)                 # lands while r1 decodes
        with pytest.raises(serving.RequestFailed):
            r2.result(timeout=300)
        dump = json.loads(eng.last_flight_dump_json)
        assert dump["failing_record"]["mode"] == "fused"
        assert [r2.request_id] in dump["failing_record"]["units"]
        assert "injected fused fault" in dump["error"]
        eng.shutdown()

    def test_on_demand_dump(self, setup, tmp_path):
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=1, block_size=4, max_total_len=16,
            max_new_tokens=2, chunk=2)
        eng.generate(PROMPT, timeout=300)
        path = tmp_path / "dump.json"
        dump = eng.dump_flight_recorder(str(path))
        assert dump["error"] is None
        assert json.loads(path.read_text())["records"] == dump["records"]
        eng.shutdown()


# ---- artifact tooling --------------------------------------------------
class TestTraceArtifacts:
    @pytest.fixture(scope="class")
    def trace_file(self, setup, tmp_path_factory):
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=4, chunk=2)
        for p in (PROMPT, PROMPT2, PROMPT):
            eng.generate(p, timeout=300)
        path = tmp_path_factory.mktemp("trace") / "trace.json"
        with open(path, "w") as f:
            json.dump(eng.trace.to_chrome_trace(), f)
        eng.shutdown()
        return path

    def test_trace_report_cli(self, trace_file):
        out = subprocess.run(
            [sys.executable, str(REPO / "tools" / "trace_report.py"),
             str(trace_file), "--json"],
            capture_output=True, text=True, check=True)
        summary = json.loads(out.stdout)
        t = summary["total"]
        assert t["requests"] == 3
        assert t["terminals"] == {"finished": 3}
        assert t["prefill_chunks"] >= 3
        assert 0.0 <= t["pad_waste"] < 1.0
        assert t["cache_hit_rate"] > 0.0         # repeat PROMPT hit
        assert t["engine_steps"] > 0
        for row in summary["requests"]:
            assert row["terminal"] == "finished"
            assert row["ttft_ms"] is not None
            assert row["total_ms"] >= row["ttft_ms"] >= 0.0
        # human rendering exercises the same summary
        txt = subprocess.run(
            [sys.executable, str(REPO / "tools" / "trace_report.py"),
             str(trace_file)], capture_output=True, text=True, check=True)
        assert "serving trace summary" in txt.stdout

    def test_load_profiler_result_reads_serving_trace(self, trace_file,
                                                      tmp_path):
        from paddle_tpu import profiler
        data = profiler.load_profiler_result(str(trace_file))
        assert "traceEvents" in data
        other = tmp_path / "not_a_trace.json"
        other.write_text("[1, 2, 3]")
        with pytest.raises(NotImplementedError):
            profiler.load_profiler_result(str(other))
        # a typo'd path stays a file error, not a format error
        with pytest.raises(OSError):
            profiler.load_profiler_result(str(tmp_path / "missing.json"))

    def test_trace_report_handles_live_requests(self, tmp_path):
        """An artifact exported mid-run (requests without a terminal
        event yet) summarizes as 'live' instead of crashing."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_report", REPO / "tools" / "trace_report.py")
        tr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tr)
        path = tmp_path / "mid_run.json"
        path.write_text(json.dumps({"traceEvents": [
            {"name": "enqueued", "ph": "i", "pid": 1, "tid": 9998,
             "ts": 1.0, "args": {"trace_id": "t0", "prompt_len": 4}},
            {"name": "enqueued", "ph": "i", "pid": 1, "tid": 9998,
             "ts": 2.0, "args": {"trace_id": "t1", "prompt_len": 4}},
            {"name": "finished", "ph": "i", "pid": 1, "tid": 0,
             "ts": 9.0, "args": {"trace_id": "t1"}},
        ]}))
        summary = tr.summarize(tr.load_events(str(path)))
        assert summary["total"]["terminals"] == {"finished": 1,
                                                "live": 1}


# ---- device-time profiler, fed by the ticks' own stamps ------------------
class TestStepProfiler:
    def test_every_synced_tick_recorded(self, setup):
        """Any profile_sample_every above 0 records every tick that
        syncs, from the tick's own stamps (no fence): the profiler's
        tick count matches the flight recorder's, its samples the
        records closed `synced`, and each sample's device wall is the
        record's dispatch_s + wait_s."""
        cfg, params = setup
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=8, chunk=2, profile_sample_every=3)
        cb.submit(PROMPT)
        cb.submit(PROMPT2)
        cb.run()
        rep = cb.profiler.report()
        recs = cb.flight.records()
        assert rep["ticks"] == cb.flight.seq    # one gate per tick
        assert rep["ticks"] >= 4
        synced = [r for r in recs if r["synced"]]
        assert rep["samples"] == len(synced) >= 4
        total = sum(r["dispatch_s"] + r["wait_s"] for r in synced)
        assert sum(row["device_sum_s"] for row in rep["shapes"]) \
            == pytest.approx(total)
        # 0 disables: nothing recorded
        cb2 = paged.ContinuousBatcher(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=8, chunk=2, profile_sample_every=0)
        cb2.submit(PROMPT)
        cb2.run()
        assert cb2.profiler.report()["samples"] == 0
        assert cb2.profiler.report()["ticks"] == cb2.flight.seq

    def test_zero_recompiles_with_sampling_on(self, setup):
        """Recording every single step must not touch the compiled-shape
        memo: compile_count stays at its warmup value."""
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=6, chunk=2, max_prefill_bucket=8,
            profile_sample_every=1, start=False)
        eng.warmup()
        eng.start()
        warm = eng.batcher.compile_count
        for p in (PROMPT, PROMPT2, list(range(1, 21))):
            eng.generate(p, timeout=300)
        assert eng.batcher.compile_count == warm
        assert eng.batcher.profiler.report()["samples"] >= 3
        eng.shutdown()

    def test_per_shape_keys_carry_mode_bucket_impl_qkey(self, setup):
        """The per-shape histograms key on (mode, bucket, units, impl,
        weight_dtype, kv_dtype) — decode keys carry the chunk length,
        prefill keys the ladder bucket, and the resolved impl/qkey ride
        every row."""
        cfg, params = setup
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=8, chunk=2, max_prefill_bucket=8,
            profile_sample_every=1)
        cb.submit(PROMPT)
        cb.step()                      # r1 decodes
        cb.submit(list(range(1, 21)))  # chunks fused onto the decode
        cb.run()
        rep = cb.profiler.report()
        by_mode = {}
        for row in rep["shapes"]:
            by_mode.setdefault(row["mode"], []).append(row)
            assert row["impl"] == cb.attention_impl
            assert row["weight_dtype"] == "fp"
            assert row["kv_dtype"] == "fp"
            assert row["count"] >= 1
            assert row["device_sum_s"] >= row["host_sum_s"] >= 0.0
            assert row["device_p99_s"] >= row["device_p50_s"] >= 0.0
        assert "decode" in by_mode and "prefill" in by_mode
        assert "fused" in by_mode       # the long prompt fused its chunks
        assert all(r["bucket"] == 2 for r in by_mode["decode"])
        assert all(r["bucket"] in cb.prefill_buckets
                   for r in by_mode["prefill"] + by_mode["fused"])
        assert all(r["units"] >= 1 for r in by_mode["fused"])

    def test_capture_window_lands_device_wall_in_timelines(
            self, setup, tmp_path, monkeypatch):
        """engine.capture_profile(steps=K) fences K ticks (and only
        those: block_until_ready is counted): the report comes back
        complete, prefill_chunk events carry device_dur next to their
        host dur, device.* spans land on the device lane of
        to_chrome_trace(), and trace_report shows the device columns."""
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=6, chunk=2, max_prefill_bucket=8,
            profile_sample_every=0, start=False)
        eng.warmup()
        eng.start()
        done = threading.Event()

        def traffic():
            for p in (PROMPT, PROMPT2, PROMPT):
                eng.generate(p, timeout=300)
            done.set()

        t = threading.Thread(target=traffic)
        # arm BEFORE traffic so the first prefill ticks are inside the
        # window (recording is off — only the capture window records)
        fences = []
        real = jax.block_until_ready
        monkeypatch.setattr(
            jax, "block_until_ready",
            lambda x: (fences.append(1), real(x))[1])
        eng.batcher.profiler.arm_capture(6)
        t.start()
        while eng.batcher.profiler.capture_active() \
                and not done.wait(0.01):
            pass
        t.join(300)
        report = eng.batcher.profiler.report()
        assert len(fences) == 6         # the window's ticks, no other
        assert report["capture"]["complete"], report["capture"]
        assert report["capture"]["steps_captured"] == 6
        step0 = report["capture"]["steps"][0]
        assert {"mode", "device_s", "host_s", "rids"} <= set(step0)
        chrome = eng.trace.to_chrome_trace()
        dev = [e for e in chrome["traceEvents"]
               if str(e.get("name", "")).startswith("device.")]
        assert dev, "no device spans in the chrome trace"
        dev_tids = {e["tid"] for e in dev}
        assert len(dev_tids) == 1
        lane_names = {m["tid"]: m["args"]["name"]
                      for m in chrome["traceEvents"]
                      if m.get("ph") == "M"
                      and m.get("name") == "thread_name"}
        assert lane_names[dev_tids.pop()] == "device steps"
        chunks = [e for e in chrome["traceEvents"]
                  if e.get("name") == "prefill_chunk"
                  and "device_dur" in e.get("args", {})]
        assert chunks, "no prefill chunk carried device_dur"
        for c in chunks:
            # a real measured device wall, distinguishable from (and
            # carried next to) the host-wall span the event renders
            assert c["args"]["device_dur"] > 0.0
            assert c["dur"] > 0.0
        path = tmp_path / "capture_trace.json"
        path.write_text(json.dumps(chrome))
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_report", REPO / "tools" / "trace_report.py")
        tr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tr)
        summary = tr.summarize(tr.load_events(str(path)))
        assert summary["total"]["device_steps"] >= 1
        assert summary["total"]["device_step_ms_total"] > 0.0
        assert any(r["device_ms"] for r in summary["requests"])
        txt = tr.render(summary)
        assert "device_ms" in txt and "device steps:" in txt
        eng.shutdown()

    def test_capture_timeout_on_idle_engine_disarms(self, setup):
        """A capture armed on an idle engine times out bounded,
        reports complete=False, AND disarms the window — a leftover
        armed capture must not silently fence every future tick once
        traffic resumes (review regression)."""
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=1, block_size=4, max_total_len=32,
            max_new_tokens=2, profile_sample_every=0, start=False)
        rep = eng.capture_profile(steps=2, timeout=0.2)
        assert rep["capture"]["complete"] is False
        assert rep["capture"]["steps_captured"] == 0
        assert eng.batcher.profiler.capture_active() is False
        # traffic after the timed-out capture pays zero fences
        # (recording is off on this engine: any sample = a leak)
        eng.start()
        eng.generate(PROMPT, timeout=300)
        assert eng.batcher.profiler.report()["samples"] == 0
        eng.shutdown()
