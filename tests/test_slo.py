"""paddle_tpu.serving.slo — the serving SLO engine.

Deterministic coverage of the tentpole's control plane: dual-window
burn-rate math on a fake clock, breach→recover hysteresis (one breach
counted per excursion, the alert held through the hysteresis band),
the Router's fleet rollup (worst-of verdicts, max burn, summed
breaches), the Prometheus surface (slo_burn_rate_* gauges,
slo_breaches_total counters, native *_hist_bucket{le=...} histogram
families — including TYPE-line grouping in the router's merged
exposition), the end-to-end breach path (engine health()["slo"] →
router rollup → /health detail without flipping the 200 →
slo_breach trace events → trace_report --slo breach windows naming
the requests that rode them), and the PR 12 operator gap: the
breaker-reset surface (supervisor reset + Router.reset_breaker +
POST /admin/reset_breaker).
"""
import json
import threading
import time

import numpy as np
import pytest
import jax

from paddle_tpu.nlp import llama
from paddle_tpu import serving
from paddle_tpu.serving.metrics import MetricsRegistry
from paddle_tpu.serving.slo import (
    SloTracker, DEFAULT_OBJECTIVES, rollup, worst_verdict)

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "tools"))
import trace_report as tr  # noqa: E402


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _tracker(objectives, t, **kw):
    kw.setdefault("fast_window_s", 1.0)
    kw.setdefault("slow_window_s", 10.0)
    kw.setdefault("eval_every_s", 0.0)     # recompute every evaluate()
    return SloTracker(objectives, clock=lambda: t[0], **kw)


class TestTrackerUnits:
    def test_unknown_objective_rejected(self):
        with pytest.raises(ValueError):
            SloTracker({"ttft_p99_typo": 1.0})
        with pytest.raises(ValueError):
            SloTracker({"ttft_s_p99": 0.0})
        with pytest.raises(ValueError):
            SloTracker({"ttft_s_p99": 1.0}, fast_window_s=10.0,
                       slow_window_s=5.0)

    def test_defaults_are_known(self):
        t = SloTracker()
        assert set(t.objectives) == set(DEFAULT_OBJECTIVES)
        rep = t.evaluate()
        # no samples: everything OK at burn 0
        assert rep["verdict"] == "OK"
        assert all(o["burn_rate_fast"] == 0.0
                   for o in rep["objectives"].values())

    def test_window_separation_and_burn_math(self):
        t = [100.0]
        s = _tracker({"ttft_s_p99": 0.2, "itl_ms_p99": 100.0}, t)
        s.record_ttft(0.6)              # burn 3.0 against 0.2
        s.record_itl(0.05)              # 50 ms against 100 → burn 0.5
        rep = s.evaluate(force=True)
        ttft = rep["objectives"]["ttft_s_p99"]
        assert ttft["value_fast"] == pytest.approx(0.6)
        assert ttft["burn_rate_fast"] == pytest.approx(3.0)
        itl = rep["objectives"]["itl_ms_p99"]
        assert itl["value_fast"] == pytest.approx(50.0)   # ms conversion
        assert itl["burn_rate_fast"] == pytest.approx(0.5)
        # advance past the fast window but inside the slow one: the
        # sample leaves the fast view, stays in the slow view
        t[0] = 102.0
        rep = s.evaluate(force=True)
        ttft = rep["objectives"]["ttft_s_p99"]
        assert ttft["value_fast"] is None
        assert ttft["burn_rate_fast"] == 0.0
        assert ttft["value_slow"] == pytest.approx(0.6)
        assert ttft["burn_rate_slow"] == pytest.approx(3.0)
        # past the slow window everything is pruned
        t[0] = 120.0
        rep = s.evaluate(force=True)
        assert rep["objectives"]["ttft_s_p99"]["value_slow"] is None

    def test_goodput_floor_and_error_rate(self):
        t = [0.0]
        s = _tracker({"goodput_tok_s": 100.0, "error_rate": 0.25}, t,
                     fast_window_s=2.0)
        # 50 tokens over a 1 s ACTIVE span (first in-window sample →
        # now) = 50 tok/s against a floor of 100 → burn 2.0 (floors
        # burn as target/value); the active-span denominator, not the
        # 2 s window, is what the rate divides by
        s.record_tokens(30)
        t[0] = 1.0
        s.record_tokens(20)
        s.record_request(error=False)
        s.record_request(error=False)
        s.record_request(error=False)
        s.record_request(error=True)          # 1/4 = 0.25 → burn 1.0
        rep = s.evaluate(force=True)
        good = rep["objectives"]["goodput_tok_s"]
        assert good["value_fast"] == pytest.approx(50.0)
        assert good["burn_rate_fast"] == pytest.approx(2.0)
        err = rep["objectives"]["error_rate"]
        assert err["value_fast"] == pytest.approx(0.25)
        assert err["burn_rate_fast"] == pytest.approx(1.0)
        assert err["verdict"] == "BREACH"

    def test_breach_recover_hysteresis(self):
        t = [0.0]
        s = _tracker({"ttft_s_p99": 0.1}, t)
        s.record_ttft(0.5)                    # burn 5.0
        rep = s.evaluate(force=True)
        assert rep["objectives"]["ttft_s_p99"]["verdict"] == "BREACH"
        assert rep["verdict"] == "BREACH"
        assert rep["breaches_total"] == 1
        edges = s.pop_transitions()
        assert [e["edge"] for e in edges] == ["breach"]
        assert edges[0]["objective"] == "ttft_s_p99"
        # still inside the fast window: the SAME excursion must not
        # count a second breach
        t[0] = 0.5
        rep = s.evaluate(force=True)
        assert rep["breaches_total"] == 1
        assert s.pop_transitions() == []
        # fast window clears (bad sample ages out), slow window still
        # carries it: BREACH exits through WARN, not straight to OK
        t[0] = 2.0
        s.record_ttft(0.01)
        rep = s.evaluate(force=True)
        o = rep["objectives"]["ttft_s_p99"]
        assert o["verdict"] == "WARN", o
        assert [e["edge"] for e in s.pop_transitions()] == ["recovered"]
        # slow window clears too → OK; breach count still 1
        t[0] = 15.0
        s.record_ttft(0.01)
        rep = s.evaluate(force=True)
        assert rep["objectives"]["ttft_s_p99"]["verdict"] == "OK"
        assert rep["breaches_total"] == 1

    def test_hysteresis_band_holds_the_alert(self):
        # once BREACH, a fast burn INSIDE (recover_burn, breach_burn)
        # must hold the alert instead of flapping
        t = [0.0]
        s = _tracker({"ttft_s_p99": 0.1}, t, warn_burn=0.75)
        s.record_ttft(0.5)
        assert s.evaluate(force=True)["verdict"] == "BREACH"
        t[0] = 2.0                       # bad sample out of fast window
        s.record_ttft(0.08)              # burn 0.8: in the band
        rep = s.evaluate(force=True)
        assert rep["objectives"]["ttft_s_p99"]["verdict"] == "BREACH"
        assert rep["breaches_total"] == 1        # held, not re-entered

    def test_goodput_rate_over_active_span_not_idle_window(self):
        """A window straddling pre-traffic idle (engine warmup, a
        quiet stretch before a burst) must not dilute real throughput
        into a phantom burn: the rate divides by the ACTIVE span —
        first in-window sample → now (regression: a fresh engine's
        slow-window goodput read ~0 and latched BREACH). A stall WITH
        samples still in the window decays the rate (the span keeps
        growing); a fully idle window is None/OK, not a breach."""
        t = [100.0]                           # long pre-traffic idle
        s = _tracker({"goodput_tok_s": 10.0}, t)
        s.record_tokens(10)
        t[0] = 100.5
        s.record_tokens(10)                   # 20 tok over 0.5 s span
        rep = s.evaluate(force=True)
        o = rep["objectives"]["goodput_tok_s"]
        assert o["value_fast"] == pytest.approx(40.0)
        assert o["value_slow"] == pytest.approx(40.0)
        assert o["verdict"] == "OK"
        # delivery stalls with the samples still in the slow window:
        # the active span stretches and the measured rate decays
        t[0] = 104.5
        o = s.evaluate(force=True)["objectives"]["goodput_tok_s"]
        assert o["value_slow"] == pytest.approx(20.0 / 4.5)
        assert o["burn_rate_slow"] == pytest.approx(10.0 / (20.0 / 4.5))
        # fully idle window: no evidence — None/OK, never a breach
        t[0] = 200.0
        o = s.evaluate(force=True)["objectives"]["goodput_tok_s"]
        assert o["value_fast"] is None and o["verdict"] == "OK"

    def test_evaluation_cache(self):
        t = [0.0]
        s = SloTracker({"ttft_s_p99": 0.1}, clock=lambda: t[0],
                       fast_window_s=1.0, slow_window_s=10.0,
                       eval_every_s=5.0)
        rep1 = s.evaluate()
        s.record_ttft(9.9)               # would breach if recomputed
        assert s.evaluate() is rep1      # cached within eval_every_s
        t[0] = 6.0
        assert s.evaluate() is not rep1  # cache expired
        assert s.evaluate(force=True)["breaches_total"] >= 0


class TestRollup:
    def test_worst_of_and_sums(self):
        a = {"verdict": "OK", "breaches_total": 1,
             "objectives": {"ttft_s_p99": {
                 "verdict": "OK", "burn_rate_fast": 0.2,
                 "burn_rate_slow": 0.1, "target": 1.0,
                 "kind": "ceiling"}}}
        b = {"verdict": "BREACH", "breaches_total": 2,
             "objectives": {"ttft_s_p99": {
                 "verdict": "BREACH", "burn_rate_fast": 3.0,
                 "burn_rate_slow": 1.5, "target": 1.0,
                 "kind": "ceiling"}}}
        agg = rollup([a, b, None])       # None = replica with slo off
        assert agg["verdict"] == "BREACH"
        assert agg["replicas_reporting"] == 2
        assert agg["breaches_total"] == 3
        o = agg["objectives"]["ttft_s_p99"]
        assert o["verdict"] == "BREACH"
        assert o["burn_rate_fast"] == 3.0
        assert o["burn_rate_slow"] == 1.5

    def test_empty_fleet_is_ok(self):
        agg = rollup([None, None])
        assert agg["verdict"] == "OK"
        assert agg["replicas_reporting"] == 0
        assert worst_verdict([]) == "OK"
        assert worst_verdict(["OK", "WARN"]) == "WARN"


class TestPrometheusBuckets:
    def test_histogram_bucket_counts_cumulative(self):
        m = MetricsRegistry()
        h = m.histogram("lat_s", buckets=[0.01, 0.1, 1.0])
        for v in (0.005, 0.05, 0.05, 0.5, 5.0):
            h.observe(v)
        assert h.buckets() == [(0.01, 1), (0.1, 3), (1.0, 4)]
        text = m.to_prometheus()
        assert "# TYPE paddle_tpu_lat_s summary" in text
        assert "# TYPE paddle_tpu_lat_s_hist histogram" in text
        assert 'paddle_tpu_lat_s_hist_bucket{le="0.1"} 3.0' in text
        # +Inf bucket equals the lifetime count
        assert 'paddle_tpu_lat_s_hist_bucket{le="+Inf"} 5.0' in text
        assert "paddle_tpu_lat_s_hist_count 5.0" in text
        # a bucketless histogram exports no histogram family
        m2 = MetricsRegistry()
        m2.histogram("plain").observe(1.0)
        assert "_hist" not in m2.to_prometheus()

    def test_engine_latency_histograms_carry_buckets(self, setup):
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=8, max_total_len=48,
            max_new_tokens=4)
        eng.generate([1, 2, 3], timeout=300)
        text = eng.metrics.to_prometheus()
        for fam in ("ttft_s", "itl_s", "queue_wait_s"):
            assert f"# TYPE paddle_tpu_{fam}_hist histogram" in text
            assert f'paddle_tpu_{fam}_hist_bucket{{le="+Inf"}}' in text
        eng.shutdown()

    def test_router_merged_hist_family_grouping(self, setup):
        """The merged exposition groups the native-histogram family's
        samples (both replicas') under exactly ONE TYPE line, with the
        replica label appended inside the existing le= braces."""
        cfg, params = setup
        r = serving.Router(params, cfg, replicas=2, max_batch=2,
                           block_size=8, max_total_len=48,
                           max_new_tokens=4)
        r.generate([1, 2, 3], timeout=300)
        lines = r.to_prometheus().splitlines()
        tl = [i for i, ln in enumerate(lines)
              if ln == "# TYPE paddle_tpu_ttft_s_hist histogram"]
        assert len(tl) == 1
        buckets = [ln for ln in lines
                   if ln.startswith("paddle_tpu_ttft_s_hist_bucket")]
        assert any(',replica="r0"}' in ln for ln in buckets)
        assert any(',replica="r1"}' in ln for ln in buckets)
        # every bucket sample sits in the contiguous block after the
        # family's one TYPE line (strict-parser grouping)
        start = tl[0]
        end = next((i for i in range(start + 1, len(lines))
                    if lines[i].startswith("# TYPE")), len(lines))
        in_block = [ln for ln in lines[start:end]
                    if ln.startswith("paddle_tpu_ttft_s_hist")]
        assert len(in_block) == len(
            [ln for ln in lines
             if ln.startswith("paddle_tpu_ttft_s_hist")])
        r.shutdown()


class TestEngineSlo:
    def test_breach_visible_in_health_prom_and_trace(self, setup,
                                                     tmp_path):
        """An impossible TTFT objective breaches on the first served
        request: health()["slo"] says BREACH, slo_breaches_total and
        the burn gauge land in the exposition, the sink carries an
        slo_breach span, and trace_report --slo shows the breach
        window WITH the request that rode it."""
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=8, max_total_len=48,
            max_new_tokens=4,
            slo_objectives={"ttft_s_p99": 1e-9},
            slo_opts={"eval_every_s": 0.0})
        eng.generate([1, 2, 3, 4], timeout=300)
        h = eng.health()
        assert h["slo"]["verdict"] == "BREACH"
        o = h["slo"]["objectives"]["ttft_s_p99"]
        assert o["burn_rate_fast"] > 1.0
        assert h["slo"]["breaches_total"] >= 1
        text = eng.metrics.to_prometheus()
        assert "paddle_tpu_slo_burn_rate_ttft_s_p99" in text
        bl = next(ln for ln in text.splitlines()
                  if ln.startswith("paddle_tpu_slo_breaches_total"))
        assert float(bl.split()[-1]) >= 1.0
        chrome = eng.trace.to_chrome_trace()
        breaches = [e for e in chrome["traceEvents"]
                    if e.get("name") == "slo_breach"]
        assert breaches and \
            breaches[0]["args"]["objective"] == "ttft_s_p99"
        path = tmp_path / "slo_trace.json"
        path.write_text(json.dumps(chrome))
        summary = tr.summarize(tr.load_events(str(path)))
        slo = summary["slo"]
        assert slo["breach_events"] >= 1
        assert slo["breach_windows"]
        w = slo["breach_windows"][0]
        assert w["objective"] == "ttft_s_p99"
        assert w["requests"], "no request attributed to the window"
        out = tr.render(summary, show_slo=True)
        assert "SLO breach windows" in out
        eng.shutdown()

    def test_injected_latency_breaches_itl_and_clears(self, setup):
        """Step hangs SHORT of the watchdog (latency degraded, replica
        alive) drive an itl_ms_p99 BREACH — the tracker watches the
        latency the engine serves — and the verdict clears once the
        fault heals and the windows forget the spike, with nothing
        compiled past warmup."""
        from paddle_tpu.serving.faults import FaultInjector
        cfg, params = setup
        inj = FaultInjector(seed=0)
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=8, max_total_len=48,
            max_new_tokens=8, chunk=2, fault_injector=inj,
            watchdog_s=30.0,
            slo_objectives={"itl_ms_p99": 150.0, "error_rate": 0.5},
            slo_opts={"fast_window_s": 1.0, "slow_window_s": 3.0,
                      "eval_every_s": 0.0}, start=False)
        eng.warmup()
        eng.start()
        eng.generate([5, 6, 7], timeout=300)
        warm = eng.batcher.compile_count
        c = inj.stats()["calls"]
        for k in range(1, 4):
            inj.hang_on_step(c + k, 0.6)
        req = eng.submit([1, 2, 3, 4])
        seen = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and seen is None:
            slo = eng.health()["slo"]
            if slo["verdict"] == "BREACH":
                seen = slo
            elif req.done:
                break
            time.sleep(0.02)
        req.result(300)
        assert seen is not None, "the hangs never drove a BREACH"
        assert seen["objectives"]["itl_ms_p99"]["verdict"] == "BREACH"
        assert seen["objectives"]["error_rate"]["verdict"] == "OK"
        assert eng.health()["status"] != "UNHEALTHY"   # degraded, alive
        inj.heal()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            eng.generate([9, 8, 7], max_new_tokens=2, timeout=300)
            if eng.health()["slo"]["verdict"] == "OK":
                break
            time.sleep(0.1)
        final = eng.health()["slo"]
        assert final["verdict"] == "OK"
        assert final["breaches_total"] >= 1
        assert eng.batcher.compile_count == warm
        eng.shutdown()

    def test_slo_off_is_none(self, setup):
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=1, block_size=8, max_total_len=48,
            max_new_tokens=2, slo=False, start=False)
        assert eng.health()["slo"] is None
        assert "slo_burn_rate" not in eng.metrics.to_prometheus()
        eng.shutdown()


class TestRouterRollup:
    def test_worst_of_rides_health_and_metrics(self, setup):
        """One replica with an impossible objective breaches; the
        router's health rollup reports the fleet worst-of and the
        merged exposition carries per-replica burn gauges plus the
        replica="router" rollup and summed breach counter."""
        cfg, params = setup
        r = serving.Router(
            params, cfg, replicas=2, max_batch=2, block_size=8,
            max_total_len=48, max_new_tokens=4,
            slo_opts={"eval_every_s": 0.0},
            per_replica=[{"slo_objectives": {"ttft_s_p99": 1e-9}},
                         None])
        # pin placement: serve through each replica at least once
        for _ in range(4):
            r.generate([9, 8, 7], timeout=300)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            h = r.health()
            if h["slo"]["verdict"] == "BREACH":
                break
            r.generate([9, 8, 7], timeout=300)
        assert h["slo"]["verdict"] == "BREACH"
        assert h["slo"]["replicas_reporting"] == 2
        assert h["slo"]["breaches_total"] >= 1
        assert h["replicas"]["r1"]["slo"]["verdict"] == "OK"
        prom = r.to_prometheus()
        assert ('paddle_tpu_slo_burn_rate_ttft_s_p99'
                '{replica="router"}') in prom
        rows = [ln for ln in prom.splitlines()
                if ln.startswith("paddle_tpu_slo_breaches_total")]
        by_label = {ln.split("{")[1].split("}")[0]: float(ln.split()[-1])
                    for ln in rows}
        assert by_label['replica="r0"'] >= 1.0
        assert by_label['replica="router"'] >= 1.0
        # over HTTP: a breach is detail, not outage (/health keeps its
        # 200), and /metrics serves the same merged exposition
        import http.client
        fe = serving.HttpFrontend(r, port=0, shutdown_router=False)
        host, port = fe.start()
        got = {}
        for path in ("/health", "/metrics"):
            conn = http.client.HTTPConnection(host, port, timeout=60)
            conn.request("GET", path)
            resp = conn.getresponse()
            got[path] = (resp.status, resp.read())
            conn.close()
        fe.shutdown(drain=True)
        assert got["/health"][0] == 200
        body = json.loads(got["/health"][1])
        assert body["slo"]["verdict"] == "BREACH"
        assert "ttft_s_p99" in body["slo"]["objectives"]
        assert 'paddle_tpu_slo_breaches_total{replica="router"}' \
            in got["/metrics"][1].decode()
        r.shutdown()


class TestRollupBreachAccounting:
    def test_counter_survives_replica_respawn(self, setup):
        """The fleet breach counter accumulates per-incarnation deltas
        keyed by engine identity: a respawned replica's fresh tracker
        restarting at 0 must neither decrement the counter nor swallow
        the NEXT real breaches behind the old global sum (review
        regression: the global high-water diff lost them)."""
        cfg, params = setup
        r = serving.Router(params, cfg, replicas=1, max_batch=1,
                           block_size=8, max_total_len=48,
                           max_new_tokens=2, start=False)
        real = r.engines

        class _Inc:       # identity stand-in for an engine incarnation
            pass
        e1, e2 = _Inc(), _Inc()

        def per(total):
            return [{"replica_id": "r0",
                     "slo": {"verdict": "OK", "objectives": {},
                             "breaches_total": total}}]
        r.engines = [e1]
        r._slo_rollup(per(5))
        assert r._c_slo_breaches.value == 5
        r._slo_rollup(per(5))                 # no new breaches
        assert r._c_slo_breaches.value == 5
        r.engines = [e2]                      # respawn: counter resets
        r._slo_rollup(per(0))
        assert r._c_slo_breaches.value == 5   # never decrements
        r._slo_rollup(per(3))                 # 3 REAL new breaches
        assert r._c_slo_breaches.value == 8   # old code: stuck at 5
        r.engines = real
        r.shutdown()


class _StubRouter:
    """Just enough router surface for frontend endpoint tests: the
    operator endpoints only call reset_breaker / capture_profile /
    health."""

    def __init__(self):
        self.resets = []

    def health(self):
        return {"status": "HEALTHY", "serving_replicas": 1,
                "slo": {"verdict": "OK"}}

    def to_prometheus(self):
        return "# TYPE x gauge\nx 1.0\n"

    def reset_breaker(self, slot):
        self.resets.append(slot)
        if slot in (9, "r9"):
            raise LookupError(f"unknown replica {slot!r}")
        if slot == "nosup":
            raise RuntimeError("reset_breaker needs auto_restart=True")
        if slot in (1, "r1"):
            return {"slot": 1, "replica": "r1", "reset": True,
                    "state": "RESTARTING"}
        return {"slot": 0, "replica": "r0", "reset": False,
                "state": "SERVING"}

    def capture_profile(self, steps=8, timeout=30.0):
        return {"r0": {"sample_every": 64, "ticks": 0, "samples": 0,
                       "shapes": [],
                       "capture": {"steps_requested": steps,
                                   "steps_captured": 0,
                                   "complete": False, "steps": []}}}

    def shutdown(self, drain=True, timeout=None):
        return True


def _post(host, port, path, payload):
    import http.client
    conn = http.client.HTTPConnection(host, port, timeout=30)
    try:
        conn.request("POST", path, json.dumps(payload),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


class TestFrontendOperatorEndpoints:
    @pytest.fixture()
    def fe(self):
        stub = _StubRouter()
        fe = serving.HttpFrontend(stub, port=0, shutdown_router=False)
        host, port = fe.start()
        yield stub, host, port
        fe.shutdown(drain=False)

    def test_reset_breaker_matrix(self, fe):
        stub, host, port = fe
        status, body = _post(host, port, "/admin/reset_breaker",
                             {"slot": 1})
        assert status == 200 and body["ok"] is True
        assert body["state"] == "RESTARTING"
        status, body = _post(host, port, "/admin/reset_breaker",
                             {"replica": "r0"})
        assert status == 409 and body["ok"] is False
        status, body = _post(host, port, "/admin/reset_breaker",
                             {"slot": 9})
        assert status == 404
        status, body = _post(host, port, "/admin/reset_breaker",
                             {"slot": "nosup"})
        assert status == 400
        status, body = _post(host, port, "/admin/reset_breaker", {})
        assert status == 400
        assert stub.resets == [1, "r0", 9, "nosup"]

    def test_profile_endpoint(self, fe):
        stub, host, port = fe
        status, body = _post(host, port, "/debug/profile",
                             {"steps": 2, "timeout_s": 0.1})
        assert status == 200
        assert body["r0"]["capture"]["steps_requested"] == 2
        status, _ = _post(host, port, "/debug/profile", {"steps": 0})
        assert status == 400
        # unbounded windows are refused: a billion-step capture would
        # fence every device call fleet-wide and pin an executor thread
        status, _ = _post(host, port, "/debug/profile",
                          {"steps": 10 ** 9})
        assert status == 400
        status, _ = _post(host, port, "/debug/profile",
                          {"steps": 2, "timeout_s": 1e9})
        assert status == 400
