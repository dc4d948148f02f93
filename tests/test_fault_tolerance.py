"""Fault-isolated serving: poisoned-step quarantine, retry with warm
re-prefill, the hung-step watchdog, and the deterministic chaos harness.

The PR's acceptance matrix:

  * poison one rid in a fused batch → the culprit alone reaches FAILED,
    every innocent finishes with BIT-identical tokens to a fault-free
    run (no re-emitted or lost streamed tokens) and zero post-warmup
    recompiles (quarantine re-execution stays on the warmed ladder);
  * a transient fault → the retry succeeds with `retries == 1` and
    token parity; an exhausted retry budget → terminal FAILED with a
    `retried` trace event trail;
  * an injected hang trips the watchdog within the configured deadline,
    `health()` reports UNHEALTHY, the flight dump names the hung tick,
    and `shutdown(drain=False)` returns instead of blocking;
  * chaos under deadline/cancel races leaks no slots or blocks
    (allocator stats clean after drain).
"""
import threading
import time

import numpy as np
import pytest
import jax

from paddle_tpu.nlp import llama
from paddle_tpu import serving
from paddle_tpu.serving import AdmissionQueue, RequestState, TraceSink
from paddle_tpu.serving.faults import FaultInjector, InjectedFault


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


_RNG = np.random.RandomState(11)
PROMPTS = [list(map(int, _RNG.randint(1, 200, L))) for L in (5, 7, 6, 9)]
BUDGETS = [8, 5, 7, 6]


def _kinds(tl):
    return [e["kind"] for e in tl["events"]]


# ---- injector units (no engine, no device) -----------------------------
class TestFaultInjector:
    def test_fail_on_step_fires_once_at_exact_call(self):
        inj = FaultInjector().fail_on_step(2)
        inj.check("decode", [0])                       # call 1: clean
        with pytest.raises(InjectedFault):
            inj.check("decode", [0])                   # call 2: fires
        inj.check("decode", [0])                       # consumed
        assert inj.stats()["injected"] == {"error": 1}

    def test_fail_on_rid_matches_probes_but_step_rules_do_not(self):
        inj = FaultInjector().fail_on_rid(7).fail_on_step(1, times=5)
        with pytest.raises(InjectedFault):
            inj.check("probe", [7], probe=True)        # rid rule fires
        inj.check("probe", [3], probe=True)            # other rid clean
        assert inj.stats()["calls"] == 0               # probes don't count
        with pytest.raises(InjectedFault):
            inj.check("decode", [3])                   # step rule, call 1

    def test_after_step_delays_rid_poison(self):
        inj = FaultInjector().fail_on_rid(1, after_step=2)
        inj.check("decode", [1])                       # call 1 <= 2
        inj.check("decode", [1])                       # call 2 <= 2
        with pytest.raises(InjectedFault):
            inj.check("decode", [1])                   # call 3 fires

    def test_exhaust_is_transient_resource_exhausted(self):
        inj = FaultInjector().exhaust_on_step(1)
        with pytest.raises(InjectedFault) as ei:
            inj.check("prefill", [0])
        assert ei.value.transient is True
        assert "RESOURCE_EXHAUSTED" in str(ei.value)
        assert ei.value.kind == "oom"

    def test_fail_rate_is_seed_deterministic(self):
        def pattern(seed):
            inj = FaultInjector(seed=seed).fail_rate(0.4, times=None)
            out = []
            for _ in range(32):
                try:
                    inj.check("decode", [0])
                    out.append(0)
                except InjectedFault:
                    out.append(1)
            return out

        assert pattern(3) == pattern(3)
        assert pattern(3) != pattern(4)
        assert sum(pattern(3)) > 0

    def test_hang_sleeps_and_heal_disarms(self):
        inj = FaultInjector().hang_on_step(1, seconds=0.05)
        t0 = time.perf_counter()
        inj.check("decode", [0])
        assert time.perf_counter() - t0 >= 0.05
        inj.fail_on_rid(9).heal()
        inj.check("decode", [9])                       # healed: clean
        assert inj.stats()["armed_rules"] == 0


# ---- scheduler: front-of-queue requeue ---------------------------------
class TestAdmissionRequeue:
    def test_requeue_beats_every_priority_and_keeps_order(self):
        q = AdmissionQueue(max_depth=8, aging_interval_s=0)
        q.push("low", priority=5)
        q.push("high", priority=0)
        q.requeue(["v1", "v2"])
        assert [q.pop() for _ in range(4)] == ["v1", "v2", "high", "low"]

    def test_requeue_bypasses_max_depth(self):
        q = AdmissionQueue(max_depth=1)
        q.push("a")
        q.requeue(["v"])                # full queue must not bounce it
        assert len(q) == 2
        assert q.pop() == "v"

    def test_later_requeue_batch_goes_in_front(self):
        q = AdmissionQueue(max_depth=8)
        q.requeue(["r1"])
        q.requeue(["r2a", "r2b"])
        assert [q.pop() for _ in range(3)] == ["r2a", "r2b", "r1"]


# ---- quarantine: the acceptance parity gate ----------------------------
class TestQuarantine:
    def _engine(self, setup, inj=None, **kw):
        cfg, params = setup
        # one-bucket ladder keeps warmup() cheap (longer resume
        # prompts chunk through it — more path coverage, not less)
        return serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=64,
            max_new_tokens=16, chunk=2, prefill_buckets=(8,),
            start=False, fault_injector=inj, **kw)

    def _serve_all(self, eng, culprit_idx=None, inj=None):
        """Warmed engine lifecycle over PROMPTS/BUDGETS; arms a
        persistent fail-on-rid poison at the culprit's FIRST streamed
        token when asked. Returns (requests, post-warmup recompiles)."""
        eng.warmup()
        eng.start()
        eng.generate(PROMPTS[0], timeout=300)
        warm = eng.batcher.compile_count
        armed = threading.Event()

        def arm(tok):
            if not armed.is_set():
                armed.set()
                inj.fail_on_rid(culprit_req.request_id)

        # pre-built handle: the engine-thread callback must never race
        # the submit loop's list append
        culprit_req = None if culprit_idx is None else \
            serving.GenerationRequest(PROMPTS[culprit_idx],
                                      max_new_tokens=BUDGETS[culprit_idx],
                                      on_token=arm)
        reqs = []
        for i, (p, mn) in enumerate(zip(PROMPTS, BUDGETS)):
            reqs.append(eng.submit(culprit_req) if i == culprit_idx
                        else eng.submit(p, max_new_tokens=mn))
        assert eng.drain(timeout=300)
        return reqs, eng.batcher.compile_count - warm

    def test_poisoned_rid_in_fused_batch_isolates_culprit(self, setup):
        """The headline gate: a mid-stream poison on one request kills
        only that request; innocents are requeued, resume from
        prompt + streamed tokens and finish BIT-identical to the
        fault-free run — with zero post-warmup recompiles and a clean
        pool."""
        eng0 = self._engine(setup)
        base, _ = self._serve_all(eng0)
        base_toks = [r.result(timeout=5) for r in base]
        eng0.shutdown()

        inj = FaultInjector(seed=0)
        eng = self._engine(setup, inj)
        reqs, recompiles = self._serve_all(eng, culprit_idx=1, inj=inj)
        # the culprit alone reaches FAILED, mid-stream (it streamed)
        assert [r.state for r in reqs].count(RequestState.FAILED) == 1
        culprit = reqs[1]
        assert culprit.state is RequestState.FAILED
        assert culprit.finish_reason == "quarantine_culprit"
        with pytest.raises(serving.RequestFailed):
            culprit.result(timeout=5)
        # streamed tokens were neither lost nor re-emitted: a strict
        # non-empty prefix of the fault-free output
        assert culprit.tokens
        assert culprit.tokens == base_toks[1][:len(culprit.tokens)]
        # innocents: bit-identical token parity with the clean run
        for i in (0, 2, 3):
            assert reqs[i].state is RequestState.FINISHED
            assert reqs[i].result(timeout=5) == base_toks[i], \
                f"innocent {i} lost token parity"
        # quarantine re-execution stayed on the warmed ladder
        assert recompiles == 0
        assert eng.batcher.alloc.stats()["blocks_in_use"] == 0
        h = eng.health()
        assert h["status"] == "DEGRADED"
        # slot-in-place recovery: the failed call committed nothing, so
        # innocents keep their KV via export/import ("restored") instead
        # of requeueing through a full re-prefill
        assert h["quarantines"] >= 1 and h["requests_restored"] >= 1
        # ... and the injector's gate stands before dispatch, so the
        # donated pool never left the batcher's hands
        assert eng.batcher.pool_rebuilds == 0
        restored = [r for i, r in enumerate(reqs) if i != 1
                    and "restored" in _kinds(eng.trace.timeline(r.trace_id))]
        assert restored, "no innocent timeline recorded its restore"
        tl = eng.trace.timeline(culprit.trace_id)
        assert _kinds(tl)[-1] == "failed"
        assert "injected fault" in tl["events"][-1]["attrs"]["error"]
        eng.shutdown()

    def test_transient_fault_retries_once_and_succeeds(self, setup):
        """fail-once-then-heal: no probe reproduces the failure, the
        lone suspect is charged one backoff retry and completes with
        token parity and retries == 1."""
        eng0 = self._engine(setup).start()
        base = eng0.generate(PROMPTS[0], timeout=300)
        eng0.shutdown()

        # call 3: the decode tick after warmup prefill+decode of the
        # single request — a mid-stream transient
        inj = FaultInjector().fail_on_step(3, transient=True)
        eng = self._engine(setup, inj, retry_backoff_s=0.01)
        r = eng.submit(PROMPTS[0])
        eng.start()
        assert r.result(timeout=300) == base
        assert r.retries == 1
        tl = eng.trace.timeline(r.trace_id)
        assert "retried" in _kinds(tl)
        assert eng.metrics.counter("requests_retried").value == 1
        assert eng.health()["status"] == "DEGRADED"
        eng.shutdown()

    def test_retry_budget_exhausted_fails_terminally(self, setup):
        """A persistently-poisoned request burns its whole retry budget
        (trace shows each retry) and then FAILS with a terminal event —
        it never livelocks the engine."""
        inj = FaultInjector()
        eng = self._engine(setup, inj, max_retries=2,
                           retry_backoff_s=0.01)
        armed = set()

        def arm(tok):
            # re-arm on every re-admission: the rid changes, the
            # request-level poison must follow it
            rid = r.request_id
            if rid not in armed:
                armed.add(rid)
                inj.fail_on_rid(rid, transient=True)

        r = eng.submit(PROMPTS[0], on_token=arm)
        eng.start()
        with pytest.raises(serving.RequestFailed):
            r.result(timeout=300)
        assert r.retries == 2
        assert r.finish_reason == "retries_exhausted"
        tl = eng.trace.timeline(r.trace_id)
        assert _kinds(tl).count("retried") == 2
        assert _kinds(tl)[-1] == "failed"
        # the engine itself stays serviceable for other traffic
        inj.heal()
        assert eng.generate(PROMPTS[2], timeout=300)
        assert eng.batcher.alloc.stats()["blocks_in_use"] == 0
        eng.shutdown()

    def test_resource_exhausted_is_retried_by_default(self, setup):
        """RESOURCE_EXHAUSTED-style allocator pressure is transient by
        default: the suspects recover instead of failing."""
        inj = FaultInjector().exhaust_on_step(3)
        eng = self._engine(setup, inj, retry_backoff_s=0.01)
        r = eng.submit(PROMPTS[0])
        eng.start()
        assert r.result(timeout=300)
        assert r.retries == 1
        eng.shutdown()

    def test_quarantine_off_restores_fail_all(self, setup):
        """The escape hatch: quarantine=False reverts to the PR 7
        boundary — every in-flight request fails on a step fault."""
        inj = FaultInjector().fail_on_step(3)
        eng = self._engine(setup, inj, quarantine=False)
        r1 = eng.submit(PROMPTS[0], max_new_tokens=8)
        r2 = eng.submit(PROMPTS[1], max_new_tokens=8)
        eng.start()
        for r in (r1, r2):
            with pytest.raises(serving.RequestFailed):
                r.result(timeout=300)
        assert eng.last_flight_dump is not None
        eng.shutdown()


# ---- the donated pool under faults --------------------------------------
class _FailsAtTheWait:
    """Stands in for a step program's tokens: the read-back raises, as a
    device failure does once the call was dispatched."""

    def __array__(self, *a, **k):
        raise InjectedFault("device failure at the wait", transient=True)


class TestDonatedPoolFaults:
    """Every step program donates its pool (`ContinuousBatcher._step_jit`):
    what a fault before dispatch, a probe and a fault after dispatch
    each leave in the batcher's hands."""

    def _batcher(self, setup, **kw):
        from paddle_tpu.nlp.paged import ContinuousBatcher
        cfg, params = setup
        return ContinuousBatcher(
            params, cfg, max_batch=2, block_size=4, max_total_len=64,
            max_new_tokens=10, chunk=2, prefill_buckets=(8,),
            prefix_cache=True, **kw)

    def test_injected_fault_leaves_the_pool_live(self, setup):
        """(a) The injector's gate is in `_Tick.__enter__`, before
        dispatch: the failed tick donated nothing, the handle taken
        before it is the batcher's still, and an innocent's KV exports
        and imports as `_quarantine` does it, decoding on to the tokens
        of an unfaulted run."""
        base = self._batcher(setup)
        rb = base.submit(PROMPTS[0])
        want = base.run()[rb]

        inj = FaultInjector()
        cb = self._batcher(setup, fault_injector=inj)
        rid = cb.submit(PROMPTS[0])
        cb.step()
        cb.step()
        k0 = cb.cache.k
        inj.fail_on_step(inj.stats()["calls"] + 1)
        with pytest.raises(InjectedFault):
            cb.step()
        assert cb.cache.k is k0 and not k0.is_deleted()
        assert cb.pool_rebuilds == 0 and len(cb._pcache) > 0
        got = list(cb.outputs[rid])
        snap = cb.export_kv(rid)
        cb.abort(rid)
        rid2 = cb.import_kv(snap)
        assert cb.run()[rid2] == want and got == want[:len(got)]
        assert cb.alloc.stats()["blocks_in_use"] == 0

    def test_probes_keep_the_pool_they_ran_on(self, setup):
        """(b) `probe_decode_slot` and `probe_queued` run a step program
        and commit nothing BUT the pool it returns: the pool they probed
        was donated to the run. The next ticks run on it and give the
        tokens of a run nobody probed."""
        base = self._batcher(setup)
        rids = [base.submit(p) for p in PROMPTS[:3]]
        out = base.run()
        want = [out[r] for r in rids]

        cb = self._batcher(setup)
        r0, r1 = cb.submit(PROMPTS[0]), cb.submit(PROMPTS[1])
        cb.step()
        cb.step()
        for slot in (0, 1):
            k0, lengths = cb.cache.k, np.array(cb.cache.lengths)
            cb.probe_decode_slot(slot)
            assert k0.is_deleted() and not cb.cache.k.is_deleted()
            assert (np.array(cb.cache.lengths) == lengths).all()
        r2 = cb.submit(PROMPTS[2])              # queued: both slots busy
        free, indexed = cb.alloc.free_blocks, len(cb._pcache)
        k0 = cb.cache.k
        cb.probe_queued(r2)
        assert k0.is_deleted() and not cb.cache.k.is_deleted()
        assert (cb.alloc.free_blocks, len(cb._pcache)) == (free, indexed)
        assert [e[0] for e in cb.queue] == [r2]
        out = cb.run()
        assert [out[r] for r in (r0, r1, r2)] == want
        assert cb.pool_rebuilds == 0

    @pytest.mark.parametrize("mode", ["decode", "fused"])
    def test_failure_after_dispatch_rebuilds_the_pool(self, setup, mode):
        """(c) A failure the device raises at the WAIT leaves a pool
        that is the failed program's result (its argument was donated):
        the batcher rebuilds it empty with the prefix index, drops every
        slot, and the engine re-admits every live request through its
        queue; their tokens are an unfaulted run's."""
        def engine():
            cfg, params = setup
            return serving.ServingEngine(
                params, cfg, max_batch=2, block_size=4, max_total_len=64,
                max_new_tokens=16, chunk=2, prefill_buckets=(8,),
                start=False, retry_backoff_s=0.01)

        def serve(eng, first_token=None):
            eng.warmup()
            eng.start()
            eng.generate(PROMPTS[0], timeout=300)   # fills the index
            reqs = [eng.submit(PROMPTS[0], max_new_tokens=BUDGETS[0],
                               on_token=first_token)]
            reqs += [eng.submit(p, max_new_tokens=mn)
                     for p, mn in zip(PROMPTS[1:], BUDGETS[1:])]
            assert eng.drain(timeout=300)
            return reqs

        eng0 = engine()
        want = [r.result(timeout=5) for r in serve(eng0)]
        eng0.shutdown()

        eng = engine()
        b = eng.batcher
        name = {"decode": "_chunk_exe", "fused": "_fused_exe"}[mode]
        getter, armed, after = getattr(b, name), [], []

        def faulty(*shape):
            exe = getter(*shape)

            def call(*args):
                out = list(exe(*args))
                if armed and armed.pop():
                    out[5] = _FailsAtTheWait()      # the tokens [B, chunk]
                return tuple(out)
            return call
        setattr(b, name, faulty)
        drop = b._drop_lost_pool

        def dropped(in_flight):
            rebuilds = b.pool_rebuilds
            drop(in_flight)
            if b.pool_rebuilds > rebuilds:
                after.append((len(b._pcache), any(b.active),
                              len(b._pending), b.alloc.stats(),
                              b.cache.k.is_deleted(),
                              float(np.abs(np.asarray(
                                  b.cache.k, np.float32)).max())))
        b._drop_lost_pool = dropped
        once = []

        def arm(tok):               # at the first streamed token, once
            if not once:
                once.append(tok)
                armed.append(True)
        reqs = serve(eng, first_token=arm)
        # ONE failure after dispatch, ONE rebuild: an empty live pool, an
        # empty index, no slot and no block held when it was done
        assert b.pool_rebuilds == 1 and len(after) == 1
        indexed, active, pending, st, deleted, top = after[0]
        assert (indexed, active, pending, deleted, top) == \
            (0, False, 0, False, 0.0)
        assert st["blocks_in_use"] == 0 and st["cached_blocks"] == 0
        # every live request went back through the queue (a suspect of
        # the failed tick as a retry, the others requeued; none restored
        # from the lost pool) and finished on an unfaulted run's tokens
        assert [r.state for r in reqs] == [RequestState.FINISHED] * 4
        assert [r.result(timeout=5) for r in reqs] == want
        h = eng.health()
        assert h["requests_restored"] == 0
        assert h["requests_requeued"] + sum(r.retries for r in reqs) >= 1
        assert b.alloc.stats()["blocks_in_use"] == 0
        eng.shutdown()


# ---- watchdog ----------------------------------------------------------
class TestWatchdog:
    def test_hung_step_trips_watchdog_and_shutdown_returns(self, setup):
        """The acceptance bar: an injected hang trips the watchdog
        within the deadline, health() goes UNHEALTHY, the flight dump
        names the hung tick's mode + units, every stranded request
        fails with a clear error, and shutdown(drain=False) returns
        instead of blocking forever."""
        cfg, params = setup
        inj = FaultInjector()
        # warmed + fusion off + one full served request before the
        # victim, so every serving-path executable has already RUN: a
        # first-call compile or cold-dispatch overrun would trip the
        # watchdog before the injected hang (the documented deploy
        # guidance: warm up before serving under a tight deadline)
        eng = serving.ServingEngine(
            params, cfg, max_batch=1, block_size=4, max_total_len=32,
            max_new_tokens=8, chunk=2, prefill_buckets=(8,),
            fused_prefill=False, watchdog_s=2.0,
            fault_injector=inj, start=False)
        eng.warmup()
        eng.start()
        assert eng.generate(PROMPTS[1], timeout=300)
        armed = threading.Event()

        def arm(tok):
            # first streamed token: hang this rid's NEXT device call —
            # a mid-stream decode tick, deterministically
            if not armed.is_set():
                armed.set()
                inj.hang_on_rid(r.request_id, seconds=8.0)

        # handle built before submission: the callback fires on the
        # engine thread and must not race this frame's assignment
        r = serving.GenerationRequest(PROMPTS[0], on_token=arm)
        eng.submit(r)
        deadline = time.monotonic() + 15.0
        while (eng.health()["status"] != "UNHEALTHY"
               and time.monotonic() < deadline):
            time.sleep(0.02)
        h = eng.health()
        assert h["status"] == "UNHEALTHY" and h["watchdog_trips"] == 1
        # the stranded request's handle unblocked with a clear error
        assert r.state is RequestState.FAILED
        assert r.finish_reason == "watchdog_hung_step"
        with pytest.raises(serving.RequestFailed) as ei:
            r.result(timeout=5)
        assert "watchdog" in repr(ei.value.request.error)
        # the dump names the hung tick (recorded BEFORE its device call)
        dump = eng.last_flight_dump
        assert "watchdog" in dump["error"]
        assert dump["failing_record"]["mode"] == "decode"
        assert dump["failing_record"]["rids"] == [r.request_id]
        # drain and shutdown return promptly (engine thread still
        # asleep inside the injected hang)
        assert eng.drain(timeout=1.0)
        t0 = time.monotonic()
        eng.shutdown(drain=False)
        assert time.monotonic() - t0 < 2.0
        # post-shutdown: submissions are refused, not queued forever
        with pytest.raises(serving.EngineStopped):
            eng.submit(PROMPTS[1])

    def test_healthy_run_never_trips(self, setup):
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=1, block_size=4, max_total_len=32,
            max_new_tokens=4, chunk=2, watchdog_s=30.0)
        assert eng.generate(PROMPTS[0], timeout=300)
        h = eng.health()
        assert h["status"] == "HEALTHY" and h["watchdog_trips"] == 0
        assert eng.shutdown() is True

    def test_first_step_grace_covers_unwarmed_compile(self, setup):
        """Arming watchdog_s WITHOUT a prior warmup() used to let the
        first step's trace+compile masquerade as a hung device call.
        The first-step grace multiplier covers exactly that window:
        a deadline far below any compile time still serves, no trips."""
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=1, block_size=4, max_total_len=32,
            max_new_tokens=4, chunk=2, prefill_buckets=(8,),
            fused_prefill=False, watchdog_s=0.05,
            watchdog_compile_grace=2400.0)      # 0.05s * grace = 120s
        assert eng.generate(PROMPTS[0], timeout=300)
        h = eng.health()
        assert h["status"] == "HEALTHY" and h["watchdog_trips"] == 0
        # a WARMED engine gets no grace at all: a genuinely hung step
        # trips at the plain deadline even with a huge grace factor
        inj_late = FaultInjector()
        eng2 = serving.ServingEngine(
            params, cfg, max_batch=1, block_size=4, max_total_len=32,
            max_new_tokens=8, chunk=2, prefill_buckets=(8,),
            fused_prefill=False, watchdog_s=2.0,
            watchdog_compile_grace=2400.0, fault_injector=inj_late,
            start=False)
        eng2.warmup()     # warmed: the grace is OFF from step one
        eng2.start()
        assert eng2.generate(PROMPTS[1], timeout=300)
        armed = threading.Event()

        def arm(tok):
            if not armed.is_set():
                armed.set()
                inj_late.hang_on_rid(r2.request_id, seconds=30.0)

        r2 = serving.GenerationRequest(PROMPTS[0], on_token=arm)
        eng2.submit(r2)
        deadline = time.monotonic() + 20.0
        while (eng2.health()["status"] != "UNHEALTHY"
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert eng2.health()["watchdog_trips"] == 1
        eng.shutdown()
        eng2.shutdown(drain=False)

    def test_no_grace_trips_on_unwarmed_first_step(self, setup):
        """The regression half: grace forced to 1.0 on an UNWARMED
        engine with a deadline below compile time reproduces the old
        misfire — proving the grace multiplier (not luck) is what
        keeps test_first_step_grace_covers_unwarmed_compile green."""
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=1, block_size=4, max_total_len=32,
            max_new_tokens=4, chunk=2, prefill_buckets=(8,),
            fused_prefill=False, watchdog_s=0.05,
            watchdog_compile_grace=1.0)
        r = eng.submit(PROMPTS[0])
        with pytest.raises(serving.RequestFailed) as ei:
            r.result(timeout=300)
        assert "watchdog" in repr(ei.value.request.error)
        assert eng.health()["status"] == "UNHEALTHY"
        assert eng.health()["watchdog_trips"] == 1
        eng.shutdown(drain=False)


# ---- chaos under races: no leaks ---------------------------------------
class TestChaosRaces:
    def test_chaos_with_cancel_and_deadline_races_leaks_nothing(
            self, setup):
        """Seeded background fault noise + deadline expiries + a
        mid-flight cancel: every request reaches a terminal state, the
        allocator drains clean, and the engine still serves afterwards."""
        cfg, params = setup
        inj = FaultInjector(seed=5).fail_rate(0.25, times=6,
                                              transient=True)
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=64,
            max_new_tokens=16, chunk=2, prefill_buckets=(8,),
            retry_backoff_s=0.01, max_retries=3, start=False,
            fault_injector=inj)
        eng.warmup()
        eng.start()
        reqs = []
        for i, (p, mn) in enumerate(zip(PROMPTS * 2, BUDGETS * 2)):
            kw = {"max_new_tokens": mn}
            if i % 4 == 3:
                kw["timeout_s"] = 0.05        # doomed to expire
            reqs.append(eng.submit(p, **kw))
        reqs[1].cancel()
        assert eng.drain(timeout=300)
        for r in reqs:
            assert r.done, f"request {r} never reached a terminal state"
        assert eng.batcher.alloc.stats()["blocks_in_use"] == 0
        assert not eng.batcher._pending and not eng.batcher.queue
        # heal and serve: the pool and slots survived the churn
        inj.heal()
        assert eng.generate(PROMPTS[0], timeout=300)
        assert eng.batcher.alloc.stats()["blocks_in_use"] == 0
        eng.shutdown()


# ---- satellites --------------------------------------------------------
class TestSatellites:
    def test_flight_dump_write_failure_is_counted(self, setup, tmp_path):
        """Satellite bugfix: a failed flight-dump disk write is counted
        in flight_dump_errors and surfaced in snapshot(), instead of
        vanishing in a silent except."""
        cfg, params = setup
        inj = FaultInjector().fail_on_step(3)
        eng = serving.ServingEngine(
            params, cfg, max_batch=1, block_size=4, max_total_len=32,
            max_new_tokens=8, chunk=2, fault_injector=inj,
            flight_dump_path=str(tmp_path))     # a DIRECTORY: open fails
        r = eng.submit(PROMPTS[0])
        with pytest.raises(serving.RequestFailed):
            r.result(timeout=300)
        snap = eng.snapshot()
        assert snap["counters"]["flight_dump_errors"] == 1
        assert snap["last_flight_dump_error"] is not None
        assert eng.health()["flight_dump_errors"] == 1
        # the in-memory dump still landed (the write failure never
        # masks the forensics themselves)
        assert eng.last_flight_dump_json is not None
        eng.shutdown()

    def test_requeue_poisoned_cascade_is_traced(self, setup):
        """Satellite: the `_requeue_poisoned` cascade (aborting a
        pending admission rolls back siblings that leaned on its
        blocks) emits `requeued` trace events, so the timeline explains
        the second `prepared` instead of showing silent churn."""
        cfg, params = setup
        from paddle_tpu.nlp.paged import ContinuousBatcher
        sink = TraceSink()
        cb = ContinuousBatcher(
            params, cfg, max_batch=4, block_size=4, max_total_len=64,
            max_new_tokens=8, chunk=3, prefix_cache=True,
            prefill_buckets=(4,), fused_prefill=True, trace=sink)
        w = PROMPTS[0]
        long_p = list(map(int, _RNG.randint(1, 200, 20)))
        shared = list(map(int, _RNG.randint(1, 200, 8)))
        cb.submit(w)
        cb.step()                         # w decoding
        cb.submit(long_p)                 # chunked pending head
        ra = cb.submit(shared + [3, 5])
        rb = cb.submit(shared + [7, 11])
        cb.step()                         # a + b pending behind long_p
        assert cb.abort(ra) is True
        tl = sink.timeline(rb)
        assert tl is not None
        ev = next(e for e in tl["events"] if e["kind"] == "requeued")
        assert ev["attrs"]["reason"] == "poisoned_sibling"
        cb.run()
        assert cb.alloc.stats()["blocks_in_use"] == 0

    def test_trace_report_counts_requeues(self, tmp_path):
        """Satellite: tools/trace_report.py reports the requeued phase
        (per-request counts + totals) from an exported artifact."""
        import json
        import sys
        sys.path.insert(0, "tools")
        try:
            import trace_report
        finally:
            sys.path.pop(0)
        sink = TraceSink()
        t = sink.start()
        sink.emit(t, "enqueued", prompt_len=4)
        sink.emit(t, "admitted", rid=0)
        sink.emit(t, "requeued", reason="quarantine_victim")
        sink.emit(t, "retried", retries=1, backoff_s=0.05)
        sink.emit(t, "admitted", rid=1, resumed=True)
        sink.finish(t, "finished", reason="length")
        path = tmp_path / "trace.json"
        path.write_text(json.dumps(sink.to_chrome_trace()))
        summary = trace_report.summarize(trace_report.load_events(
            str(path)))
        assert summary["total"]["requeued_events"] == 1
        assert summary["total"]["retried_events"] == 1
        row = summary["requests"][0]
        assert row["requeues"] == 1 and row["retries"] == 1
        assert "requeues" in trace_report.render(summary)

    def test_prometheus_exports_fault_counters(self, setup):
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=1, block_size=4, max_total_len=16,
            max_new_tokens=2, chunk=2, start=False)
        text = eng.metrics.to_prometheus()
        for name in ("step_faults", "quarantines", "requests_requeued",
                     "requests_retried", "watchdog_trips",
                     "flight_dump_errors"):
            assert f"paddle_tpu_{name}_total 0.0" in text
        eng.shutdown()
