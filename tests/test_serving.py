"""paddle_tpu.serving — the async request-serving engine over the
paged-KV continuous batcher.

Deterministic CPU coverage: concurrent requests through ServingEngine
match sequential `paged_generate` token-for-token (greedy), priority
ordering, queue-full backpressure, deadline timeout, mid-decode
cancellation returning KV blocks, per-request stop tokens, and the
step-level exception boundary (one request's callback raises → the
others complete and the engine stays alive).
"""
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.nlp import llama, paged
from paddle_tpu import serving
from paddle_tpu.serving import AdmissionQueue, QueueFullError, \
    MetricsRegistry, RequestState


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


_RNG = np.random.RandomState(42)
PROMPT_A = list(map(int, _RNG.randint(1, 200, 5)))
PROMPT_B = list(map(int, _RNG.randint(1, 200, 7)))
PROMPT_A2 = list(map(int, _RNG.randint(1, 200, 5)))
PROMPT_B2 = list(map(int, _RNG.randint(1, 200, 7)))
MAX_NEW = 6


def _paged_single(params, cfg, prompt, max_new=MAX_NEW):
    """The sequential baseline: one request through paged_generate."""
    out, _, _ = paged.paged_generate(
        params, jnp.asarray([prompt], jnp.int32),
        np.asarray([len(prompt)]), cfg, max_new_tokens=max_new,
        block_size=4)
    return [int(t) for t in np.asarray(out[0])]


@pytest.fixture(scope="module")
def baselines(setup):
    cfg, params = setup
    return {name: _paged_single(params, cfg, p) for name, p in [
        ("A", PROMPT_A), ("B", PROMPT_B),
        ("A2", PROMPT_A2), ("B2", PROMPT_B2)]}


@pytest.fixture(scope="module")
def engine(setup):
    """Shared long-lived engine (stop-token / cancellation / fault tests
    assert deltas or per-request outcomes, never absolute counters)."""
    cfg, params = setup
    eng = serving.ServingEngine(
        params, cfg, max_batch=2, block_size=4, max_total_len=32,
        max_new_tokens=20, chunk=3, max_queue_depth=16)
    yield eng
    eng.shutdown()


class TestServingEngineE2E:
    def test_concurrent_mixed_priorities_match_sequential(
            self, setup, baselines):
        """Acceptance: N=6 submissions (4 served at mixed priorities +
        one cancellation + one deadline timeout) through one engine;
        served outputs are token-identical to sequential paged_generate,
        metrics are consistent, and the pool drains back to zero."""
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=MAX_NEW, chunk=3, max_queue_depth=16,
            start=False)
        r_lo = eng.submit(PROMPT_A, priority=2)
        r_hi = eng.submit(PROMPT_B, priority=0)
        r_mid = eng.submit(PROMPT_A2, priority=1)
        # greedy decode ⇒ a shorter budget is a strict prefix of the
        # longer run, so the per-request max_new needs no new baseline
        r_short = eng.submit(PROMPT_B2, priority=2, max_new_tokens=4)
        r_timeout = eng.submit(PROMPT_A, timeout_s=0.0)
        r_cancel = eng.submit(PROMPT_B)
        r_cancel.cancel()

        eng.start()
        eng.shutdown(drain=True, timeout=300)   # graceful drain

        assert r_lo.result() == baselines["A"]
        assert r_hi.result() == baselines["B"]
        assert r_mid.result() == baselines["A2"]
        assert r_short.result() == baselines["B2"][:4]
        assert r_timeout.state is RequestState.TIMED_OUT
        assert r_cancel.state is RequestState.CANCELLED
        with pytest.raises(serving.RequestTimedOut):
            r_timeout.result()
        with pytest.raises(serving.RequestCancelled):
            r_cancel.result()

        snap = eng.snapshot()
        c = snap["counters"]
        assert c["requests_submitted"] == 6
        assert c["requests_admitted"] == 4
        assert c["requests_completed"] == 4
        assert c["requests_cancelled"] == 1
        assert c["requests_timed_out"] == 1
        assert c["requests_rejected"] == 0
        assert (c["requests_completed"] + c["requests_cancelled"]
                + c["requests_timed_out"]) == c["requests_submitted"]
        assert c["tokens_generated"] == 3 * MAX_NEW + 4
        # latency surfaces populated
        assert snap["histograms"]["ttft_s"]["count"] == 4
        assert snap["histograms"]["queue_wait_s"]["count"] == 4
        # drained: queue empty, nothing in flight, ALL KV blocks back
        assert snap["gauges"]["queue_depth"] == 0
        assert snap["gauges"]["requests_in_flight"] == 0
        assert snap["gauges"]["kv_blocks_in_use"] == 0
        assert snap["gauges"]["kv_block_utilization"] == 0.0
        assert snap["allocator"]["blocks_in_use"] == 0
        # served requests release their batcher-side output lists (no
        # unbounded growth under a long-lived engine)
        assert eng.batcher.outputs == {}

    def test_priority_over_fifo(self, setup):
        """With one batch slot, a priority-0 late arrival is admitted
        before earlier priority-5 traffic; equal priorities stay FIFO."""
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=1, block_size=4, max_total_len=32,
            max_new_tokens=2, chunk=2, aging_interval_s=100.0,
            start=False)
        a = eng.submit(PROMPT_A, priority=5)
        b = eng.submit(PROMPT_B, priority=0)
        c = eng.submit(PROMPT_A2, priority=5)
        eng.start()
        eng.shutdown(drain=True, timeout=300)
        assert all(r.state is RequestState.FINISHED for r in (a, b, c))
        assert b.admitted_index < a.admitted_index < c.admitted_index

    def test_queue_full_rejection_and_validation(self, setup):
        """Backpressure: a full queue REJECTS with QueueFullError; a
        request that can never fit fails at submit. Neither runs the
        model (the engine is never started)."""
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=MAX_NEW, max_queue_depth=2, start=False)
        q1 = eng.submit(PROMPT_A)
        q2 = eng.submit(PROMPT_B)
        q3 = serving.GenerationRequest(PROMPT_A2)
        with pytest.raises(QueueFullError):
            eng.submit(q3)
        # a backpressure-rejected request stays pristine → retryable
        assert q3.submit_time is None and q3.max_new_tokens is None
        with pytest.raises(ValueError):    # prompt + max_new > max_total
            eng.submit(list(range(1, 41)))
        with pytest.raises(ValueError):    # budget over engine-wide max
            eng.submit(PROMPT_A, max_new_tokens=99)
        # a pre-built request must not silently drop submit() kwargs
        pre = serving.GenerationRequest(PROMPT_A, priority=5)
        with pytest.raises(ValueError, match="not both"):
            eng.submit(pre, timeout_s=5.0)
        assert eng.shutdown() is True      # never started: queued → CANCELLED
        assert q1.state is RequestState.CANCELLED
        assert q2.state is RequestState.CANCELLED
        with pytest.raises(ValueError, match="already submitted"):
            eng.submit(q1)                 # a used request can't resubmit
        with pytest.raises(serving.EngineStopped):
            eng.submit(PROMPT_A)
        c = eng.snapshot()["counters"]
        assert c["requests_submitted"] == 2
        assert c["requests_rejected"] == 3
        assert c["requests_cancelled"] == 2


class TestServingEngineShared:
    def test_stop_token_finishes_early(self, engine, baselines):
        """Per-request stop id (satellite: ContinuousBatcher per-slot
        stop support) truncates at the stop token and frees the slot."""
        stop = baselines["A"][1]
        cut = baselines["A"].index(stop)  # first occurrence wins
        out = engine.generate(PROMPT_A, max_new_tokens=MAX_NEW,
                              stop_token_id=stop, timeout=300)
        assert out == baselines["A"][:cut + 1]
        assert out[-1] == stop
        engine.drain(timeout=60)
        assert engine.snapshot()["gauges"]["kv_blocks_in_use"] == 0

    def test_cancel_mid_decode_frees_blocks(self, engine):
        req = engine.submit(PROMPT_B, max_new_tokens=20)
        it = req.stream()
        first = next(it)                  # guarantees DECODING started
        req.cancel()
        assert req.wait(timeout=300)
        assert req.state is RequestState.CANCELLED
        rest = list(it)                   # cancelled stream ends cleanly
        assert req.tokens == [first] + rest
        assert len(req.tokens) < 20
        with pytest.raises(serving.RequestCancelled):
            req.result()
        assert engine.drain(timeout=300)
        assert engine.snapshot()["allocator"]["blocks_in_use"] == 0

    def test_fault_injection_isolates_request(self, engine, baselines):
        """One request's on_token callback raises → only that request
        FAILS (its blocks freed); the co-batched request completes and
        the engine keeps serving."""
        failed_before = engine.metrics.counter("requests_failed").value
        seen = []

        def boom(tok):
            seen.append(tok)
            if len(seen) == 2:
                raise RuntimeError("injected fault")

        bad = engine.submit(PROMPT_A, max_new_tokens=MAX_NEW,
                            on_token=boom)
        good = engine.submit(PROMPT_B, max_new_tokens=MAX_NEW)
        assert good.result(timeout=300) == baselines["B"]
        assert bad.wait(timeout=300)
        assert bad.state is RequestState.FAILED
        assert isinstance(bad.error, RuntimeError)
        assert len(bad.tokens) == 2
        with pytest.raises(serving.RequestFailed):
            bad.result()
        m = engine.metrics.counter("requests_failed").value
        assert m == failed_before + 1
        # engine survived: serve another request end to end
        again = engine.generate(PROMPT_A, max_new_tokens=MAX_NEW,
                                timeout=300)
        assert again == baselines["A"]
        assert engine.drain(timeout=300)
        assert engine.snapshot()["allocator"]["blocks_in_use"] == 0


@pytest.mark.slow
class TestServingStress:
    def test_many_requests_saturate_and_drain(self, setup):
        """Scale pass (excluded from tier-1): 12 mixed-priority requests
        over 2 slots with interleaved cancellations; every invariant the
        dashboard relies on must hold after the drain."""
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=8, chunk=3, max_queue_depth=32,
            aging_interval_s=0.1, start=False)
        rng = np.random.RandomState(3)
        reqs = [eng.submit(list(rng.randint(1, 200, int(L))),
                           priority=int(rng.randint(0, 3)))
                for L in rng.randint(3, 12, 12)]
        reqs[4].cancel()
        reqs[9].cancel()
        eng.start()
        eng.shutdown(drain=True, timeout=600)
        states = [r.state for r in reqs]
        assert states.count(RequestState.CANCELLED) == 2
        assert states.count(RequestState.FINISHED) == 10
        assert all(len(r.tokens) == 8
                   for r in reqs if r.state is RequestState.FINISHED)
        snap = eng.snapshot()
        c = snap["counters"]
        assert c["requests_submitted"] == 12
        assert (c["requests_completed"] + c["requests_cancelled"]) == 12
        assert snap["allocator"]["blocks_in_use"] == 0
        assert snap["gauges"]["queue_depth"] == 0
        assert eng.batcher.outputs == {}


class TestServingPrefixCache:
    """serving.cache e2e: the engine's default prefix cache must be
    invisible in outputs (token-identical to a cold engine) and visible
    in metrics — including when one of two requests sharing blocks is
    cancelled mid-decode."""

    def _engine(self, setup, max_new=MAX_NEW, **kw):
        cfg, params = setup
        return serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=max_new, chunk=3, max_queue_depth=16, **kw)

    def test_warm_outputs_match_cold_engine(self, setup):
        rng = np.random.RandomState(21)
        common = list(map(int, rng.randint(1, 200, 8)))  # 2 full blocks
        prompts = [common + [11, 12, 13], common + [14, 15], list(common)]
        cold_eng = self._engine(setup, prefix_cache=False)
        cold = [cold_eng.generate(p, timeout=300) for p in prompts]
        cold_eng.shutdown()
        assert cold_eng.snapshot()["prefix_cache"] == {"enabled": False}

        warm_eng = self._engine(setup)                   # cache on by default
        warm = [warm_eng.generate(p, timeout=300) for p in prompts]
        # serve the shared-prefix set AGAIN: now every prompt hits
        warm += [warm_eng.generate(p, timeout=300) for p in prompts]
        snap = warm_eng.snapshot()
        warm_eng.shutdown()
        assert warm == cold + cold                       # token-identical
        pc = snap["prefix_cache"]
        assert pc["enabled"] and pc["hit_rate"] > 0
        assert pc["hit_tokens"] >= 3 * 8                 # second pass ≥ fully warm
        assert snap["gauges"]["prefix_cache_hit_rate"] == pc["hit_rate"]
        assert snap["gauges"]["prefix_cache_hit_tokens"] == pc["hit_tokens"]
        # drained: no block referenced, prefix blocks parked reclaimable
        assert snap["allocator"]["blocks_in_use"] == 0
        assert snap["allocator"]["cached_blocks"] > 0

    def test_cache_aware_admission_prefers_cached_prefix(self, setup):
        """At equal priority the engine admits the request whose prefix
        is cached BEFORE earlier-queued cold traffic (scheduler `prefer`
        tie-break), so reclaimable blocks turn into skipped prefill
        before eviction can recycle them."""
        rng = np.random.RandomState(23)
        common = list(map(int, rng.randint(1, 200, 8)))  # 2 full blocks
        cold_p = list(map(int, rng.randint(1, 200, 9)))
        eng = self._engine(setup, start=False, aging_interval_s=100.0)
        # prime the cache while the loop is parked (the batcher is ours
        # until start()) and hand the outputs back
        rid = eng.batcher.submit(common + [41, 42])
        eng.batcher.run()
        eng.batcher.release(rid)
        cold = eng.submit(cold_p)                 # queued FIRST
        warm = eng.submit(common + [43])          # cached prefix, later
        eng.start()
        eng.shutdown(drain=True, timeout=300)
        assert warm.state is RequestState.FINISHED
        assert cold.state is RequestState.FINISHED
        assert warm.admitted_index < cold.admitted_index
        snap = eng.snapshot()
        assert snap["prefix_cache"]["hit_tokens"] >= 8
        # bucketed-prefill gauges ride the same snapshot
        assert snap["gauges"]["prefill_compile_count"] >= 1
        assert snap["gauges"]["prefill_pad_tokens"] > 0

    @pytest.mark.parametrize("trace", [True, False],
                             ids=["traced", "untraced"])
    def test_warmup_precompiles_and_refuses_after_start(self, setup,
                                                        trace):
        """Trace emission touches no compiled-shape memo key: traced or
        not, nothing compiles past warmup()."""
        eng = self._engine(setup, start=False, trace=trace)
        warmed = eng.warmup()
        assert warmed == eng.batcher.compile_count > 0
        eng.start()
        with pytest.raises(RuntimeError, match="before start"):
            eng.warmup()
        out = eng.generate(PROMPT_A, timeout=300)
        assert eng.batcher.compile_count == warmed  # no retrace
        eng.shutdown()
        cfg, params = setup
        assert out == _paged_single(params, cfg, PROMPT_A)

    def test_cancel_mid_decode_releases_shared_blocks(self, setup):
        """Two in-flight requests share the common prefix's blocks
        (refcount 2). Cancelling one mid-decode must decref — not
        free — the shared blocks: the survivor keeps decoding on them
        and still produces its cold-engine output."""
        rng = np.random.RandomState(22)
        common = list(map(int, rng.randint(1, 200, 8)))
        p_cancel = common + [31, 32]
        p_keep = common + [33, 34, 35]
        cold_eng = self._engine(setup, prefix_cache=False)
        keep_cold = cold_eng.generate(p_keep, timeout=300)
        cold_eng.shutdown()

        # the victim gets a 20-token budget so the cancel lands while it
        # is still decoding; the keeper's budget matches the baseline
        eng = self._engine(setup, max_new=20, start=False)
        victim = eng.submit(p_cancel, max_new_tokens=20)
        keeper = eng.submit(p_keep, max_new_tokens=MAX_NEW)
        eng.start()                     # both admitted together: 2 slots
        it = victim.stream()
        next(it)                        # decode provably started
        victim.cancel()
        assert victim.wait(timeout=300)
        assert victim.state is RequestState.CANCELLED
        assert len(victim.tokens) < 20  # genuinely cut short
        assert keeper.result(timeout=300) == keep_cold   # not corrupted
        assert eng.drain(timeout=300)
        snap = eng.snapshot()
        eng.shutdown()
        assert snap["prefix_cache"]["hit_tokens"] >= 8   # blocks were shared
        assert snap["allocator"]["blocks_in_use"] == 0   # all refs dropped
        # the shared prefix survives the cancel for future requests
        assert snap["allocator"]["cached_blocks"] > 0


class TestFusedServing:
    """Fused prefill+decode through the full engine: admissions landing
    while another request decodes piggyback on the decode chunk (the
    fused_steps gauge proves it) and stay token-identical to the
    sequential baselines; the fusion-off engine is the escape hatch."""

    def _engine(self, setup, **kw):
        cfg, params = setup
        return serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=20, chunk=3, max_queue_depth=16, **kw)

    def _serve_overlapped(self, eng, baselines):
        long_req = eng.submit(PROMPT_A, max_new_tokens=20)
        it = long_req.stream()
        first = next(it)                  # decode provably started
        # lands mid-decode: with fusion on this admission piggybacks
        out_b = eng.generate(PROMPT_B, max_new_tokens=MAX_NEW,
                             timeout=300)
        assert out_b == baselines["B"]
        rest = list(it)
        # greedy ⇒ the 6-token baseline is a strict prefix of 20 tokens
        assert ([first] + rest)[:MAX_NEW] == baselines["A"]
        assert eng.drain(timeout=300)

    def test_fused_engine_parity_and_metrics(self, setup, baselines):
        eng = self._engine(setup)         # fused_prefill on by default
        self._serve_overlapped(eng, baselines)
        snap = eng.snapshot()
        eng.shutdown()
        assert snap["gauges"]["fused_steps"] >= 1
        assert snap["gauges"]["decode_stall_steps"] == 0
        # inter-token latency surfaced (multi-step requests ⇒ gaps)
        assert snap["histograms"]["itl_s"]["count"] >= 1
        assert "p95" in snap["histograms"]["itl_s"]
        assert snap["allocator"]["blocks_in_use"] == 0

    def test_fusion_off_escape_hatch(self, setup, baselines):
        eng = self._engine(setup, fused_prefill=False)
        self._serve_overlapped(eng, baselines)
        snap = eng.snapshot()
        eng.shutdown()
        assert snap["gauges"]["fused_steps"] == 0
        assert snap["gauges"]["decode_stall_steps"] >= 1
        assert snap["allocator"]["blocks_in_use"] == 0


class TestContinuousBatcherStop:
    def test_per_request_stop_token(self, setup, baselines):
        """Batcher-level satellite: a slot with stop_token_id finishes
        the moment it emits that id — not only on global eos/budget —
        and its blocks return to the pool while the OTHER slot keeps
        decoding to its full budget."""
        cfg, params = setup
        stop = baselines["A"][1]
        cut = baselines["A"].index(stop)  # first occurrence wins
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=MAX_NEW, chunk=3)
        r_stop = cb.submit(PROMPT_A, stop_token_id=stop)
        r_full = cb.submit(PROMPT_B)
        out = cb.run()
        assert out[r_stop] == baselines["A"][:cut + 1]
        assert out[r_full] == baselines["B"]
        assert cb.alloc.stats()["blocks_in_use"] == 0

    def test_per_request_max_new(self, setup, baselines):
        cfg, params = setup
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=MAX_NEW, chunk=3)
        r = cb.submit(PROMPT_A, max_new_tokens=3)
        out = cb.run()
        assert out[r] == baselines["A"][:3]
        with pytest.raises(ValueError):
            cb.submit(PROMPT_A, max_new_tokens=MAX_NEW + 1)

    def test_validate_caps_at_configured_total(self, setup):
        """validate() enforces the CONFIGURED max_total_len, not the
        block-rounded table capacity."""
        cfg, params = setup
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=1, block_size=16, max_total_len=30,
            max_new_tokens=4, chunk=2)
        assert cb.validate(26, 4) == 4     # 30 fits exactly
        with pytest.raises(ValueError, match="max_total_len 30"):
            cb.validate(28, 4)             # 32 fits M*bs but not 30

    def test_failed_prefill_does_not_leak_blocks(self, setup,
                                                 monkeypatch):
        """A prefill that raises must return its just-allocated blocks
        to the pool (the engine's exception boundary relies on it)."""
        cfg, params = setup
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=1, block_size=4, max_total_len=32,
            max_new_tokens=4, chunk=2)
        monkeypatch.setattr(
            paged, "forward_paged",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
        cb.submit(PROMPT_A)
        with pytest.raises(RuntimeError, match="boom"):
            cb.run()
        assert cb.alloc.stats()["blocks_in_use"] == 0


class TestGenerationRequestUnit:
    def test_stream_after_terminal_does_not_block(self):
        req = serving.GenerationRequest([1, 2, 3])
        req._deliver(10)
        req._deliver(11)
        req._finish(RequestState.FINISHED, "length")
        assert list(req.stream()) == [10, 11]
        assert list(req.stream()) == []    # second pass: no hang
        assert req.result(0) == [10, 11]

    def test_stream_raises_on_failure(self):
        req = serving.GenerationRequest([1])
        req._deliver(5)
        req._finish(RequestState.FAILED, "boom",
                    error=RuntimeError("boom"))
        it = req.stream()
        assert next(it) == 5
        with pytest.raises(serving.RequestFailed):
            next(it)


class TestAdmissionQueue:
    def test_priority_then_fifo(self):
        q = AdmissionQueue(max_depth=8, aging_interval_s=100.0)
        q.push("a5", priority=5)
        q.push("b0", priority=0)
        q.push("c0", priority=0)
        q.push("d5", priority=5)
        assert [q.pop() for _ in range(4)] == ["b0", "c0", "a5", "d5"]
        assert q.pop() is None

    def test_aging_prevents_starvation(self):
        t = [0.0]
        q = AdmissionQueue(max_depth=8, aging_interval_s=2.0,
                           clock=lambda: t[0])
        q.push("old9", priority=9)
        t[0] = 19.0                       # aged by 9 levels → effective 0
        q.push("new0", priority=0)
        assert q.pop() == "old9"          # FIFO wins the tie at level 0
        assert q.pop() == "new0"

    def test_backpressure_and_defer(self):
        q = AdmissionQueue(max_depth=2)
        q.push("x")
        q.push("y")
        with pytest.raises(QueueFullError):
            q.push("z")
        # defer-on-no-blocks: the BEST item gates the whole queue
        assert q.pop(fits=lambda i: False) is None
        assert len(q) == 2
        assert q.pop(fits=lambda i: True) == "x"

    def test_reap(self):
        q = AdmissionQueue(max_depth=8)
        for i in range(4):
            q.push(i)
        assert q.reap(lambda i: i % 2 == 0) == [0, 2]
        assert [q.pop(), q.pop()] == [1, 3]

    def test_pop_many_batch_defer_and_prefer(self):
        """One admission round under one lock: best-first order, the
        head-of-line item failing `fits` stops the round, `fits` runs
        once per ACCEPTED item (callers debit resources inside it), and
        `prefer` tie-breaks within the round."""
        q = AdmissionQueue(max_depth=8, aging_interval_s=100.0)
        q.push("a1", priority=1)
        q.push("b0", priority=0)
        q.push("c1", priority=1)
        assert q.pop_many(2) == ["b0", "a1"]
        assert q.pop_many(5) == ["c1"]
        assert q.pop_many(3) == []
        q.push("big", priority=0)
        q.push("small", priority=1)
        assert q.pop_many(2, fits=lambda i: i != "big") == []
        assert len(q) == 2                 # defer leaves the queue intact
        calls = []
        got = q.pop_many(2, fits=lambda i: calls.append(i) or True)
        assert got == ["big", "small"] and calls == got
        q.push("cold", priority=1)
        q.push("warm", priority=1)
        assert q.pop_many(2, prefer=lambda i: i == "warm") \
            == ["warm", "cold"]

    def test_prefer_breaks_ties_within_priority(self):
        """Cache-aware ordering: at EQUAL effective priority a preferred
        (cached-prefix) item pops before earlier FIFO traffic, but never
        jumps a strictly better priority level."""
        q = AdmissionQueue(max_depth=8, aging_interval_s=100.0)
        q.push("cold_a", priority=1)
        q.push("warm", priority=1)
        q.push("cold_b", priority=1)
        prefer = lambda item: item == "warm"
        assert q.pop(prefer=prefer) == "warm"          # tie-break wins
        assert q.pop(prefer=prefer) == "cold_a"        # then FIFO
        # a higher-priority cold item still beats a preferred one
        q.push("hot", priority=0)
        q.push("warm2", priority=1)
        assert q.pop(prefer=lambda i: i == "warm2") == "hot"
        # prefer composes with fits-deferral: the PREFERRED head gates
        assert q.pop(fits=lambda i: i != "warm2",
                     prefer=lambda i: i == "warm2") is None
        assert q.pop() == "cold_b"


class TestMetricsRegistry:
    def test_counters_gauges_histograms(self):
        m = MetricsRegistry()
        m.counter("c").inc()
        m.counter("c").inc(2)
        m.gauge("g").set(7.5)
        h = m.histogram("h")
        for v in range(1, 101):
            h.observe(float(v))
        snap = m.snapshot()
        assert snap["counters"]["c"] == 3
        assert snap["gauges"]["g"] == 7.5
        hs = snap["histograms"]["h"]
        assert hs["count"] == 100 and hs["min"] == 1.0 and hs["max"] == 100.0
        assert abs(hs["p50"] - 50.0) <= 2.0
        assert abs(hs["p99"] - 99.0) <= 2.0

    def test_percentile_since_skips_warmup_samples(self):
        # bench emitters rank only the timed window: `since` drops the
        # first N lifetime observations (e.g. a warmup request's
        # compile-tainted gaps)
        m = MetricsRegistry()
        h = m.histogram("h")
        h.observe(1000.0)          # warmup outlier
        for v in range(1, 11):
            h.observe(float(v))
        assert h.percentile(0.99) == 1000.0
        assert h.percentile(0.99, since=1) == 10.0
        assert h.percentile(0.50, since=1) == 5.0
        assert h.percentile(0.99, since=11) is None
        # wrapped ring: samples that already fell off are skipped
        hw = m.histogram("hw")
        hw._cap = 8
        for v in range(16):
            hw.observe(float(v))
        assert hw.percentile(1.0, since=4) == 15.0
        assert hw.percentile(0.0, since=4) == 8.0   # 4..7 fell off

    def test_timer_observes_and_is_thread_safe(self):
        m = MetricsRegistry()

        def work():
            for _ in range(50):
                m.counter("n").inc()
                with m.timer("t", record_event=False):
                    pass

        threads = [threading.Thread(target=work) for _ in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        snap = m.snapshot()
        assert snap["counters"]["n"] == 200
        assert snap["histograms"]["t"]["count"] == 200

    def test_timer_emits_profiler_span(self):
        # RecordEvent integration: reusable spans must not raise even
        # when no trace is active
        m = MetricsRegistry()
        for _ in range(3):
            with m.timer("serving.span_s"):
                pass
        assert m.snapshot()["histograms"]["serving.span_s"]["count"] == 3
