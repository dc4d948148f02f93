"""paddle_tpu.serving.engine — thread-backed serving over the paged-KV
continuous batcher.

The ServingEngine is the host-side half the ROADMAP's "serve heavy
traffic" north star was missing: the device-side half (paged KV-cache
attention + ContinuousBatcher, nlp/paged.py) already decodes a ragged
in-flight batch in lock-step chunks; this engine keeps that batch
SATURATED from an admission-controlled queue and fans tokens back out to
per-request channels.

Architecture (one background thread owns the batcher; everything else
talks through locks/channels):

    submit()/generate()/stream()          consumer threads
        │  AdmissionQueue (priority + aging + backpressure)
        ▼
    engine thread loop:
        reap cancelled / expired (queued AND in-flight)
        admit while a batch slot AND the KV blocks fit   ── scheduler.py
        batcher.step()  — one compiled decode chunk      ── nlp/paged.py
        deliver tokens → request channels (+ on_token)   ── request.py
        update metrics / profiler spans                  ── metrics.py

Robustness (fault-isolated serving): a request whose on_token callback
raises fails ONLY that request (its KV blocks return to the pool). A
device-step failure enters a quarantine-and-recover pipeline instead of
killing every co-batched request: the flight recorder's last record
names the failing tick's mode and unit composition, each suspect is
re-executed INDIVIDUALLY (decode slots probe solo through the warmed
chunk executable, prefill records probe as standalone single-record
calls), and only convicted culprits fail — the innocent requeue at the
FRONT of the admission queue and re-admit with `prompt + tokens`, so
greedy decode resumes exactly where it stopped (warm via the prefix
cache; streamed tokens are never re-emitted or lost). A culprit whose
failure looks transient (`retry_transient` predicate) gets
`max_retries` backoff re-admissions before FAILED. A hung device call
is caught by the watchdog thread (`watchdog_s`): it dumps the flight
recorder, flips `health()` to UNHEALTHY, fails the stranded requests'
handles and lets shutdown()/drain() return instead of silently
hanging. `health()` is the per-replica signal a multi-replica router
polls; `serving.faults.FaultInjector` makes every one of these paths
deterministically testable. shutdown(drain=True) stops admissions,
drains in-flight work, then joins the thread.

Observability (serving.trace): a per-request TraceSink timeline rides
every request (enqueued → admitted → prefill chunks → first token →
decode dispatches → terminal state; `engine.trace.to_chrome_trace()`
exports Perfetto-loadable JSON), and the batcher's step flight
recorder is dumped — last N scheduler records plus allocator/queue
state, as JSON — automatically when a device step raises
(`last_flight_dump_json`) or on demand (`dump_flight_recorder()`).
`MetricsRegistry.to_prometheus()` renders the same metrics snapshot()
reads in the Prometheus text format.

Speculative decoding (serving.speculative / nlp.paged): with
`speculative=True` the batcher drafts `spec_k` tokens off a truncated
layer stack and the target verifies all k+1 positions in one paged
call, committing only accepted rows — greedy output identical to
plain decode, tokens/step multiplied. A FAILED spec tick quarantines
normally and its surviving requests re-admit opted out of the spec
pipeline (plain decode). Acceptance accounting rides
`snapshot()["speculative"]` and the spec_* gauges.

SLOs & device-time attribution (serving.slo / serving.profiling): an
in-process `SloTracker` watches declarative latency/goodput/error
objectives over dual rolling windows — burn rates and OK/WARN/BREACH
verdicts in `health()["slo"]`, `slo_burn_rate_*` gauges and
`slo_breaches_total` counters in the exposition, `slo_breach` trace
events for request correlation; a BREACH is detail, never an outage
signal (SLOs degrade, supervision decides). The batcher's step
profiler attributes DEVICE wall per compiled shape from the stamps of
every tick that reads its result back (`profile_sample_every=0` turns
it off; no value costs a sync), and `capture_profile(steps=K)` fences
a whole window on demand so that ticks which read nothing back are
measured too.

Phases on the profiler's clock: the loop opens `engine.housekeeping`
(reap, admission, gauges: the section under the lock),
`engine.deliver` (`_dispatch`: the token bridge and `on_token`) and
`engine.idle` (its sleep when nothing is queued or in flight) as
`RecordEvent` spans beside `serving.step_s`, and the batcher opens
`serve.tick` and its phases inside the step (nlp/paged.py `_Tick`) and
`serve.compile` round a step program's compile (`_aot`), so a jax
profiler trace names what the host did in every gap between device
programs. `snapshot()["compiles"]` is the compile log's summary of the
step programs (core/compile_cache.py).
"""
from __future__ import annotations

import json
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

from .kvtransfer import KVSnapshot, check_compatible
from ..core.compile_cache import compile_log
from ..profiler import RecordEvent
from .metrics import LATENCY_BUCKETS, MetricsRegistry
from .request import GenerationRequest, RequestState
from .scheduler import AdmissionQueue, QueueFullError
from .slo import SloTracker
from .trace import TraceSink

__all__ = ["ServingEngine", "EngineStopped", "HungStepError"]

# the batcher's step programs by name (nlp/paged.py: serve_decode_step,
# serve_fused_step, serve_prefill_step, serve_spec_draft, serve_spec_verify)
_STEP_PROGRAMS = ("^jit_serve_",)


class EngineStopped(RuntimeError):
    """submit() after shutdown began."""


class HungStepError(RuntimeError):
    """A device step exceeded the watchdog deadline: the engine thread
    is presumed wedged inside a device call that will never return.
    Attached as the terminal error to every stranded request and kept
    on `last_flight_dump` — `health()` reports UNHEALTHY from the
    moment the watchdog trips."""


def _default_transient(error: BaseException) -> bool:
    """The default retry predicate: injected faults flagged transient
    (`serving.faults.InjectedFault(transient=True)`) and
    RESOURCE_EXHAUSTED-shaped device errors (allocator pressure passes;
    a retry after backoff usually lands) are worth re-admitting —
    everything else is treated as deterministic and fails fast."""
    return bool(getattr(error, "transient", False)) \
        or "RESOURCE_EXHAUSTED" in repr(error)


class ServingEngine:
    """Async request-serving engine over a ContinuousBatcher.

    Usage:
        eng = ServingEngine(params, cfg, max_batch=4, block_size=16,
                            max_total_len=512, max_new_tokens=64)
        out = eng.generate(prompt_ids)                  # blocking
        for tok in eng.stream(prompt_ids): ...          # incremental
        req = eng.submit(prompt_ids, priority=1, timeout_s=30)
        ...; req.cancel(); eng.shutdown()

    `start=False` builds the engine with the loop parked — requests
    queue up (deterministic admission tests, warm pre-loading) until
    `start()`.
    """

    def __init__(self, params, cfg, *, max_batch: int = 4,
                 block_size: int = 16, max_total_len: int = 256,
                 max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None,
                 num_blocks: Optional[int] = None, chunk: int = 8,
                 max_queue_depth: int = 64,
                 aging_interval_s: float = 2.0,
                 metrics: Optional[MetricsRegistry] = None,
                 start: bool = True, idle_poll_s: float = 0.05,
                 prefix_cache: bool = True,
                 prefill_buckets=None, max_prefill_bucket: int = 512,
                 fused_prefill: bool = True, fused_units: int = 1,
                 attention_impl: str = "auto",
                 weight_dtype: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 speculative: bool = False, spec_k: int = 4,
                 draft_layers: Optional[int] = None,
                 spec_tree=None, spec_draft_w8: bool = False,
                 spec_attention_impl: Optional[str] = None,
                 warmup: bool = False,
                 trace: bool = True, flight_recorder_cap: int = 64,
                 flight_dump_path: Optional[str] = None,
                 quarantine: bool = True, max_retries: int = 2,
                 retry_backoff_s: float = 0.05,
                 retry_transient=None,
                 watchdog_s: Optional[float] = None,
                 watchdog_compile_grace: float = 16.0,
                 health_window_s: float = 30.0,
                 fault_injector=None,
                 slo: bool = True,
                 slo_objectives: Optional[Dict[str, float]] = None,
                 slo_opts: Optional[Dict] = None,
                 profile_sample_every: int = 64,
                 replica_id: str = "r0",
                 role: str = "both",
                 mesh=None,
                 max_prefill_group: Optional[int] = None,
                 clock=time.monotonic):
        # multi-replica attribution: every snapshot, health report,
        # flight dump and batcher-side `prepared` trace event carries
        # this id, so a Router's merged forensics stay attributable to
        # the replica that produced them (default "r0": a standalone
        # engine IS replica zero)
        self.replica_id = str(replica_id)
        # disaggregated serving (ROADMAP direction 2): a "prefill"-role
        # engine finishes every request at prefill-complete (first
        # token) and surrenders its KV as a portable snapshot on
        # `req.kv_snapshot` (reason "prefill_complete") for a decode
        # replica to adopt via submit_import(); a "decode"-role engine
        # serves normally but advertises itself as the adoption target
        # a disaggregated Router migrates to. "both" (the default) is
        # the monolithic behavior — role steers ROUTER placement, the
        # engine itself accepts plain submits in every role (probes
        # and standalone use keep working).
        role = str(role)
        if role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"role must be 'prefill', 'decode' or 'both', "
                f"got {role!r}")
        self.role = role
        if role == "prefill":
            # surrender happens at the first committed token — a spec
            # draft/verify pipeline would never complete a sweep before
            # the handoff, so keep the warmup ladder spec-free
            speculative = False
        # observability: per-request timelines (always-on-cheap unless
        # trace=False) + the batcher's step flight recorder; a step
        # failure dumps the ring + allocator/queue state to JSON
        # (`last_flight_dump_json`, and `flight_dump_path` when set).
        # max_live covers every request this engine can hold open at
        # once (queued + in flight), so the sink's leak bound can
        # never displace a running request's timeline
        self.trace: Optional[TraceSink] = TraceSink(
            max_live=max_queue_depth + max_batch + 16) if trace else None
        self._flight_dump_path = flight_dump_path
        self.last_flight_dump: Optional[Dict] = None
        self.last_flight_dump_json: Optional[str] = None
        # lazy: keep `import paddle_tpu` from pulling the whole nlp tree
        from ..nlp.paged import ContinuousBatcher, _is_latent, _layer_kinds
        if role == "prefill" and (_is_latent(cfg)
                                  or _layer_kinds(cfg) is not None):
            # the batcher refuses export_kv / import_kv for a latent pool
            # and for a kinded one (window rings beside full chains); a
            # prefill-role engine exists only to export
            raise NotImplementedError(
                "role='prefill': a latent (MLA) pool, or one whose window "
                "layers keep a ring, has no KVSnapshot form, so its KV "
                "cannot be surrendered to a decode replica")
        self.batcher = ContinuousBatcher(
            params, cfg, max_batch=max_batch, block_size=block_size,
            max_total_len=max_total_len, max_new_tokens=max_new_tokens,
            eos_token_id=eos_token_id, num_blocks=num_blocks, chunk=chunk,
            prefix_cache=prefix_cache, prefill_buckets=prefill_buckets,
            max_prefill_bucket=max_prefill_bucket,
            fused_prefill=fused_prefill, fused_units=fused_units,
            attention_impl=attention_impl,
            weight_dtype=weight_dtype, kv_dtype=kv_dtype,
            speculative=speculative, spec_k=spec_k,
            draft_layers=draft_layers,
            spec_tree=spec_tree, spec_draft_w8=spec_draft_w8,
            spec_attention_impl=spec_attention_impl,
            trace=self.trace,
            flight_recorder_cap=flight_recorder_cap,
            profile_sample_every=profile_sample_every,
            fault_injector=fault_injector,
            replica_id=self.replica_id,
            mesh=mesh, max_prefill_group=max_prefill_group)
        # tensor-parallel serving (serving/tp.py): the batcher owns the
        # sharded weights/pool; the engine mirrors the mesh shape into
        # snapshot()/health()/gauges so a Router's merged forensics can
        # attribute a multi-chip replica (None = single-device)
        self.mesh = mesh
        # the RESOLVED backend ("auto" already collapsed to the concrete
        # choice at batcher construction) — the snapshot surface.
        # Same for the resolved quantization config: the batcher owns
        # quantize_for_serving and the int8 KV pool; the engine mirrors
        # the resolved choice into snapshot()/gauges.
        self.attention_impl = self.batcher.attention_impl
        self.weight_dtype = self.batcher.weight_dtype
        self.kv_dtype = self.batcher.kv_dtype
        self.speculative = self.batcher.speculative
        self.metrics = metrics or MetricsRegistry()
        self._clock = clock
        self._idle_poll_s = idle_poll_s
        self.queue = AdmissionQueue(max_depth=max_queue_depth,
                                    aging_interval_s=aging_interval_s,
                                    clock=clock)
        self._running: Dict[int, GenerationRequest] = {}
        self._admit_seq = 0
        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)
        self._accepting = True
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._alloc_stats = self.batcher.alloc_stats()
        self._prefix_stats = self.batcher.prefix_stats()
        # fault tolerance: quarantine-by-bisection on step failures,
        # transient-culprit retries with exponential backoff, hung-step
        # watchdog, and the health surface a replica router polls
        self._quarantine_on = bool(quarantine)
        self._max_retries = int(max_retries)
        self._retry_backoff_s = float(retry_backoff_s)
        self._retry_transient = retry_transient or _default_transient
        self._watchdog_s = watchdog_s
        # compile-vs-hang disambiguation: an engine serving WITHOUT a
        # prior warmup() pays trace+compile inside the step that first
        # meets each shape (prefill buckets, the decode chunk fn, ...),
        # and any of those can dwarf a sane watchdog deadline — so
        # until warmup() has run, every step's deadline is multiplied
        # by this grace factor. The documented tradeoff: an unwarmed
        # engine detects a REAL hang `grace`x slower; warmup() before
        # start() removes the ambiguity entirely and is the deploy
        # guidance for tight deadlines (1.0 restores the old
        # undifferentiated behavior)
        self._wd_grace = max(1.0, float(watchdog_compile_grace))
        self._health_window_s = float(health_window_s)
        self._parked: List[List] = []       # [ready_time, request]
        # pending KV-snapshot adoptions: (snapshot, request) in arrival
        # order — the engine thread activates them via import_kv ahead
        # of fresh admissions (_process_imports_locked)
        self._imports: List = []
        # shadow-traffic probe feed: a bounded ring of recently COMPLETED
        # live request shapes (prompt tokens, resolved budget) — the
        # supervisor's probe_mirror restart gate replays the newest one
        # through a respawned replica instead of the synthetic prompt
        self._recent_prompts: List[Tuple[List[int], int]] = []
        self._recent_prompts_cap = 8
        # drain-and-export rendezvous (supervisor teardown): a caller's
        # box list the engine thread fills with (snapshot, request)
        # pairs for every exportable in-flight request, then clears the
        # reference (None = no drain order pending)
        self._drain_export_box: Optional[List] = None
        self._wedged = False
        self._warmed = False                # warmup() ran (AOT ladder)
        # livelock fuse tripped: the engine declared itself UNHEALTHY
        # (reason string) and stopped serving — a supervisor's cue to
        # respawn the replica, like a watchdog trip but with a live
        # (cleanly parked) engine thread
        self._broken: Optional[str] = None
        self._last_fault_t: Optional[float] = None
        self._fault_streak = 0              # consecutive failed steps
        self._max_fault_streak = 8          # livelock fuse: then fail-all
        self._flight_seq = self.batcher.flight.seq
        self._step_t0: Optional[float] = None   # watchdog reads this
        self._wd_thread: Optional[threading.Thread] = None
        self._wd_stop = threading.Event()
        self._last_dump_error: Optional[str] = None

        m = self.metrics
        self._c_submitted = m.counter("requests_submitted")
        self._c_admitted = m.counter("requests_admitted")
        self._c_rejected = m.counter("requests_rejected")
        self._c_completed = m.counter("requests_completed")
        self._c_cancelled = m.counter("requests_cancelled")
        self._c_timed_out = m.counter("requests_timed_out")
        self._c_failed = m.counter("requests_failed")
        self._c_tokens = m.counter("tokens_generated")
        self._g_queue = m.gauge("queue_depth")
        self._g_running = m.gauge("requests_in_flight")
        self._g_blocks = m.gauge("kv_blocks_in_use")
        self._g_util = m.gauge("kv_block_utilization")
        # the three request-latency histograms carry a cumulative
        # bucket ladder so to_prometheus() exports native histogram
        # families (<name>_hist_bucket{le=...}) an external Prometheus
        # can compute its own burn rates from
        self._h_ttft = m.histogram("ttft_s", buckets=LATENCY_BUCKETS)
        self._h_wait = m.histogram("queue_wait_s",
                                   buckets=LATENCY_BUCKETS)
        self._h_token = m.histogram("per_token_s")
        # inter-token latency per request: the gap between consecutive
        # step dispatches that delivered this request tokens — its p99
        # is where admission-during-decode stalls show up (and what the
        # fused prefill+decode step exists to flatten)
        self._h_itl = m.histogram("itl_s", buckets=LATENCY_BUCKETS)
        self._last_emit: Dict[int, float] = {}    # rid -> last dispatch
        # prefix-cache surface (flat-line zeros when the cache is off)
        self._g_pc_hit_tokens = m.gauge("prefix_cache_hit_tokens")
        self._g_pc_hit_rate = m.gauge("prefix_cache_hit_rate")
        self._g_pc_evictions = m.gauge("prefix_cache_evictions")
        self._g_pc_cached = m.gauge("prefix_cache_cached_blocks")
        # bucketed-prefill surface: compile count flat after warmup is
        # the TTFT story; pad tokens is the overhead bucketing costs
        self._g_prefill_compiles = m.gauge("prefill_compile_count")
        self._g_prefill_pad = m.gauge("prefill_pad_tokens")
        # fused prefill+decode surface: fused_steps counts piggybacked
        # admission chunks, decode_stall_steps counts standalone
        # prefills that ran while slots were decoding (the ITL cost)
        self._g_fused_steps = m.gauge("fused_steps")
        self._g_fused_units = m.gauge("fused_unit_count")
        self._g_decode_stalls = m.gauge("decode_stall_steps")
        # EVERY compiled device-step shape (prefill/fused ladder + the
        # plain decode chunk) — the zero-post-warmup-recompiles gate
        self._g_compiles = m.gauge("compile_count")
        # quantized-serving byte surface: pool + weight footprints are
        # fixed at construction; kv_cached_bytes tracks the reclaimable
        # prefix-cached share of the pool as requests retire
        self._g_kv_pool_bytes = m.gauge("kv_pool_bytes")
        self._g_kv_cached_bytes = m.gauge("kv_cached_bytes")
        self._g_weight_bytes = m.gauge("weight_bytes")
        self._g_kv_pool_bytes.set(self.batcher.kv_pool_bytes())
        self._g_weight_bytes.set(self.batcher.weight_bytes())
        # tensor-parallel surface: mesh device count + PER-DEVICE pool
        # bytes (the single-device totals when mesh is off), exported
        # through to_prometheus() like every gauge so trace_report's
        # replica column can attribute multi-chip replicas
        self._g_mesh_devices = m.gauge("mesh_devices")
        self._g_kv_pool_bytes_dev = m.gauge("kv_pool_bytes_per_device")
        if mesh is not None:
            from .tp import shard_info
            self._mesh_info = shard_info(mesh, self.batcher)
        else:
            self._mesh_info = {
                "mesh": None,
                "kv_pool_bytes_per_device":
                    self.batcher.kv_pool_bytes(),
                "weight_bytes_per_device": self.batcher.weight_bytes()}
        # resolved fast-path stamp (mesh on or off): which attention
        # backend and spec score path this replica ACTUALLY runs —
        # "auto" has been resolved by now, so health()/snapshot()
        # answer "is this replica on the kernel fast path" directly
        self._mesh_info["attention_impl"] = self.batcher.attention_impl
        self._mesh_info["spec_backend"] = (
            self.batcher.spec_attention_impl
            if self.batcher.speculative else None)
        self._g_mesh_devices.set(1 if mesh is None else int(mesh.tp))
        self._g_kv_pool_bytes_dev.set(
            self._mesh_info["kv_pool_bytes_per_device"])
        # speculative-decoding surface: acceptance accounting per
        # verify sweep (flat zeros with spec off — exposition stable)
        self._g_spec_steps = m.gauge("spec_steps")
        self._g_spec_accept = m.gauge("spec_accept_rate")
        self._g_spec_tps = m.gauge("spec_tokens_per_step")
        self._g_spec_accepted = m.gauge("spec_accepted_tokens")
        # per-(sweep, slot) accepted-path-length distribution — the
        # data tree-shape tuning reads (a tree whose deep levels never
        # accept is wasted verify width); buckets cover path lengths
        # 0..8+ exactly since depths are small ints
        self._h_spec_depth = m.histogram(
            "spec_accept_depth",
            buckets=[0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0])
        # fault-tolerance surface: the counters health() aggregates
        self._c_step_faults = m.counter("step_faults")
        self._c_quarantines = m.counter("quarantines")
        self._c_requeued = m.counter("requests_requeued")
        self._c_retried = m.counter("requests_retried")
        self._c_watchdog = m.counter("watchdog_trips")
        self._c_dump_errors = m.counter("flight_dump_errors")
        # KV-transfer surface (serving/kvtransfer.py): snapshots
        # exported (prefill-role handoffs, drain-and-export, failover
        # attachment) and imported (adoptions activated), plus
        # quarantine innocents restored slot-in-place instead of
        # requeued through re-prefill
        self._c_kv_exports = m.counter("kv_exports")
        self._c_kv_imports = m.counter("kv_imports")
        self._c_restored = m.counter("requests_restored")
        self._c_handoffs = m.counter("prefill_handoffs")

        # SLO engine: declarative objectives over dual rolling windows
        # (serving.slo) — fed from the same observations the
        # histograms record, surfaced in health()["slo"], Prometheus
        # (slo_burn_rate_* gauges, slo_breaches_total counter) and
        # slo_breach/slo_recovered TraceSink events. SLOs degrade,
        # supervision decides: a BREACH never stops this engine.
        self._slo: Optional[SloTracker] = None
        self._g_slo_burn: Dict[str, object] = {}
        self._c_slo_breaches = m.counter("slo_breaches")
        self._slo_breaches_seen = 0
        if slo:
            self._slo = SloTracker(slo_objectives, clock=clock,
                                   **(slo_opts or {}))
            for name in self._slo.objectives:
                self._g_slo_burn[name] = m.gauge(
                    f"slo_burn_rate_{name}")

        if warmup:
            self.warmup()
        if start:
            self.start()

    # ---- public API ------------------------------------------------------
    def warmup(self) -> int:
        """Pre-compile every prefill shape (bucket ladder x admission
        group size x cold/cached) via AOT lowering, so no serving-path
        request ever pays a prefill compile. Only valid BEFORE start():
        once the loop runs, the batcher belongs to the engine thread.
        Returns the number of shapes compiled."""
        with self._work:
            if self._thread is not None:
                raise RuntimeError(
                    "warmup() must run before start() — the engine "
                    "thread owns the batcher once the loop is live")
            n = self.batcher.warmup_prefill()
            self._warmed = True
            self._update_gauges_locked()
            return n

    def start(self) -> "ServingEngine":
        with self._work:
            if self._stop:
                raise EngineStopped("engine already shut down")
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop, name="paddle-tpu-serving",
                    daemon=True)
                self._thread.start()
            if self._watchdog_s is not None and self._wd_thread is None:
                self._wd_thread = threading.Thread(
                    target=self._watchdog_loop,
                    name="paddle-tpu-watchdog", daemon=True)
                self._wd_thread.start()
        return self

    def submit(self, prompt, *, priority: int = 0,
               max_new_tokens: Optional[int] = None,
               stop_token_id: Optional[int] = None,
               timeout_s: Optional[float] = None,
               on_token=None) -> GenerationRequest:
        """Queue a request; returns immediately with its handle.
        Raises QueueFullError on backpressure, ValueError when the
        request can NEVER fit this engine's pool (fail fast, not after
        queueing), EngineStopped after shutdown began."""
        if isinstance(prompt, GenerationRequest):
            req = prompt
            if (priority != 0 or max_new_tokens is not None
                    or stop_token_id is not None or timeout_s is not None
                    or on_token is not None):
                raise ValueError(
                    "pass decode kwargs either on the GenerationRequest "
                    "or to submit(), not both")
            if req.submit_time is not None or req.done:
                raise ValueError("GenerationRequest already submitted")
        else:
            req = GenerationRequest(prompt, priority=priority,
                                    max_new_tokens=max_new_tokens,
                                    stop_token_id=stop_token_id,
                                    timeout_s=timeout_s, on_token=on_token)
        b = self.batcher
        try:
            mn = b.validate(len(req.prompt), req.max_new_tokens)
        except ValueError:
            self._c_rejected.inc()
            raise
        if b.blocks_needed(len(req.prompt), mn) > b.alloc.num_blocks:
            self._c_rejected.inc()
            raise ValueError(
                f"request needs {b.blocks_needed(len(req.prompt), mn)} "
                f"KV blocks but the pool holds {b.alloc.num_blocks}")
        with self._work:
            if self._stop or not self._accepting:
                raise EngineStopped("engine is shutting down")
            try:
                self.queue.push(req, priority=req.priority)
            except QueueFullError:
                self._c_rejected.inc()
                raise
            # only a successful push marks the request submitted — a
            # rejected pre-built request stays pristine and retryable
            # (the engine thread can't pop it before these stamps land:
            # admission needs the lock we still hold)
            now = self._clock()
            req.submit_time = now
            if req.timeout_s is not None:
                req.deadline = now + req.timeout_s
            req.max_new_tokens = mn      # resolved; admission reads it
            self._c_submitted.inc()
            self._g_queue.set(len(self.queue))
            if self.trace is not None:
                req.trace_id = self.trace.start()
                self.trace.emit(req.trace_id, "enqueued",
                                prompt_len=len(req.prompt),
                                priority=req.priority,
                                timeout_s=req.timeout_s)
            self._work.notify_all()
        return req

    def submit_import(self, snapshot: KVSnapshot,
                      req: Optional[GenerationRequest] = None
                      ) -> GenerationRequest:
        """Queue a portable KV snapshot for adoption: the engine thread
        activates it via `ContinuousBatcher.import_kv` — fresh blocks,
        scattered codes AND int8 scales, prefix index registered —
        ahead of cold admissions, and decode resumes at
        `len(snapshot.tokens)` with ZERO prefill chunks.

        `req` is the handle to resume; its `tokens` must already hold
        exactly the snapshot's generated tokens (a live handle that
        streamed them does; a router-side fresh handle pre-seeds them).
        None builds a new handle whose `tokens` are pre-seeded — they
        appear in result(), only NEW tokens stream. Fail-fast like
        submit(): fingerprint mismatch, misaligned handle tokens and a
        chain the pool can NEVER hold raise ValueError here, not after
        queueing. EngineStopped after shutdown began."""
        b = self.batcher
        problems = check_compatible(snapshot.fingerprint,
                                    b.kv_fingerprint())
        if problems:
            self._c_rejected.inc()
            raise ValueError("KV snapshot incompatible with this "
                             "engine: " + "; ".join(problems))
        if b.import_blocks_needed(snapshot) > b.alloc.num_blocks:
            self._c_rejected.inc()
            raise ValueError(
                f"snapshot needs {b.import_blocks_needed(snapshot)} KV "
                f"blocks but the pool holds {b.alloc.num_blocks}")
        gen = list(snapshot.tokens[snapshot.prompt_len:])
        fresh_handle = req is None
        if fresh_handle:
            req = GenerationRequest(
                list(snapshot.tokens[:snapshot.prompt_len]),
                max_new_tokens=len(gen) + int(snapshot.budget),
                stop_token_id=(None if snapshot.stop_token_id < 0
                               else snapshot.stop_token_id))
            req.tokens = list(gen)
        elif len(req.tokens) != len(gen):
            self._c_rejected.inc()
            raise ValueError(
                f"handle carries {len(req.tokens)} streamed tokens but "
                f"the snapshot generated {len(gen)} — resume would "
                f"misalign the stream")
        with self._work:
            if self._stop or not self._accepting:
                raise EngineStopped("engine is shutting down")
            now = self._clock()
            if req.submit_time is None:
                req.submit_time = now
                if req.timeout_s is not None:
                    req.deadline = now + req.timeout_s
                self._c_submitted.inc()
            if self.trace is not None:
                if req.trace_id is None:
                    req.trace_id = self.trace.start()
                self.trace.emit(req.trace_id, "import_enqueued",
                                blocks=snapshot.n_blocks,
                                bytes=snapshot.nbytes,
                                resumed_tokens=len(gen),
                                src_replica=snapshot.src_replica)
            self._imports.append((snapshot, req))
            self._work.notify_all()
        return req

    def generate(self, prompt, timeout: Optional[float] = None,
                 **kw) -> List[int]:
        """Blocking one-shot: submit + wait for the full output. On
        wait timeout the request is cancelled (not left occupying a
        batch slot and its KV blocks) before TimeoutError propagates."""
        req = self.submit(prompt, **kw)
        try:
            return req.result(timeout)
        except TimeoutError:
            self.cancel(req)
            raise

    def stream(self, prompt, **kw) -> Iterator[int]:
        """Incremental one-shot: yields tokens as they are generated."""
        return self.submit(prompt, **kw).stream()

    def cancel(self, req: GenerationRequest) -> None:
        req.cancel()
        with self._work:
            self._work.notify_all()

    @property
    def is_idle(self) -> bool:
        with self._lock:
            return (not self._running and not len(self.queue)
                    and not self._parked and not self._imports)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until queue + parked retries + pending imports +
        in-flight are empty; False on timeout. Returns promptly after
        a watchdog trip (the stranded set is already failed — nothing
        will ever drain)."""
        deadline = None if timeout is None else self._clock() + timeout
        with self._work:
            while (self._running or len(self.queue) or self._parked
                   or self._imports):
                rem = self._idle_poll_s if deadline is None else \
                    min(self._idle_poll_s, deadline - self._clock())
                if rem <= 0:
                    return False
                self._work.wait(rem)
        return True

    def drain_export(self, timeout: float = 2.0) -> List:
        """Stop admissions and hand every in-flight request's KV out as
        (snapshot, request) pairs — the supervisor's pre-teardown move,
        so a respawned replica resumes them via submit_import() without
        re-prefill. The engine thread runs the export (it owns the
        batcher); this caller blocks until it does or `timeout` passes.

        Returned pairs keep their handles OPEN (still streaming to the
        consumer) — the caller MUST either re-import them or fail them.
        Requests with nothing exportable (still in prefill, export
        failed) and everything queued/parked fail here with reason
        "drained_for_restart" — a replica-indicting reason the Router's
        failover predicate re-places via warm re-prefill. Returns []
        when the loop is not running / wedged / broken (nothing can
        export — callers fall back to the cold path)."""
        box: List = []
        with self._work:
            if (self._thread is None or self._wedged
                    or self._broken is not None or self._stop):
                return []
            self._accepting = False
            self._drain_export_box = box
            self._work.notify_all()
            deadline = self._clock() + timeout
            # the engine thread performs the whole drain under ONE lock
            # hold, so the box is either untouched or complete — on
            # timeout (thread stuck in a device call) withdraw the
            # order; the caller proceeds cold
            while self._drain_export_box is not None:
                rem = deadline - self._clock()
                if rem <= 0:
                    self._drain_export_box = None
                    return []
                self._work.wait(min(self._idle_poll_s, rem))
        return box

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> bool:
        """Stop the engine. drain=True (graceful) completes queued and
        in-flight work first; drain=False cancels everything pending.
        Returns True for a clean stop; False when the drain or the
        thread join timed out (pending requests are then CANCELLED by
        the engine thread as it exits, so blocked result()/stream()
        consumers always unblock)."""
        clean = True
        deadline = None if timeout is None else self._clock() + timeout
        with self._work:
            self._accepting = False
            self._work.notify_all()
        if drain and self._thread is not None:
            clean = self.drain(timeout)
        with self._work:
            self._stop = True
            self._work.notify_all()
        self._wd_stop.set()
        if self._wd_thread is not None:
            self._wd_thread.join(1.0)
        if self._thread is not None:
            # one shared budget: drain may have spent part (or all) of it
            budget = (None if deadline is None
                      else max(0.0, deadline - self._clock()))
            # ptlint: guarded-by(_wedged-latch) — one-way bool set under
            # the lock, read lock-free: a stale False only costs a
            # longer (still bounded) join
            if self._wedged:
                # the engine thread is presumed wedged inside a device
                # call that may never return — a bounded join instead
                # of a silent hang; every request handle was already
                # failed by the watchdog, so nothing is lost by leaving
                # the daemon thread behind
                budget = 1.0 if budget is None else min(budget, 1.0)
            self._thread.join(budget)
            if self._thread.is_alive():
                # still mid decode-step; it cancels pending work itself
                # at the next loop check (only the engine thread may
                # touch the batcher — doing it here would double-free)
                return False
        else:
            # never started: no other thread owns the batcher
            self._cancel_pending_taking_lock()
        return clean

    def _cancel_pending_taking_lock(self) -> None:
        with self._work:
            self._cancel_pending_locked()

    def _cancel_pending_locked(self) -> None:
        """Cancel everything queued + parked + pending imports + in
        flight (lock held)."""
        for _, req in self._parked:
            self._finish_locked(req, RequestState.CANCELLED,
                                "engine_shutdown")
        self._parked.clear()
        for _snap, req in self._imports:
            self._finish_locked(req, RequestState.CANCELLED,
                                "engine_shutdown")
        self._imports.clear()
        for req in self.queue.clear():
            self._finish_locked(req, RequestState.CANCELLED,
                                "engine_shutdown")
        for rid, req in list(self._running.items()):
            self.batcher.abort(rid)
            self.batcher.release(rid)
            self._finish_locked(req, RequestState.CANCELLED,
                                "engine_shutdown")
        self._running.clear()
        self._update_gauges_locked()

    def __enter__(self) -> "ServingEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    def snapshot(self) -> Dict:
        """Metrics snapshot with pool stats folded in (plain dict).
        Reads the engine thread's cached allocator view — never the
        live allocator, which only the engine thread may touch."""
        # what the step programs cost to trace, lower and compile or read
        # back, from the process's compile log (a process with several
        # engines sees them all); the log has its own lock
        compiles = compile_log.summary(_STEP_PROGRAMS)
        with self._lock:
            snap = self.metrics.snapshot()
            snap["replica_id"] = self.replica_id
            snap["compiles"] = compiles
            snap["allocator"] = dict(self._alloc_stats)
            snap["prefix_cache"] = dict(self._prefix_stats)
            snap["attention_impl"] = self.attention_impl
            # the RESOLVED quantization config + the byte accounting it
            # implies (kv_block_bytes includes the int8 scale-pool
            # overhead — quantization.kv is the single source)
            b = self.batcher
            snap["quantization"] = {
                "weight_dtype": self.weight_dtype,
                "kv_dtype": self.kv_dtype,
                "weight_bytes": b.weight_bytes(),
                "kv_pool_bytes": b.kv_pool_bytes(),
                "kv_block_bytes": b.kv_block_bytes(),
                "kv_bytes_per_token": b.kv_bytes_per_token(),
            }
            # speculative decoding: resolved config + acceptance
            # accounting (enabled False and zeros when decoding plain)
            snap["speculative"] = b.spec_stats()
            # tensor-parallel serving: mesh shape + per-device bytes
            # ("mesh" None for a single-device replica — exposition
            # stays shape-stable either way)
            snap["tp"] = dict(self._mesh_info)
            # operators must notice missing forensics: the last failed
            # flight-dump disk write (None when every write landed)
            snap["last_flight_dump_error"] = self._last_dump_error
            snap["health"] = self._health_locked()
        return snap

    def load(self) -> Dict:
        """Cheap per-replica routing view (no full metrics snapshot):
        admission-queue depth, in-flight count, KV block-pool occupancy
        (engine-thread cached allocator stats — never the live
        allocator) and whether submit() would currently accept. The
        Router's policy scores replicas on exactly this dict plus
        `health()` — one lock hop per replica per routing decision."""
        with self._lock:
            stats = self._alloc_stats
            return {
                "replica_id": self.replica_id,
                "role": self.role,
                "queue_depth": len(self.queue),
                "in_flight": len(self._running),
                "parked_retries": len(self._parked),
                "pending_imports": len(self._imports),
                "kv_utilization": (stats["blocks_in_use"]
                                   / stats["capacity_blocks"]),
                "accepting": self._accepting and not self._stop
                and not self._wedged and self._broken is None,
            }

    def recent_prompts(self) -> List[Tuple[List[int], int]]:
        """Recently COMPLETED live request shapes, oldest first:
        (prompt tokens, resolved max_new budget) per entry, bounded
        ring. The supervisor's `probe_mirror` restart gate replays the
        newest through a respawned replica so readiness is proven on
        REAL traffic's shape (bucket, budget) instead of the synthetic
        probe prompt's."""
        with self._lock:
            return [(list(p), mn) for p, mn in self._recent_prompts]

    def health(self) -> Dict:
        """Per-replica health: the signal a multi-replica router polls
        before routing traffic here. `status` is "HEALTHY" (no recent
        faults), "DEGRADED" (a step fault/quarantine inside the last
        `health_window_s` — the engine recovered and keeps serving), or
        "UNHEALTHY" (the hung-step watchdog tripped: the engine thread
        is presumed wedged and no longer serves). The counters cover
        the engine's lifetime; `last_fault_age_s` and `parked_retries`
        describe right now."""
        with self._lock:
            return self._health_locked()

    def _health_locked(self) -> Dict:
        now = self._clock()
        if self._wedged or self._broken is not None:
            status = "UNHEALTHY"
        elif (self._last_fault_t is not None
              and now - self._last_fault_t <= self._health_window_s):
            status = "DEGRADED"
        else:
            status = "HEALTHY"
        return {
            "status": status,
            "replica_id": self.replica_id,
            "role": self.role,
            # mesh attribution: a multi-chip replica's health rolls up
            # through the Router with its device footprint attached
            "mesh": self._mesh_info["mesh"],
            # fast-path attribution: the RESOLVED backends this replica
            # runs (not the "auto" it may have been configured with)
            "attention_impl": self._mesh_info["attention_impl"],
            "spec_backend": self._mesh_info["spec_backend"],
            # readiness: warmed (no cold-compile TTFT cliffs left),
            # loop live, and not declared dead — the supervisor's
            # readiness gate requires this True (plus a served probe)
            # before a respawned replica rejoins rotation
            "ready": (self._warmed and self._thread is not None
                      and not self._wedged and self._broken is None
                      and not self._stop),
            "broken": self._broken,
            "step_faults": self._c_step_faults.value,
            "quarantines": self._c_quarantines.value,
            "requests_requeued": self._c_requeued.value,
            "requests_restored": self._c_restored.value,
            "requests_retried": self._c_retried.value,
            "requests_failed": self._c_failed.value,
            "watchdog_trips": self._c_watchdog.value,
            "flight_dump_errors": self._c_dump_errors.value,
            "last_fault_age_s": (None if self._last_fault_t is None
                                 else now - self._last_fault_t),
            "parked_retries": len(self._parked),
            # the SLO engine's verdict (None with slo=False): burn
            # rates + OK/WARN/BREACH per objective. Detail, not a
            # health state — a BREACH degrades, supervision decides
            "slo": self._slo_eval(),
        }

    def _slo_eval(self) -> Optional[Dict]:
        """Evaluate the SLO tracker (cached per its eval_every_s),
        sync the burn-rate gauges and breach counter, and emit one
        slo_breach / slo_recovered trace span per verdict transition
        (the tracker hands each edge out exactly once). Called with
        self._lock held (health() and the loop's gauge refresh); the
        tracker and sink take only their own leaf locks."""
        if self._slo is None:
            return None
        report = self._slo.evaluate()
        for name, o in report["objectives"].items():
            self._g_slo_burn[name].set(o["burn_rate_fast"])
        new = report["breaches_total"] - self._slo_breaches_seen
        if new > 0:
            self._c_slo_breaches.inc(new)
            self._slo_breaches_seen = report["breaches_total"]
        for tr in self._slo.pop_transitions():
            if self.trace is not None:
                self.trace.span(
                    "slo_breach" if tr["edge"] == "breach"
                    else "slo_recovered", dur=0.0,
                    objective=tr["objective"],
                    burn_rate_fast=tr["burn_rate_fast"],
                    target=tr["target"],
                    value_fast=tr["value_fast"],
                    # the breach verdict was computed over the trailing
                    # fast window — trace_report extends the breach
                    # window start back by this, so the requests whose
                    # samples TRIGGERED the breach are attributed to it
                    window_s=self._slo.fast_window_s,
                    replica_id=self.replica_id)
        return report

    def capture_profile(self, steps: int = 8,
                        timeout: Optional[float] = 30.0) -> Dict:
        """On-demand device-time capture window: fence the next
        `steps` batcher ticks (every device call, also one that reads
        nothing back), block until the window closes (bounded by `timeout` —
        an IDLE engine produces no ticks, so the report then comes
        back with ``capture.complete`` False), and return the
        profiler's report: per-shape device-wall histograms plus one
        record per captured step. The fenced steps also land
        device-lane spans and per-chunk ``device_dur`` annotations in
        the TraceSink, so ``to_chrome_trace()`` timelines carry device
        wall next to host wall. Callable from any thread — the
        frontend's ``POST /debug/profile`` calls exactly this."""
        prof = self.batcher.profiler
        prof.arm_capture(steps)
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        while prof.capture_active():
            if deadline is not None and time.monotonic() > deadline:
                # disarm on timeout: a leftover window would fence
                # every future tick once traffic resumes
                prof.cancel_capture()
                break
            time.sleep(0.005)
        return prof.report()

    def dump_flight_recorder(self, path: Optional[str] = None) -> Dict:
        """On-demand forensic dump: the batcher's last-N step records
        (mode, unit composition, bucket/pad, pool state, compile-memo
        hit/miss) plus allocator and queue state, as one JSON-safe
        dict — written to `path` when given. The same dump fires
        automatically on a step failure (`last_flight_dump` /
        `last_flight_dump_json`). Callable from any thread: the ring
        itself reads through its own lock; the surrounding pool/queue
        numbers are best-effort point-in-time reads that may be torn
        against a concurrently-running step() (forensic snapshot, not
        a transaction — only the failure-path dump, taken by the
        engine thread itself, is step-consistent)."""
        dump = self._flight_dump()
        if path is not None:
            with open(path, "w") as f:
                json.dump(dump, f, indent=2)
        return dump

    def _flight_dump(self, error: Optional[BaseException] = None) -> Dict:
        b = self.batcher
        with self._lock:
            records = b.flight.records()
            return {
                "error": None if error is None else repr(error),
                "failing_record": records[-1] if records else None,
                "records": records,
                "allocator": b.alloc_stats(),
                "queue_depth": len(self.queue),
                "running_rids": sorted(self._running),
                "pending_rids": [e[0].rid for e in b._pending],
                "active_slots": sum(b.active),
                "free_slots": b.free_slots(),
                "attention_impl": self.attention_impl,
                "replica_id": self.replica_id,
            }

    def _record_failure_dump(self, error: BaseException) -> None:
        """Step-failure boundary: snapshot the flight recorder + pool/
        queue state BEFORE the in-flight set is torn down, keep it on
        `last_flight_dump`/`last_flight_dump_json`, and best-effort
        write it to `flight_dump_path` when configured (a dump-write
        failure must never mask the original step error)."""
        dump = self._flight_dump(error)
        self.last_flight_dump = dump
        self.last_flight_dump_json = json.dumps(dump)
        if self._flight_dump_path is not None:
            try:
                with open(self._flight_dump_path, "w") as f:
                    f.write(self.last_flight_dump_json)
            except OSError as we:
                # counted, never silent: missing forensics on disk is
                # an operational fact snapshot()/health() must surface
                # even though it may not mask the original step error
                self._c_dump_errors.inc()
                with self._lock:
                    self._last_dump_error = repr(we)

    # ---- engine thread ---------------------------------------------------
    def _loop(self) -> None:
        while True:
            with self._work:
                if self._wedged:
                    return    # watchdog tore everything down already
                if self._broken is not None:
                    return    # livelock fuse declared the engine dead
                if self._stop:
                    # exit path owns the batcher: cancel whatever is
                    # left so no consumer stays blocked on its channel
                    self._cancel_pending_locked()
                    return
                if self._drain_export_box is not None:
                    # supervisor teardown: hand the in-flight set's KV
                    # out as snapshots before anything else reshapes it
                    self._drain_export_locked()
                with RecordEvent("engine.housekeeping"):
                    self._reap_queued_locked()
                    self._reap_running_locked()
                    self._release_parked_locked()
                    self._process_imports_locked()
                    self._admit_locked()
                    self._update_gauges_locked()
                if (not self._running and not len(self.queue)
                        and not self._imports):
                    if self._parked:
                        # a backoff retry is the only pending work:
                        # sleep just until the earliest one is ready
                        delay = min(e[0] for e in self._parked) \
                            - self._clock()
                        if delay > 0:
                            with RecordEvent("engine.idle"):
                                self._work.wait(min(self._idle_poll_s,
                                                    delay))
                        continue
                    if not self._accepting:
                        return            # graceful drain complete
                    self._work.notify_all()      # wake drain() waiters
                    # idle: nothing queued or in flight means no
                    # deadline can expire either, and every waker
                    # (submit/cancel/shutdown) notifies — block outright,
                    # under a span of its own: a device gap here is "no
                    # request was there", not the last step's tail
                    with RecordEvent("engine.idle"):
                        self._work.wait()
                    continue
            # the decode chunk runs OUTSIDE the lock: the batcher is only
            # ever touched from this thread, so submit()/cancel() stay
            # responsive during device work
            timer = self.metrics.timer("serving.step_s")
            self._step_t0 = self._clock()    # watchdog arms on this
            try:
                with timer:
                    emitted, finished = self.batcher.step()
            # ptlint: disable=EXC001 — step boundary: quarantine decides
            # per-request fate; errors re-raise in culprits' result()
            except Exception as e:        # device-step boundary
                self._step_t0 = None
                # ptlint: guarded-by(_wedged-latch) — one-way latch;
                # loop re-checks under the lock at the next tick top
                if self._wedged:
                    continue  # watchdog already failed the stranded set
                # forensics FIRST: the dump captures the queue/pool
                # state at failure, before recovery reshuffles the
                # in-flight set
                self._record_failure_dump(e)
                self._fault_streak += 1
                ticked = self.batcher.flight.seq != self._flight_seq
                if (self._quarantine_on and ticked
                        and self._fault_streak <= self._max_fault_streak):
                    self._quarantine(e)
                else:
                    # no tick recorded (admission-time failure — the
                    # ring's last record is stale, no basis to convict)
                    # or the livelock fuse blew: conservative fail-all
                    self._fail_all_running(e)
                    if self._fault_streak > self._max_fault_streak:
                        # the fuse is a replica-level verdict: this
                        # engine cannot complete a step — declare it
                        # UNHEALTHY so a supervisor respawns it instead
                        # of it livelocking through fail-all forever
                        self._mark_broken("fault_streak", e)
                self._flight_seq = self.batcher.flight.seq
                continue
            self._step_t0 = None
            self._fault_streak = 0
            self._flight_seq = self.batcher.flight.seq
            # ptlint: guarded-by(_wedged-latch) — one-way latch; a stale
            # False just dispatches tokens to already-failed handles
            if self._wedged:
                continue      # stranded set already failed; don't dispatch
            with RecordEvent("engine.deliver"):
                self._dispatch(emitted, finished, step_dt=timer.elapsed)

    def _reap_queued_locked(self) -> None:
        now = self._clock()
        for req in self.queue.reap(
                lambda r: r.cancel_requested or self._expired(r, now)):
            state = (RequestState.CANCELLED if req.cancel_requested
                     else RequestState.TIMED_OUT)
            self._finish_locked(req, state, "reaped_in_queue")
        # parked backoff retries honor cancellation/deadlines too — a
        # retry waiting out its backoff is still the consumer's request
        dead = [e for e in self._parked
                if e[1].cancel_requested or self._expired(e[1], now)]
        if dead:
            self._parked = [e for e in self._parked if e not in dead]
            for _, req in dead:
                state = (RequestState.CANCELLED if req.cancel_requested
                         else RequestState.TIMED_OUT)
                self._finish_locked(req, state, "reaped_parked")

    def _reap_running_locked(self) -> None:
        now = self._clock()
        for rid, req in list(self._running.items()):
            if req.cancel_requested or self._expired(req, now):
                self.batcher.abort(rid)
                self.batcher.release(rid)
                del self._running[rid]
                state = (RequestState.CANCELLED if req.cancel_requested
                         else RequestState.TIMED_OUT)
                self._finish_locked(req, state, "reaped_in_flight")

    def _expired(self, req: GenerationRequest, now: float) -> bool:
        return req.deadline is not None and now > req.deadline

    @staticmethod
    def _effective(req: GenerationRequest) -> List[int]:
        """The prompt a (re-)admission actually prefills: the original
        prompt plus every token already streamed — a fresh request's is
        just its prompt; a quarantine-requeued victim's resumes decode
        from where the failed step stopped."""
        return req.prompt + req.tokens if req.tokens else req.prompt

    def _admit_locked(self) -> None:
        b = self.batcher
        free_slots = b.free_slots()
        if free_slots <= 0:
            return
        # cache-aware ordering: at EQUAL effective priority, prefer the
        # request whose prefix is cached right now — serving it before
        # eviction recycles those blocks converts reclaimable KV into
        # skipped prefill (pure trie walk, no refcount moves). Memoized
        # per admission round: pop_many() evaluates prefer on EVERY
        # queued item, and one walk per request is enough — the slight
        # staleness across this round is harmless (same tolerance as
        # the block budget below).
        prefer = None
        if b.prefix_stats().get("enabled") is True:
            warm = {}        # id(req) -> bool, one trie walk per request

            def prefer(r):
                if id(r) not in warm:
                    warm[id(r)] = b.prefix_cached_tokens(
                        self._effective(r)) > 0
                return warm[id(r)]
        budget = {"blocks": b.alloc.free_blocks}

        def fits(r):   # max_new_tokens was resolved by submit()
            # cached-aware: a prompt whose prefix is already pinned by
            # an in-flight request needs fewer blocks of its own.
            # pop_many calls fits once per ACCEPTED item, so the block
            # budget is debited right here.
            eff = self._effective(r)
            n = b.blocks_needed(len(eff), r.max_new_tokens - len(r.tokens),
                                tokens=eff)
            if n > budget["blocks"]:
                return False
            budget["blocks"] -= n
            return True

        # one lock acquisition and one consistent priority view for the
        # whole admission round; the burst lands in the batcher's queue
        # together, so same-bucket requests prefill in one compiled
        # call. A request cancelled in the microseconds since
        # _reap_queued_locked still consumes its slot + block budget
        # for THIS round (reaped below instead of admitted) — the next
        # loop tick re-admits at full budget, a deliberate trade for
        # the single-round queue view.
        now = self._clock()
        for req in self.queue.pop_many(free_slots, fits=fits,
                                       prefer=prefer):
            if req.cancel_requested or self._expired(req, now):
                state = (RequestState.CANCELLED if req.cancel_requested
                         else RequestState.TIMED_OUT)
                self._finish_locked(req, state, "reaped_at_admission")
                continue
            # resume-aware: a quarantine/retry re-admission carries the
            # tokens already streamed as part of its prompt (warm via
            # the prefix cache) with the remaining budget, so decode
            # picks up exactly where it stopped and nothing re-emits
            resumed = bool(req.tokens) or req.admit_time is not None
            rid = b.submit(self._effective(req),
                           stop_token_id=req.stop_token_id,
                           max_new_tokens=req.max_new_tokens
                           - len(req.tokens),
                           # quarantine's plain-decode fallback: a
                           # request that rode a failed spec tick
                           # re-admits opted out of the spec pipeline
                           speculative=False if req.spec_opt_out
                           else None)
            req.request_id = rid
            req.state = RequestState.PREFILL
            if self.trace is not None and req.trace_id is not None:
                # batcher-side emissions (prepared / prefill_chunk /
                # retired) resolve to this request's timeline via rid
                self.trace.alias(rid, req.trace_id)
                self.trace.emit(req.trace_id, "admitted", rid=rid,
                                resumed=resumed,
                                queue_wait_s=now - req.submit_time)
            if not resumed:
                # first admission only: queue-wait/admitted measure the
                # original arrival, not recovery churn (requeues and
                # retries have their own counters)
                req.admit_time = now
                req.admitted_index = self._admit_seq
                self._admit_seq += 1
                self._h_wait.observe(now - req.submit_time)
                if self._slo is not None:
                    self._slo.record_queue_wait(now - req.submit_time)
                self._c_admitted.inc()
            self._running[rid] = req

    def _process_imports_locked(self) -> None:
        """Activate pending KV-snapshot adoptions (engine thread, lock
        held) — BEFORE fresh admissions: an import resumes a request
        that already streamed tokens, so it outranks cold work.
        Head-of-line in arrival order: when the head does not fit
        (slot/blocks) the whole line waits — fairness over packing,
        same discipline as the admission queue."""
        b = self.batcher
        now = self._clock()
        while self._imports:
            snap, req = self._imports[0]
            if req.cancel_requested or self._expired(req, now):
                self._imports.pop(0)
                state = (RequestState.CANCELLED if req.cancel_requested
                         else RequestState.TIMED_OUT)
                self._finish_locked(req, state, "reaped_pending_import")
                continue
            if (b.free_slots() <= 0
                    or b.import_blocks_needed(snap)
                    > b.alloc.free_blocks):
                break
            self._imports.pop(0)
            on_rid = None
            if self.trace is not None and req.trace_id is not None:
                tid = req.trace_id
                # alias the rid the instant import_kv assigns it, so
                # the batcher's own "imported" emit (fired inside
                # import_kv, before control returns here) resolves to
                # the request's timeline instead of a phantom rid lane
                on_rid = lambda r: self.trace.alias(r, tid)
            try:
                rid = b.import_kv(snap, on_rid=on_rid)
            # ptlint: disable=EXC001 — per-request boundary: a bad
            # snapshot fails ONLY this request; the error is attached
            # to the handle and re-raised in its result()
            except Exception as e:
                self._finish_locked(req, RequestState.FAILED,
                                    "kv_import_failed", error=e)
                continue
            req.request_id = rid
            req.state = RequestState.DECODING
            # no engine-level "imported" emit: the batcher's own (fired
            # inside import_kv, resolved through the on_rid alias)
            # already carries slot/blocks/bytes/resumed_tokens
            if req.admit_time is None:
                req.admit_time = now
                req.admitted_index = self._admit_seq
                self._admit_seq += 1
                self._c_admitted.inc()
            self._c_kv_imports.inc()
            self._running[rid] = req

    def _drain_export_locked(self) -> None:
        """Engine-thread half of drain_export() (lock held): export
        every in-flight request's KV into the caller's box as a
        (snapshot, request) pair — the handle stays OPEN for the
        caller to resume via submit_import() on the respawned engine —
        and fail everything that cannot travel (prefill not committed,
        export raised, queued/parked) with "drained_for_restart" so
        the Router's failover re-places it warm via re-prefill. Runs
        under ONE lock hold: the box is either untouched or complete
        when drain_export()'s wait wakes."""
        box = self._drain_export_box
        b = self.batcher
        for rid, req in list(self._running.items()):
            snap = None
            if not req.cancel_requested:
                try:
                    snap = b.export_kv(rid)
                # ptlint: disable=EXC001 — per-request boundary: an
                # export failure downgrades THIS request to the warm
                # re-prefill path, nothing else
                except Exception:
                    snap = None
            b.abort(rid)
            b.release(rid)
            self._last_emit.pop(rid, None)
            if snap is not None:
                self._c_kv_exports.inc()
                box.append((snap, req))
            else:
                self._finish_locked(req, RequestState.FAILED,
                                    "drained_for_restart")
        self._running.clear()
        # pending adoptions already carry their snapshots — pass them
        # through to the respawned engine untouched
        for snap, req in self._imports:
            box.append((snap, req))
        self._imports.clear()
        for _, req in self._parked:
            self._finish_locked(req, RequestState.FAILED,
                                "drained_for_restart")
        self._parked.clear()
        for req in self.queue.clear():
            self._finish_locked(req, RequestState.FAILED,
                                "drained_for_restart")
        self._drain_export_box = None
        self._update_gauges_locked()
        self._work.notify_all()

    def _dispatch(self, emitted: Dict[int, List[int]],
                  finished: List[int],
                  step_dt: Optional[float] = None) -> None:
        now = self._clock()
        ntok = sum(len(t) for t in emitted.values())
        if step_dt is not None and ntok:
            self._h_token.observe(step_dt / ntok)
        if self._slo is not None and ntok:
            self._slo.record_tokens(ntok)   # goodput floor's numerator
        if self.trace is not None and step_dt is not None:
            # the sink-side twin of the serving.step_s timer span —
            # same duration, so the Chrome trace's steps lane lines up
            # with the histogram (and the XPlane RecordEvent spans)
            self.trace.span("engine.step", dur=step_dt, tokens=ntok)
        # prefill-role surrender: requests that produced their first
        # token(s) this step but did NOT finish hand their KV over as
        # a snapshot (reason "prefill_complete") — collected in the
        # emit loop, exported after it
        handoffs: List[int] = []
        for rid, toks in emitted.items():
            # ptlint: thread-confined — the token bridge: emission runs
            # lock-free on the engine thread so submit()/cancel() stay
            # responsive; rid-keyed dict ops are GIL-atomic and a
            # concurrent cancel only turns this get() into a skip
            req = self._running.get(rid)
            if req is None:
                continue                  # aborted in between
            # ptlint: thread-confined — token bridge (see above): only
            # the engine thread writes ITL timestamps per live rid
            last = self._last_emit.get(rid)
            if last is not None:
                self._h_itl.observe(now - last)
                if self._slo is not None:
                    self._slo.record_itl(now - last)
            # ptlint: thread-confined — token bridge (see above)
            self._last_emit[rid] = now
            traced = self.trace is not None and req.trace_id is not None
            ndelivered = 0
            try:
                for t in toks:
                    if req.first_token_time is None:
                        req.first_token_time = now
                        self._h_ttft.observe(now - req.submit_time)
                        if self._slo is not None:
                            self._slo.record_ttft(now - req.submit_time)
                        # emitted at the stamp, not after the loop: a
                        # later on_token failure must not leave the
                        # timeline disagreeing with the ttft histogram
                        if traced:
                            self.trace.emit(
                                req.trace_id, "first_token",
                                ttft_s=now - req.submit_time)
                    req._deliver(t)
                    ndelivered += 1
                    self._c_tokens.inc()
                    if req.on_token is not None:
                        req.on_token(t)
            # ptlint: disable=EXC001 — per-request boundary: the consumer
            # callback's error fails ONLY this request; it is attached to
            # the handle and re-raised in its result()/stream()
            except Exception as e:        # per-request boundary
                if traced and ndelivered:
                    # the tokens up to the failure WERE delivered
                    self.trace.emit(req.trace_id, "decode_emit",
                                    n=ndelivered)
                self.batcher.abort(rid)
                self.batcher.release(rid)
                with self._work:
                    self._running.pop(rid, None)
                    self._finish_locked(req, RequestState.FAILED,
                                        "on_token_raised", error=e)
            else:
                if traced:
                    self.trace.emit(req.trace_id, "decode_emit",
                                    n=len(toks))
                if self.role == "prefill" and rid not in finished:
                    handoffs.append(rid)
        for rid in handoffs:
            self._surrender(rid)
        with self._work:
            for rid in finished:
                self.batcher.release(rid)    # tokens already delivered
                req = self._running.pop(rid, None)
                if req is None:
                    continue
                self._finish_locked(req, RequestState.FINISHED,
                                    self._finish_reason(req))
            self._update_gauges_locked()
            self._work.notify_all()

    def _surrender(self, rid: int) -> None:
        """Prefill-role handoff (engine thread): the request committed
        its first token(s) — prefill is done, decode belongs to a
        decode replica. Export its KV, attach the snapshot to the
        handle and FINISH it with reason "prefill_complete"; a
        disaggregated Router migrates the snapshot to a decode replica
        and the client stream continues seamlessly. When the export
        itself fails the snapshot stays None and the Router falls back
        to warm re-prefill from `prompt + tokens` — same terminal
        reason, one fallback ladder."""
        with self._work:
            req = self._running.get(rid)
        if req is None:
            return
        snap = None
        try:
            snap = self.batcher.export_kv(rid)
        # ptlint: disable=EXC001 — per-request boundary: an export
        # failure downgrades THIS handoff to the re-prefill path
        except Exception:
            snap = None
        self.batcher.abort(rid)
        self.batcher.release(rid)
        with self._work:
            self._running.pop(rid, None)
            self._last_emit.pop(rid, None)
            req.kv_snapshot = snap
            self._c_handoffs.inc()
            if snap is not None:
                self._c_kv_exports.inc()
            if self.trace is not None and req.trace_id is not None:
                self.trace.emit(
                    req.trace_id, "prefill_complete",
                    exported=snap is not None,
                    bytes=0 if snap is None else snap.nbytes,
                    tokens_kept=len(req.tokens))
            self._finish_locked(req, RequestState.FINISHED,
                                "prefill_complete")

    def _finish_reason(self, req: GenerationRequest) -> str:
        last = req.tokens[-1] if req.tokens else None
        if req.stop_token_id is not None and last == req.stop_token_id:
            return "stop_token"
        if self.batcher.eos is not None and last == self.batcher.eos:
            return "eos"
        return "length"

    def _finish_locked(self, req: GenerationRequest, state: RequestState,
                       reason: str, error=None) -> None:
        counter = {
            RequestState.FINISHED: self._c_completed,
            RequestState.CANCELLED: self._c_cancelled,
            RequestState.TIMED_OUT: self._c_timed_out,
            RequestState.FAILED: self._c_failed,
        }[state]
        if not req.done:
            counter.inc()
            if state is RequestState.FINISHED:
                # feed the shadow-probe ring: only CLEANLY served
                # requests are worth replaying through a respawn gate
                # (a failed shape would gate readiness on a poison)
                self._recent_prompts.append(
                    (list(req.prompt),
                     self.batcher.max_new if req.max_new_tokens is None
                     else req.max_new_tokens))
                del self._recent_prompts[:-self._recent_prompts_cap]
            if self._slo is not None and state in (
                    RequestState.FINISHED, RequestState.FAILED,
                    RequestState.TIMED_OUT):
                # error_rate feed: FAILED/TIMED_OUT are server misses;
                # a cancellation is the client's choice, not recorded
                self._slo.record_request(
                    state is not RequestState.FINISHED)
            if self.trace is not None and req.trace_id is not None:
                self.trace.finish(
                    req.trace_id, state.name.lower(), reason=reason,
                    error=None if error is None else repr(error))
        self._last_emit.pop(req.request_id, None)
        req._finish(state, reason, error=error, now=self._clock())
        self._work.notify_all()

    # ---- fault tolerance -------------------------------------------------
    def _quarantine(self, error: BaseException) -> None:
        """Step-failure recovery (engine thread): convict by re-running
        the failing tick's units individually, FAIL (or park for a
        backoff retry) only the culprits, and requeue every innocent
        in-flight request at the front of the admission queue — each
        victim re-admits with `prompt + tokens` so greedy decode
        resumes exactly where it stopped, warm through the prefix
        cache (the failed tick's retire path registered its blocks).

        Suspects come from the flight recorder's last record: decode
        slot rids for a decode tick, decode rids + unit rids for a
        fused tick, unit rids for a standalone prefill (the batcher
        already rolled those back onto its queue). A suspect whose solo
        probe raises is a culprit; when NO probe reproduces the failure
        (a transient — fail-once-then-heal, allocator pressure), every
        suspect is treated as a transient culprit and charged a retry,
        so recovery still converges instead of replaying the same
        doomed co-batch forever."""
        b = self.batcher
        records = b.flight.records()
        rec = records[-1] if records else {}
        mode = rec.get("mode")
        if mode == "fused":
            suspects = list(rec.get("decode_rids", [])) + \
                [r for u in rec.get("units", []) for r in u]
        else:       # "decode" | "prefill" | "spec_*" all carry rids
            suspects = list(rec.get("rids", []))
        # a FAILED speculative tick indicts the spec pipeline for the
        # requests riding it: every survivor (requeued victim or
        # retried culprit) falls back to plain decode on re-admission
        # — the draft/verify pair must not get a second chance to
        # poison the same request's recovery
        spec_tick = str(mode or "").startswith("spec")
        with self._lock:
            self._c_step_faults.inc()
            self._c_quarantines.inc()
            self._last_fault_t = self._clock()
            suspects = [r for r in suspects if r in self._running]
        # probes run OUTSIDE the lock (device work; only this thread
        # touches the batcher) so submit()/cancel() stay responsive —
        # and UNDER the watchdog (_step_t0 armed per probe): a probe is
        # a device re-execution and can hang exactly like the step did
        culprits: Dict[int, BaseException] = {}
        for rid in suspects:
            slot = next((s for s in range(b.B)
                         if b.active[s] and b.slot_req[s] == rid), None)
            self._step_t0 = self._clock()
            try:
                if slot is not None:
                    b.probe_decode_slot(slot)
                else:
                    b.probe_queued(rid)
            # ptlint: disable=EXC001 — probe verdict boundary: ANY error
            # re-raised solo convicts this request; it is attached to the
            # handle and re-raised in its result()
            except Exception as pe:
                culprits[rid] = pe
            finally:
                self._step_t0 = None
            # ptlint: guarded-by(_wedged-latch) — one-way latch read
            if self._wedged:
                # a hung probe tripped the watchdog: every handle is
                # already failed — no recovery left to run
                return
        convicted = bool(culprits)
        if not convicted:
            # nobody reproduces solo: transient — every suspect pays a
            # retry (bounded by max_retries, so this converges)
            culprits = {rid: error for rid in suspects}
        with self._work:
            order = sorted(self._running.items(),
                           key=lambda kv: kv[1].admitted_index or 0)
            victims: List[GenerationRequest] = []
            restorable: List = []        # (request, snapshot) innocents
            for rid, req in order:
                snap = None
                if rid not in culprits and not req.cancel_requested:
                    # slot-in-place recovery (PR 8 follow-on): a call
                    # that failed BEFORE its dispatch committed NOTHING
                    # (commits happen after the device call returns), so
                    # an innocent's slot state is intact — export its KV
                    # now and re-import below instead of requeueing it
                    # through a full re-prefill of `prompt + tokens`. A
                    # call that failed AFTER dispatch took the donated
                    # pool with it: the batcher rebuilt it empty and
                    # dropped every slot (`_drop_lost_pool`), the export
                    # raises, and the request takes the requeue path
                    try:
                        snap = b.export_kv(rid)
                    # ptlint: disable=EXC001 — per-request boundary: an
                    # unexportable innocent degrades to the requeue path
                    except Exception:
                        snap = None
                b.abort(rid)
                b.release(rid)
                self._last_emit.pop(rid, None)
                if spec_tick:
                    req.spec_opt_out = True
                if rid in culprits:
                    self._retry_or_fail_locked(req, culprits[rid],
                                               convicted)
                elif snap is not None:
                    restorable.append((req, snap))
                else:
                    victims.append(req)
            self._running.clear()
            for req, snap in restorable:
                try:
                    rid2 = b.import_kv(snap)
                # ptlint: disable=EXC001 — per-request boundary: a
                # failed re-import falls back to the requeue path —
                # nothing lost, just cold
                except Exception:
                    victims.append(req)
                    continue
                req.request_id = rid2
                self._running[rid2] = req
                self._c_kv_exports.inc()
                self._c_kv_imports.inc()
                self._c_restored.inc()
                if self.trace is not None and req.trace_id is not None:
                    self.trace.alias(rid2, req.trace_id)
                    self.trace.emit(req.trace_id, "restored",
                                    reason="quarantine_victim",
                                    rid=rid2,
                                    tokens_kept=len(req.tokens),
                                    re_prefill=0,
                                    spec_fallback=spec_tick)
            for req in victims:
                self._c_requeued.inc()
                if self.trace is not None and req.trace_id is not None:
                    self.trace.emit(req.trace_id, "requeued",
                                    reason="quarantine_victim",
                                    tokens_kept=len(req.tokens),
                                    spec_fallback=spec_tick)
            self.queue.requeue(victims)
            self._update_gauges_locked()
            self._work.notify_all()

    def _retry_or_fail_locked(self, req: GenerationRequest,
                              error: BaseException,
                              convicted: bool) -> None:
        """A quarantined culprit's fate: transient-looking failures
        (per the `retry_transient` predicate) park for an exponential-
        backoff re-admission until `max_retries` is spent; everything
        else — and an exhausted budget — is terminal FAILED."""
        try:
            transient = bool(self._retry_transient(error))
        # ptlint: disable=EXC001 — user-supplied predicate boundary: a
        # broken predicate must degrade to fail-fast, not kill the loop
        except Exception:
            transient = False
        if transient and req.retries < self._max_retries:
            req.retries += 1
            self._c_retried.inc()
            backoff = self._retry_backoff_s * (2.0 ** (req.retries - 1))
            if self.trace is not None and req.trace_id is not None:
                self.trace.emit(req.trace_id, "retried",
                                retries=req.retries, backoff_s=backoff,
                                convicted=convicted, error=repr(error))
            self._parked.append([self._clock() + backoff, req])
        else:
            reason = ("retries_exhausted" if transient
                      else "quarantine_culprit")
            self._finish_locked(req, RequestState.FAILED, reason,
                                error=error)

    def _release_parked_locked(self) -> None:
        """Move backoff-expired retries to the front of the admission
        queue (they held admission before; fresh traffic waits)."""
        if not self._parked:
            return
        now = self._clock()
        ready = [e[1] for e in self._parked if e[0] <= now]
        if ready:
            self._parked = [e for e in self._parked if e[0] > now]
            self.queue.requeue(ready)

    def _watchdog_loop(self) -> None:
        """Monitor thread: a device step still running past
        `watchdog_s` means the engine thread is wedged inside a call
        that may never return — dump forensics, flip health to
        UNHEALTHY and fail the stranded requests' HANDLES (channels
        and events only: the batcher belongs to the wedged thread and
        its device state is unrecoverable anyway) so consumers,
        drain() and shutdown() unblock with a clear error."""
        poll = max(0.005, min(0.05, self._watchdog_s / 4.0))
        while not self._wd_stop.wait(poll):
            t0 = self._step_t0
            # ptlint: guarded-by(_wedged-latch) — the watchdog is the
            # ONLY writer of _wedged; its own stale read is impossible
            if t0 is None or self._wedged:
                continue
            # compile-vs-hang: on a never-warmed engine ANY step may be
            # paying a fresh trace+compile (first prefill bucket, the
            # decode chunk fn, a new shape later) — a cost the deadline
            # was never sized for, and one that used to masquerade as
            # a hung device call. The compile-grace multiplier covers
            # exactly the unwarmed window; a warmed engine gets no
            # grace (every serving-path executable already compiled).
            deadline = self._watchdog_s
            # ptlint: guarded-by(_warmed-latch) — one-way warmup latch;
            # a stale False only extends the compile grace one poll
            if not self._warmed:
                deadline *= self._wd_grace
            stuck = self._clock() - t0
            if stuck > deadline:
                self._trip_watchdog(stuck)

    def _trip_watchdog(self, stuck_s: float) -> None:
        err = HungStepError(
            f"device step exceeded the {self._watchdog_s}s watchdog "
            f"deadline ({stuck_s:.3f}s and counting) — engine thread "
            f"presumed wedged; see last_flight_dump for the hung "
            f"tick's mode and unit composition")
        # forensics first: the flight ring's last record IS the hung
        # tick (recorded before its device call)
        self._record_failure_dump(err)
        with self._work:
            if self._wedged:
                return
            self._wedged = True
            self._accepting = False
            self._c_watchdog.inc()
            self._c_step_faults.inc()
            self._last_fault_t = self._clock()
            stranded = list(self._running.items())
            self._running.clear()
            parked = [e[1] for e in self._parked]
            self._parked.clear()
            queued = self.queue.clear()
            for _, req in stranded:
                self._finish_locked(req, RequestState.FAILED,
                                    "watchdog_hung_step", error=err)
            for req in parked + queued:
                self._finish_locked(req, RequestState.FAILED,
                                    "watchdog_engine_unhealthy",
                                    error=err)
            self._work.notify_all()

    def _mark_broken(self, reason: str, error: BaseException) -> None:
        """Livelock-fuse verdict (engine thread): the engine declares
        itself UNHEALTHY without a wedged thread — in-flight requests
        were already failed by `_fail_all_running`; queued and parked
        ones fail here with `fault_streak_engine_unhealthy` (a
        replica-indicting reason: the Router's default failover
        predicate re-places them on a healthy replica, and a
        supervisor sees UNHEALTHY and respawns this one). The loop
        parks at its next tick; shutdown() joins normally."""
        with self._work:
            if self._broken is not None:
                return
            self._broken = reason
            self._accepting = False
            parked = [e[1] for e in self._parked]
            self._parked.clear()
            for req in parked + self.queue.clear():
                self._finish_locked(req, RequestState.FAILED,
                                    "fault_streak_engine_unhealthy",
                                    error=error)
            self._update_gauges_locked()
            self._work.notify_all()

    def _fail_all_running(self, error: BaseException) -> None:
        """The conservative step-failure fallback (quarantine off, no
        tick recorded, or the consecutive-failure fuse blew): every
        in-flight request fails with the step error attached. The
        failed call committed nothing, so each request's KV is still
        exportable — attach a snapshot to the handle (`kv_snapshot`)
        on the way down: a Router failing the request over to another
        replica imports it there instead of re-prefilling (falling
        back to warm re-prefill when the export didn't land)."""
        with self._work:
            self._c_step_faults.inc()
            self._last_fault_t = self._clock()
            for rid, req in list(self._running.items()):
                try:
                    req.kv_snapshot = self.batcher.export_kv(rid)
                    self._c_kv_exports.inc()
                # ptlint: disable=EXC001 — per-request boundary: a
                # failed export just means this victim re-prefills on
                # the survivor replica
                except Exception:
                    req.kv_snapshot = None
                self.batcher.abort(rid)
                self.batcher.release(rid)
                self._finish_locked(req, RequestState.FAILED,
                                    "decode_step_raised", error=error)
            self._running.clear()
            self._update_gauges_locked()

    def _update_gauges_locked(self) -> None:
        self._slo_eval()
        stats = self.batcher.alloc_stats()
        self._alloc_stats = stats          # snapshot() reads this cache
        pc = self.batcher.prefix_stats()
        self._prefix_stats = pc
        self._g_queue.set(len(self.queue))
        self._g_running.set(len(self._running))
        self._g_blocks.set(stats["blocks_in_use"])
        self._g_util.set(stats["blocks_in_use"] / stats["capacity_blocks"])
        self._g_prefill_compiles.set(self.batcher.prefill_compile_count)
        self._g_compiles.set(self.batcher.compile_count)
        self._g_prefill_pad.set(self.batcher.prefill_pad_tokens)
        self._g_fused_steps.set(self.batcher.fused_steps)
        self._g_fused_units.set(self.batcher.fused_unit_count)
        self._g_decode_stalls.set(self.batcher.decode_stall_steps)
        self._g_kv_cached_bytes.set(self.batcher.kv_cached_bytes())
        sp = self.batcher.spec
        self._g_spec_steps.set(sp.steps)
        self._g_spec_accept.set(sp.accept_rate())
        self._g_spec_tps.set(sp.tokens_per_step())
        self._g_spec_accepted.set(sp.accepted)
        for d in sp.drain_depths():
            self._h_spec_depth.observe(float(d))
        if pc.get("enabled"):
            self._g_pc_hit_tokens.set(pc["hit_tokens"])
            self._g_pc_hit_rate.set(pc["hit_rate"])
            self._g_pc_evictions.set(pc["evictions"])
            self._g_pc_cached.set(pc["cached_blocks"])
