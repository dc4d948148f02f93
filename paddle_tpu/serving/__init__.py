"""paddle_tpu.serving — async request-serving engine over the paged-KV
continuous batcher.

The host-side serving layer the ROADMAP north star calls for: a
thread-backed `ServingEngine` owns a `ContinuousBatcher`
(`paddle_tpu.nlp.paged`) and keeps its in-flight batch saturated from a
bounded priority queue, with per-request lifecycle (deadlines,
cancellation, per-request stop tokens / budgets), streaming output
channels, lock-safe metrics, and a step-level exception boundary that
fails only the affected requests.

    from paddle_tpu import serving

    eng = serving.ServingEngine(params, cfg, max_batch=4,
                                block_size=16, max_total_len=512,
                                max_new_tokens=64)
    out = eng.generate(prompt_ids)                   # blocking
    for tok in eng.stream(prompt_ids):               # incremental
        ...
    req = eng.submit(prompt_ids, priority=1, timeout_s=30.0,
                     stop_token_id=eos)              # async handle
    print(eng.snapshot())                            # metrics + pool
    eng.shutdown()                                   # graceful drain

Modules: `engine` (ServingEngine loop), `request` (lifecycle/channels),
`scheduler` (admission queue: priority + FIFO + aging + backpressure),
`metrics` (counters/gauges/histograms + profiler-span timers +
Prometheus text exposition via `MetricsRegistry.to_prometheus()`),
`cache` (automatic prefix cache: trie index over shared KV blocks,
refcounted by `RefcountingBlockAllocator` — on by default; pass
`prefix_cache=False` to serve cold), `trace` (per-request trace
timelines with Chrome-trace/Perfetto export + the step flight
recorder the engine dumps on a device-step failure), `faults`
(deterministic fault injection: the chaos harness behind the engine's
quarantine / retry / watchdog recovery paths,
tests/test_fault_tolerance.py), `router` (N-replica routing: health +
occupancy + prefix-affinity policy, cross-replica failover via
resume-from-`prompt + tokens`), `supervisor` (self-healing replica
lifecycle: auto-restart with a readiness gate, exponential backoff
and a crash-loop circuit breaker — `Router(auto_restart=True)`),
`kvtransfer` (portable per-request KV-block snapshots: the
dependency-free `KVSnapshot` container behind
`ContinuousBatcher.export_kv`/`import_kv` — disaggregated
prefill/decode handoff via `Router(disaggregated=True)` +
`ServingEngine(role="prefill"|"decode")`, warm failover, and
supervisor drain-export-respawn-resume), `frontend` (stdlib asyncio
HTTP: `POST /v1/generate`,
`POST /v1/stream` SSE, `GET /health`, `GET /metrics` with
per-replica labels, `POST /admin/reset_breaker`,
`POST /debug/profile`), `slo` (the SLO engine: declarative
objectives evaluated over dual rolling windows into burn rates and
OK/WARN/BREACH verdicts — `health()["slo"]`, `slo_burn_rate_*`
gauges, `slo_breaches_total` counters, fleet rollup in the Router),
`profiling` (device-time attribution: every tick that reads its
result back files its issue-to-read-back wall, stamped by the batcher's
tick helper, into per-shape histograms, with no fence; on-demand
capture windows fence the ticks an operator asks for), `speculative` (self-speculative decoding config +
acceptance accounting: the draft-and-verify pipeline behind
`ServingEngine(speculative=True, spec_k=, draft_layers=)` — a
truncated-layer draft proposes k tokens, the target verifies all k+1
positions in one paged call and commits only accepted rows, so greedy
output is provably identical to plain decode while tokens/step
multiplies).
"""
from __future__ import annotations

from .cache import PrefixCacheIndex  # noqa: F401
from .faults import FaultInjector, InjectedFault  # noqa: F401
from .metrics import Counter, Gauge, Histogram, MetricsRegistry  # noqa: F401
from .request import (  # noqa: F401
    GenerationRequest, RequestState, TERMINAL_STATES,
    RequestError, RequestCancelled, RequestFailed, RequestTimedOut,
)
from .profiling import StepProfiler  # noqa: F401
from .scheduler import AdmissionQueue, QueueFullError  # noqa: F401
from .speculative import SpecConfig, SpecStats  # noqa: F401
from .slo import SloTracker, DEFAULT_OBJECTIVES  # noqa: F401
from .kvtransfer import KVSnapshot  # noqa: F401
from .trace import TraceSink, FlightRecorder  # noqa: F401

__all__ = [
    "ServingEngine", "EngineStopped", "HungStepError",
    "GenerationRequest", "RequestState", "TERMINAL_STATES",
    "RequestError", "RequestCancelled", "RequestFailed", "RequestTimedOut",
    "AdmissionQueue", "QueueFullError",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "TraceSink", "FlightRecorder",
    "SloTracker", "StepProfiler",
    "SpecConfig", "SpecStats",
    "KVSnapshot",
    "FaultInjector", "InjectedFault",
    "PrefixCacheIndex", "RefcountingBlockAllocator",
    "ContinuousBatcher", "PagedKVCache",
    "Router", "NoReplicaAvailable", "default_policy", "HttpFrontend",
    "ReplicaSupervisor",
]


def __getattr__(name: str):
    # ServingEngine pulls the nlp model stack — resolve lazily so plain
    # `import paddle_tpu` (which imports this package) stays light
    if name in ("ServingEngine", "EngineStopped", "HungStepError"):
        from . import engine
        return getattr(engine, name)
    if name in ("Router", "NoReplicaAvailable", "default_policy"):
        from . import router
        return getattr(router, name)
    if name == "HttpFrontend":
        from . import frontend
        return getattr(frontend, name)
    if name == "ReplicaSupervisor":
        from . import supervisor
        return getattr(supervisor, name)
    if name in ("ContinuousBatcher", "PagedKVCache",
                "RefcountingBlockAllocator"):
        from ..nlp import paged
        return getattr(paged, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
