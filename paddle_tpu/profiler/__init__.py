"""paddle.profiler — profiling facade over jax.profiler.

Reference analog: python/paddle/profiler/ (Profiler with scheduler
wait/warmup/active windows, RecordEvent RAII spans, Chrome-trace export,
summary tables) over the C++ host tracer + CUPTI device tracer
(paddle/fluid/platform/profiler/) — upstream-canonical, unverified,
SURVEY.md §0, §5 'Tracing/profiling'.

TPU-native design: jax.profiler is the host+device tracer — XPlane traces
capture XLA executions, TPU kernels, and host annotations; the output dir is
TensorBoard/Perfetto/xprof-compatible (the reference exports Chrome trace;
XPlane supersedes it). RecordEvent maps to jax.profiler.TraceAnnotation,
the scheduler windows are re-implemented on step_begin/step_end since XLA
needs no warmup distinction beyond compilation (already cached by step 1).
"""
from __future__ import annotations

import enum
import os
from typing import Callable, Iterable, Optional

import jax


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """paddle.profiler.make_scheduler parity: step → state."""
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """Returns an on_trace_ready callback. The trace lands as XPlane protos
    under dir_name (readable by TensorBoard's profile plugin / xprof, which
    render the same timeline Chrome tracing did for the reference)."""
    def handler(prof):
        pass  # trace already written to prof._dir by stop_trace
    handler._dir = dir_name
    return handler


export_protobuf_tracing = export_chrome_tracing


class Profiler:
    """paddle.profiler.Profiler parity.

    with Profiler(targets=[...], scheduler=(2, 5)) as p:
        for batch in loader:
            train_step(batch)
            p.step()
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready=None, record_shapes=False,
                 profile_memory=False, timer_only=False, **kwargs):
        self._dir = getattr(on_trace_ready, "_dir", None) or \
            os.environ.get("PADDLE_PROFILER_DIR", "/tmp/paddle_tpu_profile")
        if isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self._scheduler = make_scheduler(
                closed=max(lo, 0), ready=0, record=hi - lo, repeat=1)
        elif scheduler is None:
            self._scheduler = None  # record everything between start/stop
        else:
            self._scheduler = scheduler
        self._step = 0
        self._tracing = False
        self._timer_only = timer_only

    # --- lifecycle -------------------------------------------------------
    def start(self):
        if self._scheduler is None:
            self._start_trace()
        else:
            self._apply_state(self._scheduler(self._step))
        return self

    def stop(self):
        if self._tracing:
            self._stop_trace()

    def step(self, num_samples: Optional[int] = None):
        self._step += 1
        if self._scheduler is not None:
            self._apply_state(self._scheduler(self._step))

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # --- internals -------------------------------------------------------
    def _apply_state(self, state: ProfilerState):
        recording = state in (ProfilerState.RECORD,
                              ProfilerState.RECORD_AND_RETURN)
        if recording and not self._tracing:
            self._start_trace()
        elif not recording and self._tracing:
            self._stop_trace()

    def _start_trace(self):
        if self._timer_only:
            self._tracing = True
            return
        os.makedirs(self._dir, exist_ok=True)
        jax.profiler.start_trace(self._dir)
        self._tracing = True

    def _stop_trace(self):
        if not self._timer_only:
            jax.profiler.stop_trace()
        self._tracing = False

    # --- reporting -------------------------------------------------------
    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        return (f"[paddle_tpu profiler] trace written to {self._dir} — "
                "open with TensorBoard's profile plugin or xprof")

    def export(self, path: Optional[str] = None, format: str = "json"):
        return self._dir


class RecordEvent:
    """RAII span recorded into the device/host trace
    (reference: platform::RecordEvent; here jax.profiler.TraceAnnotation).

    Reusable: one RecordEvent may go through many begin()/end() cycles
    (the serving engine opens the same-named span every decode step), so
    a fresh TraceAnnotation is created per begin. Keyword attributes
    (plain host values: `seq=12, mode="fused"`) go on to the
    TraceAnnotation, which stores them as the event's stats; with no
    trace running they cost nothing."""

    def __init__(self, name: str, event_type=None, **attrs):
        self.name = name
        self._attrs = attrs
        self._ann = None

    def begin(self):
        if self._ann is not None:
            raise RuntimeError(f"RecordEvent {self.name!r} already begun")
        self._ann = jax.profiler.TraceAnnotation(self.name, **self._attrs)
        self._ann.__enter__()

    def end(self):
        if self._ann is None:
            raise RuntimeError(f"RecordEvent {self.name!r} not begun")
        ann, self._ann = self._ann, None
        ann.__exit__(None, None, None)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def load_profiler_result(filename: str):
    """Load a serving trace artifact back in-process.

    The serving stack's Chrome-trace JSON (`serving.trace.TraceSink.
    to_chrome_trace()`, dumped with `json.dump`) uses the
    same host clock as the `MetricsRegistry.timer` RecordEvent spans,
    so its timelines correlate with a concurrent jax-profiler capture.
    This loader returns that artifact as the parsed dict (inspect
    ``result["traceEvents"]`` or feed it to tools/trace_report.py).
    XPlane device traces are still read by TensorBoard/xprof, not
    reloaded here."""
    import json
    # OSError (missing/unreadable path) propagates — a typo'd path
    # must stay distinguishable from an unsupported trace format
    with open(filename) as f:
        try:
            data = json.load(f)
        except ValueError:
            data = None
    if isinstance(data, dict) and "traceEvents" in data:
        return data
    raise NotImplementedError(
        "XPlane traces are read by TensorBoard/xprof, not reloaded in-process"
        " (paddle_tpu/profiler/__init__.py); only serving trace JSON"
        " (TraceSink.to_chrome_trace()) loads here")
