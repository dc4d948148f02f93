"""What every runner needs round its measured window: JAX's own count of
compilations, and a profiler trace of some seconds from the window's
middle, taken in a thread of its own."""
from __future__ import annotations

import tempfile
import threading
import time
from typing import Any, Dict


def compile_listener() -> Dict[str, int]:
    """{"n": XLA programs this process has got an executable for so far},
    counted by JAX's own event. On this JAX (0.9.0) the event wraps
    `compile_or_get_cached`, so a program read back from the persistent
    cache fires it too and is counted like one that compiled; the count's
    use, a difference over the window, is sound either way (the program's
    own log, `compile_log`, tells a hit from a miss: PERF.md section 3)."""
    from jax import monitoring
    seen = {"n": 0}

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            seen["n"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def trace_thread(start_after: float, length: float, out: Dict[str, Any]):
    """Start a thread that traces `length` seconds from `start_after`
    seconds on and leaves the trace's directory in out["dir"]. The
    Python tracer stays off: it slows the host it is measuring."""
    import jax

    def body():
        time.sleep(start_after)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        out["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(out["dir"], profiler_options=opts)
        time.sleep(length)
        jax.profiler.stop_trace()

    th = threading.Thread(target=body, name="bench-trace", daemon=True)
    th.start()
    return th
