"""Where the persistent XLA compile cache lives, and what every compiled
program cost.

A cold start compiles every step program (a served engine's warm-up
ladder is dozens of them); JAX's persistent compilation cache turns the
next process's compiles into file reads. The cache directory is part of
every entry's key, so it has to be the SAME path run after run: placed
from outside through `JAX_COMPILATION_CACHE_DIR` (JAX reads the
variable itself — nothing is set in code then), or else one fixed
directory inside the checkout. Never a temporary, pid- or time-derived
path, which could not hit twice.

`compile_log` is the process's record of its XLA programs: one record a
program that was traced, lowered and compiled or read back from that
cache, with the seconds of each stage. It is fed by `jax.monitoring`'s
public listeners, which `enable_compile_cache()` installs (the library
never does), and by the batcher's ahead-of-time helper, which times its
own three stages (`nlp/paged.py::ContinuousBatcher._aot`).
"""
from __future__ import annotations

import collections
import contextlib
import os
import re
import threading
import time
from typing import Any, Dict, Iterable, Iterator, List, Optional

import jax

# <checkout>/.jax_compile_cache — listed in .gitignore
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")

# JAX's stage events (each a scalar when the stage opens and a duration
# when it closes, on the compiling thread) -> the record's field
_BACKEND = "/jax/core/compile/backend_compile_duration"
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
           _BACKEND: "executable_s"}
# fired inside the last stage, which wraps the cache's lookup too
_CACHE_VERDICTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_hits": "hit"}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

_API_CALL = re.compile(r"(\w+)\((.*)\)")
_NOT_IN_A_MODULE_NAME = re.compile(r"[^\w.-]")


def program_name(fun_name: str) -> str:
    """A program's name as the device trace's "XLA Modules" line prints
    it, without the fingerprint: `jit_serve_decode_step`, from what
    JAX's events call it while tracing (`serve_decode_step`) and after
    (`jit(serve_decode_step)`), or from a jitted function's `__name__`."""
    m = _API_CALL.fullmatch(fun_name)
    api, fun = m.groups() if m else ("jit", fun_name)
    return _NOT_IN_A_MODULE_NAME.sub("_", f"{api}_{fun}").rstrip("_")


class _Compiling(threading.local):
    """What one thread is compiling."""
    depth = 0       # stages open on this thread
    outer = None    # the outermost open stage's event
    rec = None      # the record being assembled
    own = False     # `program()` holds it and times it itself


class CompileLog:
    """A ring of the last `cap` programs this process compiled, oldest
    first. A record: `name` (`program_name`), `t` (`time.perf_counter()`
    when the executable was in hand: the clock of `FlightRecorder`,
    `TraceSink` and the `serve.*` stamps), `trace_s`, `lower_s` (jaxpr to
    StableHLO, Pallas's lowerings inside it), `executable_s` (the
    backend's compile, or the persistent cache's read), `cache` ("hit",
    "miss", or "off": no request to the cache was seen), `cache_read_s`
    on a hit, and, where an ahead-of-time site gave them, `key` (its
    memo's) and `alias_bytes` (the bytes of the program's results that
    live in a donated argument's buffer, the compiler's
    `alias_size_in_bytes`: a step program that writes the KV pool in
    place reads at least the pool's bytes, one that copies it 0).

    A jit called inside another's trace or lowering (a kernel's
    module-level `jax.jit`, `jnp`'s own) compiles to no program of its
    own: its events fall inside the outer program's times and make no
    record."""

    clock = staticmethod(time.perf_counter)

    def __init__(self, cap: int = 4096):
        self._ring: collections.deque = collections.deque(maxlen=cap)
        self._lock = threading.Lock()
        self._thread = _Compiling()

    # -- what the listeners and the ahead-of-time helper write ------------
    def _close(self, rec: Dict[str, Any]) -> None:
        for f in _STAGES.values():
            rec.setdefault(f, 0.0)  # a stage JAX had cached (a second
            #                         `lower()` of one trace) took no time
        rec.setdefault("t", self.clock())
        with self._lock:
            self._ring.append(rec)

    def on_stage_open(self, event: str, _value=None, fun_name: str = "",
                      **_kw) -> None:
        if event not in _STAGES:
            return
        th = self._thread
        th.depth += 1
        if th.depth > 1:
            return
        th.outer = event
        name = program_name(fun_name)
        if not th.own and (th.rec is None or th.rec["name"] != name):
            th.rec = {"name": name, "cache": "off"}

    def on_duration(self, event: str, secs: float, fun_name: str = "",
                    **_kw) -> None:
        th = self._thread
        if event == _CACHE_READ:
            if th.rec is not None and th.depth == 1:
                th.rec["cache_read_s"] = secs
            return
        if event not in _STAGES or th.depth == 0:
            return      # depth 0: installed while a stage was open
        th.depth -= 1
        if th.depth or th.own or th.rec is None:
            return
        th.rec[_STAGES[event]] = secs
        if event == _BACKEND:
            rec, th.rec = th.rec, None
            self._close(rec)

    def on_event(self, event: str, **_kw) -> None:
        verdict = _CACHE_VERDICTS.get(event)
        th = self._thread
        if verdict and th.rec is not None and th.depth == 1 \
                and th.outer == _BACKEND:
            th.rec["cache"] = verdict

    @contextlib.contextmanager
    def program(self, name: str, key: Optional[str] = None
                ) -> Iterator[Dict[str, Any]]:
        """The record of a program whose caller runs the stages apart and
        times them itself: yields the open record (the caller sets `t`,
        `trace_s`, `lower_s`, `executable_s`; the listeners, where
        installed, add `cache` and `cache_read_s`) and closes it on a
        clean exit."""
        th = self._thread
        rec = {"name": name, "cache": "off"}
        if key is not None:
            rec["key"] = key
        was = th.rec, th.own
        th.rec, th.own = rec, True
        try:
            yield rec
        finally:
            th.rec, th.own = was
        self._close(rec)

    # -- what the readers and the operator read ---------------------------
    def records(self, programs: Optional[Iterable[str]] = None,
                since: Optional[float] = None,
                until: Optional[float] = None) -> List[Dict[str, Any]]:
        """Copies of the records, oldest first: those whose `name` one of
        the regular expressions `programs` finds (all where None), with
        `since <= t <= until` where given."""
        pats = None if programs is None else [re.compile(p) for p in programs]
        with self._lock:
            return [dict(r) for r in self._ring
                    if (pats is None
                        or any(p.search(r["name"]) for p in pats))
                    and (since is None or r["t"] >= since)
                    and (until is None or r["t"] <= until)]

    def summary(self, programs: Optional[Iterable[str]] = None,
                since: Optional[float] = None,
                until: Optional[float] = None) -> Dict[str, Any]:
        """Counts and seconds by stage over `records(...)`: `count`,
        `trace_s`, `lower_s`, `executable_s`, `cache_read_s`,
        `alias_bytes`, `hits`, `misses` (every program the cache did not
        hold, "off" too) and `last_miss` (its `name` and `t`, or None)."""
        recs = self.records(programs, since, until)
        out: Dict[str, Any] = {"count": len(recs)}
        for f in (*_STAGES.values(), "cache_read_s"):
            out[f] = sum(r.get(f, 0.0) for r in recs)
        out["alias_bytes"] = sum(r.get("alias_bytes", 0) for r in recs)
        missed = [r for r in recs if r["cache"] != "hit"]
        out["hits"] = len(recs) - len(missed)
        out["misses"] = len(missed)
        out["last_miss"] = {"name": missed[-1]["name"],
                            "t": missed[-1]["t"]} if missed else None
        return out

    def clear(self) -> None:
        """Forget every record (a process that makes several runs)."""
        with self._lock:
            self._ring.clear()


compile_log = CompileLog()
_listening = False


def _listen() -> None:
    """Feed `compile_log` from JAX's public monitoring events, once."""
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring
    monitoring.register_scalar_listener(compile_log.on_stage_open)
    monitoring.register_event_duration_secs_listener(compile_log.on_duration)
    monitoring.register_event_listener(compile_log.on_event)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on before the first compile, and
    the compile log's listeners with it. Returns the directory in use.
    Entry points (chip_smoke.py, benchmark/run.py, the examples) call
    this once; the library never does — importing the package configures
    nothing and listens to nothing."""
    _listen()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    return _CHECKOUT_CACHE_DIR
