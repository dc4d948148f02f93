"""ptlint (paddle_tpu.analysis) — rule unit tests on purpose-built
fixtures (a true positive AND a true negative per rule), suppression
comments, the baseline ratchet, the CLI, and the whole-package gate:
`paddle_tpu/` must be clean beyond the committed baseline.

These tests exercise the AST engine only — no jax tracing happens, so
the file is cheap even inside the tier-1 budget."""
import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

from paddle_tpu.analysis import (
    ALL_RULES, RULES_BY_ID, analyze_source, apply_baseline,
    load_baseline, load_project, run_rules, save_baseline,
)
from paddle_tpu.analysis.callgraph import build_callgraph
from paddle_tpu.analysis.core import FileContext, Project
from paddle_tpu.analysis.rules.memo import discover_memo_caches
from paddle_tpu.analysis.rules.sync import derive_hot_paths
from paddle_tpu.analysis.runner import main as ptlint_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def graph_of(src, relpath="paddle_tpu/mod.py"):
    ctx = FileContext(relpath, textwrap.dedent(src), relpath)
    project = Project([ctx])
    return build_callgraph(project), ctx


_real_tree_cache = []


def real_tree():
    """The whole-package Project, loaded once per test session: the
    clean-gate and the hot-set superset test share it (and its cached
    call graph) so the tier-1 wall-clock pays one parse, not three."""
    if not _real_tree_cache:
        project, errs = load_project(
            [os.path.join(REPO, "paddle_tpu")], REPO)
        assert errs == []
        _real_tree_cache.append(project)
    return _real_tree_cache[0]


def run_src(src, rule=None, relpath="snippet.py"):
    fs = analyze_source(textwrap.dedent(src), relpath=relpath)
    return [f for f in fs if rule is None or f.rule == rule]


def rule_ids(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# TRACE001
# ---------------------------------------------------------------------------

def test_trace_print_in_decorated_jit():
    fs = run_src("""
        import jax
        @jax.jit
        def f(x):
            print("tracing!", x)
            return x
    """, "TRACE001")
    assert len(fs) == 1 and "print()" in fs[0].message


def test_trace_closure_append_in_wrapped_fn():
    fs = run_src("""
        import jax
        log = []
        def f(x):
            log.append(x)
            return x
        g = jax.jit(f)
    """, "TRACE001")
    assert len(fs) == 1 and "log.append" in fs[0].message


def test_trace_global_statement_and_attr_store():
    fs = run_src("""
        from jax import jit
        state = {}
        class Holder: pass
        h = Holder()
        @jit
        def f(x):
            global counter
            counter = 1
            h.field = x
            return x
    """, "TRACE001")
    msgs = " | ".join(f.message for f in fs)
    assert "global" in msgs and "attribute 'field'" in msgs


def test_trace_scan_body_flagged():
    fs = run_src("""
        from jax import lax
        def body(carry, x):
            print(carry)
            return carry, x
        out = lax.scan(body, 0, None)
    """, "TRACE001")
    assert len(fs) == 1 and "body of jax.lax.scan" in fs[0].message


def test_trace_fori_and_while_bodies_flagged():
    # fori_loop's body is args[2], while_loop's cond/body are args[0:2]
    fs = run_src("""
        from jax import lax
        def body(i, carry):
            print(i)
            return carry
        out = lax.fori_loop(0, 10, body, 0)
        def cond(c):
            print(c)
            return True
        out2 = lax.while_loop(cond, lambda c: c, 0)
    """, "TRACE001")
    assert len(fs) == 2


def test_trace_negative_eager_fn_and_local_mutation():
    fs = run_src("""
        import jax
        def eager(x):
            print(x)          # not traced: fine
            return x
        @jax.jit
        def f(x):
            acc = []
            acc.append(x)     # local list: fine
            return acc
    """, "TRACE001")
    assert fs == []


def test_trace_same_name_method_not_confused_with_jitted_inner():
    # LLMEngine.run regression: the HOST-side method shares the name of
    # the nested traced fn; only the inner one is traced
    fs = run_src("""
        import jax
        class Engine:
            def run(self):
                print("host side, fine")
                def run(params):
                    return params
                return jax.jit(run)
    """, "TRACE001")
    assert fs == []


# ---------------------------------------------------------------------------
# SYNC001
# ---------------------------------------------------------------------------

def test_sync_hot_path_flags_syncs():
    fs = run_src("""
        import numpy as np
        import jax.numpy as jnp
        class Batcher:
            def step(self):
                active = jnp.asarray(self.active)     # re-upload
                toks = np.asarray(self.toks)          # host copy
                loss = self.metrics.item()            # blocking sync
                return int(jnp.argmax(self.logits))   # blocking cast
    """, "SYNC001", relpath="paddle_tpu/nlp/paged.py")
    assert len(fs) == 4
    msgs = " | ".join(f.message for f in fs)
    assert "re-uploads" in msgs and ".item()" in msgs


def test_sync_negative_cold_path_and_host_values():
    # same code in a non-hot file: silent; host-only casts in a hot
    # file: silent
    assert run_src("""
        import numpy as np
        class Batcher:
            def step(self):
                return np.asarray(self.toks)
    """, "SYNC001", relpath="paddle_tpu/other/module.py") == []
    assert run_src("""
        class Batcher:
            def step(self):
                n = int(len(self.queue))    # host int: fine
                return n
    """, "SYNC001", relpath="paddle_tpu/nlp/paged.py") == []


def test_sync_item_in_traced_fn_any_file():
    fs = run_src("""
        import jax
        @jax.jit
        def f(x):
            return x.item()
    """, "SYNC001")
    assert len(fs) == 1


# ---------------------------------------------------------------------------
# call graph (analysis.callgraph): the engine under SYNC001's closure
# and GUARD001's thread attribution
# ---------------------------------------------------------------------------

def test_callgraph_resolves_through_self_attr_types():
    # the constructor-assignment type map: self.q = Queue() makes
    # self.q.push() an edge to Queue.push
    graph, ctx = graph_of("""
        class Queue:
            def push(self, item):
                pass
        class Engine:
            def __init__(self):
                self.q = Queue()
            def admit(self):
                self.q.push(1)
    """)
    mod = ctx.module_name
    assert (mod, "Queue", "push") in graph.edges[(mod, "Engine", "admit")]


def test_callgraph_resolves_local_ctor_then_self_assign():
    # the normalize-an-optional-arg idiom: a local built from a ctor
    # (possibly inside an `if`) then stored on self still types the attr
    graph, ctx = graph_of("""
        class Sink:
            def emit(self):
                pass
        class Engine:
            def __init__(self, sink=None):
                if sink is None:
                    sink = Sink()
                self._sink = sink
            def tick(self):
                self._sink.emit()
    """)
    mod = ctx.module_name
    assert (mod, "Sink", "emit") in graph.edges[(mod, "Engine", "tick")]


def test_callgraph_cross_module_resolution(tmp_path):
    # imports + the class index resolve edges across files
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "sched.py").write_text(textwrap.dedent("""
        class Queue:
            def pop(self):
                pass
    """))
    (pkg / "eng.py").write_text(textwrap.dedent("""
        from .sched import Queue
        class Engine:
            def __init__(self):
                self.q = Queue()
            def tick(self):
                self.q.pop()
    """))
    project, errs = load_project([str(pkg)], str(tmp_path))
    assert errs == []
    graph = build_callgraph(project)
    assert ("pkg.sched", "Queue", "pop") in \
        graph.edges[("pkg.eng", "Engine", "tick")]


def test_callgraph_thread_entrypoint_discovery():
    graph, ctx = graph_of("""
        import asyncio
        import threading
        from concurrent.futures import ThreadPoolExecutor
        class Engine:
            def __init__(self):
                self._pool = ThreadPoolExecutor(2)
            def start(self):
                threading.Thread(target=self._loop, daemon=True).start()
                threading.Timer(1.0, self._tick).start()
                self._pool.submit(self._work, 1)
                asyncio.run_coroutine_threadsafe(self._serve(), loop)
            def _loop(self): pass
            def _tick(self): pass
            def _work(self, n): pass
            async def _serve(self): pass
    """)
    mod = ctx.module_name
    roots = {(r.key, r.kind) for r in graph.thread_roots}
    assert ((mod, "Engine", "_loop"), "Thread(target=)") in roots
    assert ((mod, "Engine", "_tick"), "Timer") in roots
    assert ((mod, "Engine", "_work"), "executor.submit") in roots
    assert ((mod, "Engine", "_serve"), "run_coroutine_threadsafe") in roots
    # spawning is NOT calling: start() gets no edge to the targets
    assert (mod, "Engine", "_loop") not in graph.edges[(mod, "Engine",
                                                        "start")]


def test_callgraph_closure_propagates_and_cycles_terminate():
    graph, ctx = graph_of("""
        def a():
            b()
        def b():
            c()
        def c():
            a()        # cycle
        def lonely():
            pass
    """)
    mod = ctx.module_name
    reach = graph.reachable([(mod, None, "a")])
    assert reach == {(mod, None, "a"), (mod, None, "b"), (mod, None, "c")}
    prov = graph.closure_provenance([(mod, None, "a")])
    assert prov[(mod, None, "c")] == (mod, None, "a")


def test_callgraph_function_reference_args_make_edges():
    # callbacks run on the caller's thread: pop(fits=self._fits) must
    # put _fits inside pop's caller's closure
    graph, ctx = graph_of("""
        class Engine:
            def admit(self):
                self.q.pop(fits=self._fits, prefer=best)
            def _fits(self, r):
                return True
        def best(r):
            return False
    """)
    mod = ctx.module_name
    out = graph.edges[(mod, "Engine", "admit")]
    assert (mod, "Engine", "_fits") in out
    assert (mod, None, "best") in out


# ---------------------------------------------------------------------------
# LOCK001
# ---------------------------------------------------------------------------

def test_lock_bare_acquire():
    fs = run_src("""
        import threading
        _lock = threading.Lock()
        def f():
            _lock.acquire()
            _lock.release()
    """, "LOCK001")
    assert len(fs) == 1 and "bare" in fs[0].message


def test_lock_blocking_calls_under_lock():
    fs = run_src("""
        import queue
        import threading
        import time
        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._chan = queue.Queue()
            def bad_sleep(self):
                with self._lock:
                    time.sleep(0.1)
            def bad_get(self):
                with self._lock:
                    return self._chan.get()
    """, "LOCK001")
    msgs = " | ".join(f.message for f in fs)
    assert len(fs) == 2 and "sleeps" in msgs and "blocking" in msgs


def test_lock_timeout_none_still_blocking():
    fs = run_src("""
        import queue
        import threading
        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._chan = queue.Queue()
            def bad(self):
                with self._lock:
                    return self._chan.get(timeout=None)   # blocks forever
    """, "LOCK001")
    assert len(fs) == 1


def test_lock_negatives_with_condition_and_timeouts():
    fs = run_src("""
        import queue
        import threading
        import time
        class Engine:
            def __init__(self):
                self._lock = threading.RLock()
                self._work = threading.Condition(self._lock)
                self._chan = queue.Queue()
            def ok(self):
                with self._work:
                    self._work.wait()           # releases the lock
                    self._chan.get(timeout=1)   # bounded
                    self._chan.get_nowait()
                time.sleep(0.1)                 # outside the lock
    """, "LOCK001")
    assert fs == []


def test_lock_order_inconsistency_nested_with():
    fs = run_src("""
        import threading
        a_lock = threading.Lock()
        b_lock = threading.Lock()
        def f():
            with a_lock:
                with b_lock:
                    pass
        def g():
            with b_lock:
                with a_lock:
                    pass
    """, "LOCK001")
    assert len(fs) == 2
    assert all("inconsistent lock order" in f.message for f in fs)


def test_lock_order_inconsistency_cross_class():
    # the ServingEngine <-> AdmissionQueue shape: holding my lock while
    # calling a method of a typed attribute that takes ITS lock
    fs = run_src("""
        import threading
        class A:
            def __init__(self):
                self._lock = threading.Lock()
                self.b = B()
            def m(self):
                with self._lock:
                    self.b.n()          # A._lock -> B._lock
        class B:
            def __init__(self):
                self._lock = threading.Lock()
                self.a = A()
            def n(self):
                with self._lock:
                    pass
            def p(self):
                with self._lock:
                    self.a.m()          # B._lock -> A._lock: conflict
    """, "LOCK001")
    assert len(fs) == 2
    assert all("inconsistent lock order" in f.message for f in fs)


def test_lock_order_consistent_is_clean():
    fs = run_src("""
        import threading
        a_lock = threading.Lock()
        b_lock = threading.Lock()
        def f():
            with a_lock:
                with b_lock:
                    pass
        def g():
            with a_lock:
                with b_lock:
                    pass
    """, "LOCK001")
    assert fs == []


# ---------------------------------------------------------------------------
# GUARD001: cross-thread access to lock-guarded fields
# ---------------------------------------------------------------------------

_RACY_ENGINE = """
    import threading
    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
        def start(self):
            threading.Thread(target=self._loop, daemon=True).start()
        def _loop(self):
            with self._lock:
                self.count += 1
        def peek(self):
            return self.count
"""


def test_guard_true_race_flagged():
    fs = run_src(_RACY_ENGINE, "GUARD001")
    assert len(fs) == 1
    f = fs[0]
    assert "count" in f.message and "Engine._lock" in f.message
    assert "Engine.peek" in f.message
    assert f.snippet == "return self.count"


def test_guard_with_lock_access_clean():
    fs = run_src(_RACY_ENGINE.replace(
        "        def peek(self):\n            return self.count",
        "        def peek(self):\n"
        "            with self._lock:\n"
        "                return self.count"), "GUARD001")
    assert fs == []


def test_guard_single_thread_class_clean():
    # no thread entry points anywhere: every access is one context,
    # thread-confined de facto — even unlocked reads stay silent
    fs = run_src("""
        import threading
        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
            def bump(self):
                with self._lock:
                    self.count += 1
            def peek(self):
                return self.count
    """, "GUARD001")
    assert fs == []


def test_guard_locked_suffix_convention_clean():
    # *_locked methods document "caller holds my lock": their bodies
    # are checked as if the class's guard locks were held
    fs = run_src(_RACY_ENGINE.replace(
        "        def peek(self):\n            return self.count",
        "        def peek(self):\n"
        "            with self._lock:\n"
        "                return self._peek_locked()\n"
        "        def _peek_locked(self):\n"
        "            return self.count"), "GUARD001")
    assert fs == []


def test_guard_suppression_guarded_by_and_disable():
    fs = run_src(_RACY_ENGINE.replace(
        "            return self.count",
        "            # ptlint: guarded-by(_lock) — callers hold it\n"
        "            return self.count"), "GUARD001")
    assert fs == []
    fs = run_src(_RACY_ENGINE.replace(
        "            return self.count",
        "            return self.count"
        "  # ptlint: disable=GUARD001 — stats-only read"), "GUARD001")
    assert fs == []


def test_guard_thread_confined_field_annotation():
    # thread-confined on the defining assignment exempts the FIELD:
    # both the unlocked read and any other access stay silent
    fs = run_src(_RACY_ENGINE.replace(
        "            self.count = 0",
        "            # ptlint: thread-confined — engine-thread stats\n"
        "            self.count = 0"), "GUARD001")
    assert fs == []


def test_guard_cross_class_field_via_type_map():
    # the AdmissionQueue shape: another class reaches into a typed
    # attr's guarded internals without that class's lock
    src = """
        import threading
        class Queue:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []
            def push(self, x):
                with self._lock:
                    self._items.append(x)
        class Engine:
            def __init__(self):
                self.q = Queue()
            def start(self):
                threading.Thread(target=self._loop, daemon=True).start()
            def _loop(self):
                self.q.push(1)
            def depth(self):
                return len(self.q._items){LOCK}
    """
    fs = run_src(src.replace("{LOCK}", ""), "GUARD001")
    assert len(fs) == 1
    assert "_items" in fs[0].message and "Queue._lock" in fs[0].message
    # holding the OWNER's lock through the typed attr is clean
    locked = src.replace(
        "                return len(self.q._items){LOCK}",
        "                with self.q._lock:\n"
        "                    return len(self.q._items)")
    assert run_src(locked, "GUARD001") == []


def test_guard_inherited_field_shares_storage():
    # Base writes the field under its lock; a Derived-only method
    # reads it unlocked from another thread. Same instance storage,
    # same actual lock — the chain is one component, still a race
    src = """
        import threading
        class Base:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0
            def bump(self):
                with self._lock:
                    self.count += 1
        class Derived(Base):
            def start(self):
                threading.Thread(target=self.bump).start()
            def peek(self):
                return self.count
    """
    fs = run_src(src, "GUARD001")
    assert len(fs) == 1 and "count" in fs[0].message
    # holding the (inherited) lock in the derived method is clean:
    # 'Derived._lock' and 'Base._lock' canonicalize to one lock
    locked = src.replace(
        "            def peek(self):\n                return self.count",
        "            def peek(self):\n"
        "                with self._lock:\n"
        "                    return self.count")
    assert run_src(locked, "GUARD001") == []


def test_guard_mutating_call_counts_as_guarded_write():
    # a field only ever .append()ed under the lock is still guarded
    fs = run_src("""
        import threading
        class Log:
            def __init__(self):
                self._lock = threading.Lock()
                self._events = []
            def start(self):
                threading.Thread(target=self._loop).start()
            def _loop(self):
                with self._lock:
                    self._events.append(1)
            def dump(self):
                return list(self._events)
    """, "GUARD001")
    assert len(fs) == 1 and "_events" in fs[0].message


# ---------------------------------------------------------------------------
# SYNC001 closure: seed roots derive their transitive callees
# ---------------------------------------------------------------------------

def test_sync_closure_derives_new_helper():
    # the whole point of the refactor: a helper step() calls is hot the
    # day it's written, with no hand-list entry
    fs = run_src("""
        class Batcher:
            def step(self):
                self._new_helper()
            def _new_helper(self):
                return self.metrics.item()
    """, "SYNC001", relpath="paddle_tpu/nlp/paged.py")
    assert len(fs) == 1
    assert "_new_helper" in fs[0].message
    assert "via" in fs[0].message          # provenance names the root


def test_sync_closure_follows_inherited_helper():
    # a helper defined only on a base class is still on the hot path
    # when a hot root calls it through self — method resolution walks
    # the in-tree base chain, so 'covered the day it's written' holds
    # for mixin/base refactors too
    fs = run_src("""
        class Base:
            def _helper(self):
                return self.metrics.item()
        class Batcher(Base):
            def step(self):
                self._helper()
    """, "SYNC001", relpath="paddle_tpu/nlp/paged.py")
    assert len(fs) == 1 and "_helper" in fs[0].message


def test_callgraph_method_resolves_through_base_chain():
    graph, _ctx = graph_of("""
        class Base:
            def helper(self):
                pass
        class Mid(Base):
            pass
        class Leaf(Mid):
            def run(self):
                self.helper()
    """)
    key = graph.method("Leaf", "helper")
    assert key is not None and key[1] == "Base"
    run_key = graph.method("Leaf", "run")
    assert key in graph.edges[run_key]


def test_sync_closure_crosses_files(tmp_path):
    # a hot root in one module pulls a callee in ANOTHER module into
    # the hot set — the hand list could never say this
    pkg = tmp_path / "nlp"
    pkg.mkdir()
    (pkg / "util.py").write_text(textwrap.dedent("""
        class Sink:
            def emit(self):
                return self.buf.item()
    """))
    (pkg / "paged.py").write_text(textwrap.dedent("""
        from .util import Sink
        class Batcher:
            def __init__(self):
                self._sink = Sink()
            def step(self):
                self._sink.emit()
    """))
    project, errs = load_project([str(pkg)], str(tmp_path))
    assert errs == []
    fs = [f for f in run_rules(project, ALL_RULES) if f.rule == "SYNC001"]
    assert len(fs) == 1 and fs[0].path.endswith("util.py")


def test_sync_dead_root_reported():
    # a root pattern matching nothing in its file is DEAD — the report
    # that stops a rename from silently shrinking coverage
    ctx = FileContext("paddle_tpu/nlp/paged.py",
                      "class Batcher:\n    def step(self):\n        pass\n",
                      "paddle_tpu/nlp/paged.py")
    hot, dead = derive_hot_paths(Project([ctx]))
    assert ("nlp/paged.py", "run") in dead
    assert all(name != "run" for _, node, _ in hot.values()
               for name in [node.name])


# the hand-maintained HOT_PATHS list as it stood before the call-graph
# closure replaced it (PR 14 state, verbatim): the derived hot set must
# remain a SUPERSET of everything this list matched, forever — deleting
# a hand entry is only legal because the closure provably covers it
_OLD_HOT_PATHS = (
    ("nlp/paged.py",
     r"^(step|run|_step_fused|_prefill_pending|_run_standalone_unit"
     r"|_paged_gqa_attention|forward_paged|_write_pool|_write_pool_int8"
     r"|_trace_emit|_trace_chunks|_record_tick"
     r"|_step_spec|_emit_spec|_spec_any|_drain_emitted"
     r"|_forward_spec|_spec_gqa_attention|_profile_t0|_profile_commit)$"),
    ("nlp/ragged_attention.py",
     r"^(ragged_paged_attention|_rpa_kernel|resolve_attention_impl)$"),
    ("quantization/kv.py",
     r"^(quantize|dequantize|rescale_codes|scale_of)$"),
    ("serving/engine.py", r"^(_loop|_dispatch|step|load|_slo_eval)$"),
    ("serving/slo.py",
     r"^(record_ttft|record_itl|record_queue_wait|record_tokens"
     r"|record_request|_record|evaluate|pop_transitions)$"),
    ("serving/profiling.py",
     r"^(should_fence|record|arm_capture|capture_active)$"),
    ("serving/speculative.py",
     r"^(record_step|accept_rate|tokens_per_step)$"),
    ("serving/router.py",
     r"^(submit|_place|_views|_bridge|_monitor_loop|_sweep_locked"
     r"|_handle_terminal|_failover)$"),
    ("serving/frontend.py",
     r"^(_handle|_generate|_stream_sse|_submit|_read_request)$"),
    ("serving/supervisor.py",
     r"^(_loop|_restart_slot|_probe|slot_serving|info)$"),
    ("serving/trace.py",
     r"^(emit|finish|start|alias|span|now|record)$"),
)


def test_sync_derived_hot_set_superset_of_old_list():
    """No silent coverage loss: every function the old hand list
    matched on the REAL tree is in the derived hot set."""
    import ast
    import re
    project = real_tree()
    hot, dead = derive_hot_paths(project)
    derived = {}
    for ctx, node, _reason in hot.values():
        derived.setdefault(ctx.relpath, set()).add(node.name)
    missing = []
    for suffix, rx in _OLD_HOT_PATHS:
        pat = re.compile(rx)
        for ctx in project.files:
            if ctx.tree is None or not ctx.relpath.endswith(suffix):
                continue
            for n in ast.walk(ctx.tree):
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and pat.match(n.name) \
                        and n.name not in derived.get(ctx.relpath, set()):
                    missing.append(f"{ctx.relpath}::{n.name}")
    assert missing == [], f"hot-set coverage lost vs the old hand list: " \
                          f"{missing}"
    # and the live seed roots are all alive on the real tree
    assert dead == [], f"dead HOT_ROOTS entries on the real tree: {dead}"


# ---------------------------------------------------------------------------
# EXC001
# ---------------------------------------------------------------------------

def test_exc_broad_swallow_flagged():
    fs = run_src("""
        def f():
            try:
                work()
            except Exception:
                pass
        def g():
            try:
                work()
            except:
                return None
    """, "EXC001")
    assert len(fs) == 2


def test_exc_log_substring_names_do_not_count_as_logging():
    # catalog/dialog contain 'log' but are NOT logging calls
    fs = run_src("""
        def f(self):
            try:
                work()
            except Exception as e:
                self.catalog.append(e)
        def g(self):
            try:
                work()
            except Exception:
                self.dialog.close()
    """, "EXC001")
    assert len(fs) == 2


def test_exc_negatives():
    fs = run_src("""
        import logging
        import warnings
        def a():
            try:
                work()
            except ValueError:        # narrow: fine
                pass
        def b():
            try:
                work()
            except Exception:
                raise                 # re-raise: fine
        def c():
            try:
                work()
            except Exception as e:
                logging.warning(e)    # logged: fine
        def d():
            try:
                work()
            except Exception as e:
                warnings.warn(str(e))
    """, "EXC001")
    assert fs == []


# ---------------------------------------------------------------------------
# API001 (multi-file: needs a real project on disk)
# ---------------------------------------------------------------------------

_mini_count = [0]


def _mini_project(tmp_path, init_src, mod_src):
    _mini_count[0] += 1
    pkg = tmp_path / f"pkg{_mini_count[0]}"
    pkg.mkdir()
    (pkg / "__init__.py").write_text(textwrap.dedent(init_src))
    (pkg / "mod.py").write_text(textwrap.dedent(mod_src))
    project, errs = load_project([str(pkg)], str(tmp_path))
    assert errs == []
    return [f for f in run_rules(project, ALL_RULES) if f.rule == "API001"]


def test_api_missing_docstring_across_modules(tmp_path):
    fs = _mini_project(
        tmp_path,
        """
        from .mod import documented, bare
        __all__ = ["documented", "bare", "local_bare"]
        def local_bare():
            return 1
        """,
        '''
        def documented():
            """Has one."""
        def bare():
            return 2
        ''')
    names = sorted(f.message.split("'")[1] for f in fs)
    assert names == ["bare", "local_bare"]


def test_api_negative_all_documented_or_no_all(tmp_path):
    assert _mini_project(
        tmp_path,
        """
        from .mod import documented
        __all__ = ["documented"]
        """,
        '''
        def documented():
            """Yes."""
        ''') == []
    # no __all__: implicit surface, skipped entirely
    assert _mini_project(
        tmp_path,
        "from .mod import bare\n",
        "def bare():\n    return 2\n") == []


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------

def test_suppression_inline_and_standalone():
    clean = run_src("""
        def f():
            try:
                work()
            except Exception:  # ptlint: disable=EXC001 — justified here
                pass
        def g():
            try:
                work()
            # ptlint: disable=EXC001 — two-line justification, the
            # comment block carries to the handler line below
            except Exception:
                pass
    """, "EXC001")
    assert clean == []


def test_suppression_survives_blank_line():
    assert run_src("""
        def f():
            try:
                work()
            # ptlint: disable=EXC001 — justified

            except Exception:
                pass
    """, "EXC001") == []


def test_suppression_disable_all_and_wrong_rule():
    assert run_src("""
        def f():
            try:
                work()
            except Exception:  # ptlint: disable=all
                pass
    """, "EXC001") == []
    # disabling a DIFFERENT rule does not silence this one
    fs = run_src("""
        def f():
            try:
                work()
            except Exception:  # ptlint: disable=SYNC001
                pass
    """, "EXC001")
    assert len(fs) == 1


# ---------------------------------------------------------------------------
# baseline ratchet
# ---------------------------------------------------------------------------

_VIOLATION = ("def f():\n    try:\n        g()\n"
              "    except Exception:\n        pass\n")


def _write_pkg(tmp_path, n_violations):
    src = "".join(_VIOLATION.replace("def f", f"def f{i}")
                  for i in range(n_violations))
    p = tmp_path / "code.py"
    p.write_text(src or "x = 1\n")
    return p


def test_baseline_absorbs_then_ratchets(tmp_path):
    p = _write_pkg(tmp_path, 1)
    bl = tmp_path / "baseline.json"
    args = [str(p), "--root", str(tmp_path), "--baseline", str(bl)]
    assert ptlint_main(args + ["--update-baseline"]) == 0
    assert ptlint_main(args) == 0                 # baselined: clean
    # adding a NEW violation fails even though the old one is baselined
    _write_pkg(tmp_path, 2)
    assert ptlint_main(args) == 1


def test_baseline_shrinks_cleanly(tmp_path, capsys):
    p = _write_pkg(tmp_path, 2)
    bl = tmp_path / "baseline.json"
    args = [str(p), "--root", str(tmp_path), "--baseline", str(bl)]
    assert ptlint_main(args + ["--update-baseline"]) == 0
    # identical handler lines share one fingerprint with count 2
    assert sum(load_baseline(str(bl)).values()) == 2
    # burn one down: the run stays green and reports the stale entry
    _write_pkg(tmp_path, 1)
    capsys.readouterr()
    assert ptlint_main(args) == 0
    assert "stale" in capsys.readouterr().out
    # --update-baseline shrinks the file to the surviving violation
    assert ptlint_main(args + ["--update-baseline"]) == 0
    assert sum(load_baseline(str(bl)).values()) == 1


def test_baseline_apply_counts():
    fs = analyze_source(_VIOLATION + _VIOLATION.replace("def f", "def h"))
    assert len(fs) == 2
    base = {fs[0].fingerprint: 1}
    res = apply_baseline(fs, base)
    assert len(res.new) == 1 and len(res.baselined) == 1 and not res.stale


def test_baseline_save_load_roundtrip(tmp_path):
    fs = analyze_source(_VIOLATION)
    path = tmp_path / "b.json"
    saved = save_baseline(str(path), fs)
    assert load_baseline(str(path)) == saved
    assert apply_baseline(fs, saved).new == []


# ---------------------------------------------------------------------------
# CLI / integration
# ---------------------------------------------------------------------------

def test_cli_json_format(tmp_path, capsys):
    p = _write_pkg(tmp_path, 1)
    rc = ptlint_main([str(p), "--root", str(tmp_path), "--no-baseline",
                      "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1 and out["exit"] == 1
    assert out["new"][0]["rule"] == "EXC001"
    assert out["new"][0]["path"] == "code.py"


def test_cli_select_and_list_rules(tmp_path, capsys):
    p = _write_pkg(tmp_path, 1)
    rc = ptlint_main([str(p), "--root", str(tmp_path), "--no-baseline",
                      "--select", "SYNC001"])
    assert rc == 0                                # EXC001 not selected
    assert ptlint_main(["--list-rules"]) == 0
    assert "TRACE001" in capsys.readouterr().out
    assert ptlint_main([str(p), "--select", "NOPE"]) == 2


def test_cli_github_format_annotations(tmp_path, capsys):
    p = _write_pkg(tmp_path, 1)
    rc = ptlint_main([str(p), "--root", str(tmp_path), "--no-baseline",
                      "--format", "github"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "::error file=code.py,line=" in out
    assert "title=ptlint EXC001::" in out
    # clean tree: no ::error lines, summary still printed
    (tmp_path / "clean.py").write_text("x = 1\n")
    rc = ptlint_main([str(tmp_path / "clean.py"), "--root", str(tmp_path),
                      "--no-baseline", "--format", "github"])
    out = capsys.readouterr().out
    assert rc == 0 and "::error" not in out and "0 new finding" in out


def test_cli_hot_report_nonblocking(tmp_path, capsys):
    pkg = tmp_path / "nlp"
    pkg.mkdir()
    (pkg / "paged.py").write_text(
        "class Batcher:\n"
        "    def step(self):\n"
        "        self._helper()\n"
        "    def _helper(self):\n"
        "        pass\n")
    rc = ptlint_main([str(pkg), "--root", str(tmp_path), "--hot-report"])
    out = capsys.readouterr().out
    assert rc == 0                      # informational: never fails
    assert "derived hot set" in out
    assert "_helper" in out and "via" in out
    assert "DEAD hot-path roots" in out     # `run` has no match here


def test_cli_hot_report_warns_on_parse_error(tmp_path, capsys):
    # a file that fails to parse contributes no functions: the report
    # must lead with the gap, not present a silently shrunken hot set
    pkg = tmp_path / "nlp"
    pkg.mkdir()
    (pkg / "paged.py").write_text("def step(:\n")
    rc = ptlint_main([str(pkg), "--root", str(tmp_path), "--hot-report"])
    out = capsys.readouterr().out
    assert rc == 0                      # still informational
    assert "WARNING" in out and "incomplete" in out
    assert "paged.py" in out


def test_cli_time_budget_exceeded(tmp_path, capsys):
    p = tmp_path / "ok.py"
    p.write_text("x = 1\n")
    args = [str(p), "--root", str(tmp_path), "--no-baseline"]
    assert ptlint_main(args + ["--time-budget", "600"]) == 0
    capsys.readouterr()
    # a zero budget always trips: clean findings still fail the run
    rc = ptlint_main(args + ["--time-budget", "0"])
    err = capsys.readouterr().err
    assert rc == 1 and "TIME BUDGET EXCEEDED" in err


def test_parse_error_reported_not_crash(tmp_path, capsys):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    rc = ptlint_main([str(p), "--root", str(tmp_path), "--no-baseline"])
    assert rc == 1
    assert "PARSE" in capsys.readouterr().out


def test_ptlint_script_runs_standalone():
    # the CI entry point: must work WITHOUT importing the framework
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ptlint.py"),
         "--list-rules"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    for rid in RULES_BY_ID:
        assert rid in out.stdout


def test_repo_clean_beyond_committed_baseline():
    """The acceptance gate: paddle_tpu/ has no findings beyond the
    committed baseline, and the baseline has no stale entries."""
    findings = run_rules(real_tree(), ALL_RULES)
    base = load_baseline(os.path.join(REPO, "tools",
                                      "ptlint_baseline.json"))
    res = apply_baseline(findings, base)
    assert res.new == [], "\n".join(
        f"{f.location} {f.rule} {f.message}" for f in res.new)
    assert res.stale == {}, res.stale


@pytest.mark.slow
def test_module_entrypoint_exits_zero():
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "paddle_tpu/",
         "--root", REPO],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# KEY001 — memo-key soundness
# ---------------------------------------------------------------------------

# the paged idiom in miniature: one key helper feeding get/set/member
# sites, a _build_* closure that bakes `self.cfg` into the lowered
# program, and the two declared-mandatory config tuples
_MEMO_OK = """
    import jax

    class Batcher:
        def __init__(self, cfg, impl, wq, kq):
            self.cfg = cfg
            # ptlint: trace-config
            self.impl = impl
            # ptlint: trace-config
            self._qkey = (wq, kq)
            self._step_cache = {}

        def _key(self, n):
            return (n, self.cfg, self.impl) + self._qkey

        def _build_step(self):
            cfg = self.cfg

            def step(x):
                return x * cfg.scale

            return jax.jit(step)

        def _step_exe(self, n):
            key = self._key(n)
            exe = self._step_cache.get(key)
            if exe is None:
                exe = self._build_step()
                self._step_cache[key] = exe
            return exe

        def warmed(self, n):
            return self._key(n) in self._step_cache
"""


def test_key_clean_paged_idiom():
    assert run_src(_MEMO_OK, "KEY001") == []


def test_key_mutation_deleting_qkey_yields_exactly_one_finding():
    """The teeth test: drop `+ self._qkey` from the key helper (the
    PR 9 bug shape) — exactly one finding, of the stale-executable
    kind, because `_qkey` is declared trace-config (key-mandatory)."""
    mutated = _MEMO_OK.replace(
        "return (n, self.cfg, self.impl) + self._qkey",
        "return (n, self.cfg, self.impl)")
    assert mutated != _MEMO_OK
    fs = run_src(mutated, "KEY001")
    assert len(fs) == 1, [f.message for f in fs]
    assert "_qkey" in fs[0].message and "STALE" in fs[0].message


def test_key_mutation_deleting_impl_yields_exactly_one_finding():
    mutated = _MEMO_OK.replace(
        "return (n, self.cfg, self.impl) + self._qkey",
        "return (n, self.cfg) + self._qkey")
    fs = run_src(mutated, "KEY001")
    assert len(fs) == 1
    assert "impl" in fs[0].message and "trace-config" in fs[0].message


def test_key_missing_config_read_under_trace():
    """Finding kind 1: the builder bakes `self.depth` in, the key
    doesn't carry it — a depth change serves a stale executable."""
    fs = run_src("""
        import jax

        class B:
            def __init__(self, cfg, depth):
                self.cfg = cfg
                self.depth = depth
                self._c_cache = {}

            def _build_c(self):
                c, d = self.cfg, self.depth

                def f(x):
                    return x * c.scale + d

                return jax.jit(f)

            def _c_exe(self, n):
                key = (n, self.cfg)
                exe = self._c_cache.get(key)
                if exe is None:
                    exe = self._build_c()
                    self._c_cache[key] = exe
                return exe
    """, "KEY001")
    assert len(fs) == 1, [f.message for f in fs]
    assert "depth" in fs[0].message and "STALE" in fs[0].message


def test_key_spurious_element_never_read():
    """Finding kind 2: `self.tag` rides the key but nothing traced
    reads it — every distinct tag recompiles an identical program."""
    fs = run_src("""
        import jax

        class B:
            def __init__(self, cfg, tag):
                self.cfg = cfg
                self.tag = tag
                self._c_cache = {}

            def _build_c(self):
                c = self.cfg

                def f(x):
                    return x * c.scale

                return jax.jit(f)

            def _c_exe(self, n):
                key = (n, self.cfg, self.tag)
                exe = self._c_cache.get(key)
                if exe is None:
                    exe = self._build_c()
                    self._c_cache[key] = exe
                return exe
    """, "KEY001")
    assert len(fs) == 1, [f.message for f in fs]
    assert "tag" in fs[0].message and "never read" in fs[0].message


def test_key_membership_check_drift():
    """Finding kind 3: the warmup `in`-check forgot an element the
    `.get` key carries — the PR 9/14 warmup-assertion bug shape."""
    fs = run_src("""
        import jax

        class B:
            def __init__(self, cfg, depth):
                self.cfg = cfg
                self.depth = depth
                self._c_cache = {}

            def _build_c(self):
                c, d = self.cfg, self.depth

                def f(x):
                    return x * c.scale + d

                return jax.jit(f)

            def _c_exe(self, n):
                key = (n, self.cfg, self.depth)
                exe = self._c_cache.get(key)
                if exe is None:
                    exe = self._build_c()
                    self._c_cache[key] = exe
                return exe

            def warmed(self, n):
                return (n, self.cfg) in self._c_cache
    """, "KEY001")
    assert len(fs) == 1, [f.message for f in fs]
    assert "membership check" in fs[0].message
    assert "not term-identical" in fs[0].message


def test_key_wildcard_locals_do_not_drift():
    """Shape locals named differently at different sites (`n` vs `m`)
    and different constant tags are NOT drift — only attr structure."""
    fs = run_src("""
        import jax

        class B:
            def __init__(self, cfg):
                self.cfg = cfg
                self._c_cache = {}

            def _build_c(self):
                c = self.cfg

                def f(x):
                    return x * c.scale

                return jax.jit(f)

            def _c_exe(self, n, phase):
                key = (n, "draft", self.cfg)
                exe = self._c_cache.get(key)
                if exe is None:
                    exe = self._build_c()
                    self._c_cache[key] = exe
                return exe

            def warmed(self, m):
                return (m, "verify", self.cfg) in self._c_cache
    """, "KEY001")
    assert fs == [], [f.message for f in fs]


def test_key_memo_invariant_class_wide_suppression():
    """`# ptlint: memo-invariant(...)` on the __init__ assignment
    exempts the attr's keyless reads component-wide."""
    fs = run_src("""
        import jax

        class B:
            def __init__(self, cfg, eos):
                self.cfg = cfg
                # ptlint: memo-invariant(eos id fixed at construction)
                self.eos = eos
                self._c_cache = {}

            def _build_c(self):
                c, e = self.cfg, self.eos

                def f(x):
                    return x * c.scale + e

                return jax.jit(f)

            def _c_exe(self, n):
                key = (n, self.cfg)
                exe = self._c_cache.get(key)
                if exe is None:
                    exe = self._build_c()
                    self._c_cache[key] = exe
                return exe
    """, "KEY001")
    assert fs == [], [f.message for f in fs]


def test_key_memo_invariant_per_read_line_suppression():
    """The per-read form: annotating the read line inside the builder
    exempts that site without declaring the attr class-wide."""
    fs = run_src("""
        import jax

        class B:
            def __init__(self, cfg, eos):
                self.cfg = cfg
                self.eos = eos
                self._c_cache = {}

            def _build_c(self):
                c = self.cfg
                e = self.eos  # ptlint: memo-invariant(fixed at ctor)

                def f(x):
                    return x * c.scale + e

                return jax.jit(f)

            def _c_exe(self, n):
                key = (n, self.cfg)
                exe = self._c_cache.get(key)
                if exe is None:
                    exe = self._build_c()
                    self._c_cache[key] = exe
                return exe
    """, "KEY001")
    assert fs == [], [f.message for f in fs]


def test_key_inheritance_through_base_chain():
    """The builder lives on the base class, the memo method on the
    derived one — the component walk still derives the traced reads."""
    fs = run_src("""
        import jax

        class Base:
            def __init__(self, cfg, gamma):
                self.cfg = cfg
                self.gamma = gamma
                self._c_cache = {}

            def _build_c(self):
                c, g = self.cfg, self.gamma

                def f(x):
                    return x * c.scale + g

                return jax.jit(f)

        class Derived(Base):
            def _c_exe(self, n):
                key = (n, self.cfg)
                exe = self._c_cache.get(key)
                if exe is None:
                    exe = self._build_c()
                    self._c_cache[key] = exe
                return exe
    """, "KEY001")
    assert len(fs) == 1, [f.message for f in fs]
    assert "gamma" in fs[0].message and "STALE" in fs[0].message


def test_key_disable_comment_works():
    mutated = _MEMO_OK.replace(
        "            exe = self._step_cache.get(key)",
        "            # ptlint: disable=KEY001 — fixture justification\n"
        "            exe = self._step_cache.get(key)").replace(
        "return (n, self.cfg, self.impl) + self._qkey",
        "return (n, self.cfg, self.impl)")
    assert run_src(mutated, "KEY001") == []


def test_key_bookkeeping_dicts_not_policed():
    """A dict that only stores (a metrics gauge, a result log) is not
    the memo idiom — no get/member pairing, no findings."""
    fs = run_src("""
        class B:
            def __init__(self, cfg):
                self.cfg = cfg
                self._log_cache = {}

            def record(self, n, v):
                self._log_cache[(n, self.cfg)] = v
    """, "KEY001")
    assert fs == []


def test_trace001_sees_every_step_program_as_traced():
    """The batcher's step programs are jitted by its own `_step_jit`
    (`jax.jit` with the KV pool donated), not by a bare `jax.jit` at the
    builder: the rule has to know that wrapper, or the five functions
    that ARE the served path drop out of TRACE001's and SYNC001's sight
    in silence."""
    from paddle_tpu.analysis.rules.trace import find_traced_functions
    (ctx,) = [f for f in real_tree().files
              if f.relpath.endswith("nlp/paged.py")]
    traced = {fn.name: why for fn, why in find_traced_functions(ctx)}
    for name in ("serve_prefill_step", "serve_decode_step",
                 "serve_fused_step", "serve_spec_draft",
                 "serve_spec_verify"):
        assert traced.get(name) == "wrapped by self._step_jit", name


def test_key001_discovers_every_paged_cache():
    """Coverage floor, same idiom as the SYNC001 superset pin: every
    `self._*_cache` attribute in nlp/paged.py must be discovered (and
    qualify as a memo cache) — a refactor that renames a cache out of
    the rule's sight fails here, not three PRs later."""
    project = real_tree()
    graph = build_callgraph(project)
    caches = discover_memo_caches(graph)
    qualified = set()
    for (_canon, name), entry in caches.items():
        kinds = {s.kind for s in entry["sites"]}
        if "set" in kinds and ({"get", "member"} & kinds):
            qualified.add(name)
    src = open(os.path.join(REPO, "paddle_tpu", "nlp", "paged.py"),
               encoding="utf-8").read()
    in_source = set(re.findall(r"self\.(_\w+_cache)\b", src))
    # the four compiled-shape caches the rule was built for are the
    # floor — pinned by name so a silent discovery regression is loud
    assert {"_prefill_cache", "_fused_cache", "_chunk_cache",
            "_spec_cache"} <= in_source
    assert in_source <= qualified, (
        f"caches in paged.py not discovered by KEY001: "
        f"{sorted(in_source - qualified)}")


# ---------------------------------------------------------------------------
# ASYNC001 — blocking calls in async bodies
# ---------------------------------------------------------------------------

def test_async_time_sleep_flagged():
    fs = run_src("""
        import time

        async def handler():
            time.sleep(1)
    """, "ASYNC001")
    assert len(fs) == 1 and "time.sleep" in fs[0].message


def test_async_future_result_and_acquire_flagged():
    fs = run_src("""
        async def handler(fut, lock):
            fut.result()
            lock.acquire()
    """, "ASYNC001")
    assert len(fs) == 2
    assert any("result" in f.message for f in fs)
    assert any("acquire" in f.message for f in fs)


def test_async_router_call_flagged():
    fs = run_src("""
        class Frontend:
            def __init__(self, router):
                self.router = router

            async def handle(self, prompt):
                return self.router.submit(prompt)
    """, "ASYNC001")
    assert len(fs) == 1 and "serving-tier" in fs[0].message


def test_async_getattr_bound_router_local_flagged():
    fs = run_src("""
        class Frontend:
            def __init__(self, router):
                self.router = router

            async def handle(self, slot):
                reset = getattr(self.router, "reset_breaker", None)
                return reset(slot)
    """, "ASYNC001")
    assert len(fs) == 1 and "getattr" in fs[0].message


def test_async_callgraph_resolved_blocking_helper():
    """The `self._submit` -> `router.submit` shape: the async body
    calls a sync helper whose closure blocks — flagged at the call."""
    fs = run_src("""
        class Frontend:
            def __init__(self, router):
                self.router = router

            def _submit(self, prompt):
                return self.router.submit(prompt)

            async def handle(self, prompt):
                return self._submit(prompt)
    """, "ASYNC001")
    assert len(fs) == 1
    assert "_submit" in fs[0].message
    assert "run_in_executor" in fs[0].message


def test_async_negatives():
    """awaited calls, run_in_executor-routed work, sync functions'
    own bodies, and nested sync defs are all fine."""
    fs = run_src("""
        import asyncio
        import time

        class Frontend:
            def __init__(self, router):
                self.router = router

            async def handle(self, prompt):
                loop = asyncio.get_running_loop()
                text = await loop.run_in_executor(
                    None, lambda: self.router.to_prometheus())
                data = await self.read(prompt)
                return text, data

            async def read(self, prompt):
                await asyncio.sleep(0.01)
                return prompt

            def shutdown(self, fut):
                # sync: blocks the CALLER's thread, not the loop
                time.sleep(0.1)
                return fut.result()

            async def spawn(self):
                def worker():
                    return self.router.submit("x")
                return worker
    """, "ASYNC001")
    assert fs == [], [f.message for f in fs]


def test_async_disable_comment_works():
    fs = run_src("""
        class Frontend:
            def __init__(self, router):
                self.router = router

            async def health(self):
                # ptlint: disable=ASYNC001 — short-lock snapshot
                return self.router.health()
    """, "ASYNC001")
    assert fs == []


def test_real_frontend_async_clean():
    """serving/frontend.py is burned down: the real fixes + inline
    justifications hold (a new blocking call in a handler fails)."""
    fs = [f for f in run_rules(real_tree(), ALL_RULES)
          if f.rule == "ASYNC001"]
    assert fs == [], [f"{f.location} {f.message}" for f in fs]


# ---------------------------------------------------------------------------
# --changed-only / --fail-dead-roots / parse memo
# ---------------------------------------------------------------------------

_BROAD_EXCEPT = "try:\n    work()\nexcept Exception:\n    pass\n"


def _git(args, cwd):
    subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t"]
                   + args, cwd=cwd, check=True, capture_output=True)


def test_cli_changed_only_scopes_to_git_diff(tmp_path, capsys):
    """a.py (committed, has a finding) is invisible; b.py (untracked,
    same finding) reports — the pre-commit loop only sees the diff."""
    (tmp_path / "a.py").write_text(_BROAD_EXCEPT)
    _git(["init", "-q"], tmp_path)
    _git(["add", "a.py"], tmp_path)
    _git(["commit", "-qm", "seed"], tmp_path)
    (tmp_path / "b.py").write_text(_BROAD_EXCEPT)
    rc = ptlint_main([str(tmp_path), "--root", str(tmp_path),
                      "--no-baseline", "--changed-only",
                      "--format", "json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert {f["path"] for f in data["new"]} == {"b.py"}
    assert data["focused_files"] == 1
    # full run still sees both — the scoping is opt-in
    rc = ptlint_main([str(tmp_path), "--root", str(tmp_path),
                      "--no-baseline", "--format", "json"])
    data = json.loads(capsys.readouterr().out)
    assert {f["path"] for f in data["new"]} == {"a.py", "b.py"}


def test_cli_changed_only_clean_tree_reports_nothing(tmp_path, capsys):
    (tmp_path / "a.py").write_text(_BROAD_EXCEPT)
    _git(["init", "-q"], tmp_path)
    _git(["add", "a.py"], tmp_path)
    _git(["commit", "-qm", "seed"], tmp_path)
    rc = ptlint_main([str(tmp_path), "--root", str(tmp_path),
                      "--no-baseline", "--changed-only",
                      "--format", "json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 0 and data["new"] == [] and data["focused_files"] == 0


def test_cli_fail_dead_roots_gates(tmp_path, capsys):
    """On a tree with none of the hot-root files, every HOT_ROOTS
    pattern is dead: the flag turns that into exit 1 (without it the
    same run passes — the report alone never gated)."""
    (tmp_path / "ok.py").write_text("x = 1\n")
    args = [str(tmp_path / "ok.py"), "--root", str(tmp_path),
            "--no-baseline"]
    assert ptlint_main(args) == 0
    capsys.readouterr()
    rc = ptlint_main(args + ["--fail-dead-roots"])
    captured = capsys.readouterr()
    assert rc == 1
    assert "DEAD hot-path root" in captured.err


def test_focus_scopes_run_rules():
    bad = textwrap.dedent("""
        def f():
            try:
                work()
            except Exception:
                pass
    """)
    a = FileContext("a.py", bad, "a.py")
    b = FileContext("b.py", bad, "b.py")
    project = Project([a, b])
    assert {f.path for f in run_rules(project, ALL_RULES)} == \
        {"a.py", "b.py"}
    project.focus = {"b.py"}
    assert {f.path for f in run_rules(project, ALL_RULES)} == {"b.py"}


def test_parse_memo_reuses_tree_for_unchanged_source():
    src = "def f():\n    return 1\n"
    a = FileContext("m.py", src, "m.py")
    b = FileContext("m.py", src, "m.py")
    assert a.tree is b.tree
    c = FileContext("m.py", src + "\nx = 2\n", "m.py")
    assert c.tree is not a.tree
