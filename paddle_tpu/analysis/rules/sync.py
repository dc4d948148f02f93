"""SYNC001 — implicit host↔device synchronization in decode hot paths.

On TPU a `float()` / `int()` / `bool()` / `.item()` / `np.asarray()` on
a device value blocks the host until the device catches up; inside the
serving decode loop that turns an async pipeline into lock-step
ping-pong (the Ragged Paged Attention serving stack lives and dies by
keeping the decode loop free of these). The rule polices

  * the decode hot path — SEED ROOTS (`step()`-shaped entry points,
    below) plus every function they transitively call inside the
    package, derived from the call graph (`analysis.callgraph`), so a
    new step helper is covered the day it's written without anyone
    extending a hand-maintained list, and
  * every traced function (where `int(tracer)` is an outright error
    that only surfaces at trace time).

Flagged: `.item()`, `np.asarray`/`np.array`/`jax.device_get` calls,
`int`/`float`/`bool` whose argument mentions a jax value, and per-step
`jnp.asarray(self.<state>)` host→device re-uploads (cache a device
mirror instead — see ContinuousBatcher's device-state mirrors).

`HOT_ROOTS` entries are (relpath suffix, name regexes). A root pattern
that matches no function is DEAD — reported by `ptlint --hot-report`
(run non-blocking in CI) so renames can't silently shrink coverage.
Before the call-graph closure existed this list named every hot helper
by hand (~60 entries grown PR over PR); the closure derives those, and
`tests/test_analysis.py::test_sync_derived_hot_set_superset_of_old_list`
pins the old hand list as a floor so the refactor can never lose
coverage.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Tuple

from ..callgraph import FnKey, build_callgraph, fn_label
from ..core import FileContext, Finding, Project, Rule, dotted
from .trace import find_traced_functions

# (relpath suffix, function-name regexes): the decode hot path's SEED
# ROOTS. Everything these transitively call inside the package is hot
# automatically — list entry points and compiled-step bodies here, not
# their helpers.
HOT_ROOTS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    # the batcher's scheduler ticks: plain, fused, speculative — plus
    # forward_paged, which jit-traced model code calls without a
    # host-side call edge the graph could follow
    ("nlp/paged.py",
     ("step", "run", "_step_fused", "_step_spec", "_forward_spec",
      "forward_paged", "_prefill_pending", "_run_standalone_unit",
      # the tick helper (`_Tick`): every kind of tick runs inside it,
      # and the graph cannot follow a `with` statement into the
      # context manager's methods. Its one device touch, `fence`,
      # is the documented capture-window exception: it blocks only
      # while an operator's capture window is armed
      "__enter__", "__exit__", "phase", "fence", "call_s", "device_s",
      # the KV migration hop: export coalesces one device_get while
      # the source engine's loop is paused on it; import scatters into
      # the destination pool between its steps — both on serving ticks
      "export_kv", "import_kv")),
    # the kernel + impl pick: entered from traced code / engine setup;
    # _shard_specs is the shard_map composition surface — the
    # PartitionSpecs every mesh'd kernel call partitions under
    ("nlp/ragged_attention.py",
     ("ragged_paged_attention", "_rpa_kernel", "resolve_attention_impl",
      "_shard_specs")),
    # int8 paged-KV math runs inside every compiled step when
    # kv_dtype="int8"; called from traced bodies, so rooted explicitly
    ("quantization/kv.py",
     ("quantize", "dequantize", "rescale_codes", "scale_of")),
    # the engine thread's tick and the per-request dispatch fan-out
    ("serving/engine.py", ("_loop", "_dispatch", "load",
                           # KV handoff surfaces: called from the
                           # router's monitor thread / supervisor
                           # restart thread while engines keep stepping
                           "submit_import", "drain_export")),
    # router/frontend tier: per-request routing, the monitor sweep and
    # the HTTP handlers are entry points on their own threads
    ("serving/router.py", ("submit", "_monitor_loop", "_bridge",
                           "_migrate")),
    ("serving/frontend.py", ("_handle", "_generate", "_stream_sse")),
    # supervisor health-poll loop + the per-routing-decision probe
    ("serving/supervisor.py", ("_loop", "_restart_slot", "restart_slot",
                               "slot_serving", "info")),
    # tensor-parallel mesh surfaces: shard_info feeds snapshot()/
    # health()/metrics on their own threads while engines keep
    # stepping; build_shardings runs during a supervisor respawn
    # concurrent with the survivor's ticks; key() seeds the _mkey
    # element every compiled-shape memo key carries
    ("serving/tp.py", ("shard_info", "build_shardings", "key")),
    # per-tick accessors the graph cannot derive: they are invoked
    # through handles the type map can't follow (capture windows armed
    # over HTTP, spec stats read through as_dict plumbing, trace spans
    # opened on request handles) — pinned as roots so a host sync in
    # them still taxes no step
    # ... and what the tick helper emits through, reached from `_Tick`
    # through its batcher attribute: the profiler's gate and sample,
    # the flight record and its close, the sink's device-lane span
    ("serving/profiling.py", ("arm_capture", "capture_active",
                              "should_fence", "record")),
    ("serving/speculative.py", ("accept_rate", "tokens_per_step")),
    ("serving/trace.py", ("start", "finish", "alias", "now", "record",
                          "close", "span")),
)

def derive_hot_paths(project: Project):
    """(hot, dead): `hot` maps id(def node) -> (ctx, node, reason) for
    every function on the derived decode hot path; `dead` lists
    (suffix, pattern) root entries matching no function. Cached on the
    Project — the rule and `--hot-report` share one derivation."""
    cache = getattr(project, "cache", {})
    if "sync_hot_paths" in cache:
        return cache["sync_hot_paths"]
    graph = build_callgraph(project)
    roots: Dict[FnKey, None] = {}
    dead: List[Tuple[str, str]] = []
    for suffix, patterns in HOT_ROOTS:
        for pattern in patterns:
            rx = re.compile(pattern)
            matched = False
            for key, (ctx, _node) in graph.functions.items():
                if ctx.relpath.endswith(suffix) and rx.fullmatch(key[2]):
                    matched = True
                    roots.setdefault(key)
            if not matched:
                dead.append((suffix, pattern))
    prov = graph.closure_provenance(roots)
    hot: Dict[int, Tuple[FileContext, ast.AST, str]] = {}
    for key, root in prov.items():
        ctx, node = graph.functions[key]
        reason = ("decode hot path" if key == root
                  else f"decode hot path (via {fn_label(root)})")
        hot[id(node)] = (ctx, node, reason)
    result = (hot, dead)
    cache["sync_hot_paths"] = result
    return result

HOST_COPY_CALLS = {
    "numpy.asarray", "numpy.array", "np.asarray", "np.array",
    "jax.device_get",
}
DEVICE_UPLOAD_CALLS = {"jax.numpy.asarray", "jax.numpy.array"}
CAST_BUILTINS = {"int", "float", "bool"}


def _mentions_jax(node: ast.AST, resolve) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, (ast.Attribute, ast.Name)):
            target = resolve(sub)
            if target and (target == "jax" or target.startswith("jax.")):
                return True
    return False


class HostSyncRule(Rule):
    """SYNC001: flags host↔device syncs (.item(), np.asarray, casts on
    jax values, per-step uploads) in decode hot paths and traced fns."""

    id = "SYNC001"
    severity = "error"
    description = ("implicit host↔device sync (int()/float()/.item()/"
                   "np.asarray) in a decode hot path or traced function")

    def run(self, project: Project) -> Iterator[Finding]:
        # hot-set derivation is whole-program (the call graph sees every
        # file); only per-file emission honors `--changed-only` focus
        derived, _dead = derive_hot_paths(project)
        for ctx in project.files:
            if ctx.tree is None or not project.focused(ctx.relpath):
                continue
            hot = self._hot_functions(ctx, derived)
            classified = {id(fn) for fn, _ in hot}
            for fn, where in hot:
                yield from self._check_fn(ctx, fn, where, classified)

    def _hot_functions(self, ctx: FileContext,
                       derived) -> List[Tuple[ast.AST, str]]:
        hot: List[Tuple[ast.AST, str]] = []
        seen = set()
        for hot_ctx, node, reason in derived.values():
            if hot_ctx is ctx and id(node) not in seen:
                seen.add(id(node))
                hot.append((node, reason))
        hot.sort(key=lambda pair: getattr(pair[0], "lineno", 0))
        for fn, why in find_traced_functions(ctx):
            if id(fn) not in seen:
                seen.add(id(fn))
                hot.append((fn, f"traced function ({why})"))
        return hot

    def _check_fn(self, ctx: FileContext, fn: ast.AST, where: str,
                  classified) -> Iterator[Finding]:
        name = getattr(fn, "name", "<fn>")
        resolve = ctx.aliases.resolve
        # walk the body, but don't descend into nested defs that are
        # classified hot/traced themselves — they report their own
        stack: List[ast.AST] = list(ast.iter_child_nodes(fn))
        nodes: List[ast.AST] = []
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and id(node) in classified:
                continue
            nodes.append(node)
            stack.extend(ast.iter_child_nodes(node))
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Attribute) and func.attr == "item" \
                    and not node.args:
                yield ctx.finding(
                    self, node,
                    f".item() in '{name}' ({where}) blocks the host on "
                    f"the device — hoist out of the hot loop")
                continue
            target = resolve(func)
            if target in HOST_COPY_CALLS:
                yield ctx.finding(
                    self, node,
                    f"{dotted(func)}() device→host copy in '{name}' "
                    f"({where}) — sync once per chunk at most, outside "
                    f"the per-token loop")
            elif target in DEVICE_UPLOAD_CALLS and node.args and (
                    isinstance(node.args[0], ast.Attribute)):
                yield ctx.finding(
                    self, node,
                    f"{dotted(func)}({dotted(node.args[0])}) re-uploads "
                    f"host state to device every call of '{name}' "
                    f"({where}) — cache a device mirror, refresh on "
                    f"change")
            elif (isinstance(func, ast.Name)
                  and func.id in CAST_BUILTINS and node.args
                  and _mentions_jax(node.args[0], resolve)):
                yield ctx.finding(
                    self, node,
                    f"{func.id}() on a jax value in '{name}' ({where}) "
                    f"blocks the host — batch the readback instead")
