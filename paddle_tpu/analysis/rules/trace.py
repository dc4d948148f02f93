"""TRACE001 — Python side effects inside traced (jitted) functions.

On TPU every hot function runs as a traced XLA program: the Python body
executes ONCE at trace time, so `print`, mutation of closed-over or
global state, and `list.append` on a closure don't do what eager code
promised — they fire once per compilation (or never again), silently.
GSPMD-style traced programs (PAPERS: GSPMD) have no recovery path for
this; the checker rejects it outright.

A function counts as traced when it is
  * decorated with `jax.jit` / `jax.pmap` / `paddle_tpu.jit.to_static`
    (directly, called, or through `functools.partial`),
  * wrapped by name later (`g = jax.jit(f)`, `self._f = jax.jit(f)`; the
    batcher's own `self._step_jit(f)`, which is `jax.jit` with the KV
    pool donated), or
  * passed as a traced function of `jax.lax.scan` / `while_loop` /
    `fori_loop` / `cond` (at that primitive's function arg positions).
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Set, Tuple

from ..core import FileContext, Finding, Project, Rule, dotted

# dotted names whose call/decoration marks a function as traced
TRACING_WRAPPERS = {
    "jax.jit", "jax.pmap", "jax.experimental.pjit.pjit",
    "paddle_tpu.jit.to_static", "jit.to_static",
    "self._step_jit",       # nlp/paged.py: the step programs' jit
}
# control-flow primitives whose function-valued args are traced, with
# the positional indices those functions sit at
TRACING_BODY_TAKERS = {
    "jax.lax.scan": (0,),
    "jax.lax.while_loop": (0, 1),     # cond_fun, body_fun
    "jax.lax.fori_loop": (2,),        # lower, upper, body_fun
    "jax.lax.cond": (1, 2),           # pred, true_fun, false_fun
    "lax.scan": (0,),
    "lax.while_loop": (0, 1),
    "lax.fori_loop": (2,),
    "lax.cond": (1, 2),
}
MUTATING_METHODS = {
    "append", "extend", "insert", "remove", "pop", "clear", "update",
    "add", "setdefault", "popitem", "discard", "sort", "reverse",
}


def _is_tracing_expr(node: ast.AST, resolve) -> bool:
    """Does this decorator/callee expression denote a tracing wrapper?
    Handles `jax.jit`, `jax.jit(...)` and `functools.partial(jax.jit, ...)`."""
    if isinstance(node, ast.Call):
        target = resolve(node.func)
        if target in TRACING_WRAPPERS:
            return True
        if target in ("functools.partial", "partial") and node.args:
            return _is_tracing_expr(node.args[0], resolve)
        return False
    return resolve(node) in TRACING_WRAPPERS


def find_traced_functions(ctx: FileContext) -> List[Tuple[ast.AST, str]]:
    """All function defs in `ctx` that end up traced, with the reason.

    `g = jax.jit(f)` resolves `f` LEXICALLY: among same-named defs the
    one whose enclosing function scope is an ancestor of the call wins
    (an `LLMEngine.run` method is not confused with a nested `def run`
    handed to jax.jit inside another method).

    Memoized per FileContext: TRACE001 and SYNC001 both need this walk
    — it runs once per file per load, not once per rule."""
    cached = getattr(ctx, "_traced_fns", None)
    if cached is not None:
        return cached
    if ctx.tree is None:
        ctx._traced_fns = []
        return []
    resolve = ctx.aliases.resolve
    # name -> [(def node, ancestor-fn chain)] for bare-name-visible defs
    defs: Dict[str, List[Tuple[ast.AST, Tuple[int, ...]]]] = {}
    wrap_calls: List[Tuple[ast.Call, str, Tuple[int, ...]]] = []
    traced: List[Tuple[ast.AST, str]] = []
    seen: Set[int] = set()

    def mark(fn: ast.AST, why: str) -> None:
        if id(fn) not in seen:
            seen.add(id(fn))
            traced.append((fn, why))

    def walk(node: ast.AST, fn_stack: Tuple[int, ...],
             in_class: bool) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if not in_class:  # methods aren't visible as bare names
                    defs.setdefault(child.name, []).append(
                        (child, fn_stack))
                for dec in child.decorator_list:
                    if _is_tracing_expr(dec, resolve):
                        mark(child, f"decorated @{dotted(dec) or 'jit'}")
                walk(child, fn_stack + (id(child),), False)
            elif isinstance(child, ast.ClassDef):
                walk(child, fn_stack, True)
            else:
                if isinstance(child, ast.Call):
                    target = resolve(child.func)
                    if target and (target in TRACING_WRAPPERS
                                   or target in TRACING_BODY_TAKERS):
                        wrap_calls.append((child, target, fn_stack))
                walk(child, fn_stack, in_class)

    walk(ctx.tree, (), False)
    for call, target, call_stack in wrap_calls:
        positions = (TRACING_BODY_TAKERS[target]
                     if target in TRACING_BODY_TAKERS else (0,))
        for pos in positions:
            if pos >= len(call.args) or not isinstance(call.args[pos],
                                                       ast.Name):
                continue
            # visible candidates: def's scope chain is a prefix of the
            # call's; the deepest one shadows the rest
            best = None
            for fn, def_stack in defs.get(call.args[pos].id, ()):
                if call_stack[: len(def_stack)] == def_stack and (
                        best is None or len(def_stack) > len(best[1])):
                    best = (fn, def_stack)
            if best is not None:
                kind = ("wrapped by" if target in TRACING_WRAPPERS
                        else "body of")
                mark(best[0], f"{kind} {target}")
    ctx._traced_fns = traced
    return traced


def _local_bindings(fn: ast.AST) -> Set[str]:
    """Every name bound anywhere inside `fn` (params, assignments, loop
    targets, withitems, nested defs, imports, comprehensions). Names NOT
    here are free — closed-over or global."""
    bound: Set[str] = set()
    args = fn.args
    for a in (args.posonlyargs + args.args + args.kwonlyargs
              + ([args.vararg] if args.vararg else [])
              + ([args.kwarg] if args.kwarg else [])):
        bound.add(a.arg)

    def add_target(t: ast.AST) -> None:
        # only NAME targets bind; `x.y = ...` / `x[i] = ...` mutate x,
        # they don't make it local
        if isinstance(t, ast.Name):
            bound.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                add_target(e)
        elif isinstance(t, ast.Starred):
            add_target(t.value)

    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                add_target(t)
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            if isinstance(node.target, ast.Name):
                bound.add(node.target.id)
        elif isinstance(node, ast.NamedExpr):
            add_target(node.target)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            add_target(node.target)
        elif isinstance(node, ast.withitem) and node.optional_vars:
            add_target(node.optional_vars)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            if node is not fn:
                bound.add(node.name)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn:
                sub = node.args
                for a in (sub.posonlyargs + sub.args + sub.kwonlyargs
                          + ([sub.vararg] if sub.vararg else [])
                          + ([sub.kwarg] if sub.kwarg else [])):
                    bound.add(a.arg)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for a in node.names:
                bound.add((a.asname or a.name).split(".")[0])
        elif isinstance(node, ast.comprehension):
            add_target(node.target)
        elif isinstance(node, ast.ExceptHandler) and node.name:
            bound.add(node.name)
    return bound


class TraceSideEffectRule(Rule):
    """TRACE001: flags print/global/nonlocal/closure mutation inside
    functions that jax traces (see module docstring for the catalog)."""

    id = "TRACE001"
    severity = "error"
    description = ("side effect (print / closure mutation / global state) "
                   "inside a jit-traced function — runs at trace time only")

    def run(self, project: Project) -> Iterator[Finding]:
        for ctx in project.files:
            if ctx.tree is None or not project.focused(ctx.relpath):
                continue
            for fn, why in find_traced_functions(ctx):
                yield from self._check_fn(ctx, fn, why)

    def _check_fn(self, ctx: FileContext, fn: ast.AST,
                  why: str) -> Iterator[Finding]:
        bound = _local_bindings(fn)
        declared: Set[str] = set()
        name = getattr(fn, "name", "<lambda>")
        for node in ast.walk(fn):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                declared.update(node.names)
                yield ctx.finding(
                    self, node,
                    f"{type(node).__name__.lower()} "
                    f"{', '.join(node.names)} inside traced function "
                    f"'{name}' ({why}) — writes happen at trace time only")
            elif isinstance(node, ast.Call):
                if (isinstance(node.func, ast.Name)
                        and node.func.id == "print"
                        and "print" not in bound):
                    yield ctx.finding(
                        self, node,
                        f"print() inside traced function '{name}' ({why}) "
                        f"— fires once per compilation, use jax.debug.print")
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr in MUTATING_METHODS
                      and isinstance(node.func.value, ast.Name)
                      and node.func.value.id not in bound):
                    tgt = node.func.value.id
                    yield ctx.finding(
                        self, node,
                        f"mutating call {tgt}.{node.func.attr}() on "
                        f"closed-over/global '{tgt}' inside traced "
                        f"function '{name}' ({why})")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    root = t
                    if isinstance(root, (ast.Subscript, ast.Attribute)):
                        base = root.value
                        if (isinstance(base, ast.Name)
                                and base.id not in bound):
                            kind = ("subscript"
                                    if isinstance(root, ast.Subscript)
                                    else f"attribute '{root.attr}'")
                            yield ctx.finding(
                                self, node,
                                f"store to {kind} of closed-over/global "
                                f"'{base.id}' inside traced function "
                                f"'{name}' ({why})")
                    elif (isinstance(root, ast.Name) and root.id in declared):
                        pass  # already reported at the global/nonlocal stmt
