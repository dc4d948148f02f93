"""KEY001 — memo-key soundness for the compiled-shape caches.

The batcher memoizes AOT-compiled executables in `self._*_cache` dicts
keyed on (shape, config) tuples. The invariant those keys must hold is
whole-program: every `self.<attr>` the builder's traced closure bakes
into the lowered program must be part of the key — a missing element
means a config change silently serves a STALE executable (wrong math,
no error), a spurious element means every distinct value recompiles an
identical program (the recompile storms the tests' zero-recompile gates
only catch per-workload). PR 9 threaded the quantization pair
(`_qkey`) through all four caches and PR 14 threaded the spec config
(`_skey`); both needed review fixes for drifted keys. This rule is
that review, mechanized:

  1. DISCOVER every memo-cache site: `self._X_cache.get(key)` /
     `self._X_cache[key] = ...` pairs plus the warmup/assertion
     membership checks (`key in self._X_cache`), and normalize each
     key expression into its constituent terms — `self.<attr>` reads,
     constants, and per-call locals (shape wildcards). Tuple
     concatenation (`(...) + self._skey + self._qkey`), local `key =`
     assignments, and one level of `self._key_helper(...)` expansion
     (a helper whose body returns a tuple expression) all normalize.
  2. DERIVE the trace-relevant config per cache by walking the call
     graph from the builder's traced closure — the `_build_*` /
     `_forward_*` methods the memo method lowers — and collecting
     every `self.<attr>` read reachable inside the class's
     inheritance component (`CallGraph.component_attr_reads`).
     Module-level helpers take explicit arguments, so the component
     restriction is exactly "state the closure can bake in".
  3. REPORT three finding kinds:
       * config-read-under-trace-missing-from-key (stale executable);
       * key-element-never-read-under-trace (spurious recompiles);
       * membership-check-key-drift — an `in`-check (or paired store)
         whose term sequence is not identical to the `.get` key's,
         the exact shape of the PR 9/14 warmup-assertion bugs.

Declaration grammar, symmetric to GUARD001's:

    self._qkey = (wdt, kdt)     # ptlint: trace-config
    self.cfg = cfg              # ptlint: memo-invariant(frozen at ctor)

`# ptlint: trace-config` on an attr's defining assignment in
`__init__` declares it KEY-MANDATORY: it must appear in every memo key
of the component (that is how `_qkey`/`_skey` are enforced even though
the traced code never reads them — the memo method splices the
precomputed tuple in), and it is exempt from the spurious-element
check. `# ptlint: memo-invariant(reason)` documents a deliberately
keyless read — on the `__init__` assignment it exempts the attr
class-wide, on a read line it exempts that read site. Both accept a
standalone comment line applying to the next code line, and the plain
`# ptlint: disable=KEY001` escape hatch works as for every rule.

Term comparison is splice- and name-insensitive where it must be:
locals are shape values that differ by name across sites (`G`/`Pb` in
the memo method vs `Gp`/`bucket` at the warmup assertion), so
wildcards match wildcards and constants, and constants match each
other regardless of value (a 'draft'/'verify' phase tag is a
legitimate per-site difference); `self.<attr>` terms must match
exactly, position by position — drift is a structural difference, a
missing/extra/renamed attr element.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, NamedTuple, Optional, Set, Tuple

from ..callgraph import CallGraph, FnKey, build_callgraph, fn_label
from ..core import FileContext, Finding, Project, Rule

# memo-cache attrs: `_prefill_cache`, `_spec_cache`, ... (fullmatch, so
# metric gauges like `_g_kv_cached_bytes` never qualify)
CACHE_NAME_RE = re.compile(r"_\w+_cache")
# the traced-closure roots a memo method lowers
BUILDER_NAME_RE = re.compile(r"_(?:build|forward)_\w+")

_ANNOT_RE = re.compile(
    r"#\s*ptlint:\s*(trace-config|memo-invariant\(([^)]*)\))")

_MAX_EXPAND = 3          # key-helper / local-assignment expansion depth


def parse_memo_annotations(
        lines: List[str]) -> Dict[int, Tuple[str, Optional[str]]]:
    """1-based line -> ('trace-config', None) | ('memo-invariant',
    reason). Standalone comment lines carry to the next code line,
    like `# ptlint: disable=` does."""
    out: Dict[int, Tuple[str, Optional[str]]] = {}
    pending: Optional[Tuple[str, Optional[str]]] = None
    for i, text in enumerate(lines, start=1):
        stripped = text.strip()
        match = _ANNOT_RE.search(text)
        ann: Optional[Tuple[str, Optional[str]]] = None
        if match:
            ann = (("trace-config", None)
                   if match.group(1) == "trace-config"
                   else ("memo-invariant", (match.group(2) or "").strip()))
        if stripped.startswith("#") or not stripped:
            if ann:
                pending = ann
            continue
        here = ann or pending
        pending = None
        if here:
            out[i] = here
    return out


class _Term(NamedTuple):
    """One normalized memo-key element."""

    kind: str      # 'attr' (self.<value>) | 'const' | 'wild' (local/shape)
    value: str


def _fmt_terms(terms: Tuple[_Term, ...]) -> str:
    bits = []
    for t in terms:
        if t.kind == "attr":
            bits.append(f"self.{t.value}")
        elif t.kind == "const":
            bits.append(t.value)
        else:
            bits.append(f"<{t.value}>")
    return "(" + ", ".join(bits) + ")"


def _compatible(a: Tuple[_Term, ...], b: Tuple[_Term, ...]) -> bool:
    """Term-identical up to value wildcards: attrs must match position
    by position; constants and local-name wildcards (per-call shape
    values and bucket tags like a 'draft'/'verify' phase, legitimately
    different per site) match each other freely. Drift is a structural
    difference — a missing/extra/renamed attr element — not a
    different value in the same slot."""
    if len(a) != len(b):
        return False
    for ta, tb in zip(a, b):
        if ta.kind == "attr" or tb.kind == "attr":
            if ta.kind != tb.kind or ta.value != tb.value:
                return False
        # const/wild vs const/wild: compatible
    return True


def _last_local_assign(fn: ast.AST, name: str,
                       before_line: int) -> Optional[ast.Assign]:
    """The latest single-target `name = ...` in `fn` before the use."""
    best: Optional[ast.Assign] = None
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == name \
                and node.lineno < before_line \
                and (best is None or node.lineno > best.lineno):
            best = node
    return best


def _flatten_key(expr: ast.AST, graph: CallGraph, cls: Optional[str],
                 fn: ast.AST, subst: Dict[str, List[_Term]],
                 depth: int) -> List[_Term]:
    """Normalize a key expression into its term sequence.

    Splice-insensitive by design: `(a, self._skey)` and
    `(a,) + self._skey` flatten identically — presence and order of
    attr terms is what soundness needs, not tuple nesting."""
    if depth < 0:
        return [_Term("wild", "...")]
    if isinstance(expr, ast.Tuple):
        out: List[_Term] = []
        for elt in expr.elts:
            inner = elt.value if isinstance(elt, ast.Starred) else elt
            out.extend(_flatten_key(inner, graph, cls, fn, subst, depth))
        return out
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
        return (_flatten_key(expr.left, graph, cls, fn, subst, depth)
                + _flatten_key(expr.right, graph, cls, fn, subst, depth))
    if isinstance(expr, ast.Constant):
        return [_Term("const", repr(expr.value))]
    if isinstance(expr, ast.Attribute) and isinstance(expr.value, ast.Name) \
            and expr.value.id == "self":
        return [_Term("attr", expr.attr)]
    if isinstance(expr, ast.Name):
        if expr.id in subst:
            return list(subst[expr.id])
        assign = _last_local_assign(fn, expr.id, expr.lineno)
        if assign is not None:
            return _flatten_key(assign.value, graph, cls, fn, subst,
                                depth - 1)
        return [_Term("wild", expr.id)]
    if isinstance(expr, ast.Call):
        # `self._key_helper(args)` whose body is a single
        # `return <tuple expr>`: expand with param -> arg substitution
        # (how `_spec_key("draft")` keys normalize)
        func = expr.func
        if isinstance(func, ast.Attribute) \
                and isinstance(func.value, ast.Name) \
                and func.value.id == "self" and cls is not None \
                and depth > 0 and not expr.keywords:
            target = graph.method(cls, func.attr)
            if target is not None:
                _tctx, tfn = graph.functions[target]
                rets = [n for n in ast.walk(tfn)
                        if isinstance(n, ast.Return) and n.value is not None]
                if len(rets) == 1:
                    params = [a.arg for a in tfn.args.args[1:]]
                    sub: Dict[str, List[_Term]] = {}
                    for p, a in zip(params, expr.args):
                        sub[p] = _flatten_key(a, graph, cls, fn, subst,
                                              depth - 1)
                    return _flatten_key(rets[0].value, graph, target[1],
                                        tfn, sub, depth - 1)
        return [_Term("wild", ast.unparse(expr)[:40])]
    return [_Term("wild", type(expr).__name__)]


def _self_cache_attr(expr: ast.AST) -> Optional[str]:
    """`self._X_cache` -> '_X_cache', else None."""
    if isinstance(expr, ast.Attribute) and isinstance(expr.value, ast.Name) \
            and expr.value.id == "self" \
            and CACHE_NAME_RE.fullmatch(expr.attr):
        return expr.attr
    return None


def _cache_sites(
        meth: ast.AST) -> Iterator[Tuple[str, str, ast.AST, ast.AST]]:
    """(kind, cache attr, key expr, anchor node) for every memo-cache
    access in one method: get / set / membership."""
    for node in ast.walk(meth):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "get" and node.args:
            name = _self_cache_attr(node.func.value)
            if name:
                yield ("get", name, node.args[0], node)
        elif isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Subscript):
                    name = _self_cache_attr(tgt.value)
                    if name:
                        yield ("set", name, tgt.slice, node)
        elif isinstance(node, ast.Compare) and len(node.ops) == 1 \
                and isinstance(node.ops[0], (ast.In, ast.NotIn)):
            name = _self_cache_attr(node.comparators[0])
            if name:
                yield ("member", name, node.left, node)


class _Site(NamedTuple):
    kind: str                 # 'get' | 'set' | 'member'
    mkey: FnKey
    ctx: FileContext
    node: ast.AST
    terms: Tuple[_Term, ...]


def _target_attrs(tgt: ast.AST) -> Iterator[str]:
    """self-attr names bound by one assignment target (tuple targets
    included — `self.params, self.cfg = params, cfg`)."""
    if isinstance(tgt, ast.Attribute) and isinstance(tgt.value, ast.Name) \
            and tgt.value.id == "self":
        yield tgt.attr
    elif isinstance(tgt, (ast.Tuple, ast.List)):
        for e in tgt.elts:
            yield from _target_attrs(e)


def discover_memo_caches(
        graph: CallGraph) -> Dict[Tuple[str, str], Dict[str, object]]:
    """Every memo-cache site in the project, grouped per inheritance
    component: {(canonical class, cache attr) -> {'cls', 'sites',
    'methods'}}. Discovery only — qualification (a real memo cache
    both stores and looks up) is the caller's filter. Exposed so the
    coverage pin test can assert the real tree's caches are all seen."""
    cindex = graph.class_index
    caches: Dict[Tuple[str, str], Dict[str, object]] = {}
    for cname in sorted(cindex.classes):
        ctx, clsnode = cindex.classes[cname]
        canon = cindex.canonical(cname)
        for meth in clsnode.body:
            if not isinstance(meth, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            mkey: FnKey = (ctx.module_name, cname, meth.name)
            for kind, name, key_expr, anchor in _cache_sites(meth):
                terms = tuple(_flatten_key(key_expr, graph, cname,
                                           meth, {}, _MAX_EXPAND))
                entry = caches.setdefault((canon, name), {
                    "cls": cname, "sites": [], "methods": set()})
                entry["sites"].append(
                    _Site(kind, mkey, ctx, anchor, terms))
                if kind in ("get", "set"):
                    entry["methods"].add(mkey)
    return caches


class MemoKeyRule(Rule):
    """KEY001: whole-program memo-key soundness for the compiled-shape
    caches (see module docstring for the three finding kinds)."""

    id = "KEY001"
    severity = "error"
    description = ("compiled-shape memo key is unsound: config read "
                   "under trace missing from the key (stale "
                   "executable), key element never read under trace "
                   "(spurious recompiles), or a membership check that "
                   "drifted from the paired .get key")

    def run(self, project: Project) -> Iterator[Finding]:
        graph = build_callgraph(project)
        cindex = graph.class_index
        ann_cache: Dict[int, Dict[int, Tuple[str, Optional[str]]]] = {}

        def annot(ctx: FileContext) -> Dict[int, Tuple[str, Optional[str]]]:
            key = id(ctx)
            if key not in ann_cache:
                ann_cache[key] = parse_memo_annotations(ctx.lines)
            return ann_cache[key]

        caches = discover_memo_caches(graph)
        for canon, name in sorted(caches):
            entry = caches[(canon, name)]
            sites: List[_Site] = entry["sites"]           # type: ignore
            kinds = {s.kind for s in sites}
            # a memo cache stores AND looks up; a dict that only ever
            # stores (or only tests membership) is bookkeeping, not the
            # compiled-shape idiom this rule polices
            if "set" not in kinds or not ({"get", "member"} & kinds):
                continue
            # `--changed-only`: every finding anchors at a cache site,
            # so a cache whose sites all live outside the focus set
            # cannot emit — skip its (call-graph-walking) analysis
            if not any(project.focused(s.ctx.relpath) for s in sites):
                continue
            yield from self._check_cache(graph, cindex, annot, canon,
                                         name, entry)

    # ---- per-cache analysis ----------------------------------------------
    def _check_cache(self, graph: CallGraph, cindex, annot, canon: str,
                     name: str, entry: Dict[str, object]
                     ) -> Iterator[Finding]:
        sites: List[_Site] = entry["sites"]               # type: ignore
        cls: str = entry["cls"]                           # type: ignore
        memo_methods: Set[FnKey] = entry["methods"]       # type: ignore
        get_sites = [s for s in sites if s.kind == "get"]
        set_sites = [s for s in sites if s.kind == "set"]
        member_sites = [s for s in sites if s.kind == "member"]
        primary = get_sites[0] if get_sites else set_sites[0]
        key_attrs = {t.value for t in primary.terms if t.kind == "attr"}
        trace_cfg, invariant = self._component_annotations(cindex, annot,
                                                           canon)

        # (b') declared-mandatory attrs must ride EVERY key of the
        # component — how `_qkey`/`_skey` are enforced even though the
        # traced code never reads the precomputed tuples themselves
        for attr in sorted(trace_cfg):
            if attr not in key_attrs:
                yield primary.ctx.finding(
                    self, primary.node,
                    f"memo cache '{name}': `self.{attr}` is declared "
                    f"`# ptlint: trace-config` (key-mandatory for this "
                    f"class) but missing from this key "
                    f"{_fmt_terms(primary.terms)} — a config change "
                    f"would serve a STALE compiled executable; splice "
                    f"it into the key like the sibling caches do")

        # ---- derive the trace-relevant config set from the builders
        builders: Set[FnKey] = set()
        for mkey in memo_methods:
            for callee in graph.edges.get(mkey, ()):
                if callee[1] is not None \
                        and cindex.canonical(callee[1]) == canon \
                        and BUILDER_NAME_RE.fullmatch(callee[2]):
                    builders.add(callee)
        if not builders:
            derived: Dict[str, List] = {}
        else:
            derived = graph.component_attr_reads(sorted(builders), cls)
            # methods referenced from traced code (`self._emit_one`,
            # vmap'd `self._write_pool`) and the cache dicts themselves
            # are not config
            derived = {a: r for a, r in derived.items()
                       if graph.method(cls, a) is None
                       and not CACHE_NAME_RE.fullmatch(a)}

        if builders:
            # (a) config read under trace but missing from the key
            for attr in sorted(set(derived) - key_attrs):
                if attr in trace_cfg:
                    continue             # already reported as mandatory
                if attr in invariant:
                    continue             # class-wide memo-invariant
                read_sites = derived[attr]
                if any(annot(graph.functions[k][0]).get(
                        node.lineno, (None,))[0] == "memo-invariant"
                        for k, node in read_sites):
                    continue             # read-site memo-invariant
                rkey, rnode = read_sites[0]
                yield primary.ctx.finding(
                    self, primary.node,
                    f"memo cache '{name}': `self.{attr}` is read under "
                    f"trace by the builder closure "
                    f"('{fn_label(rkey)}' line {rnode.lineno}) but is "
                    f"not part of the memo key "
                    f"{_fmt_terms(primary.terms)} — changing it would "
                    f"serve a STALE compiled executable; add it to the "
                    f"key, or annotate the read (or its __init__ "
                    f"assignment) `# ptlint: memo-invariant(reason)` "
                    f"if it is genuinely fixed for the object's "
                    f"lifetime")

            # (b) key element never read under trace: spurious recompiles
            flagged: Set[str] = set()
            for t in primary.terms:
                if t.kind != "attr" or t.value in flagged:
                    continue
                if t.value in derived or t.value in trace_cfg:
                    continue
                flagged.add(t.value)
                yield primary.ctx.finding(
                    self, primary.node,
                    f"memo cache '{name}': key element `self.{t.value}` "
                    f"is never read under trace by the builder closure "
                    f"— every distinct value recompiles an identical "
                    f"program (spurious recompile storm); drop it from "
                    f"the key, or declare the attr's __init__ "
                    f"assignment `# ptlint: trace-config` if the "
                    f"traced dependency is out of the call graph's "
                    f"sight")

        # (c) membership checks / paired stores must match the .get key
        if get_sites:
            ref = get_sites[0]
            for s in member_sites + set_sites + get_sites[1:]:
                if _compatible(s.terms, ref.terms):
                    continue
                what = ("membership check"
                        if s.kind == "member" else f"{s.kind} site")
                yield s.ctx.finding(
                    self, s.node,
                    f"memo cache '{name}': {what} key "
                    f"{_fmt_terms(s.terms)} in "
                    f"'{fn_label(s.mkey)}' is not term-identical to "
                    f"the paired .get key {_fmt_terms(ref.terms)} in "
                    f"'{fn_label(ref.mkey)}' — it tests a key the "
                    f"cache never stores, so the warmup/assertion "
                    f"passes (or fails) for the wrong reason")

    @staticmethod
    def _component_annotations(
            cindex, annot, canon: str
    ) -> Tuple[Set[str], Dict[str, str]]:
        """(trace-config attrs, memo-invariant attr -> reason) declared
        on __init__ defining assignments anywhere in the component."""
        trace_cfg: Set[str] = set()
        invariant: Dict[str, str] = {}
        for cname in sorted(cindex.classes):
            if cindex.canonical(cname) != canon:
                continue
            ctx, clsnode = cindex.classes[cname]
            file_ann = annot(ctx)
            for meth in clsnode.body:
                if not (isinstance(meth, ast.FunctionDef)
                        and meth.name == "__init__"):
                    continue
                for node in ast.walk(meth):
                    if not isinstance(node, ast.Assign):
                        continue
                    ann = file_ann.get(node.lineno)
                    if ann is None:
                        continue
                    for tgt in node.targets:
                        for attr in _target_attrs(tgt):
                            if ann[0] == "trace-config":
                                trace_cfg.add(attr)
                            else:
                                invariant[attr] = ann[1] or ""
        return trace_cfg, invariant
