"""Share of the training step's device time that goes to the operations
under some `jax.named_scope` names, in %: as `moe_scope_share` (exclusive
device time of the matching XLA Ops events over the device time of every
program that matches `programs` in the traced span; operations NAMED by one
of `patterns` count too: the chip's grouped GEMM is a custom call whose
event carries no scope path), but a component of an operation's scope path
counts when it IS one of the scopes or WRAPS it: under `jax.grad` the
forward of a scope `s` is traced as `jvp(s)`, its backward as
`transpose(jvp(s))`, and inside a remat's recomputation as `s` again.
`xstats.scope_of` matches whole components and would read the recomputed
forward alone.

A metric whose scope ENCLOSES a whole module (`"between": true` in its
data file) also counts the operations that carry no scope path at all (the
grouped GEMMs, the copies and slices the compiler adds) where the nearest
operations with a path before AND after them lie under the scope: the
module's own, since its forward, recomputation and backward run back to
back.

The train runner keeps no `trace_dir` on `obs`; the trace thread's
directory is then the newest of its kind under the temporary directory
(as `tools/dump_stats.py` finds it), taken only if its programs are the
ones `obs["trace"]` holds: another run's directory gives no reading."""
import bisect
import glob
import os
import re
import tempfile

from ..harness import xplane
from . import xstats

_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def scope_under(path: str, scopes) -> str:
    """The innermost of `scopes` on a scope path, a component counting when
    it is the scope or wraps it (`jvp(s)`, `transpose(jvp(s))`); "" if
    none."""
    for part in reversed(path.rstrip(":").split("/")):
        while True:
            if part in scopes:
                return part
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
    return ""


def scoped_seconds(table, spec):
    """(exclusive device seconds under the scopes or named by the patterns
    inside the programs, those programs' device seconds)."""
    dev = xplane.device_planes(table)[0]
    progs = sorted((s, s + d) for n, s, d, _ in
                   xplane.line_events(dev, xplane.MODULES_LINE)
                   if any(p in n for p in spec["programs"]))
    ops = sorted(xplane.line_events(dev, xplane.OPS_LINE),
                 key=lambda e: (e[1], -e[2]))
    if not progs or not ops:
        return 0.0, 0.0
    scopes = set(spec["scopes"])
    paths = [str(e[3].get("tf_op", "")) for e in ops]
    under = [bool(scope_under(p, scopes)) for p in paths]
    if spec.get("between"):
        under = _between(under, ["/" in p for p in paths])
    starts = [p[0] for p in progs]
    spent = 0
    for (name, s, _, _), (_, _, own), inside in zip(
            ops, xplane.leaf_exclusive([e[:3] for e in ops]), under):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < progs[i][1] and (
                inside or any(p in name for p in spec.get("patterns", ()))):
            spent += own
    return spent / 1e9, sum(e - s for s, e in progs) / 1e9


def _between(under, has_path):
    """`under`, with every operation that has no scope path taking the
    verdict its nearest neighbours with one share (False where they
    differ, or one is missing)."""
    before, last = [], False
    for u, p in zip(under, has_path):
        last = u if p else last
        before.append(last)
    out, nxt = list(under), False
    for i in range(len(under) - 1, -1, -1):
        if has_path[i]:
            nxt = under[i]
        else:
            out[i] = before[i] and nxt
    return out


def _same_programs(a, b) -> bool:
    """Whether two tables hold the same programs at the same times, to a
    microsecond (the two loaders round an event's start apart)."""
    def programs(t):
        planes = xplane.device_planes(t)
        return [e[:3] for e in xplane.line_events(
            planes[0], xplane.MODULES_LINE)] if planes else None
    a, b = programs(a), programs(b)
    return a is not None and b is not None and len(a) == len(b) and all(
        x[0] == y[0] and abs(x[1] - y[1]) <= 1000 and abs(x[2] - y[2]) <= 1000
        for x, y in zip(a, b))


def read(spec, obs):
    if obs.get("trace") is None and obs.get("trace_stats") is None:
        return None
    if obs.get("trace_stats") is None and not obs.get("trace_dir"):
        dirs = glob.glob(os.path.join(tempfile.gettempdir(), "bench_trace_*"))
        if not dirs:
            return None
        newest = max(dirs, key=os.path.getmtime)
        table = xstats.load(xplane.find_xplane(newest))
        if not _same_programs(table, obs["trace"]):
            return None
        obs["trace_dir"], obs["trace_stats"] = newest, table
    table = xstats.of_run(obs)
    if table is None:
        return None
    spent, total = scoped_seconds(table, spec)
    if not spent or not total:
        return None             # a program without the scopes: no reading
    return 100.0 * spent / total
