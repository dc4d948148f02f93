"""Share of the decoding step programs' device time that goes to moving
the KV pool, in %: the exclusive device time of the operations whose scope
path (`tf_op` of the event's metadata) lies under one of the metric's
`scopes`, the per-layer slice out of the stacked pool and the write back
into it, over the device time of every program that matches `programs`
in the traced span. Plain and fused programs alike, so any traced span
reads a number. Copies the compiler adds on its own carry no scope and are
not counted."""
import bisect

from ..harness import xplane
from . import xstats


def read(spec, obs):
    table = xstats.of_run(obs)
    if table is None:
        return None
    dev = xplane.device_planes(table)[0]
    progs = sorted((s, s + d) for n, s, d, _ in
                   xplane.line_events(dev, xplane.MODULES_LINE)
                   if any(p in n for p in spec["programs"]))
    ops = sorted(xplane.line_events(dev, xplane.OPS_LINE),
                 key=lambda e: (e[1], -e[2]))
    if not progs or not ops:
        return None
    # same order in, same order out: leaf_exclusive sorts by this key
    exclusive = xplane.leaf_exclusive([e[:3] for e in ops])
    starts = [p[0] for p in progs]
    moved = 0
    for (_, s, _, stats), (_, _, own) in zip(ops, exclusive):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < progs[i][1] \
                and xstats.scope_of(stats, spec["scopes"]):
            moved += own
    if not moved:
        return None             # a program without the scopes: no reading
    return 100.0 * moved / sum(e - s for s, e in progs)
