"""Share of their roofline that the routed experts' grouped GEMMs reach in
serving, in %: the least seconds the traced ticks' expert work needs on
this chip, over the exclusive device seconds of the operations under the
scope `moe_experts`, or named `ragged-dot` (the metric file's `patterns`),
in those ticks' step programs.

The least comes from the program's own counters on each tick's flight
record, read back with the tick's tokens: `moe_experts_hit` (expert-layers
that got a token, summed over layers and steps: each reads its three
matrices once) and `moe_pairs` (token-expert pairs computed here). A tick
is bound by the larger of its bytes over the chip's bandwidth and its
FLOPs over its peak (the family's `expert_ffn_cost`). Only ticks that
carry the counters count, on both sides: the device time is that of the
`programs` named in the metric's file (the decode and fused steps; a
standalone prefill reads nothing back and has no counters)."""
import bisect

from ..harness import device, manifest, xplane
from . import xstats
from .ragged_attn_roofline import traced_ticks


def scoped_seconds(table, spec, t0=None, t1=None):
    """(exclusive device seconds of the operations under one of
    `spec["scopes"]`, or named by one of `spec.get("patterns")`, inside the
    `spec["programs"]` that start in [t0, t1); those programs' device
    seconds). The chip's grouped GEMM is a custom call whose event carries
    no scope path (`tf_op` reads `ragged-dot-none:`), so it is found by its
    name."""
    dev = xplane.device_planes(table)[0]
    inside = (lambda s: True) if t0 is None else (lambda s: t0 <= s < t1)
    progs = sorted((s, s + d) for n, s, d, _ in
                   xplane.line_events(dev, xplane.MODULES_LINE)
                   if inside(s) and any(p in n for p in spec["programs"]))
    ops = sorted(xplane.line_events(dev, xplane.OPS_LINE),
                 key=lambda e: (e[1], -e[2]))
    if not progs or not ops:
        return 0.0, 0.0
    exclusive = xplane.leaf_exclusive([e[:3] for e in ops])
    starts = [p[0] for p in progs]
    spent = 0
    for (name, s, _, stats), (_, _, own) in zip(ops, exclusive):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < progs[i][1] and (
                xstats.scope_of(stats, spec["scopes"])
                or any(p in name for p in spec.get("patterns", ()))):
            spent += own
    return spent / 1e9, sum(e - s for s, e in progs) / 1e9


def read(spec, obs):
    table = xstats.of_run(obs)
    fam = manifest.plugin("models", spec["family"])
    if table is None or not hasattr(fam, "expert_ffn_cost"):
        return None
    found = traced_ticks(table, obs.get("flight"), spec["tick_span"])
    if found is None:
        return None
    recs, t0, t1 = found
    recs = [r for r in recs if r.get("moe_pairs") is not None]
    if not recs:
        return None
    spent, _ = scoped_seconds(table, spec, t0, t1)
    peak = device.peaks(obs["device_kind"])
    least = sum(fam.roofline_seconds(
        fam.expert_ffn_cost(obs["dims"], r["moe_pairs"],
                            r["moe_experts_hit"]), peak)[0] for r in recs)
    if not spent or not least:
        return None
    return 100.0 * least / spent
