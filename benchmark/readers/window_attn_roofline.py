"""Share of its roofline that the ragged paged-attention kernel reaches in
serving a decoder whose layers are of two KINDS, in %: as
`ragged_attn_roofline` (the least seconds the traced ticks' attention
needs on this chip, over the device seconds of the kernel's events in
those ticks, the ticks cut and paired with their flight records in the
same way), with each call costed per layer kind by the family's
`attention_cost`: a full layer reads every key a row can see, a window
layer at most the last W of each query, and FLOPs count visible pairs
only. `ragged_attn_roofline` multiplies one cost by the number of layers
and cannot say this.

A decode tick is `chunk` kernel calls a layer, the rows one token longer
each time; a fused tick is two calls a layer (the decode rows, the prefill
rows) and then the rest of the chunk; a standalone prefill tick is one
call of its rows, unless it is cold: a cold prefill runs flash attention
and not this kernel. Each call is bound by the larger of its bytes and
its FLOPs, times the layers of its kind.
"""
from typing import Any, Dict

from ..harness import device, manifest, xplane
from . import xstats
from .ragged_attn_roofline import traced_ticks


def tick_least_seconds(fam, d: Dict[str, Any], peak,
                       rec: Dict[str, Any]) -> float:
    """The least seconds the kernel calls of one tick need, all layers."""
    mode = rec.get("mode")
    ctx = [int(c) for c in rec.get("decode_ctx") or []]
    spans = [tuple(s) for s in rec.get("prefill_spans") or []]
    calls = []                      # (decode_ctx, prefill_spans) a call
    if mode == "prefill" and not rec.get("cold"):
        calls.append(((), spans))
    elif mode in ("decode", "fused"):
        for i in range(int(rec.get("chunk") or 0)):
            calls.append(([c + i for c in ctx], ()))
        if mode == "fused":
            calls.append(((), spans))
    total = 0.0
    for kind in ("full", "window"):
        layers = d["kinds"].count(kind)
        total += layers * sum(
            fam.roofline_seconds(fam.attention_cost(d, kind, c, s), peak)[0]
            for c, s in calls if c or s)
    return total


def read(spec, obs):
    table = xstats.of_run(obs)
    fam = manifest.plugin("models", spec["family"])
    if table is None or "kinds" not in obs.get("dims", {}):
        return None
    found = traced_ticks(table, obs.get("flight"), spec["tick_span"])
    if found is None:
        return None
    recs, t0, t1 = found
    dev = xplane.device_planes(table)[0]
    secs = sum(dur for name, s, dur, _ in
               xplane.line_events(dev, xplane.OPS_LINE)
               if t0 <= s < t1 and any(p in name for p in spec["patterns"])
               ) / 1e9
    peak = device.peaks(obs["device_kind"])
    least = sum(tick_least_seconds(fam, obs["dims"], peak, r) for r in recs)
    if not secs or not least:
        return None
    return 100.0 * least / secs
