"""Share of its roofline that the ragged paged-attention kernel reaches
in serving, in %: the least seconds the traced ticks' attention needs on
this chip, over the device seconds of the kernel's events in those ticks.

Each traced `serve.tick` span carries the `seq` of its flight record, and
the record says what the kernel was asked for: the context length of every
live decode row at issue (`decode_ctx`), the decode steps of the tick
(`chunk`), and the `[start, end]` of every packed prefill row
(`prefill_spans`). A decode tick is `chunk` kernel calls a layer, the rows
one token longer each time; a fused tick is one mixed call (the decode rows
and the prefill rows together) and then the rest of the chunk; a standalone
prefill tick is one call of its rows, unless it is cold: a cold prefill
runs flash attention and not this kernel. A tick that did not sync (a
standalone prefill's non-final chunk) may still be running when the next
begins, so the span is cut to start after a synced tick and to end with
one, and everything between is summed on both sides.
"""
from typing import Any, Dict, List

from ..harness import device, manifest, xplane
from . import xstats


def prefill_attention_cost(d: Dict[str, Any], spans, itemsize: int = 2
                           ) -> Dict[str, float]:
    """One layer of paged attention for prefill rows that each hold the
    queries `[start, end)` of a sequence whose first `end` keys are
    cached: every live K and V element read once, the row's q read and
    its output written once; the query at position p sees p + 1 keys,
    QK^T and PV at 2 FLOPs each per key element and query head."""
    keys = float(sum(end for _, end in spans))
    queries = float(sum(end - start for start, end in spans))
    seen = float(sum((end - start) * start
                     + (end - start) * (end - start + 1) / 2.0
                     for start, end in spans))
    return {"bytes": (2 * keys * d["KV"] + 2 * queries * d["H"]) * d["hd"]
            * itemsize,
            "flops": 4.0 * seen * d["H"] * d["hd"]}


def tick_least_seconds(fam, d, peak, rec: Dict[str, Any]) -> float:
    """The least seconds the kernel calls of one tick need, all layers:
    each call is bound by the larger of its bytes and its FLOPs."""
    mode = rec.get("mode")
    ctx = [int(c) for c in rec.get("decode_ctx") or []]
    spans = [tuple(s) for s in rec.get("prefill_spans") or []]
    calls: List[Dict[str, float]] = []
    if mode == "prefill" and not rec.get("cold"):
        calls.append(prefill_attention_cost(d, spans))
    elif mode in ("decode", "fused"):
        for i in range(int(rec.get("chunk") or 0)):
            cost = fam.decode_attention_cost(d, [c + i for c in ctx])
            if i == 0 and mode == "fused":
                pre = prefill_attention_cost(d, spans)
                cost = {k: cost[k] + pre[k] for k in cost}
            calls.append(cost)
    return d["L"] * sum(fam.roofline_seconds(c, peak)[0] for c in calls)


def traced_ticks(table, flight, span: str):
    """The flight records of the traced ticks, cut so that the device
    work between the span's ends is theirs alone: (records, start_ns,
    end_ns), or None."""
    by_seq = {r["seq"]: r for r in flight or []}
    ticks = [(st["seq"], s, s + dur)
             for _, s, dur, st in xstats.host_events(table, span)
             if st.get("seq") in by_seq]
    ticks.sort()
    # begin after a tick that synced, end with one that did
    while ticks and not by_seq.get(ticks[0][0] - 1, {}).get("synced"):
        ticks.pop(0)
    while ticks and not by_seq[ticks[-1][0]].get("synced"):
        ticks.pop()
    if not ticks:
        return None
    recs = [by_seq[q] for q in range(ticks[0][0], ticks[-1][0] + 1)
            if q in by_seq]
    return recs, ticks[0][1], ticks[-1][2]


def read(spec, obs):
    table = xstats.of_run(obs)
    if table is None:
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
    fam = manifest.plugin("models", spec.get("family", "dense_decoder"))
    peak = device.peaks(obs["device_kind"])
    least = sum(tick_least_seconds(fam, obs["dims"], peak, r) for r in recs)
    if not secs or not least:
        return None
    return 100.0 * least / secs
