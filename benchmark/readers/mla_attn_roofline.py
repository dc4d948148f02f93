"""Share of its roofline that the latent (MLA) paged-attention kernel
reaches in serving, in %: the least seconds the traced ticks' absorbed
attention needs on this chip, over the device seconds of the kernel's
events (`mla_paged_attention`) in those ticks.

As `ragged_attn_roofline` (whose cut of the traced ticks this uses): each
`serve.tick` span picks its flight record, whose `decode_ctx`, `chunk` and
`prefill_spans` say what the kernel was asked for. A decode tick is `chunk`
calls a layer, the rows one token longer each time; a fused tick calls the
kernel for its decode rows and for its prefill rows, then runs the rest of
the chunk; a standalone prefill tick is one call of its rows unless it is
cold (a cold prefill attends in the expanded form through the flash
kernel). The cost of a call is the family's `latent_attention_cost`: per
key and layer one cached row read once, per visible pair the score over the
row and the value sum over its latent part."""
from typing import Any, Dict, List

from ..harness import device, manifest, xplane
from . import xstats
from .ragged_attn_roofline import traced_ticks


def _decode(fam, d, ctx: List[int]) -> Dict[str, float]:
    keys = float(sum(ctx))
    return fam.latent_attention_cost(d, keys, float(len(ctx)), keys)


def _prefill(fam, d, spans) -> Dict[str, float]:
    pairs = float(sum((e - s) * s + (e - s) * (e - s + 1) / 2.0
                      for s, e in spans))
    return fam.latent_attention_cost(
        d, pairs, float(sum(e - s for s, e in spans)),
        float(sum(e for _, e in spans)))


def tick_least_seconds(fam, d, peak, rec: Dict[str, Any]) -> float:
    """The least seconds the kernel calls of one tick need, all layers:
    each call bound by the larger of its bytes and its FLOPs."""
    mode = rec.get("mode")
    ctx = [int(c) for c in rec.get("decode_ctx") or []]
    spans = [tuple(s) for s in rec.get("prefill_spans") or []]
    calls: List[Dict[str, float]] = []
    if mode == "prefill" and not rec.get("cold"):
        calls.append(_prefill(fam, d, spans))
    elif mode in ("decode", "fused"):
        for i in range(int(rec.get("chunk") or 0)):
            if ctx:
                calls.append(_decode(fam, d, [c + i for c in ctx]))
            if i == 0 and mode == "fused" and spans:
                calls.append(_prefill(fam, d, spans))
    return d["L"] * sum(fam.roofline_seconds(c, peak)[0] for c in calls)


def read(spec, obs):
    table = xstats.of_run(obs)
    fam = manifest.plugin("models", spec["family"])
    if table is None or not hasattr(fam, "latent_attention_cost") \
            or "R" not in obs["dims"]:
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
