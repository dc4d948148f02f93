#!/usr/bin/env python
"""Summarize a serving trace artifact (a dumped `to_chrome_trace()`).

Reads the Chrome-trace JSON exported by `serving.trace.TraceSink.
to_chrome_trace()` and answers, per request and in aggregate, the
questions the raw timeline is too granular for:

  * per-phase time breakdown — queue wait (enqueued→admitted), prefill
    (sum of prefill_chunk spans), decode (first_token→terminal), total;
  * pad waste — bucket-padding tokens vs real suffix tokens across
    every prefill chunk (the overhead the bucket ladder trades for
    zero recompiles);
  * cache-hit attribution — prompt tokens the prefix cache skipped,
    per request and total, next to the tokens actually prefilled;
  * scheduling mix — fused vs standalone prefill chunks, engine step
    span count/total;
  * quantization — the resolved weight/KV dtype config each request
    was prepared under, and the KV bytes its block footprint pins
    (per-block bytes off the prepared event, int8 scale overhead
    included);
  * recovery churn — the "requeued" phase: how often each request went
    back to the queue front (quarantine victims, rolled-back pending
    siblings) and how many backoff retries it consumed, so a
    fault-tolerance event cascade is visible instead of reading as
    unexplained repeat prefills;
  * replica attribution — which replica served each request (the
    `replica_id` the batcher stamps on `prepared` events, or the
    Router's `routed`/`failover` events in a merged multi-replica
    artifact), a per-replica request breakdown in the totals, and a
    `failovers` churn column so the cross-replica recovery path reads
    like the in-replica requeue one;
  * KV migration — disaggregated prefill→decode handoffs (`migrated`
    events: a per-request migrations count and handoff latency column,
    plus aggregate count/bytes and warm-vs-reprefill split), and
    slot-in-place quarantine restores (`restored` events) counted into
    the recovery totals next to requeues;
  * self-healing churn — supervisor `restarting`/`restarted` events
    (replica-scoped spans, no trace_id) counted into the recovery
    totals next to failovers, so a replica that died and was respawned
    is visible in the same summary as the requests it stranded;
  * device-time attribution — when a profiler capture window ran
    (`ServingEngine.capture_profile` / `POST /debug/profile`), the
    fenced `device.*` spans and per-chunk ``device_dur`` annotations
    land device-wall columns next to the host-wall ones
    (``device_ms`` per request, device step totals), so a TTFT
    regression is attributable to the kernel vs host scheduling;
    artifacts that predate the capture fields render "-" instead of
    crashing;
  * SLO breach windows (``--slo``) — `slo_breach` / `slo_recovered`
    spans from the engine's SLO tracker become per-objective breach
    windows, each listing the requests whose timelines rode it — the
    request-correlated view of "which users felt the burn".

Standard library only (no jax import): runs anywhere the JSON landed.
`--json` prints the summary as one JSON object instead of
the text table.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import Counter, defaultdict

TERMINAL = {"finished", "cancelled", "failed", "timed_out"}


def load_events(path: str):
    """The artifact's non-metadata trace events, sorted by timestamp."""
    with open(path) as f:
        data = json.load(f)
    evs = [e for e in data.get("traceEvents", []) if e.get("ph") != "M"]
    evs.sort(key=lambda e: e.get("ts", 0.0))
    return evs


def summarize(events) -> dict:
    """Aggregate the per-request phase/pad/cache numbers (all times in
    milliseconds; `ts`/`dur` in the artifact are microseconds)."""
    per_req = defaultdict(lambda: {
        "enqueued_ts": None, "admitted_ts": None, "first_token_ts": None,
        "terminal_ts": None, "terminal": None, "prompt_len": None,
        "slot": None, "prefill_ms": 0.0, "chunks": 0, "fused_chunks": 0,
        "pad_tokens": 0, "real_tokens": 0, "cached_tokens": 0,
        "generated": 0, "requeues": 0, "retries": 0, "kv_bytes": 0,
        "replica": None, "failovers": 0, "device_ms": None,
        "migrations": 0, "handoff_ms": None, "restored": 0,
        "spec_steps": 0, "spec_accepted": 0, "spec_emitted": 0,
        "first_ts": None, "last_ts": None,
    })
    steps = {"count": 0, "total_ms": 0.0}
    # device-wall spans from a profiler capture window (device.decode /
    # device.fused / device.prefill on the device lane)
    dev_steps = {"count": 0, "total_ms": 0.0}
    quant = {"weight_dtype": None, "kv_dtype": None}
    # fast-path attribution stamped on every prepared event: the
    # resolved attention backend, spec score path and TP mesh degree —
    # so a mixed fleet's artifacts say which replicas ran the kernel
    fastpath = {"attention_impl": None, "spec_backend": None,
                "mesh_tp": None}
    # replica-scoped (not request-scoped) churn: supervisor restart
    # events ride the engine sinks' span lane with no trace_id
    restarts = {"restarting": 0, "restarted": 0}
    # KV migration spans (router handoffs, destination sink, no
    # trace_id — the per-request twin is counted into the rows below):
    # count + payload bytes + the warm/re-prefill split
    migration = {"count": 0, "bytes": 0, "kv_import": 0, "reprefill": 0}
    # speculative decoding: spec_draft spans are engine-scoped (one
    # per tick), spec_verify events are per-request with accepted
    # counts — the accepted-per-step column comes from the latter
    spec_draft_spans = 0
    spec_depth_hist: Counter = Counter()
    # SLO verdict transitions (engine-scoped spans, no trace_id):
    # paired breach→recovered edges become breach windows below
    slo_edges = []
    for e in events:
        name, args = e.get("name"), e.get("args", {})
        if name == "engine.step":
            steps["count"] += 1
            steps["total_ms"] += e.get("dur", 0.0) / 1e3
            continue
        if isinstance(name, str) and name.startswith("device."):
            dev_steps["count"] += 1
            dev_steps["total_ms"] += e.get("dur", 0.0) / 1e3
            continue
        if name in ("slo_breach", "slo_recovered"):
            slo_edges.append({
                "edge": name, "ts": e.get("ts", 0.0),
                "objective": args.get("objective"),
                "replica": args.get("replica_id"),
                "burn_rate_fast": args.get("burn_rate_fast"),
                "window_s": args.get("window_s"),
                "target": args.get("target")})
            continue
        if name in ("restarting", "restarted"):
            restarts[name] += 1
            continue
        if name == "migrated" and args.get("trace_id") is None:
            # the router's destination-sink span (the per-request
            # "migrated" event carries a trace_id and lands in the
            # rows; this aggregate-only twin must not double-count it)
            migration["count"] += 1
            migration["bytes"] += args.get("bytes", 0)
            via = args.get("via")
            if via in migration:
                migration[via] += 1
            continue
        if name == "spec_draft":
            spec_draft_spans += 1
            continue
        tid = args.get("trace_id")
        if tid is None:
            continue
        r = per_req[tid]
        ts = e.get("ts", 0.0)
        if r["first_ts"] is None or ts < r["first_ts"]:
            r["first_ts"] = ts
        if r["last_ts"] is None or ts > r["last_ts"]:
            r["last_ts"] = ts
        if name == "enqueued":
            r["enqueued_ts"] = ts
            r["prompt_len"] = args.get("prompt_len")
        elif name == "admitted":
            r["admitted_ts"] = ts
        elif name == "routed":
            # the Router's placement decision (replica + policy score)
            r["replica"] = args.get("replica", r["replica"])
        elif name == "failover":
            # cross-replica recovery: the request resumed elsewhere
            r["failovers"] += 1
            r["replica"] = args.get("to_replica", r["replica"])
        elif name == "migrated":
            # disaggregated handoff: prefill KV imported (or warm
            # re-prefilled) at the decode replica this event rode
            r["migrations"] += 1
            r["replica"] = args.get("to_replica", r["replica"])
            if args.get("handoff_s") is not None:
                r["handoff_ms"] = (r["handoff_ms"] or 0.0) \
                    + args["handoff_s"] * 1e3
        elif name == "restored":
            r["restored"] += 1
        elif name == "prepared":
            r["slot"] = args.get("slot")
            r["replica"] = args.get("replica_id", r["replica"])
            # quantized-serving bytes: the batcher stamps its resolved
            # dtype config + per-block bytes (scale overhead included)
            # on every prepared event, so the report can price each
            # request's KV residency without re-deriving model geometry
            r["kv_bytes"] = (args.get("blocks", 0)
                             * args.get("kv_block_bytes", 0))
            quant["weight_dtype"] = args.get("weight_dtype",
                                             quant["weight_dtype"])
            quant["kv_dtype"] = args.get("kv_dtype", quant["kv_dtype"])
            for fk in fastpath:
                fastpath[fk] = args.get(fk, fastpath[fk])
        elif name == "prefill_chunk":
            r["chunks"] += 1
            r["prefill_ms"] += e.get("dur", 0.0) / 1e3
            r["pad_tokens"] += args.get("pad", 0)
            r["real_tokens"] += args.get("end", 0) - args.get("start", 0)
            r["cached_tokens"] += args.get("cached_tokens", 0)
            if args.get("fused"):
                r["fused_chunks"] += 1
            # device wall rides only on chunks a capture window fenced
            # (device_dur is seconds; absent on older artifacts)
            if args.get("device_dur") is not None:
                r["device_ms"] = (r["device_ms"] or 0.0) \
                    + args["device_dur"] * 1e3
        elif name == "first_token":
            r["first_token_ts"] = ts
        elif name == "spec_verify":
            r["spec_steps"] += 1
            r["spec_accepted"] += args.get("accepted", 0)
            r["spec_emitted"] += args.get("emitted", 0)
            # per-(sweep, request) accepted-path-length distribution —
            # the tree-shape tuning signal (mirrors the engine's
            # spec_accept_depth Prometheus histogram)
            if args.get("accepted") is not None:
                spec_depth_hist[int(args["accepted"])] += 1
            # a capture window's fenced spec ticks carry device wall
            # exactly like fenced prefill chunks do
            if args.get("device_dur") is not None:
                r["device_ms"] = (r["device_ms"] or 0.0) \
                    + args["device_dur"] * 1e3
        elif name == "retired":
            r["generated"] = args.get("generated", 0)
        elif name == "requeued":
            r["requeues"] += 1
        elif name == "retried":
            r["retries"] += 1
        elif name in TERMINAL:
            r["terminal_ts"] = ts
            r["terminal"] = name

    rows = []
    for tid, r in per_req.items():
        def delta(a, b):
            return None if r[a] is None or r[b] is None \
                else (r[b] - r[a]) / 1e3
        rows.append({
            # an artifact exported mid-run carries requests with no
            # terminal event yet — report them as "live", don't crash
            "trace_id": tid, "terminal": r["terminal"] or "live",
            "replica": r["replica"], "failovers": r["failovers"],
            "slot": r["slot"], "prompt_len": r["prompt_len"],
            "generated": r["generated"],
            "queue_wait_ms": delta("enqueued_ts", "admitted_ts"),
            "ttft_ms": delta("enqueued_ts", "first_token_ts"),
            "decode_ms": delta("first_token_ts", "terminal_ts"),
            "total_ms": delta("enqueued_ts", "terminal_ts"),
            "prefill_ms": round(r["prefill_ms"], 3),
            "device_ms": (None if r["device_ms"] is None
                          else round(r["device_ms"], 3)),
            "first_ts": r["first_ts"], "last_ts": r["last_ts"],
            "chunks": r["chunks"], "fused_chunks": r["fused_chunks"],
            "cached_tokens": r["cached_tokens"],
            "prefilled_tokens": r["real_tokens"],
            "pad_tokens": r["pad_tokens"],
            "requeues": r["requeues"], "retries": r["retries"],
            "restored": r["restored"],
            "migrations": r["migrations"],
            "handoff_ms": (None if r["handoff_ms"] is None
                           else round(r["handoff_ms"], 3)),
            "kv_bytes": r["kv_bytes"],
            "spec_steps": r["spec_steps"],
            "spec_accepted": r["spec_accepted"],
            # accepted DRAFT tokens per verify sweep (the emitted
            # count adds the corrected token on top — the engine's
            # tokens_per_step); None when it never rode a spec tick
            "acc_per_step": (round(r["spec_accepted"] / r["spec_steps"],
                                   2) if r["spec_steps"] else None),
        })
    # (len, str) sorts t2 before t10 — ids are a prefix plus a
    # monotonic sequence number, so length order IS numeric order
    rows.sort(key=lambda x: (len(x["trace_id"]), x["trace_id"]))
    pad = sum(x["pad_tokens"] for x in rows)
    real = sum(x["prefilled_tokens"] for x in rows)
    cached = sum(x["cached_tokens"] for x in rows)
    total = {
        "requests": len(rows),
        "terminals": dict(sorted(
            Counter(x["terminal"] for x in rows).items())),
        "prefill_chunks": sum(x["chunks"] for x in rows),
        "fused_chunks": sum(x["fused_chunks"] for x in rows),
        "prefilled_tokens": real,
        "pad_tokens": pad,
        "pad_waste": round(pad / (pad + real), 4) if pad + real else 0.0,
        "cached_tokens": cached,
        "cache_hit_rate": round(cached / (cached + real), 4)
        if cached + real else 0.0,
        "engine_steps": steps["count"],
        "engine_step_ms_total": round(steps["total_ms"], 3),
        "device_steps": dev_steps["count"],
        "device_step_ms_total": round(dev_steps["total_ms"], 3),
        "device_ms_total": round(sum(x["device_ms"] or 0.0
                                     for x in rows), 3),
        "requeued_events": sum(x["requeues"] for x in rows),
        "retried_events": sum(x["retries"] for x in rows),
        "restored_events": sum(x["restored"] for x in rows),
        "failover_events": sum(x["failovers"] for x in rows),
        "restart_events": restarts["restarted"],
        "restarting_events": restarts["restarting"],
        "migration_events": migration["count"],
        "migration_bytes": migration["bytes"],
        "migrations_kv_import": migration["kv_import"],
        "migrations_reprefill": migration["reprefill"],
        "spec_draft_spans": spec_draft_spans,
        "spec_verify_steps": sum(x["spec_steps"] for x in rows),
        "spec_accepted_tokens": sum(x["spec_accepted"] for x in rows),
        # per (sweep, request): accepted DRAFT tokens, and total
        # tokens landed (accepted + the corrected one — comparable to
        # plain decode's 1.0); both 0.0 for a plain-decode artifact
        "accepted_per_step": round(
            sum(x["spec_accepted"] for x in rows)
            / max(1, sum(x["spec_steps"] for x in rows)), 4),
        "spec_tokens_per_step": round(
            sum(r["spec_emitted"] for r in per_req.values())
            / max(1, sum(x["spec_steps"] for x in rows)), 4),
        "spec_accept_depth_hist": {str(k): v for k, v in
                                   sorted(spec_depth_hist.items())},
        "replicas": dict(sorted(Counter(
            x["replica"] for x in rows
            if x["replica"] is not None).items())),
        "weight_dtype": quant["weight_dtype"],
        "kv_dtype": quant["kv_dtype"],
        "kv_bytes_total": sum(x["kv_bytes"] for x in rows),
        "attention_impl": fastpath["attention_impl"],
        "spec_backend": fastpath["spec_backend"],
        "mesh_tp": fastpath["mesh_tp"],
    }
    return {"total": total, "requests": rows,
            "slo": _breach_windows(slo_edges, rows)}


def _breach_windows(slo_edges, rows) -> dict:
    """Pair slo_breach → slo_recovered edges per (objective, replica)
    into breach windows, each listing the trace ids whose timelines
    overlap it (the requests that rode the breach). An edge set from
    an artifact that predates SLO tracking is simply empty."""
    edges = sorted(slo_edges, key=lambda e: e.get("ts", 0.0))
    open_w, windows = {}, []
    for e in edges:
        key = (e.get("objective"), e.get("replica"))
        if e["edge"] == "slo_breach":
            if key not in open_w:
                w = {"objective": e.get("objective"),
                     "replica": e.get("replica"),
                     "start_ms": round(e.get("ts", 0.0) / 1e3, 3),
                     "end_ms": None,        # None = still open at export
                     "burn_rate_fast": e.get("burn_rate_fast"),
                     # the verdict was computed over this trailing
                     # window — request attribution reaches back by it
                     "window_s": e.get("window_s"),
                     "target": e.get("target"), "requests": []}
                open_w[key] = w
                windows.append(w)
        else:
            w = open_w.pop(key, None)
            if w is not None:
                w["end_ms"] = round(e.get("ts", 0.0) / 1e3, 3)
    for w in windows:
        # reach back over the fast window that triggered the verdict:
        # the offending samples predate the breach event by up to it
        s_us = w["start_ms"] * 1e3 - (w.get("window_s") or 0.0) * 1e6
        e_us = None if w["end_ms"] is None else w["end_ms"] * 1e3
        for r in rows:
            a, b = r.get("first_ts"), r.get("last_ts")
            if a is None or b is None:
                continue
            if (e_us is None or a <= e_us) and b >= s_us:
                w["requests"].append(r["trace_id"])
    return {"breach_events": sum(1 for e in edges
                                 if e["edge"] == "slo_breach"),
            "recovered_events": sum(1 for e in edges
                                    if e["edge"] == "slo_recovered"),
            "breach_windows": windows}


def _fmt(v):
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.2f}"
    return str(v)


def render(summary: dict, show_slo: bool = False) -> str:
    """The human view: one aggregate block + one row per request
    (plus, with `show_slo`, the breach-window section)."""
    t = summary["total"]
    lines = [
        "== serving trace summary ==",
        f"requests: {t['requests']}  terminals: {t['terminals']}",
        f"prefill chunks: {t['prefill_chunks']} "
        f"({t['fused_chunks']} fused)  prefilled tokens: "
        f"{t['prefilled_tokens']}  pad: {t['pad_tokens']} "
        f"(waste {t['pad_waste']:.1%})",
        f"cache-hit tokens: {t['cached_tokens']} "
        f"(hit rate {t['cache_hit_rate']:.1%})",
        f"engine steps: {t['engine_steps']} "
        f"({t['engine_step_ms_total']:.1f} ms total)  device steps: "
        f"{t.get('device_steps', 0)} "
        f"({t.get('device_step_ms_total', 0.0):.1f} ms device wall)",
        f"recovery: {t['requeued_events']} requeues, "
        f"{t['retried_events']} retries, "
        f"{t.get('restored_events', 0)} restored, "
        f"{t['failover_events']} failovers, "
        f"{t['restart_events']} restarts",
        f"migrations: {t.get('migration_events', 0)} "
        f"({t.get('migrations_kv_import', 0)} kv_import, "
        f"{t.get('migrations_reprefill', 0)} reprefill)  "
        f"bytes moved: {t.get('migration_bytes', 0)}",
        f"speculative: {t.get('spec_verify_steps', 0)} verify steps, "
        f"{t.get('spec_accepted_tokens', 0)} accepted "
        f"({t.get('accepted_per_step', 0.0)} accepted/step, "
        f"{t.get('spec_tokens_per_step', 0.0)} tokens/step)  "
        f"accept-depth hist: "
        + (" ".join(f"{k}:{v}" for k, v in sorted(
            t.get("spec_accept_depth_hist", {}).items(),
            key=lambda kv: int(kv[0]))) or "-"),
        f"replicas: {t['replicas'] or '-'}",
        f"quantization: weights {t['weight_dtype'] or '-'}, "
        f"kv {t['kv_dtype'] or '-'}  kv bytes admitted: "
        f"{t['kv_bytes_total']}",
        f"fast path: attention {t.get('attention_impl') or '-'}, "
        f"spec backend {t.get('spec_backend') or '-'}, "
        f"mesh tp {t.get('mesh_tp') or '-'}",
        "",
    ]
    cols = ["trace_id", "terminal", "replica", "slot", "prompt_len",
            "generated", "queue_wait_ms", "ttft_ms", "decode_ms",
            "prefill_ms", "device_ms", "chunks", "fused_chunks",
            "cached_tokens", "pad_tokens", "requeues", "retries",
            "failovers", "migrations", "handoff_ms",
            "acc_per_step", "kv_bytes"]
    # old artifacts may predate a column: .get keeps the report
    # rendering instead of KeyError-crashing on missing fields
    rows = [[_fmt(r.get(c)) for c in cols] for r in summary["requests"]]
    widths = [max(len(c), *(len(r[i]) for r in rows)) if rows else len(c)
              for i, c in enumerate(cols)]
    lines.append("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
    for r in rows:
        lines.append("  ".join(v.ljust(w) for v, w in zip(r, widths)))
    if show_slo:
        slo = summary.get("slo") or {}
        wins = slo.get("breach_windows", [])
        lines += ["", "== SLO breach windows ==",
                  f"breaches: {slo.get('breach_events', 0)}  "
                  f"recoveries: {slo.get('recovered_events', 0)}"]
        if not wins:
            lines.append("no breach windows in this artifact")
        for w in wins:
            end = "open" if w["end_ms"] is None else f"{w['end_ms']:.1f}"
            lines.append(
                f"[{w['start_ms']:.1f} ms → {end}] "
                f"{w['objective']} on {w['replica'] or '-'} "
                f"(burn {w['burn_rate_fast']}, target {w['target']}) — "
                f"{len(w['requests'])} requests rode it: "
                f"{', '.join(w['requests']) or '-'}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="Chrome-trace JSON dumped from "
                                  "TraceSink.to_chrome_trace()")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as one JSON object")
    ap.add_argument("--slo", action="store_true",
                    help="append the SLO section: breach windows "
                         "(slo_breach → slo_recovered spans) and the "
                         "requests whose timelines rode each one")
    a = ap.parse_args(argv)
    summary = summarize(load_events(a.trace))
    try:
        print(json.dumps(summary) if a.json
              else render(summary, show_slo=a.slo))
    except BrokenPipeError:
        pass                 # downstream (e.g. `| head`) closed early
    return 0


if __name__ == "__main__":
    sys.exit(main())
