"""Find the knee of an open-loop cell once: one process, one set-up, a
window at each rate (or, for a closed loop, each number of clients).

    python3 benchmark/tools/sweep.py --workload <cell> --values 2,3,4,5 \
        --seconds 25 [--seed 1] [--engine '{"kv_dtype": "int8"}']

Prints the runner's lines per value, then one table of the windows and
the knee by the one rule of benchmark/README.md (`knee`, below); the
cell's `rate_per_s` is four fifths of it.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run                   # noqa: E402
from benchmark.harness import device, manifest, stats     # noqa: E402
from benchmark.readers import flight_mean                 # noqa: E402

# the rule's two numbers (benchmark/README.md, "How the knee is read")
KEEP_UP = 0.9           # finished / sent, inside the window
TTFT_STEP = 3.0         # first-token p90 against the rate one step below
# rows decoding a tick, as the metric `decode_batch_mean` reads them
ROWS_LIVE = {"field": "active_slots", "modes": ["decode", "fused"]}


def window_row(value, m):
    """What the rule reads of one window, and what PERF.md tabulates."""
    recs, failed = m["recs"], m["failed"]
    t_end = m["obs"]["window"][1]
    # as the runner prints it: from when a request was DUE; one still
    # waiting enters with its wait so far
    ttft = [((r.t_first if r.t_first is not None else t_end) - r.due) * 1e3
            for r in recs if r not in failed]
    return {"value": value, "sent": len(recs),
            "finished": sum(r.done for r in recs), "failed": len(failed),
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p90_ms": stats.percentile(ttft, 90),
            "tpot_p90_ms": m["values"]["tpot_p90_ms"],
            "tok_s": m["values"]["serve_tok_s"],
            "rows_live": flight_mean.read(ROWS_LIVE, m["obs"])}


def knee(rows):
    """The highest swept value up to which every window kept up (at least
    KEEP_UP of its requests finished inside it), none failed or was
    refused, and the first-token p90 stayed under TTFT_STEP times the
    window one step below; None where the lowest value already fails. A
    window that gave no row (the generator ran late, a compile) fails."""
    best, below = None, None
    for row in sorted(rows, key=lambda r: r["value"]):
        if row.get("sent") is None or row["failed"] \
                or row["finished"] < KEEP_UP * row["sent"] \
                or (below is not None
                    and row["ttft_p90_ms"] >= TTFT_STEP * below):
            break
        best, below = row["value"], row["ttft_p90_ms"]
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--values", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--engine", default="{}")
    ap.add_argument("--check", type=int, default=0,
                    help="1: compare each window's outputs with the reference"
                         " (frees nothing between windows: last value only)")
    args = ap.parse_args(argv)
    root = manifest.ROOT
    cell = manifest.cell(root, args.workload)
    config = manifest.config(root, cell["config"])
    mix = manifest.traffic(root, cell["traffic"])
    dev = device.start(int(cell["chips"]))
    runner = manifest.plugin("runners", "serve")
    ctx = bench_run.Context(root, args.workload, cell, config, mix, args.seed,
                            args.seconds, False, T_START,
                            {"engine": json.loads(args.engine)}, dev["kind"])
    sv = runner.setup(ctx)
    print(f"set-up took {time.time() - T_START:.1f} s on {dev}", flush=True)
    key = "rate_per_s" if mix["kind"] == "serve_open" else "clients"
    rows = []
    try:
        for i, v in enumerate(args.values.split(",")):
            cell[key] = float(v) if key == "rate_per_s" else int(v)
            print(f"--- {key} {v}", flush=True)
            row = {"value": cell[key]}
            try:
                m = runner.measure(ctx, sv, args.seconds, args.seed + i)
                row = window_row(cell[key], m)
            except RuntimeError as e:
                print(f"window failed: {e}")
            sv["eng"].drain(120)
            row["seed"] = args.seed + i
            row["peak_bytes"] = device.memory_peak_bytes(int(cell["chips"]))
            print(f"peak bytes {row['peak_bytes']}")
            rows.append(row)
        for row in rows:
            print("sweep: " + json.dumps(row), flush=True)
        print(f"sweep: knee by the rule {knee(rows)} ({key}; kept up "
              f">= {KEEP_UP}, first-token p90 < {TTFT_STEP} x the step "
              f"below, none failed)", flush=True)
    finally:
        sv["eng"].shutdown(drain=False, timeout=60)
    return 0


if __name__ == "__main__":
    sys.exit(main())
