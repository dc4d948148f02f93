"""Run one cell on several seeds, one process per run as the driver does,
and reduce the set: per metric the median, the quartile spread, the range
of the runs without the one farthest from the median (ISSUE 42's reading)
and the driver's spread (the quartile spread of the runs without that one),
each as a share of the median. Every run's exit code and the last 30
lines it printed are kept under the output directory, whatever it did.

    python3 benchmark/tools/runset.py --workload <cell> --seeds 1,2,3,4,5,6 \
        --seconds 40 [--trace 0] [--sets 2] --out <dir under chiprun_out>

This parent never touches JAX, so each child gets the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import stats        # noqa: E402


KEPT = ("correct:", "note:", "window:", "generator lateness")


def trimmed_range(vals):
    """The driver's spread as its reasons describe it: the range of the
    runs, leaving out the one farthest from the median, over the median."""
    med = stats.median(vals)
    rest = sorted(vals, key=lambda v: abs(v - med))[:-1]
    return (max(rest) - min(rest)) / med


def driver_spread(vals):
    """The spread the driver's check holds against half the bound, as its
    refusal of PR 42 words it: the middle half (first to third quartile)
    of the runs without the one farthest from the median, over the median
    of them all."""
    med = stats.median(vals)
    rest = sorted(vals, key=lambda v: abs(v - med))[:-1]
    return stats.spread(rest) * stats.median(rest) / med


def reduce_set(lines):
    out = {}
    for name in lines[0]["metrics"]:
        vals = [ln["metrics"][name]["value"] for ln in lines
                if name in ln["metrics"]]
        many = len(vals) >= 3
        out[name] = {"median": stats.median(vals), "values": vals,
                     "spread": stats.spread(vals) if many else None,
                     "trimmed_range": trimmed_range(vals) if many else None,
                     "driver_spread": driver_spread(vals)
                     if len(vals) >= 4 else None}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out_dir = os.path.join(ROOT, "chiprun_out", args.out)
    os.makedirs(out_dir, exist_ok=True)

    def say(text):
        print(text, flush=True)
        with open(os.path.join(out_dir, "summary.txt"), "a") as f:
            f.write(text + "\n")

    ok = True
    for s in range(args.sets):
        lines = []
        for seed in args.seeds.split(","):
            t_run = time.time()
            p = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                 "--workload", args.workload, "--seed", seed, "--seconds",
                 args.seconds, "--trace", args.trace],
                cwd=ROOT, capture_output=True, text=True)
            with open(os.path.join(out_dir, f"set{s}_seed{seed}.txt"),
                      "w") as f:
                f.write(f"exit {p.returncode}\n" + "\n".join(
                    p.stdout.splitlines()[-30:]) + "\n--- stderr\n"
                    + "\n".join(p.stderr.splitlines()[-30:]) + "\n")
            tail = p.stdout.strip().splitlines()[-1:] or [""]
            if p.returncode != 0 or not tail[0].startswith("{"):
                say(f"set {s} seed {seed}: exit {p.returncode} after "
                    f"{time.time() - t_run:.0f} s\n"
                    f"{p.stdout[-1500:]}\n{p.stderr[-3000:]}")
                ok = False
                continue
            line = json.loads(tail[0])
            lines.append(line)
            checks = [ln for ln in p.stdout.splitlines()
                      if ln.startswith(KEPT)]
            say(f"set {s} seed {seed}: exit {p.returncode} after "
                f"{time.time() - t_run:.0f} s " + json.dumps(
                    {k: line[k] for k in ("correct", "attempted", "failed")})
                + " " + json.dumps({k: v["value"] for k, v in
                                    line["metrics"].items()})
                + f" peak {line['device']['memory_peak_bytes']}")
            for c in checks:
                say("    " + c)
            ok = ok and line["correct"]
        if lines:
            say(f"set {s} reduced: " + json.dumps(reduce_set(lines)))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
