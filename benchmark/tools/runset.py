"""Run one cell on several seeds, one process per run as the driver does,
and reduce the set: per metric the median and the quartile spread.

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

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import stats        # noqa: E402


def reduce_set(lines):
    out = {}
    for name in lines[0]["metrics"]:
        vals = [ln["metrics"][name]["value"] for ln in lines
                if name in ln["metrics"]]
        out[name] = {"median": stats.median(vals), "values": vals,
                     "spread": stats.spread(vals) if len(vals) >= 2 else None}
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
    ok = True
    for s in range(args.sets):
        lines = []
        for seed in args.seeds.split(","):
            p = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                 "--workload", args.workload, "--seed", seed, "--seconds",
                 args.seconds, "--trace", args.trace],
                cwd=ROOT, capture_output=True, text=True)
            with open(os.path.join(out_dir, f"set{s}_seed{seed}.txt"),
                      "w") as f:
                f.write(p.stdout + "\n--- stderr\n" + p.stderr[-6000:])
            tail = p.stdout.strip().splitlines()[-1:] or [""]
            if p.returncode != 0 or not tail[0].startswith("{"):
                print(f"set {s} seed {seed}: exit {p.returncode}\n"
                      f"{p.stdout[-1500:]}\n{p.stderr[-3000:]}", flush=True)
                ok = False
                continue
            line = json.loads(tail[0])
            lines.append(line)
            checks = [ln for ln in p.stdout.splitlines()
                      if ln.startswith(("correct:", "note:", "window:"))]
            print(f"set {s} seed {seed}: " + json.dumps(
                {k: line[k] for k in ("correct", "attempted", "failed")})
                + " " + json.dumps({k: v["value"] for k, v in
                                    line["metrics"].items()})
                + f" peak {line['device']['memory_peak_bytes']}", flush=True)
            for c in checks:
                print("    " + c, flush=True)
            ok = ok and line["correct"]
        if lines:
            print(f"set {s} reduced: " + json.dumps(reduce_set(lines)),
                  flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
