"""Find the knee of an open-loop cell once: one process, one set-up, a
window at each rate (or, for a closed loop, each number of clients).

    python3 benchmark/tools/sweep.py --workload <cell> --values 2,3,4,5 \
        --seconds 25 [--seed 1] [--engine '{"kv_dtype": "int8"}']

Prints one line per value. The knee is the highest rate at which the
window's tails stay flat and the requests finished keep up with those
sent; the cell's `rate_per_s` is four fifths of it (benchmark/README.md).
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
from benchmark.harness import device, manifest            # noqa: E402


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
    try:
        for i, v in enumerate(args.values.split(",")):
            cell[key] = float(v) if key == "rate_per_s" else int(v)
            print(f"--- {key} {v}", flush=True)
            try:
                runner.measure(ctx, sv, args.seconds, args.seed + i)
            except RuntimeError as e:
                print(f"window failed: {e}")
            sv["eng"].drain(120)
            print(f"peak bytes "
                  f"{device.memory_peak_bytes(int(cell['chips']))}")
    finally:
        sv["eng"].shutdown(drain=False, timeout=60)
    return 0


if __name__ == "__main__":
    sys.exit(main())
