"""Many windows of one served cell on ONE engine (one process, one set-up),
each kept whole: what a seed's order does to a cell is read from these
before a set of one-process runs is paid for (`tools/runset.py`).

    python3 benchmark/tools/seedset.py --workload <cell> --out <dir> \
        --plan '[{"sweep": [2.5, 3, 4], "seeds": [1, 2]},
                 {"windows": [[null, 11], [null, 11], [0.8, 12]],
                  "mix": {"order_block": 16}, "tag": "hands"}]'

A plan is a list of stages run in order. `sweep`: a window at each rate
on each seed (seed + position, as `tools/sweep.py`), the knee by the one
rule of benchmark/README.md, the LOWER of the seeds' knees kept. `windows`:
[rate, seed] pairs; a null rate is four fifths of the knee kept so far
(the cell's own `knee_per_s` until a sweep has run). `mix` lays keys over
the cell's traffic mix for that stage, on top of the cell's own `mix` keys
(`{"order_block": 0}` is the plain order again). Every window writes one
JSON file under chiprun_out/<out>/: the row `tools/sweep.py` prints, every
block's time per token, every request's sizes and stamps. No window is
compared with the reference (`tools/runset.py` does that).
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
from benchmark.tools import sweep                        # noqa: E402


def keep(path, row, m, blk):
    """One window, whole: the row, the blocks, the requests, the ticks."""
    recs, failed = m["recs"], m["failed"]
    t0 = m["obs"]["window"][0]
    row = dict(row)
    row["blocks_ms"] = [round(1e3 * t, 4) for r in recs if r not in failed
                        for t in stats.block_times(r.stamps, blk)]
    row["requests"] = [
        [len(r.req.prompt), r.req.n_out, round(r.due - t0, 4),
         None if r.t_first is None else round(r.t_first - t0, 4),
         None if r.t_last is None else round(r.t_last - t0, 4), r.n_tok]
        for r in recs]
    row["ticks"] = [[round(f["t"] - t0, 4), f["mode"],
                     f.get("active_slots"),
                     round(f.get("dispatch_s", 0) + f.get("wait_s", 0), 5)]
                    for f in m["obs"]["flight"]]
    with open(path, "w") as f:
        json.dump(row, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plan", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--sweep-seconds", type=float, default=40.0)
    ap.add_argument("--engine", default='{"flight_recorder_cap": 65536}')
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    root = manifest.ROOT
    out_dir = os.path.join(ROOT, "chiprun_out", args.out)
    os.makedirs(out_dir, exist_ok=True)
    cell = manifest.cell(root, args.workload)
    config = manifest.config(root, cell["config"])
    mix = manifest.traffic(root, cell["traffic"])
    cell_mix = cell.get("mix", {})
    dev = device.start(int(cell["chips"]))
    runner = manifest.plugin("runners", "serve")
    ctx = bench_run.Context(root, args.workload, cell, config, mix, 1,
                            args.seconds, False, T_START,
                            {"engine": json.loads(args.engine)}, dev["kind"])
    sv = runner.setup(ctx)
    print(f"set-up took {time.time() - T_START:.1f} s on {dev}", flush=True)
    blk = int(config["engine"].get("chunk", 8))
    knee = cell.get("knee_per_s")

    def window(rate, seed, seconds, mix, name):
        cell["rate_per_s"] = float(rate)
        cell["mix"] = {**cell_mix, **mix}
        print(f"--- {name}: rate {rate} seed {seed} mix {mix}", flush=True)
        row = {"value": float(rate)}
        try:
            m = runner.measure(ctx, sv, seconds, seed)
            row = sweep.window_row(float(rate), m)
            row.update(seed=seed, mix=mix, seconds=seconds)
            keep(os.path.join(out_dir, name + ".json"), row, m, blk)
        except RuntimeError as e:
            print(f"window failed: {e}")
            row["seed"] = seed
        sv["eng"].drain(120)
        row["peak_bytes"] = device.memory_peak_bytes(int(cell["chips"]))
        print("seedset: " + json.dumps(row), flush=True)
        return row

    try:
        for si, stage in enumerate(json.loads(args.plan)):
            mix = stage.get("mix", {})
            tag = stage.get("tag", f"stage{si}")
            if "sweep" in stage:
                knees = []
                for seed in stage["seeds"]:
                    rows = [window(v, seed + i, args.sweep_seconds, mix,
                                   f"{tag}_sweep_s{seed}_r{v}")
                            for i, v in enumerate(stage["sweep"])]
                    knees.append(sweep.knee(rows))
                    print(f"seedset: knee by the rule {knees[-1]} on seed "
                          f"{seed}", flush=True)
                if None in knees:
                    print("seedset: no knee: the lowest rate already fails")
                    return 1
                knee = min(knees)
                print(f"seedset: knee kept {knee}, rate {0.8 * knee:g}",
                      flush=True)
            vals = []
            for wi, (rate, seed) in enumerate(stage.get("windows", [])):
                rate = round(0.8 * knee, 4) if rate is None else rate
                row = window(rate, seed, args.seconds, mix,
                             f"{tag}_w{wi}_s{seed}")
                if row.get("tpot_p90_ms"):
                    vals.append(row["tpot_p90_ms"])
            if len(vals) >= 3:
                print(f"seedset: {tag}: tpot_p90_ms {vals} median "
                      f"{stats.median(vals):.4f} quartile spread "
                      f"{stats.spread(vals):.4f}", flush=True)
    finally:
        sv["eng"].shutdown(drain=False, timeout=60)
    return 0


if __name__ == "__main__":
    sys.exit(main())
