"""`tools/control_broken.py` for any sparse-expert family: the numbers that
decide `correct`, with the controls that have to come out as not correct,
in one process, the BROKEN forwards taken from the family's own reference
(`CONTROLS` in benchmark/reference/<family>.py: a name and the keyword
that breaks the reference's forward) and not from a list in this file:

    python3 benchmark/tools/control_family.py --workload mellum2-code \
        --seeds 11,12 --seconds 51 [--controls fp8,no_window] [--sample 24]

One engine, one set-up; each seed gets its own weights (swapped in while
the engine is idle), its own traffic and a window at the cell's load;
after each window the engine drains, and a seeded sample of the finished
requests (the cell's `correct.sample`, the longest among them, as a run
picks it) is compared after the engine is freed. Beside the sound reading
it reads, on the same prompts and tokens, the gaps of the token that each
control's forward puts first: `fp8` (every matmul's operands rounded to
float8, the nearest precision below bfloat16) and the family's own. Each
control has to miss one of the cell's limits. Not part of a run.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse     # noqa: E402
import gc           # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run                     # noqa: E402
from benchmark.harness import device, manifest             # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--sample", type=int, default=0,
                    help="requests compared a seed (0: the cell's own)")
    ap.add_argument("--controls", default="all",
                    help="comma-separated names, 'all' or 'none'")
    ap.add_argument("--control-seeds", type=int, default=2,
                    help="the controls run on the first so many seeds")
    args = ap.parse_args(argv)
    root = manifest.ROOT
    cell = manifest.cell(root, args.workload)
    config = manifest.config(root, cell["config"])
    mix = manifest.traffic(root, cell["traffic"])
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.sample:
        cell["correct"]["sample"] = args.sample
    dev = device.start(int(cell["chips"]))
    runner = manifest.plugin("runners", "serve")
    ctx = bench_run.Context(root, args.workload, cell, config, mix, seeds[0],
                            args.seconds, False, T_START,
                            {"allow_compile": True}, dev["kind"])
    sv = runner.setup(ctx)
    fam, d = sv["fam"], sv["d"]
    print(f"set-up took {time.time() - T_START:.1f} s", flush=True)
    kept = []
    try:
        for i, seed in enumerate(seeds):
            if i:
                sv["eng"].batcher.params = None     # one copy at a time
                gc.collect()
                sv["eng"].batcher.params = runner.make_params(
                    fam, config, ctx.pcfg, d, seed)
            ctx.seed = seed
            try:
                m = runner.measure(ctx, sv, args.seconds, seed)
            except RuntimeError as e:
                print(f"seed {seed}: window failed: {e}", flush=True)
                sv["eng"].drain(600)
                continue
            print(f"seed {seed}: values {json.dumps(m['values'])}, "
                  f"{len(m['failed'])} failed", flush=True)
            sample = runner.pick_sample(ctx, m["recs"])
            kept.append((seed, [r.req.prompt for r in sample],
                         [list(r.handle.tokens)[:r.req.n_out]
                          for r in sample]))
            sv["eng"].drain(600)
    finally:
        sv["eng"].shutdown(drain=False, timeout=60)
    sv.clear()
    gc.collect()
    ref = manifest.plugin("reference", config["family"])
    controls = {"fp8": {"act": ref.fp8}, **getattr(ref, "CONTROLS", {})}
    if args.controls == "none":
        controls = {}
    elif args.controls != "all":
        controls = {k: controls[k] for k in args.controls.split(",")}
    limits = cell["correct"]["limits"]
    for i, (seed, prompts, served) in enumerate(kept):
        todo = {"sound": {}, **(controls if i < args.control_seeds else {})}
        for name, kw in todo.items():
            t0 = time.time()
            g = ref.served_gaps(seed, d, prompts, served,
                                weight_dtype=ctx.pcfg.param_dtype, **kw)
            row = {"seed": seed, "control": name, "requests": len(prompts),
                   "tokens": int(g.size), "served_gap_max": float(g.max()),
                   "served_gap_mean": float(g.mean()),
                   "longest": max(len(p) + len(s)
                                  for p, s in zip(prompts, served)),
                   "reference_s": round(time.time() - t0, 1)}
            row["correct"] = all(row[k] <= float(v)
                                 for k, v in limits.items())
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
