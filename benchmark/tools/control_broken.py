"""The numbers that decide `correct` for a sparse-expert family, with the
controls that have to come out as not correct, in one process:

    python3 benchmark/tools/control_broken.py --workload axk1-chat \
        --seeds 11,12 --seconds 51

As tools/control.py (one engine, one set-up, each seed its own weights,
traffic and window at the cell's load, every finished request compared
after the engine is freed), and beside the sound reading it reads, on the
same prompts and tokens, the gaps of the token that a BROKEN forward puts
first: every matmul's operands rounded to float8 (`fp8`, the nearest
precision below bfloat16), the shared expert left out (`drop_shared`), the
gates' factor left out (`route_scale` 1). Each control has to miss one of
the cell's limits. Not part of a run.
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
    ap.add_argument("--sample", type=int, default=64)
    args = ap.parse_args(argv)
    root = manifest.ROOT
    cell = manifest.cell(root, args.workload)
    config = manifest.config(root, cell["config"])
    mix = manifest.traffic(root, cell["traffic"])
    seeds = [int(s) for s in args.seeds.split(",")]
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
            m = runner.measure(ctx, sv, args.seconds, seed)
            print(f"seed {seed}: values {json.dumps(m['values'])}",
                  flush=True)
            sv["eng"].drain(600)
            done = [r for r in m["recs"] if r.handle is not None
                    and r.handle.state.name == "FINISHED"][:args.sample]
            kept.append((seed, [r.req.prompt for r in done],
                         [list(r.handle.tokens)[:r.req.n_out]
                          for r in done]))
    finally:
        sv["eng"].shutdown(drain=False, timeout=60)
    sv.clear()
    gc.collect()
    ref = manifest.plugin("reference", config["family"])
    controls = {"sound": {}, "fp8": {"act": ref.fp8},
                "drop_shared": {"drop_shared": True},
                "route_scale_1": {"route_scale": 1.0}}
    limits = cell["correct"]["limits"]
    for seed, prompts, served in kept:
        for name, kw in controls.items():
            g = ref.served_gaps(seed, d, prompts, served,
                                weight_dtype=ctx.pcfg.param_dtype, **kw)
            row = {"seed": seed, "control": name, "requests": len(prompts),
                   "tokens": int(g.size), "served_gap_max": float(g.max()),
                   "served_gap_mean": float(g.mean())}
            row["correct"] = all(row[k] <= float(v)
                                 for k, v in limits.items())
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
