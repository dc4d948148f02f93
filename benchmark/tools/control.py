"""Read the numbers that decide `correct` over many seeds in one process:
for the program as configured (`--engine '{}'`: the sound readings), or with
a lower-precision path of the program switched on (`--engine
'{"kv_dtype": "int8"}'`: the control, which has to come out as not correct).

    python3 benchmark/tools/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 51 --engine '{"kv_dtype": "int8"}' --out explore/int8.json

One engine, one set-up; each seed gets its own weights (swapped in while the
engine is idle), its own traffic and a window at the cell's own load. This
tool reads tokens, not times: after each window the engine drains, and every
request of the window is compared. The reference runs after the engine is
freed. `--lower int8_blocks` or `fp8` also reads, on the same prompts and
tokens, the gap of the token that the reference computed with that rounding
puts first. `--out` keeps every request's times, every token's stamp and
every gap (how the metrics' spread over seeds was read). Not part of a run.
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

import numpy as np                                         # noqa: E402

from benchmark import run as bench_run                     # noqa: E402
from benchmark.harness import device, manifest             # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--engine", default="{}")
    ap.add_argument("--lower", default="")
    ap.add_argument("--out", default="",
                    help="file under chiprun_out/ for every request's times")
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
                            {"engine": json.loads(args.engine),
                             "allow_compile": True}, dev["kind"])
    sv = runner.setup(ctx)
    fam, d = sv["fam"], sv["d"]
    print(f"set-up took {time.time() - T_START:.1f} s", flush=True)
    kept, dumped = [], {}
    try:
        if json.loads(args.engine):
            # a path that is not the cell's own builds host-side programs
            # on first use inside the window: this tool reads tokens, not
            # times, so no lateness check
            cell["max_late_share"] = 1e9
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
                sv["eng"].drain(300)
                continue
            t0 = m["obs"]["window"][0]
            dumped[str(seed)] = {
                "values": m["values"],
                "requests": [[r.req.idx, len(r.req.prompt), r.req.n_out,
                              r.due - t0, r.n_tok,
                              None if r.t_first is None else r.t_first - t0,
                              None if r.t_last is None else r.t_last - t0]
                             for r in m["recs"]],
                "stamps": [[round(t - t0, 4) for t in r.stamps]
                           for r in m["recs"]]}
            # tokens, not times: let every request of the window finish
            sv["eng"].drain(600)
            sample = [r for r in m["recs"] if r.handle is not None
                      and r.handle.state.name == "FINISHED"]
            print(f"seed {seed}: {len(sample)} of {len(m['recs'])} requests "
                  f"finished after the drain", flush=True)
            kept.append((seed, [r.req.prompt for r in sample],
                         [list(r.handle.tokens)[:r.req.n_out]
                          for r in sample]))
    finally:
        sv["eng"].shutdown(drain=False, timeout=60)
    sv.clear()
    gc.collect()
    ref = manifest.plugin("reference", config["family"])
    # int8_blocks rounds what a cache holds; fp8 the operands of every matmul
    hooks = {"": {}, "int8_blocks": {"lower": ref.int8_blocks},
             "fp8": {"act": ref.fp8}}
    lowers = [x for x in args.lower.split(",") if x]
    rows = []
    for seed, prompts, served in kept:
        g = ref.served_gaps(seed, d, prompts, served,
                            weight_dtype=ctx.pcfg.param_dtype)
        row = {"seed": seed, "requests": len(prompts), "tokens": int(g.size),
               "served_gap_max": float(g.max()),
               "served_gap_mean": float(g.mean())}
        for name in lowers:
            lo = ref.served_gaps(seed, d, prompts, served,
                                 weight_dtype=ctx.pcfg.param_dtype,
                                 **hooks[name])
            row[f"{name}_gap_max"] = float(lo.max())
            row[f"{name}_gap_mean"] = float(lo.mean())
        rows.append(row)
        dumped[str(seed)]["gaps"] = [round(float(x), 5) for x in g]
        print(json.dumps(row), flush=True)
    if args.out:
        path = os.path.join(root, "chiprun_out", args.out)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(dumped, f)
    for key in rows[0]:
        if key.endswith(("max", "mean")):
            vals = [r[key] for r in rows]
            print(f"{key}: smallest {min(vals):.6g}, largest {max(vals):.6g}"
                  f", median {float(np.median(vals)):.6g} over {len(vals)} "
                  f"seeds (engine {args.engine})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
