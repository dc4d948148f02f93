"""A serve cell's set-up by phase, on the chip: imports and device,
weights, engine, warm-up, start and pre-roll on the wall clock (what
`setup_s` adds up), and under them the program's compile log
(`paddle_tpu/core/compile_cache.py`): one line a program with its trace,
lowering and executable seconds and whether the compile cache held it.
From the checkout in the working directory: it builds the engine as
`benchmark/run.py` does, from the same files, so in one checkout and one
cache directory its step programs are `run.py`'s and read back what a
run left (PERF.md section 6, PR 38).

Usage: python tools/setup_phases.py --workload axk1-chat --seed 1
       [--profile chiprun_out/setup/warmup.txt]   (cProfile of the warm-up)
"""
import argparse
import os
import sys
import time

T0 = time.time()
sys.path.insert(0, os.getcwd())


def print_compile_log(log, pool_bytes: int) -> None:
    """The log's step programs, one line each, and its sums."""
    steps = ["^jit_serve_"]         # the batcher's step programs, by name
    print("  program, key: trace + lowering + executable s (of it the "
          "cache's read), cache, MiB of results aliased onto donated "
          f"arguments (the pool: {pool_bytes / 2**20:.1f})")
    for r in log.records(steps):
        print(f"  {r['name']} {r['key']}: {r['trace_s']:.2f} + "
              f"{r['lower_s']:.2f} + {r['executable_s']:.2f} "
              f"({r.get('cache_read_s', 0.0):.2f}) {r['cache']} "
              f"{r.get('alias_bytes', 0) / 2**20:.1f}")
    for what, s in (("the step programs", log.summary(steps)),
                    ("every program", log.summary())):
        print(f"  {what}: {s['count']}, trace {s['trace_s']:.2f} lowering "
              f"{s['lower_s']:.2f} executable {s['executable_s']:.2f} (the "
              f"cache's read {s['cache_read_s']:.2f}) s, {s['hits']} hits, "
              f"{s['misses']} misses, {s['alias_bytes'] / 2**20:.1f} MiB "
              f"aliased")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--profile", default="",
                    help="write a cProfile of the warm-up here (slows the "
                    "Python phases by a third)")
    args = ap.parse_args(argv)

    from benchmark import run as brun
    from benchmark.harness import device, manifest
    from benchmark.runners import serve
    root = os.getcwd()
    cell = manifest.cell(root, args.workload)
    config = manifest.config(root, cell["config"])
    mix = manifest.traffic(root, cell["traffic"])
    dev = device.start(int(cell["chips"]))      # the log's listeners too
    print("device", dev, flush=True)
    from paddle_tpu.core.compile_cache import compile_log

    t = [time.time()]
    ctx = brun.Context(root, args.workload, cell, config, mix, args.seed,
                       1.0, False, T0, {}, dev["kind"])
    fam = manifest.plugin("models", config["family"])
    d = fam.dims(config)
    ctx.pcfg = pcfg = fam.program_config(config)
    import jax
    params = serve.make_params(fam, config, pcfg, d, args.seed)
    jax.block_until_ready(params)
    t.append(time.time())
    eng = serve.build_engine(config, params, pcfg, False, {})
    del params
    t.append(time.time())
    if args.profile:
        import cProfile
        import pstats
        prof = cProfile.Profile()
        prof.enable()
    warmed = eng.warmup()
    if args.profile:
        prof.disable()
        os.makedirs(os.path.dirname(args.profile) or ".", exist_ok=True)
        with open(args.profile, "w") as f:
            pstats.Stats(prof, stream=f).sort_stats(
                "cumulative").print_stats(70)
    t.append(time.time())
    try:
        eng.start()
        serve.preroll(eng, config, d["V"], args.seed)
        t.append(time.time())
    finally:
        eng.shutdown(drain=False, timeout=60)
    print(f"phases: imports and device {t[0] - T0:.2f}  weights "
          f"{t[1] - t[0]:.2f}  engine {t[2] - t[1]:.2f}  warm-up "
          f"{t[3] - t[2]:.2f} ({warmed} programs)  start and pre-roll "
          f"{t[4] - t[3]:.2f}  total {t[4] - T0:.2f} s")
    print_compile_log(compile_log, eng.batcher.kv_pool_bytes())
    return 0


if __name__ == "__main__":
    sys.exit(main())
