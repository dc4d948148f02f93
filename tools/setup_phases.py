"""A serve cell's set-up by phase, on the chip: imports and device,
weights, engine, warm-up (lowering, the compile or the compile cache's
read, Pallas's lowerings apart), start and pre-roll. What `setup_s`
adds up, from the checkout in the working directory (so a parent
checkout reads its own program: run it from there).

Usage: python tools/setup_phases.py --workload axk1-chat --seed 1
       [--profile chiprun_out/setup/warmup.txt]   (cProfile of the warm-up)
Run it twice in one call: its programs did not find the cache entries
that `benchmark/run.py` left for the same tree (seen in two calls, PR
37), so the first run compiles (3-6 min) and the second reads the
cache, which is what a warm `setup_s` is (PERF.md section 6, PR 37).
"""
import argparse
import os
import sys
import time

T0 = time.time()
sys.path.insert(0, os.getcwd())


def _timed(acc, name, fn):
    def wrapped(*a, **k):
        t = time.perf_counter()
        try:
            return fn(*a, **k)
        finally:
            c = acc.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += time.perf_counter() - t
    return wrapped


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
    dev = device.start(int(cell["chips"]))
    print("device", dev, flush=True)

    # JAX's own stages, timed where they are looked up at call time
    from jax._src import compilation_cache, compiler, stages
    from jax._src.pallas.mosaic import pallas_call_registration as reg
    acc = {}
    stages.Traced.lower = _timed(acc, "lower", stages.Traced.lower)
    stages.Lowered.compile = _timed(acc, "compile", stages.Lowered.compile)
    compiler.compile_or_get_cached = _timed(
        acc, "compile or cache", compiler.compile_or_get_cached)
    compilation_cache.get_executable_and_time = _timed(
        acc, "cache read", compilation_cache.get_executable_and_time)
    reg.pallas_call_tpu_lowering_rule = _timed(
        acc, "pallas lowering", reg.pallas_call_tpu_lowering_rule)

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
    warm = {k: tuple(v) for k, v in acc.items()}
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
    for k, (n, s) in sorted(warm.items()):
        print(f"  in the warm-up: {k}: {n} calls, {s:.2f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
