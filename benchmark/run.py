r"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One process per run. Knows no cell, model or metric by name: the workload
names a cell file, the cell a configuration and a traffic mix, the mix's
`kind` a runner, the configuration's `family` a model file and a plain
reference, and every per-layer metric a reader (benchmark/README.md).
The last line of standard output is the result object.
"""
from __future__ import annotations

import time

T_START = time.time()               # set-up is counted from here

import argparse                     # noqa: E402
import dataclasses                  # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402
from typing import Any, Dict, Optional     # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import device, manifest, result, xplane  # noqa: E402

# the runner of a kind of traffic mix, where it is not the kind's own name
RUNNER_OF_KIND = {"serve_open": "serve", "serve_closed": "serve"}


@dataclasses.dataclass
class Context:
    root: str
    workload: str
    cell: Dict[str, Any]
    config: Dict[str, Any]
    mix: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t_start: float
    overrides: Dict[str, Any]
    device_kind: str = ""
    pcfg: Any = None


def layer_values(specs, obs) -> Dict[str, Optional[float]]:
    out = {}
    for spec in specs:
        reader = manifest.plugin("readers", spec["reader"])
        out[spec["name"]] = reader.read(spec, obs)
    return out


def main(argv=None, root: str = manifest.ROOT,
         overrides: Optional[Dict[str, Any]] = None,
         t_start: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = manifest.cell(root, args.workload)
    config = manifest.config(root, cell["config"])
    mix = manifest.traffic(root, cell["traffic"])
    kind = mix["kind"]
    runner = manifest.plugin("runners", RUNNER_OF_KIND.get(kind, kind))

    dev = device.start(int(cell["chips"]))      # JAX is first touched here
    cache_dir = dev.pop("compile_cache")
    print(f"{args.workload}: config {cell['config']}, traffic "
          f"{cell['traffic']} ({kind}), seed {args.seed}, {args.seconds:g} s"
          f", trace {args.trace}; {dev['count']} x {dev['kind']}; compile "
          f"cache {cache_dir}", flush=True)

    ctx = Context(root, args.workload, cell, config, mix, args.seed,
                  args.seconds, bool(args.trace),
                  T_START if t_start is None else t_start, overrides or {},
                  dev["kind"])
    out = runner.run(ctx)

    dev["memory_peak_bytes"] = out["memory_peak_bytes"]
    if args.trace:
        table = out["obs"]["trace"]
        if ctx.overrides.get("keep_trace"):     # tools only
            xplane.dump(table, ctx.overrides["keep_trace"])
        busy_s, window_s = xplane.busy_seconds(table)
        dev["busy_s"], dev["window_s"] = busy_s, window_s
        specs = manifest.per_layer(root, args.workload)
        metrics = result.metrics_block(specs, layer_values(specs, out["obs"]))
        print(f"trace: {window_s:.1f} s, programs: "
              f"{xplane.program_counts(table)}")
        for m in specs:
            if m["name"] not in metrics:
                print(f"note: per-layer metric {m['name']} found nothing to "
                      f"read in this run and is left out")
        breakdown = {"device_ops": xplane.top_ops(table),
                     "idle_gaps": xplane.idle_gaps(table)}
    else:
        specs = manifest.end_to_end(root, args.workload)
        metrics = result.metrics_block(specs, out["values"])
        breakdown = None
        missing = [m["name"] for m in specs if m["name"] not in metrics]
        if missing:
            sys.exit(f"benchmark: no value for end-to-end metric(s) "
                     f"{missing}")
    print(result.last_line(out["correct"], out["attempted"], out["failed"],
                           metrics, dev, breakdown))
    return 0


if __name__ == "__main__":
    sys.exit(main())
