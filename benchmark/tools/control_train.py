"""The training cell's control: the plain reference followed in float32 and
again with the operands of every matmul rounded to float8 (the nearest
precision below the bfloat16 the configuration states), on several seeds.
The gaps between the two are what the lower precision gives in the
program's place; they have to lie beyond the cell's limits.

    python3 benchmark/tools/control_train.py --workload <cell> --seeds 1,2,3
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np                                          # noqa: E402

from benchmark.harness import device, manifest              # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--lower", default="fp8")
    args = ap.parse_args(argv)
    root = manifest.ROOT
    cell = manifest.cell(root, args.workload)
    config = manifest.config(root, cell["config"])
    mix = manifest.traffic(root, cell["traffic"])
    import jax.numpy as jnp
    device.start(int(cell["chips"]))
    fam = manifest.plugin("models", config["family"])
    ref = manifest.plugin("reference", config["family"])
    runner = manifest.plugin("runners", "train")
    d, hp = fam.dims(config), runner.hyper(config)
    B, S = int(mix["batch"]), int(mix["seq_len"])
    steps, rows = int(cell["correct"]["steps"]), int(cell["correct"]["rows"])
    for seed in (int(s) for s in args.seeds.split(",")):
        key = fam.seed_key(seed)
        toks = lambda k: np.asarray(                         # noqa: E731
            fam.train_tokens(key, k, B, S, d["V"]))
        want = ref.train_follow(seed, d, toks, steps, hp, jnp.bfloat16, rows)
        low = ref.train_follow(seed, d, toks, steps, hp, jnp.bfloat16, rows,
                               lower=getattr(ref, args.lower))
        row = {"seed": seed,
               "loss_gap": max(abs(a - b) for a, b in
                               zip(low["loss"], want["loss"])),
               "grad_norm_gap": runner.worst_leaf_gap(low["grad_norm"],
                                                      want["grad_norm"]),
               "delta_norm_gap": runner.worst_leaf_gap(low["delta_norm"],
                                                       want["delta_norm"])}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
