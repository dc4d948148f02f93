"""`tools/control_train.py` for a family whose reference brings broken
programs (`CONTROLS` in benchmark/reference/<family>.py): the sound
reference followed ONCE a seed, then every control beside it (each of
CONTROLS; `fp8`: the operands of every matmul rounded to float8 and their
cotangents to a scaled e5m2, the nearest precision below the bfloat16 the
configuration states; `half_batch`: the sound reference on a batch whose
second half repeats its first, which is the loss and the gradient of half
the sequences). A control's gaps are what it would give in the program's
place; each has to miss one of the cell's limits.

    python3 benchmark/tools/control_train_family.py --workload <cell> \
        --seeds 1,2 [--controls fp8,hres_identity] [--control-seeds 1] \
        [--program] [--out <name>]

`--out` keeps every leaf's norms (the program's, the sound reference's and
each control's) under chiprun_out/<name>/<seed>.json.

With `--program` the PROGRAM's own first steps are read first on every seed
(one compiled step for all of them, as the train runner builds it): the
three numbers that decide `correct`, and the leaves that read worst, which
a run's last line does not show.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np                                          # noqa: E402

from benchmark.harness import device, manifest              # noqa: E402


def program_steps(config, fam, runner, d, hp, seeds, B, S, steps):
    """{seed: the program's losses, first gradient norms and change norms
    over its first `steps` steps}, one compiled step for every seed, built
    and read as `runners/train.py` builds and reads it."""
    import gc
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nlp import train
    pcfg = fam.program_config(config)
    t = config["trainer"]
    tx = train.make_optimizer(
        hp["learning_rate"], weight_decay=hp["weight_decay"], b1=hp["b1"],
        b2=hp["b2"], grad_clip=hp["grad_clip"],
        state_quant=t.get("state_quant"))
    step = train.make_train_step(pcfg, tx, mesh=None)
    init = jax.jit(tx.init)
    tokens = jax.jit(lambda k, i: fam.train_tokens(k, i, B, S, d["V"]))
    grad_of = jax.jit(lambda o: runner.first_grad_norms(o, hp["b1"]))
    out = {}
    for seed in seeds:
        t0 = time.time()
        params = fam.make_params(seed, d, pcfg.param_dtype)
        state = train.TrainState(jnp.zeros((), jnp.int32), params,
                                 init(params))
        del params
        key = fam.seed_key(seed)
        losses, grads = [], None
        for i in range(steps):
            state, m = step(state, tokens(key, jnp.int32(i)))
            losses.append(m["loss"])
            if i == 0:
                grads = grad_of(state.opt_state)
        out[seed] = {
            "loss": [float(x) for x in losses],
            "grad_norm": runner.flat_norms(grads),
            "delta_norm": runner.change_norms(fam, d, seed, pcfg.param_dtype,
                                              state.params),
            "counters": {k: float(v) for k, v in m.items()}}
        print(json.dumps({"seed": seed, "control": "program_steps",
                          "loss": out[seed]["loss"],
                          "counters": out[seed]["counters"],
                          "seconds": round(time.time() - t0, 1)}), flush=True)
        del state, m, grads
        gc.collect()
    del step, init
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="all")
    ap.add_argument("--control-seeds", type=int, default=1 << 30,
                    help="the controls run on the first so many seeds")
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--out")
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
    limits = cell["correct"]["limits"]
    names = ["fp8", "half_batch", *getattr(ref, "CONTROLS", {})] \
        if args.controls == "all" else \
        [n for n in args.controls.split(",") if n != "none"]
    seeds = [int(s) for s in args.seeds.split(",")]
    got = program_steps(config, fam, runner, d, hp, seeds, B, S, steps) \
        if args.program else {}
    for i, seed in enumerate(seeds):
        key = fam.seed_key(seed)
        toks = lambda k: np.asarray(                         # noqa: E731
            fam.train_tokens(key, k, B, S, d["V"]))
        t0 = time.time()
        want = ref.train_follow(seed, d, toks, steps, hp, jnp.bfloat16, rows)
        print(json.dumps({"seed": seed, "control": "sound",
                          "loss": want["loss"], "clip": want["clip"],
                          "reference_s": round(time.time() - t0, 1)}),
              flush=True)
        kept = {"sound": want}
        if seed in got:
            kept["program"] = got[seed]
            print(json.dumps(gaps(runner, seed, "program", got[seed], want,
                                  limits)), flush=True)
        for name in (names if i < args.control_seeds else []):
            t0 = time.time()
            if name == "half_batch":
                half = lambda k: np.concatenate(            # noqa: E731
                    [toks(k)[:B // 2]] * 2)
                low = ref.train_follow(seed, d, half, steps, hp,
                                       jnp.bfloat16, rows)
            else:
                low = ref.train_follow(seed, d, toks, steps, hp,
                                       jnp.bfloat16, rows,
                                       lower=getattr(ref, name))
            kept[name] = low
            print(json.dumps({**gaps(runner, seed, name, low, want, limits),
                              "reference_s": round(time.time() - t0, 1)}),
                  flush=True)
        if args.out:
            path = os.path.join(root, "chiprun_out", args.out)
            os.makedirs(path, exist_ok=True)
            with open(os.path.join(path, f"{seed}.json"), "w") as f:
                json.dump(kept, f)
    return 0


def gaps(runner, seed, name, got, want, limits):
    """The three numbers that decide `correct`, for `got` in the program's
    place, and per norm the six leaves that read worst (gap, leaf, got's
    norm, the reference's)."""
    row = {"seed": seed, "control": name, "loss": got["loss"],
           "loss_gap": max(abs(a - b) for a, b in
                           zip(got["loss"], want["loss"]))}
    for what in ("grad_norm", "delta_norm"):
        row[what + "_gap"] = runner.worst_leaf_gap(got[what], want[what])
        med = sorted(want[what].values())[len(want[what]) // 2]
        row[what + "_worst"] = sorted(
            ((round(abs(got[what][k] - v) / max(v, med), 5), k,
              float(f"{got[what][k]:.5g}"), float(f"{v:.5g}"))
             for k, v in want[what].items()), reverse=True)[:6]
    row["correct"] = all(row[k] <= float(v) for k, v in limits.items())
    return row


if __name__ == "__main__":
    sys.exit(main())
