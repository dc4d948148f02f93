"""Kind `train`: the program's one jitted train step (`train.make_train_step`
with the optimizer `train.make_optimizer` builds), driven on a fresh batch
of seeded random tokens every step.

Set-up builds ONE object, the compiled step with its state, drives it from
the seed through its first steps (reading what decides `correct`), and hands
that same object to the window. The plain reference follows those first
steps after the program's state is freed.
"""
from __future__ import annotations

import collections
import gc
import math
import time
from typing import Any, Dict

from ..harness import device, manifest, window, xplane

clock = time.perf_counter


def hyper(config: Dict[str, Any]) -> Dict[str, Any]:
    t = config["trainer"]
    return {"learning_rate": float(t["learning_rate"]),
            "weight_decay": float(t["weight_decay"]), "b1": float(t["b1"]),
            "b2": float(t["b2"]), "eps": float(t["eps"]),
            "grad_clip": float(t["grad_clip"])}


def build(ctx, fam, d):
    """(step function, state, batch maker): the object the window drives."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nlp import train
    hp = hyper(ctx.config)
    t = ctx.config["trainer"]
    pcfg = ctx.pcfg
    tx = train.make_optimizer(
        hp["learning_rate"], weight_decay=hp["weight_decay"], b1=hp["b1"],
        b2=hp["b2"], grad_clip=hp["grad_clip"],
        state_quant=t.get("state_quant"))
    params = fam.make_params(ctx.seed, d, pcfg.param_dtype)
    state = train.TrainState(jnp.zeros((), jnp.int32), params,
                             jax.jit(tx.init)(params))
    step = train.make_train_step(pcfg, tx, mesh=None)
    B, S = int(ctx.mix["batch"]), int(ctx.mix["seq_len"])
    # the key is an argument, not a constant: one program for every seed
    key = fam.seed_key(ctx.seed)
    tokens = jax.jit(lambda k, i: fam.train_tokens(k, i, B, S, d["V"]))
    batch = lambda i: tokens(key, i)                        # noqa: E731
    hook = ctx.overrides.get("wrap_step")           # tests only
    if hook is not None:
        step = hook(step)
    return step, state, batch


def first_grad_norms(opt_state, b1: float) -> Dict[str, Any]:
    """Per leaf, the norm of the first gradient as the optimizer applied
    it, worked out from its state after one step: m1 = (1 - b1) g."""
    import jax
    import jax.numpy as jnp

    def norm(q):
        if hasattr(q, "codes"):                     # 8-bit: codes x scale
            x = q.codes.astype(jnp.float32) * q.scale
        else:
            x = q.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(x))) / (1.0 - b1)

    return jax.tree.map(norm, opt_state.m,
                        is_leaf=lambda q: hasattr(q, "codes"))


def change_norms(fam, d, seed: int, dtype, params) -> Dict[str, float]:
    """Per leaf, the norm of the parameters' change from the seeded
    weights. Those are drawn again one layer at a time, each in a call of
    its own (a draw fused into the subtraction may skip its rounding to
    `dtype`), so that no second tree is ever resident."""
    import jax
    import jax.numpy as jnp
    key = fam.seed_key(seed)
    sq = lambda a, b: jnp.sum(jnp.square(                   # noqa: E731
        a.astype(jnp.float32) - b.astype(jnp.float32)))
    # the key is an argument, not a constant: one program for every seed
    make = jax.jit(lambda k, i: fam.layer_weights(fam.layer_key(k, i), d,
                                                  dtype))
    layer_sq = jax.jit(lambda layers, i, w0: jax.tree.map(
        lambda a, b: sq(a[i], b), layers, w0))
    total = {k: 0.0 for k in params["layers"]}
    for i in range(d["L"]):
        i = jnp.int32(i)
        for k, v in layer_sq(params["layers"], i, make(key, i)).items():
            total[k] += float(v)
    outer = jax.jit(lambda k: fam.outer_weights(k, d, dtype))(key)
    for k, w0 in outer.items():
        total[k] = float(jax.jit(sq)(params[k], w0))
    return {k: math.sqrt(v) for k, v in total.items()}


def flat_norms(tree) -> Dict[str, float]:
    """{leaf name: float}: layer leaves by their key, outer leaves by theirs."""
    out = {k: v for k, v in tree.items() if k != "layers"}
    out.update(tree["layers"])
    return {k: float(v) for k, v in out.items()}


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float]) -> float:
    """The gap between the program's norm and the reference's, by the worst
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    med = sorted(want.values())[len(want) // 2]
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in want)


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    fam = manifest.plugin("models", ctx.config["family"])
    d = fam.dims(ctx.config)
    ctx.pcfg = fam.program_config(ctx.config)
    hp = hyper(ctx.config)
    n_follow = int(ctx.cell["correct"]["steps"])
    compiles = window.compile_listener()
    step, state, batch = build(ctx, fam, d)
    B, S = int(ctx.mix["batch"]), int(ctx.mix["seq_len"])

    # --- the first steps, through the window's own call and feed
    grad_of = jax.jit(lambda o: first_grad_norms(o, hp["b1"]))
    losses, got_grad = [], None
    for i in range(n_follow):
        state, m = step(state, batch(jnp.int32(i)))
        losses.append(m["loss"])
        if i == 0:
            got_grad = grad_of(state.opt_state)
    got_delta = change_norms(fam, d, ctx.seed, ctx.pcfg.param_dtype,
                             state.params)
    losses = [float(x) for x in losses]
    got_grad = flat_norms(got_grad)
    del grad_of
    print(f"set-up: first {n_follow} steps, losses "
          + ", ".join(f"{x:.5f}" for x in losses), flush=True)

    # --- the window: whole steps, at most two in flight, ending drained
    tr: Dict[str, Any] = {}
    th = None
    if ctx.trace:
        th = window.trace_thread(ctx.seconds * 0.4, float(ctx.cell.get(
            "trace_seconds", 4.0)), tr)
    before = compiles["n"]
    jax.block_until_ready(state)
    setup_s = time.time() - ctx.t_start
    t0 = clock()
    flying = collections.deque()
    done, i = [], n_follow
    while True:
        state, m = step(state, batch(jnp.int32(i)))
        i += 1
        flying.append(m["loss"])
        if len(flying) >= 2:
            done.append(float(flying.popleft()))
            if clock() - t0 >= ctx.seconds:
                break
    done.extend(float(x) for x in flying)
    jax.block_until_ready(state)
    t1 = clock()
    if th is not None:
        th.join(120)
    in_window = compiles["n"] - before
    mem = device.memory_peak_bytes(int(ctx.cell["chips"]))
    tokens = len(done) * B * S
    values = {"setup_s": setup_s, "train_tok_s": tokens / (t1 - t0)}
    print(f"set-up took {setup_s:.1f} s\nwindow: {len(done)} steps of "
          f"{B} x {S} in {t1 - t0:.3f} s, {values['train_tok_s']:.1f} "
          f"tokens/s, last loss {done[-1]:.5f}", flush=True)
    if in_window:
        raise RuntimeError(
            f"{in_window} XLA program(s) compiled inside the measured "
            f"window: not steady state, no result")
    finite = all(math.isfinite(x) for x in losses + done)
    obs = {"values": values, "dims": d, "seq_len": S, "batch": B,
           "device_kind": ctx.device_kind,
           "chips": int(ctx.cell["chips"]),
           "counters": {}, "trace": None}
    if ctx.trace:
        obs["trace"] = xplane.load(xplane.find_xplane(tr["dir"]))

    # --- the program's state is gone before the reference follows
    del state, step, batch, m, flying
    gc.collect()
    ref = manifest.plugin("reference", ctx.config["family"])
    key = fam.seed_key(ctx.seed)
    t_ref = clock()
    want = ref.train_follow(
        ctx.seed, d,
        lambda k: np.asarray(fam.train_tokens(key, k, B, S, d["V"])),
        n_follow, hp, weight_dtype=ctx.pcfg.param_dtype,
        rows=int(ctx.cell["correct"].get("rows", 1)))
    numbers = {
        "loss_gap": max(abs(a - b) for a, b in zip(losses, want["loss"])),
        "grad_norm_gap": worst_leaf_gap(got_grad, want["grad_norm"]),
        "delta_norm_gap": worst_leaf_gap(got_delta, want["delta_norm"]),
    }
    ok = finite
    if not finite:
        print("correct: a loss is not finite FAILED")
    for name, value in numbers.items():
        limit = float(ctx.cell["correct"]["limits"][name])
        good = bool(math.isfinite(value) and value <= limit)
        ok = ok and good
        print(f"correct: {name} {value:.6g} (limit {limit:.6g}) "
              f"{'ok' if good else 'FAILED'}")
    print(f"correct: reference losses "
          + ", ".join(f"{x:.5f}" for x in want["loss"])
          + f"; gradient norm {want['grad_norm_total']:.4f}, clip "
          + ", ".join(f"{c:.4f}" for c in want["clip"])
          + f"; reference took {clock() - t_ref:.1f} s (not in setup_s)",
          flush=True)
    return {"correct": ok, "attempted": len(done), "failed": 0,
            "values": values, "obs": obs, "memory_peak_bytes": mem,
            "numbers": numbers,
            "detail": {"got_grad": got_grad, "want_grad": want["grad_norm"],
                       "got_delta": got_delta,
                       "want_delta": want["delta_norm"]}}
