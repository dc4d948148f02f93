"""Microbenchmarks for the MoE-step hot spots (gathers, 8-bit Adam, the
served expert layer's grouped GEMMs).

Usage: python tools/micro_moe.py [gather|opt|share]
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _force(out):
    jax.block_until_ready(out)


def timeit(f, *args, n=10):
    out = f(*args)
    _force(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    _force(out)
    return (time.perf_counter() - t0) / n


import jax
import jax.numpy as jnp


def bench_gather():
    from paddle_tpu.kernels.moe_dispatch import (_gather_rows_jnp,
                                                 gather_rows_pallas)
    rng = np.random.default_rng(0)
    # bench shapes: dispatch direction [1, 81920, D] -> [1, 102400, D]
    # (~20% of idx invalid), combine direction the reverse
    for (N, M, frac_valid) in [(81920, 102400, 0.8), (102400, 81920, 1.0)]:
        src = jnp.asarray(rng.normal(size=(1, N, 2048)), jnp.bfloat16)
        idx = rng.integers(0, N, (1, M)).astype(np.int32)
        drop = rng.random((1, M)) > frac_valid
        idx[drop] = -1
        idx_sorted = np.sort(idx, axis=1)  # monotone variant
        idx = jnp.asarray(idx)
        idxs = jnp.asarray(idx_sorted)
        gb = (M * frac_valid + M) * 2048 * 2 / 1e9  # read + write
        jnp_f = jax.jit(_gather_rows_jnp)
        t = timeit(jnp_f, src, idx)
        print(f"N={N} M={M}: jnp gather       {t*1e3:7.2f} ms  {gb/t:6.1f} GB/s")
        for bm in (128, 256):
            pal = jax.jit(lambda s, i, bm=bm: gather_rows_pallas(s, i, bm=bm))
            t = timeit(pal, src, idx)
            print(f"N={N} M={M}: pallas bm={bm:4d}  {t*1e3:7.2f} ms  {gb/t:6.1f} GB/s")
        t = timeit(pal, src, idxs)
        print(f"N={N} M={M}: pallas bm=256 SORTED idx {t*1e3:7.2f} ms  {gb/t:6.1f} GB/s")


def bench_opt():
    from paddle_tpu.nlp import moe, train
    cfg = moe.MoeConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        moe_intermediate_size=1024, num_experts=16, num_experts_per_tok=2,
        num_shared_experts=1, num_hidden_layers=12, num_attention_heads=16,
        num_key_value_heads=8, max_position_embeddings=2048,
        param_dtype=jnp.bfloat16)
    tx = train.make_optimizer(1e-4, state_quant="8bit", grad_clip=1.0)
    params = moe.init_params(jax.random.key(0), cfg)
    opt_state = tx.init(params)
    grads = jax.tree.map(lambda p: (p * 1e-3).astype(p.dtype), params)

    @jax.jit
    def upd(grads, opt_state, params):
        updates, opt_state = tx.update(grads, opt_state, params)
        import optax
        return optax.apply_updates(params, updates), opt_state

    t = timeit(upd, grads, opt_state, params, n=5)
    nparams = sum(x.size for x in jax.tree.leaves(params))
    # traffic: params r+w (2B), grads r (2B), moments r+w (2x1B+scales)
    gb = nparams * (2 * 2 + 2 + 2 * 2 * 1) / 1e9
    print(f"8bit adam update: {t*1e3:.1f} ms for {nparams/1e9:.2f}B params "
          f"(~{gb:.1f} GB traffic -> {gb/t:.0f} GB/s)")


def bench_share(D=7168, F=2048):
    """The served expert layer (`moe.expert_share_ffn`) at A.X-K1's
    widths on one chip of 16: 6 expert layers x 12 held experts of
    7168 x 2048 under a 192-wide top-8 router, 48 layer-steps a program
    (a decode chunk: 8 steps x 6 layers), milliseconds a layer-step.

    1. The tile rule `moe._short_rows` rests on: the three grouped GEMMs
       alone over a sorted buffer of R rows, h of the layer's 12 experts
       hit with r rows each.
    2. The layer itself, routing its own random tokens (`live` of T rows
       valid), in passes over the short buffer and in one pass over a
       buffer of all T x k pairs (what it was before PR 28)."""
    from paddle_tpu.nlp import moe
    n, Lm, k, E, steps = 12, 6, 8, 192, 8
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.key(0), 5)
    w = {m: (jax.random.normal(kk, (Lm, n) + shp, bf) * 0.02)
         for m, kk, shp in (("gate", keys[0], (D, F)), ("up", keys[1], (D, F)),
                            ("down", keys[2], (F, D)))}
    router = jax.random.normal(keys[3], (D, E), bf) * 0.01
    print(f"device {jax.devices()[0].device_kind}; one expert's three "
          f"matrices {3 * D * F * 2 / 1e6:.2f} MB = "
          f"{3 * D * F * 2 / 819e9 * 1e3:.4f} ms at 819 GB/s", flush=True)

    @jax.jit
    def gemms(x, sizes, wg, wu, wd):
        ws = {m: a.reshape(Lm * n, *a.shape[2:])
              for m, a in (("gate", wg), ("up", wu), ("down", wd))}

        def layer_step(x, i):
            gs = jax.lax.dynamic_update_slice(
                jnp.zeros((Lm * n,), jnp.int32), sizes, (i % Lm * n,))
            return x + moe._grouped_mlp(x, ws, gs) * bf(1e-3), None

        return jax.lax.scan(layer_step, x, jnp.arange(steps * Lm))[0]

    print("1. grouped GEMMs alone: rows, experts hit x rows each, "
          "ms a layer-step, ms a hit expert")
    for R, hits, each in (
            [(R, h, 2) for R in (512, 256, 128, 384, 640) for h in (1, 3, 6)]
            + [(R, 12, 24) for R in (4608, 1024, 1152, 896, 640, 384)]
            + [(R, 12, 48) for R in (8192, 1152, 1024, 640)]):
        x = jax.random.normal(keys[4], (R, D), bf)
        sizes = jnp.asarray([each] * hits + [0] * (n - hits), jnp.int32)
        t = timeit(gemms, x, sizes, w["gate"], w["up"], w["down"]) \
            / (steps * Lm) * 1e3
        print(f"   rows {R:5d} hit {hits:2d} x {each:2d}: {t:7.4f} ms, "
              f"{t / hits:.4f} a hit expert", flush=True)

    def layer_program():       # traced anew under each `_short_rows`
        @jax.jit
        def run(h, valid, router, wg, wu, wd):
            lp = {"router": router, "experts_gate": wg, "experts_up": wu,
                  "experts_down": wd}

            def layer_step(c, i):
                h, full, hit = c
                y, st = moe.expert_share_ffn(h, lp, k=k, first=0, scale=2.5,
                                             valid=valid, layer=i % Lm)
                # the next layer-step routes other tokens
                return (jnp.roll(h, 1, axis=1) + y * bf(1e-3),
                        full + st["moe_full_passes"],
                        hit + st["moe_experts_hit"]), None

            z = jnp.zeros((), jnp.int32)
            return jax.lax.scan(layer_step, (h, z, z),
                                jnp.arange(steps * Lm))[0]
        return run

    print("2. the layer (router, sort, gathers, GEMMs, combine): tokens, "
          "valid, ms a layer-step short buffer / all pairs, layer-steps of "
          "48 that overflowed a buffer, experts hit a layer-step")
    short_rows = moe._short_rows
    for T, live in ((64, 5), (64, 16), (64, 64), (192, 133), (576, 517),
                    (576, 576)):
        h = jax.random.normal(jax.random.fold_in(keys[4], T + live), (T, D),
                              bf)
        valid = jnp.arange(T) < live
        got = {}
        for name, rule in (("short", short_rows),
                           ("full", lambda pairs, held, routed: pairs)):
            moe._short_rows = rule
            run = layer_program()
            args = (h, valid, router, w["gate"], w["up"], w["down"])
            t = timeit(run, *args) / (steps * Lm) * 1e3
            _, full, hit = run(*args)
            got[name] = (t, int(full), int(hit) / (steps * Lm))
        moe._short_rows = short_rows
        print(f"   T {T:4d} valid {live:4d} (short buffer "
              f"{short_rows(T * k, n, E)} of {T * k} rows): "
              f"{got['short'][0]:.4f} / {got['full'][0]:.4f} ms, "
              f"overflowed {got['short'][1]} / {got['full'][1]}, "
              f"hit {got['short'][2]:.2f}", flush=True)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "gather"
    {"gather": bench_gather, "opt": bench_opt, "share": bench_share}[which]()
