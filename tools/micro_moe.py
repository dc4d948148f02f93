"""Microbenchmarks for the MoE-step hot spots (gathers, 8-bit Adam, the
served expert layer's grouped GEMMs).

Usage: python tools/micro_moe.py [gather|opt|share] [--shape axk1|mellum2]
       [--Lm .. --n .. --E .. --k .. --D .. --F ..]
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


SHAPES = {
    # Lm expert layers x n held of E routed experts, top-k, D x F
    "axk1": dict(Lm=6, n=12, E=192, k=8, D=7168, F=2048),      # axk1-ep16
    "mellum2": dict(Lm=8, n=64, E=64, k=8, D=2304, F=896),     # mellum2-l8
}


def _hit_sizes(n, pairs, hits):
    """[n] rows on each held expert: `pairs` rows over `hits` experts
    spread evenly over the n, as evenly as they divide."""
    sizes = np.zeros((n,), np.int32)
    at = np.round(np.linspace(0, n - 1, hits)).astype(int)
    sizes[at] = pairs // hits
    sizes[at[:pairs % hits]] += 1
    return jnp.asarray(sizes)


def bench_share(Lm=6, n=12, E=192, k=8, D=7168, F=2048, steps=8,
                tiling=()):
    """The served expert layer (`moe.expert_share_ffn`) on one chip that
    holds n of E routed experts of D x F in each of Lm expert layers
    (defaults: A.X-K1's, `axk1-ep16`; `--shape mellum2` is `mellum2-l8`),
    steps x Lm layer-steps a program (a decode chunk), milliseconds a
    GEMM (a third of a layer-step's gate, up and down).

    1. The three grouped GEMMs alone, by GROUPS IN THE STACK: the same
       rows and experts hit against a stack of 1, 2 and Lm layers'
       experts, `lax.ragged_dot` (the stack's groups all handed over,
       one layer's filled in) beside the repo's kernel
       (`kernels/grouped_gemm.py`, which addresses this layer's experts
       in the stack; absent in a tree without it).
    2. The same at the whole stack, by buffer rows, pairs and experts
       hit: what a hit expert costs.
    2b. (`--tiling tm:MiB ...`) the kernel under candidate tilings.
    3. The layer itself, routing its own random tokens (`live` of T rows
       valid), in passes over the short buffer and in one pass over a
       buffer of all T x k pairs (what it was before PR 28)."""
    from paddle_tpu.nlp import moe
    try:
        from paddle_tpu.kernels import grouped_gemm as gg
    except ImportError:
        gg = None
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.key(0), 5)
    w = {m: (jax.random.normal(kk, (Lm, n) + shp, bf) * 0.02)
         for m, kk, shp in (("gate", keys[0], (D, F)), ("up", keys[1], (D, F)),
                            ("down", keys[2], (F, D)))}
    router = jax.random.normal(keys[3], (D, E), bf) * 0.01
    print(f"device {jax.devices()[0].device_kind}; Lm {Lm} n {n} E {E} "
          f"k {k} D {D} F {F}; one expert's matrix {D * F * 2 / 1e6:.2f} MB"
          f" = {D * F * 2 / 819e9 * 1e3:.4f} ms at 819 GB/s", flush=True)

    def ragged(x, ws, sizes, base):
        gs = jax.lax.dynamic_update_slice(
            jnp.zeros((ws["gate"].shape[0],), jnp.int32), sizes, (base,))
        return moe._grouped_mlp(x, ws, gs)

    def kernel(x, ws, sizes, base):
        work = gg.gemm_work_list(sizes, base, rows=x.shape[0])
        return moe._served_mlp(x, ws, sizes, base, work)

    forms = [("ragged_dot", ragged)] + (
        [("kernel", kernel)] if gg is not None else [])

    def gemms(form, layers):
        @jax.jit
        def run(x, sizes, wg, wu, wd):
            ws = {m: a.reshape(layers * n, *a.shape[2:])
                  for m, a in (("gate", wg), ("up", wu), ("down", wd))}

            def layer_step(x, i):
                return x + form(x, ws, sizes, i % layers * n) * bf(1e-3), None

            return jax.lax.scan(layer_step, x,
                                jnp.arange(steps * layers))[0]
        return run

    def ms_a_gemm(form, layers, R, sizes):
        x = jax.random.normal(keys[4], (R, D), bf)
        return timeit(gemms(form, layers), x, sizes,
                      *(w[m][:layers] for m in ("gate", "up", "down"))) \
            / (steps * layers * 3) * 1e3

    def line(layers, R, pairs, hits, forms=forms):
        sizes = _hit_sizes(n, pairs, hits)
        got = [(name, ms_a_gemm(f, layers, R, sizes)) for name, f in forms]
        print(f"   groups {layers * n:4d} rows {R:5d} pairs {pairs:5d} on "
              f"{hits:2d}: " + "; ".join(
                  f"{name} {t:7.4f} ms a GEMM, {t / hits:.4f} a hit expert"
                  for name, t in got), flush=True)

    # a decode step's 1 and 3 live rows, a full decode batch, a fused
    # step's 512-token chunk beside 32 decode rows
    decode = moe._short_rows(32 * k, n, E)
    cases = [(min(decode, 32 * k), 1 * k, min(k, n)),
             (min(decode, 32 * k), 3 * k, min(3 * k - 4, n)),
             (min(decode, 32 * k), min(decode, 32 * k), n),
             (544 * k * n // E, 544 * k * n // E, n)]
    print("1. grouped GEMMs alone, by groups in the stack")
    for layers in sorted({1, 2, Lm}):
        for R, pairs, hits in cases[:3]:
            line(layers, R, pairs, hits)
    print("2. grouped GEMMs alone at the whole stack, by buffer rows, "
          "pairs and experts hit")
    for R, pairs, hits in (
            [(R, h * 2, h) for R in (128, 256, 384, 512, 640)
             for h in sorted({1, min(3, n), n // 2})]
            + [(R, n * 24, n) for R in (4608, 1024, 1152, 896, 640, 384)
               if R >= n * 24]
            + [cases[3]] + [(cases[3][0] * 2, cases[3][1], n)]):
        line(Lm, R, pairs, hits)

    def set_tiling(tm, block_bytes):
        """Patch the kernel's row tile and weight-block bytes; its jits
        trace again."""
        was = gg._ROW_TILE, gg._W_BLOCK_BYTES
        gg._ROW_TILE, gg._W_BLOCK_BYTES = tm, block_bytes
        for f in (gg._grouped_gemm, gg.gemm_work_list):
            f.clear_cache()
        return was

    for tm, mib in tiling:
        was = set_tiling(tm, mib << 20)
        print(f"2b. the kernel with row tiles of {tm} and weight blocks of "
              f"at most {mib} MiB (its own: {was[0]}, {was[1] >> 20})")
        for R, pairs, hits in cases + [(cases[3][0] * 2, cases[3][1], n)]:
            line(Lm, R, pairs, hits, forms[1:])
        set_tiling(*was)

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

    print("3. the layer (router, sort, gathers, GEMMs, combine): tokens, "
          "valid, ms a layer-step short buffer / all pairs, layer-steps of "
          f"{steps * Lm} that overflowed a buffer, experts hit a layer-step")
    short_rows = moe._short_rows
    for T, live in ((32, 1), (32, 3), (64, 5), (64, 16), (64, 64),
                    (192, 133), (576, 517), (576, 576)):
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
              f"{min(short_rows(T * k, n, E), T * k)} of {T * k} rows): "
              f"{got['short'][0]:.4f} / {got['full'][0]:.4f} ms, "
              f"overflowed {got['short'][1]} / {got['full'][1]}, "
              f"hit {got['short'][2]:.2f}", flush=True)


def main(argv):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("which", nargs="?", default="gather",
                    choices=("gather", "opt", "share"))
    ap.add_argument("--shape", choices=sorted(SHAPES), default="axk1",
                    help="share: a served configuration's shape")
    for name in ("Lm", "n", "E", "k", "D", "F"):
        ap.add_argument("--" + name, type=int,
                        help=f"share: {name}, over the shape's")
    ap.add_argument("--tiling", nargs="*", default=(), metavar="TM:MIB",
                    help="share: candidate tilings of the kernel's, row "
                    "tile : MiB a weight block may take")
    args = ap.parse_args(argv)
    if args.which == "share":
        shape = {**SHAPES[args.shape],
                 **{name: v for name in SHAPES["axk1"]
                    if (v := getattr(args, name)) is not None}}
        return bench_share(**shape, tiling=[
            tuple(int(x) for x in t.split(":")) for t in args.tiling])
    {"gather": bench_gather, "opt": bench_opt}[args.which]()


if __name__ == "__main__":
    main(sys.argv[1:])
