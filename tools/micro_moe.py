"""Microbenchmarks for the MoE-step hot spots (gathers, 8-bit Adam).

Usage: python tools/micro_moe.py [gather|opt]
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


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "gather"
    {"gather": bench_gather, "opt": bench_opt}[which]()
