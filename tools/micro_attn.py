"""Microbenchmark of the paged attention kernels' calls as the server makes
them, for the kernel in the tree.

Usage: python tools/micro_attn.py latent
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from micro_moe import timeit


def latent_program(H=64, W=576, V=512, bs=16, M=128, layers=7, steps=8):
    """(program, inputs): `steps` x `layers` calls of the latent kernel as
    the layer scan of a decode chunk makes them: a step's calls share
    positions and valid (and the work list built from them, where the
    tree's kernel walks one), each reads its own layer's blocks of one
    stacked pool, and a step's output feeds the next step's positions,
    so that no call is hoisted. `inputs(R, P, live_rows, first_pos)`:
    `[R, P]` queries at `first_pos` on, the rows `live_rows` valid."""
    from paddle_tpu.nlp import ragged_attention as ra
    # a tree whose kernel walks the full grid has no list to build
    work_list = getattr(ra, "mla_work_list", None)
    bf = jnp.bfloat16
    scale = 1.0 / np.sqrt(192.0)

    @jax.jit
    def program(q, pool, table, positions, valid):
        blocks = pool.shape[0] // layers

        def step(pos, _):
            kw = {} if work_list is None else {"work": work_list(
                pos, valid, block_size=bs, table_width=M)}

            def layer(s, li):
                o = ra.mla_paged_attention(q, pool, table + li * blocks, pos,
                                           valid, scale=scale, v_width=V,
                                           **kw)
                return s + jnp.sum(o.astype(jnp.float32)), None
            s, _ = jax.lax.scan(layer, jnp.float32(0), jnp.arange(layers))
            # never true, and unknown to the compiler: the next step's
            # calls wait for this step's whole output
            return pos + jnp.isnan(s).astype(pos.dtype), None
        return jax.lax.scan(step, positions, None, length=steps)[0]

    def inputs(R, P, live_rows, first_pos):
        kq, kp = jax.random.split(jax.random.key(R * 1000 + P))
        q = jax.random.normal(kq, (R, P, H, W), bf)
        pool = jax.random.normal(kp, (layers * R * M, bs, W), bf)
        table = jnp.arange(R * M, dtype=jnp.int32).reshape(R, M)
        valid = np.zeros((R, P), bool)
        valid[live_rows] = True
        positions = np.broadcast_to(first_pos + np.arange(P), (R, P))
        return (q, pool, table, jnp.asarray(positions, jnp.int32),
                jnp.asarray(valid))

    return program, inputs


def spread(R, live):
    """`live` of `R` rows, evenly spread."""
    return np.unique(np.linspace(0, R - 1, live).round().astype(int)) \
        if live else np.zeros((0,), int)


def bench_latent(**sizes):
    """The latent (MLA) kernel (`ragged_attention.mla_paged_attention`) at
    A.X-K1's widths (64 heads over one cached row of 512 + 64 columns,
    blocks of 16 tokens, a table 128 blocks wide), milliseconds a call:
    what 16 more steps of 7 layers add to a program of 8
    (`latent_program`), so that what a program pays once (its launch,
    the pool's layout copy on entry: 3.6 ms for the 1 GiB of 64 slots)
    is not spread over its calls.

    1. The decode call `[slots, 1]`: slots x live rows x context, the live
       rows spread evenly over the slots, every live row at the same
       context.
    2. The fused step's prefill rows `[4, 512]`: rows live x where in the
       prompt the 512-token chunk starts."""
    steps = sizes.pop("steps", 8)
    short, inputs = latent_program(steps=steps, **sizes)
    long, _ = latent_program(steps=3 * steps, **sizes)
    calls = sizes.get("layers", 7) * 2 * steps
    W = sizes.get("W", 576)

    def a_call(*args):
        return (timeit(long, *args) - timeit(short, *args)) / calls * 1e3

    print(f"device {jax.devices()[0].device_kind}; a cached row "
          f"{W * 2} B, a 512-token context {512 * W * 2 / 819e9 * 1e3:.5f} "
          f"ms of bytes at 819 GB/s", flush=True)

    print("1. decode call [slots, 1]: slots, live rows, context -> ms a "
          "call")
    for R in (16, 32, 64):
        for live in sorted({0, 1, 4, 16, R}):
            for ctx in (128, 512, 1920):
                t = a_call(*inputs(R, 1, spread(R, live), ctx - 1))
                print(f"   slots {R:2d} live {live:2d} context {ctx:4d}: "
                      f"{t:7.4f} ms", flush=True)

    print("2. prefill rows [4, 512]: rows live, chunk starts at -> ms a "
          "call")
    for live in (0, 1, 4):
        for start in (0, 512, 1024):
            t = a_call(*inputs(4, 512, np.arange(live), start))
            print(f"   rows {live} of 4, start {start:4d}: {t:7.4f} ms",
                  flush=True)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "latent"
    {"latent": bench_latent}[which]()
