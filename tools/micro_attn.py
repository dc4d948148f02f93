"""Microbenchmark of the paged attention kernels' calls as the server makes
them, for the kernel in the tree.

Usage: python tools/micro_attn.py latent | gqa [nb ...]
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
    work_list = getattr(ra, "attn_work_list",
                        getattr(ra, "mla_work_list", None))
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


def gqa_program(H, KV, hd=128, bs=16, M=128, layers=4, steps=8,
                window=None, ring=False, pool_blocks=4096):
    """`latent_program` for the ragged GQA kernel
    (`ragged_attention.ragged_paged_attention`): `steps` x `layers` calls
    as a decode chunk's layer scan makes them, a step's calls sharing
    positions, valid and (where the tree's kernel walks one) the work
    list built from them, each reading its own layer's `pool_blocks`
    blocks of the K and V pools; `window` / `ring` make them a window
    layer's calls over a ring table `M` wide. A table row's entries wrap
    over the layer's blocks (rows alias each other's blocks: the pools
    stay small, the bytes a call moves are its live rows')."""
    from paddle_tpu.nlp import ragged_attention as ra
    # a tree whose kernel walks the full grid has no list to build
    work_list = getattr(ra, "gqa_work_list", None)
    bf = jnp.bfloat16
    kind = {} if window is None else {"window": window, "ring": ring}

    @jax.jit
    def program(q, kp, vp, table, positions, valid):
        def step(pos, _):
            kw = dict(kind)
            if work_list is not None:
                kw["work"] = work_list(pos, valid, M, kp.shape, kp.dtype,
                                       window=window)

            def layer(s, li):
                o = ra.ragged_paged_attention(
                    q, kp, vp, table + li * pool_blocks, pos, valid, **kw)
                return s + jnp.sum(o.astype(jnp.float32)), None
            s, _ = jax.lax.scan(layer, jnp.float32(0), jnp.arange(layers))
            # never true, and unknown to the compiler: the next step's
            # calls wait for this step's whole output
            return pos + jnp.isnan(s).astype(pos.dtype), None
        return jax.lax.scan(step, positions, None, length=steps)[0]

    def inputs(R, P, live_rows, first_pos):
        kq, kk, kv = jax.random.split(jax.random.key(R * 1000 + P), 3)
        q = jax.random.normal(kq, (R, P, H, hd), bf)
        kp = jax.random.normal(kk, (layers * pool_blocks, bs, KV, hd), bf)
        vp = jax.random.normal(kv, (layers * pool_blocks, bs, KV, hd), bf)
        table = (jnp.arange(R * M, dtype=jnp.int32) % pool_blocks
                 ).reshape(R, M)
        valid = np.zeros((R, P), bool)
        valid[live_rows] = True
        positions = np.broadcast_to(first_pos + np.arange(P), (R, P))
        return (q, kp, vp, table, jnp.asarray(positions, jnp.int32),
                jnp.asarray(valid))

    return program, inputs


# (name, heads, KV heads, slots, table width, window, ring, contexts): the
# decode calls of the two served GQA configurations
GQA_DECODE = (
    ("mistral-7b-l16 [16, 1] over 128", 32, 8, 16, 128, None, False,
     (128, 560, 2040)),
    ("mellum2-l8 full [32, 1] over 800", 32, 4, 32, 800, None, False,
     (512, 3000, 12700)),
    ("mellum2-l8 window 1024 [32, 1] over a ring of 97", 32, 4, 32, 97,
     1024, True, (512, 3000, 12700)),
)


def bench_gqa(*nbs, steps=8, layers=4):
    """The ragged GQA kernel at the served configurations' widths,
    milliseconds a call (what 16 more steps of `layers` layers add to a
    program of 8, as `bench_latent` reads it): the decode calls by live
    rows x context (every live row at the same context, the live rows
    spread evenly over the slots), then the fused step's prefill rows
    `[1, 512]` and `[4, 128]` by rows live x where the chunk starts.
    `nbs`: blocks a step to try beside what the tree's tiling helper
    picks (a tree whose kernel walks one block a step ignores them)."""
    from paddle_tpu.nlp import ragged_attention as ra
    tiling = getattr(ra, "_attn_tiling", None)

    def with_nb(nb):
        # the candidate: the tiling helper made to pick `nb` (the jitted
        # kernel and list are traced again under it)
        jax.clear_caches()
        if tiling is not None:
            ra._attn_tiling = tiling if nb is None else (
                lambda P, M, q_tile, blocks_per_step=None, *a, **k:
                tiling(P, M, q_tile, nb, *a, **k))

    programs = {}

    def a_call(cfg, *args):
        key = tuple(cfg.items())
        if key not in programs:
            programs[key] = (
                gqa_program(steps=steps, layers=layers, **cfg),
                gqa_program(steps=3 * steps, layers=layers, **cfg)[0])
        (short, inputs), long = programs[key]
        x = inputs(*args)
        return (timeit(long, *x) - timeit(short, *x)) / (layers * 2 * steps
                                                         ) * 1e3

    print(f"device {jax.devices()[0].device_kind}", flush=True)
    for nb in (None,) + tuple(int(n) for n in nbs if tiling is not None):
        with_nb(nb)
        programs.clear()
        print(f"== blocks a step: {nb or 'as the tree picks'}", flush=True)
        for name, H, KV, R, M, window, ring, ctxs in GQA_DECODE:
            cfg = dict(H=H, KV=KV, M=M, window=window, ring=ring)
            kb = 2 * KV * 128 * 2 / 1024
            print(f"1. {name}: live rows, context -> ms a call ({kb:.0f} "
                  f"KiB of K and V a key: a 3000-key context "
                  f"{3000 * kb * 1024 / 819e9 * 1e3:.4f} ms at 819 GB/s)")
            for live in sorted({0, 1, 2, 4, R}):
                for ctx in (ctxs if live else ctxs[:1]):
                    t = a_call(cfg, R, 1, spread(R, live), ctx - 1)
                    print(f"   live {live:2d} context {ctx:5d}: {t:7.4f} ms",
                          flush=True)
        for name, H, KV, M, window, ring in (
                ("mistral-7b-l16 over 128", 32, 8, 128, None, False),
                ("mellum2-l8 full over 800", 32, 4, 800, None, False),
                ("mellum2-l8 window over a ring of 97", 32, 4, 97, 1024,
                 True)):
            cfg = dict(H=H, KV=KV, M=M, window=window, ring=ring)
            print(f"2. {name}, prefill rows: shape, rows live, chunk starts "
                  f"at -> ms a call")
            for R, P in ((1, 512), (4, 128)):
                for live in sorted({0, 1, R}):
                    for start in ((0, 1024) if live else (0,)):
                        if start + P > M * 16 and not ring:
                            continue
                        t = a_call(cfg, R, P, np.arange(live), start)
                        print(f"   [{R}, {P}] rows {live}, start {start:4d}: "
                              f"{t:7.4f} ms", flush=True)
    with_nb(None)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "latent"
    {"latent": bench_latent, "gqa": bench_gqa}[which](*sys.argv[2:])
