"""Device-side loop timing harness for TPU microbenchmarks.

Per-call dispatch overhead swamps ms-scale kernels when they are timed
with a host loop. loop_time() runs N
iterations inside ONE jit (fori_loop with a rolled-index data dependency so
XLA cannot hoist the loop-invariant kernel call) and returns seconds/iter.
"""
import time

import jax
import jax.numpy as jnp


def loop_time(f, args, n=20, roll_arg=None, reps=3):
    """f(*args) -> array. roll_arg: index of an int array arg to roll by i
    each iteration (defeats loop-invariant hoisting); None rolls arg 0."""
    ra = 0 if roll_arg is None else roll_arg

    @jax.jit
    def run(*args):
        def body(i, acc):
            a = list(args)
            a[ra] = jnp.roll(a[ra], i, axis=-1)
            out = f(*a)
            first = jax.tree.leaves(out)[0]
            return acc + first.reshape(-1)[:8].astype(jnp.float32).sum()
        return jax.lax.fori_loop(0, n, body, jnp.zeros((), jnp.float32))

    best = float("inf")
    for _ in range(reps):
        run(*args).block_until_ready()
        t0 = time.perf_counter()
        run(*args).block_until_ready()
        best = min(best, (time.perf_counter() - t0) / n)
    return best
