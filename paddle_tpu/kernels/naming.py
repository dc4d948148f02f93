"""Stable names for Pallas kernels in a device trace.

The TPU compiler names a custom call's instruction after the function
that encloses it, and a profiler trace's "XLA Ops" event carries the
instruction's name: a kernel called straight from a scan body reads
`%closed_call.N` there, one called inside `jax.jit(flash_attention_pallas)`
reads `%flash_attention_pallas.N`. So every kernel of the main paths sits
in a module-level `jax.jit` whose function has the kernel's stable name;
XLA inlines the nested call before it fuses, so the name is all that
changes in the compiled program, and one jitted object per kernel lets
JAX's trace cache serve every program that calls it at the same shapes
(a `jax.jit` made afresh at each call site cost the served warm-up 12 s
of kernel tracing: my chip runs, PR 24). Pass the same name as `name=`
to the `pallas_call` for Mosaic's own dumps.
"""
import jax


def named_jit(name: str, **jit_kwargs):
    """`jax.jit(fn, **jit_kwargs)` as a decorator, with the jitted
    function (and so the kernel's instruction) called `name` whatever
    the Python identifier is."""
    def deco(fn):
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn, **jit_kwargs)
    return deco
