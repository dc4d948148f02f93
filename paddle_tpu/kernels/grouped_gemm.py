"""Grouped GEMM over a work list, for the served expert layer
(`nlp/moe.py::expert_share_ffn`): rows sorted by expert against ONE
layer's experts addressed inside the stack of all layers'.

`lax.ragged_dot` takes the stack as its groups, which costs it nothing,
but every HIT expert pays it a row tile of the chip's own choosing: five
times its bytes' time at Mellum2's widths (PERF.md section 6, PR 37).
Here the grid is a list of the live (row tile, hit expert) items, built
on the device from the rows on each expert (as
`ragged_attention.attn_work_list` is for the attention kernels), the
weight block of an item is read in place from the stack,
`w[base + expert]`, and the row tile is this module's own. The design is
that of JAX's `pallas.ops.tpu.megablox.gmm` (group metadata, live tiles
only), which has no offset into the weights.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .naming import named_jit

# what one weight block may take of VMEM (the pipeline holds two): a
# Mellum2 expert's matrix (2304 x 896 bf16, 4.13 MB) goes whole, an
# A.X-K1 one (7168 x 2048, 29 MB) in column slabs of [7168, 512] and
# [2048, 1792]; blocks of at most 4 MiB read 4% slower there and of 16
# MiB 1% (`tools/micro_moe.py share --tiling`, PERF.md section 6, PR 37)
_W_BLOCK_BYTES = 8 << 20
_ROW_TILE = 128
# two weight blocks, two row tiles and two output tiles, with room: the
# compiler's default of 16 MiB would not hold A.X-K1's
_VMEM_LIMIT_BYTES = 40 << 20


class GemmWork(NamedTuple):
    """A grouped GEMM's work list (`gemm_work_list`)."""
    # [4, L] int32, item i: its row tile, its group in the stack (base +
    # expert), the expert's first row and the row after its last
    items: jax.Array
    count: jax.Array        # [] the items that are live work: the grid


def _row_tile(S: int) -> int:
    """Rows of a tile of a buffer of `S` sorted rows: 128 at most. An item
    multiplies a whole tile whatever it owns of it, and a tile of 128
    costs the MXU little more than one of 32 (the weights' pass through
    it is what takes the time): on the chip tiles of 32 and 64 read 3-4%
    faster where a decode step hits 8 experts of 2304 x 896 with a row
    each and 4-6% slower where a prefill chunk gives each of 64 experts
    68 rows, tiles of 256 the reverse (PERF.md section 6, PR 37)."""
    return min(S, _ROW_TILE)


def _gemm_tiling(S: int, K: int, N: int, itemsize: int):
    """(tm, tn) of a call over `S` sorted rows against [K, N] matrices:
    `_row_tile` rows and the widest column slab, a multiple of 128 that
    divides N, whose [K, tn] block stays under `_W_BLOCK_BYTES`. K is
    never cut: an item's result is one dot, and consecutive items of one
    expert find its block in VMEM."""
    tn = N
    if N % 128 == 0:
        fits = [t for t in range(128, N + 1, 128)
                if N % t == 0 and K * t * itemsize <= _W_BLOCK_BYTES]
        tn = fits[-1] if fits else 128
    return _row_tile(S), tn


def gemm_items(S: int, n: int) -> int:
    """Static length of the list of a call over `S` rows and `n` experts:
    every row tile once, and once more for each further expert that
    starts inside it."""
    return -(-S // _row_tile(S)) + n - 1


# one jitted object: every step program that builds a list at the same
# shapes shares one trace of it
@functools.partial(jax.jit, static_argnames=("rows",))
def gemm_work_list(sizes, base, *, rows: int) -> GemmWork:
    """The work of the grouped GEMMs over a buffer of `rows` sorted rows
    of which the first sum(sizes) belong to the experts, `sizes` [n] rows
    each in order: one item for every (row tile, expert with rows) that
    overlap, by expert and within an expert by tile, so that a tile two
    experts share is visited once for each, consecutively, and an
    expert's items are consecutive. An expert without rows is in no
    item. `base` (int32 scalar) is where this layer's experts start in
    the stack. The list has the static length `gemm_items`; the first
    `count` entries are live. It does not depend on the matrices: a pass
    builds it once for its gate, up and down GEMMs."""
    # lax primitives throughout: every jnp function is a nested jit, and
    # each step program lowers each of them anew (warm set-up, PERF.md
    # section 6, PR 37)
    n = sizes.shape[0]
    tm = _row_tile(rows)
    L = gemm_items(rows, n)
    i32 = jnp.int32
    sizes = lax.convert_element_type(sizes, i32)
    ends = lax.cumsum(sizes)
    tiles = lax.select(
        sizes > 0,
        lax.div(ends - 1, i32(tm)) - lax.div(ends - sizes, i32(tm)) + 1,
        lax.full_like(sizes, 0))
    item_ends = lax.cumsum(tiles)
    i = lax.iota(i32, L)

    def across(v):              # [n] along the items
        return lax.broadcast_in_dim(v, (L, n), (1,))

    # the experts before item i
    done = across(item_ends) <= lax.broadcast_in_dim(i, (L, n), (0,))
    e = lax.min(lax.reduce_sum(lax.convert_element_type(done, i32), [1]),
                i32(n - 1))
    # `ends` does not fall, so the expert's first row is the largest end
    # before it and its last the smallest end from it on
    zero = lax.full((L, n), 0, i32)
    lo = lax.reduce_max(lax.select(done, across(ends), zero), [1])
    hi = lax.reduce_min(
        lax.select(done, lax.full((L, n), rows, i32), across(ends)), [1])
    first = lax.reduce_max(lax.select(done, across(item_ends), zero), [1])
    tile = lax.min(lax.div(lo, i32(tm)) + i - first,
                   i32(-(-rows // tm) - 1))
    base = lax.convert_element_type(base, i32)
    items = lax.concatenate(
        [lax.expand_dims(v, [0]) for v in (tile, base + e, lo, hi)], 0)
    return GemmWork(items, item_ends[-1])


def _gemm_kernel(items_ref, x_ref, w_ref, o_ref):
    """One item: the row tile `x_ref` [tm, K] times the expert's column
    slab `w_ref` [K, tn], stored to the rows of `o_ref` [tm, tn] that are
    the expert's own. The tile's other rows keep what the experts before
    wrote there (a tile's items are consecutive, so its block stays in
    VMEM between them)."""
    import jax.experimental.pallas as pl

    i = pl.program_id(1)
    tm = o_ref.shape[0]
    row = items_ref[0, i] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, 1), 0)
    mine = (row >= items_ref[2, i]) & (row < items_ref[3, i])
    y = jnp.dot(x_ref[...], w_ref[...],
                preferred_element_type=jnp.promote_types(o_ref.dtype,
                                                         jnp.float32))
    o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


def grouped_gemm(rows, w, sizes, base, *, work=None, interpret=None):
    """rows [S, K] sorted by expert, w [G, K, N] (the layers' stack, its
    experts as groups), `sizes` [n] the rows on each of this layer's
    experts, `base` (int32 scalar) the layer's first group in the stack
    -> [S, N] in the rows' dtype, float32 accumulation: what
    `lax.ragged_dot` gives for the rows inside an expert. A row past the
    experts' sum, or in a tile that no item visits, is never written and
    means nothing. `work`: the list `gemm_work_list` built for these
    sizes, base and rows, where the caller has it (a pass's three GEMMs
    walk the same one); built here otherwise.

    The grid is (column slabs, the list's live items): an item's weight
    block is `w[base + expert, :, slab]`, read in place from the stack,
    once for an expert's consecutive items; a call whose list is empty
    runs no step. `interpret=None` picks Pallas interpret mode off the
    TPU, here and not under the jit, whose trace is shared by shapes."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S, n = rows.shape[0], sizes.shape[0]
    if work is None:
        work = gemm_work_list(sizes, base, rows=S)
    if work.items.shape != (4, gemm_items(S, n)):
        raise ValueError(f"work list of {work.items.shape} does not fit a "
                         f"call over {S} rows and {n} experts")
    return _grouped_gemm(rows, w, work, interpret=interpret)


@named_jit("grouped_gemm", static_argnames=("interpret",))
def _grouped_gemm(rows, w, work, *, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, K = rows.shape
    N = w.shape[2]
    tm, tn = _gemm_tiling(S, K, N, w.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N // tn, work.count),
        in_specs=[
            pl.BlockSpec((tm, K), lambda j, i, items: (items[0, i], 0)),
            pl.BlockSpec((None, K, tn),
                         lambda j, i, items: (items[1, i], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, items: (items[0, i], j)),
    )
    call = pl.pallas_call(
        _gemm_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, N), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret, name="grouped_gemm")
    # traced with jax_enable_x64 on, index math lowers as 64-bit, which
    # the TPU compiler refuses (the interpreter takes either, and float64
    # rows where a test hands it them)
    with jax.enable_x64(interpret and jax.config.jax_enable_x64):
        return call(work.items, rows, w)
