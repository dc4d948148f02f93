"""paddle_tpu.nlp.ragged_attention — Pallas ragged paged-attention.

The serving decode path is gather/HBM-bound: `_paged_gqa_attention`
(nlp/paged.py) gathers the FULL block-table width per step in XLA —
every request pays `M * block_size` keys of HBM traffic no matter how
short its live sequence is, and BENCH shows decode ~25x below prefill
throughput because of it. This module is the kernel half of the fix
(design: "Ragged Paged Attention: A High-Performance and Flexible LLM
Inference Kernel for TPU", PAPERS.md, arxiv 2604.15464):

  * the grid is a WORK LIST (`attn_work_list`): one step for every
    chunk of `nb` consecutive chain blocks of every (request row, query
    tile) that has a valid query, as many steps as the list is long (a
    grid bound read on the device). The block table, the per-(row, tile)
    LIVE chain lengths and the list ride scalar prefetch — the BlockSpec
    index maps resolve each step's row and tile from them before the
    kernel body runs, and the body resolves the chunk's pool block ids
    and copies those blocks from the pools, left in HBM, into VMEM, one
    item ahead of the one it computes on, so the KV gather IS the
    kernel's own DMA (no XLA gather materializing [B, M*bs, KV, hd] in
    HBM); the query tile (`q_tile`, default 128) bounds VMEM residency
    so wide prefill buckets fit a core;
  * a padded / inactive row, or a tile whose queries are all padding, is
    never visited: a call's grid steps and HBM traffic track
    ceil(len/block_size) blocks of its live rows, not slots x table
    width, and a call with nothing live walks no step at all;
  * a flash-style online softmax (running max / sum / accumulator in
    VMEM scratch, carried across a (row, tile)'s consecutive items)
    starts at the tile's first chunk and finalizes at its last;
  * per-query causal masking (`key position j <= positions[row, p]`)
    matches the XLA path exactly, so the one kernel serves single-token
    decode rows, bucketed/chunked cached-prefix prefill rows, AND the
    mixed decode+prefill batch of the fused step — the Ragged Paged
    Attention mixed-mode shape. Invalid (padded) query rows produce
    zeros instead of the XLA path's never-read garbage.

Tensor parallel (ROADMAP direction 7): a `mesh=` kwarg runs the same
kernel under `shard_map` — each device executes the per-device
pallas_call on its contiguous head shard (GSPMD cannot partition a
pallas_call, but it can stitch per-shard kernel outputs on the head
axis), with the block table, the work list and dequant scales
replicated. Per-head math is shard-independent, so the sharded result
is bit-identical to the mesh-off kernel — the GSPMD-paper property
that sharded programs inherit single-device kernels.

The XLA gather path stays the reference implementation: CPU runs it by
default (`resolve_attention_impl("auto")`), and the parity suite
(tests/test_ragged_attention.py) pins pallas==xla on decode, prefill,
fused and prefix-cache-COW batches — on CPU via `interpret=True`, which
this wrapper selects automatically off-TPU.

int8 paged KV (ROADMAP direction 4, the PR 6 follow-on): when the pool
stores int8 codes, per-(layer, block) abs-max scales ride scalar
prefetch next to the block table and the kernel dequantizes each
copied block INSIDE the step, under its own scale (quantization.kv's
`dequantize`, the same math as the XLA path's after-the-gather
reference) — the gather-fused structure makes the dequant free, so a
quantized request's HBM traffic is its int8 block bytes, ~half the fp
bytes the unquantized chain moves.

The latent (MLA) kernel below walks the same kind of list, built by the
same helper.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..quantization import kv as kvq

__all__ = ["ragged_paged_attention", "mla_paged_attention",
           "resolve_attention_impl"]

_NEG_INF = -1e30


def resolve_attention_impl(impl: str) -> str:
    """Resolve an `attention_impl` choice to a concrete backend.

    "auto" picks "pallas" on TPU and "xla" everywhere else (the XLA
    gather path is the reference/fallback implementation and the only
    compiled path on CPU — pallas off-TPU runs in interpret mode, which
    is for parity testing, not speed). "pallas" and "xla" pass through;
    anything else raises ValueError.
    """
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(
            f"attention_impl must be 'auto', 'pallas' or 'xla', "
            f"got {impl!r}")
    return impl


def first_visible_block(positions, valid, window: int, bs: int, axis: int):
    """The chain block that holds the first key any valid query along
    `axis` may see under a window of `window` keys (its smallest valid
    position's window start); 0 where no query is valid. The kernel's
    walk starts there (per row and tile), the XLA twin's gather too (per
    row): one definition, so the two agree."""
    lo = jnp.min(jnp.where(valid, positions - (int(window) - 1),
                           jnp.iinfo(jnp.int32).max), axis=axis)
    return jnp.where(jnp.any(valid, axis=axis),
                     jnp.maximum(lo, 0) // bs, 0).astype(jnp.int32)


# ---------------------------------------------------------------------------
# the grid both kernels walk: a list of the live (row, query tile, chunk)
# items of a call and nothing else
# ---------------------------------------------------------------------------

# Pool blocks in flight a step, in two slots: what `nb` blocks of a chunk
# may take of a core's VMEM beside the q/o blocks and the softmax state.
_CHUNK_VMEM_BYTES = 4 << 20


def _attn_tiling(P: int, M: int, q_tile: int, blocks_per_step=None,
                 pools: int = 1, block_bytes: int = 0):
    """How a `[R, P]` call over a table `M` blocks wide is cut: (Pt
    queries a tile, T tiles a row, nb blocks a chunk, C chunks a table
    row). One place, for both kernels, so that the work list, the kernel
    and the host's count of the full grid agree.

    `nb` follows from the call's shapes alone, as the chip priced them
    (`tools/micro_attn.py`). A tile of queries (P > 1) takes 16 blocks a
    step in either kernel: its `[Pt * rows, nb * bs]` float32 scores
    bound it above, and below it a step's fixed work (the q block, the
    softmax state of hundreds of rows) is paid too often (8 blocks: 12%
    slower, PR 34). A decode call (P == 1) of the latent kernel (`pools`
    1: keys and values alias in one pool, every query head over its one
    KV head) takes 32, the chip pricing a step by its operands, about 50
    ns each (PR 30). The GQA kernel's (`pools` 2, a K and a V pool of KV
    heads) takes 8: its cost is a block's (each KV head's keys a strided
    load of every block), every slot of a chunk is moved and scored,
    live or clamped, and a wider chunk pays more for a chain's ragged
    end than it saves in steps (4 blocks: 30% slower at long contexts;
    16: 45% slower at 8-block chains and no faster at long ones, PR 34).
    `block_bytes` (one block of every pool) keeps a chunk's two slots of
    blocks inside `_CHUNK_VMEM_BYTES`; a table narrower than a chunk is
    one chunk (a ring of 97 blocks is thirteen decode chunks)."""
    if blocks_per_step is None:
        blocks_per_step = 16 if P > 1 else 32 if pools == 1 else 8
        if block_bytes:
            blocks_per_step = min(blocks_per_step,
                                  _CHUNK_VMEM_BYTES // (2 * block_bytes))
    nb = max(1, min(int(blocks_per_step), M))
    q_tile = max(1, min(q_tile, P))
    # largest divisor of P that fits the tile budget: bucketed widths
    # are powers of two, so this is q_tile itself for every P the
    # serving path produces; an awkward P (non-pow2 bucket caps, exact
    # unbucketed shapes) still tiles at its largest fitting divisor
    # rather than silently reverting to a VMEM-unbounded whole-row tile
    Pt = max(d for d in range(1, q_tile + 1) if P % d == 0)
    return Pt, P // Pt, nb, -(-M // nb)


def attn_grid_steps(R: int, P: int, M: int, q_tile: int = 16,
                    blocks_per_step=None, pools: int = 1,
                    block_bytes: int = 0) -> int:
    """The full grid of a `[R, P]` call: every (row, query tile, chunk),
    live or not. What a kernel walked before its grid was a work list
    (the GQA kernel one block a step, `nb` times as many), and the
    length the list's arrays have."""
    _, T, _, C = _attn_tiling(P, M, q_tile, blocks_per_step, pools,
                              block_bytes)
    return R * T * C


# Query-head rows (H * Pt) of one tile. A row of 128 lanes costs 20 bytes
# an element in VMEM: the q and o blocks in two slots each (bf16), the
# float32 accumulator, and the running max and sum, whose one column is
# padded to the 128 lanes. 4,608 rows are 11.25 MiB beside a chunk's 2 MiB
# of blocks and the scores, under a core's 16 MiB of scoped VMEM, which a
# step program also gives the call's small operands (6,144 rows, 128
# queries of 48 heads, compiled alone and not inside a prefill step: 17.71
# MiB of 16.75). 128 queries of up to 36 heads fit; of 48 or 72, 64 do.
_TILE_HEAD_ROWS = 4608


def gqa_tiling_args(pool_shape, pool_dtype, q_tile: int = 128,
                    heads=None) -> dict:
    """What `_attn_tiling` / `attn_grid_steps` / `attn_work_list` take
    for the GQA kernel over K and V pools `[N, bs, KV, hd]`: its query
    tile, its two pools, and a block's bytes in both. `heads` (the
    call's query heads, H) holds the tile to `_TILE_HEAD_ROWS` rows of
    query heads: the list and the kernel call must be given the same."""
    _, bs, KV, hd = pool_shape
    if heads:
        q_tile = max(1, min(q_tile, _TILE_HEAD_ROWS // int(heads)))
    return dict(q_tile=q_tile, pools=2,
                block_bytes=2 * bs * KV * hd * jnp.dtype(pool_dtype).itemsize)


class AttnWork(NamedTuple):
    """A kernel call's work list (`attn_work_list`)."""
    live: jax.Array      # [R, T] live BLOCKS of each (row, query tile)
    row: jax.Array       # [R*T*C] the row of work item i
    tile: jax.Array      # [R*T*C] its query tile
    chunk: jax.Array     # [R*T*C] its chunk of `nb` blocks
    count: jax.Array     # [] the items that are live work: the grid
    # [R, T] the chain block each (row, tile)'s walk starts at (a window
    # layer's); None where every walk starts at block 0
    first: Optional[jax.Array] = None


# one jitted object: every step program that builds a list at the same
# shapes shares one trace of it (kernels/naming.py does so for the kernels)
@functools.partial(jax.jit, static_argnames=(
    "block_size", "table_width", "q_tile", "blocks_per_step", "pools",
    "block_bytes", "window"))
def attn_work_list(positions, valid, *, block_size: int, table_width: int,
                   q_tile: int = 16, blocks_per_step=None, pools: int = 1,
                   block_bytes: int = 0, window=None) -> AttnWork:
    """The work of one kernel call, from what the call sees: for every
    (row, query tile) with a valid query, its `ceil(live_blocks / nb)`
    chunks, row-major, so that one (row, tile)'s chunks are consecutive
    and in order. The arrays have the static length of the full grid
    (`attn_grid_steps`); the first `count` entries are the list. It
    depends on neither the layer nor the pool, so a forward builds it
    once a row group (and layer kind) and every layer's call takes it
    (`work=`).

    `window` (W keys, a sliding-window layer's): a (row, tile)'s walk
    starts at the block of its first visible key (`first`) and `live`
    counts the blocks from there on, at most the table's width (a ring
    holds no more); chunk c block b is chain block first + c * nb + b."""
    R, P = positions.shape
    Pt, T, nb, C = _attn_tiling(P, table_width, q_tile, blocks_per_step,
                                pools, block_bytes)
    positions = positions.astype(jnp.int32).reshape(R, T, Pt)
    valid = valid.reshape(R, T, Pt)
    # valid query p needs chain keys up to position positions[r, p], all
    # written before the call — so a tile's walk stops at
    # ceil((its max valid position + 1) / bs)
    live_tok = jnp.max(jnp.where(valid, positions + 1, 0), axis=2)
    live = (live_tok + block_size - 1) // block_size
    first = None
    if window is not None:
        first = first_visible_block(positions, valid, window, block_size, 2)
        live = live - first
    live = jnp.minimum(live, table_width).astype(jnp.int32)
    chunks = ((live + nb - 1) // nb).reshape(R * T)
    ends = jnp.cumsum(chunks, dtype=jnp.int32)
    i = jnp.arange(R * T * C, dtype=jnp.int32)
    done = ends[None, :] <= i[:, None]       # the (row, tile)s before item i
    item = jnp.minimum(jnp.sum(done, axis=1, dtype=jnp.int32), R * T - 1)
    # where item i's (row, tile) starts: the largest end not past i
    start = jnp.max(jnp.where(done, ends[None, :], 0), axis=1)
    return AttnWork(live, item // T, item % T, i - start, ends[-1], first)


def gqa_work_list(positions, valid, table_width: int, pool_shape, pool_dtype,
                  *, window=None, slab: bool = False,
                  q_tile: int = 128, heads=None) -> AttnWork:
    """The list a `ragged_paged_attention` call over a K pool of
    `pool_shape` / `pool_dtype` (the GLOBAL pool's, under a mesh) and a
    table `table_width` wide walks for `positions` and `valid` [R, P]:
    what the call builds itself without `work=`. `slab`: the call scores
    a suffix slab too, so a tile whose valid queries see no pool key yet
    (position -1: an empty chain) still gets its one item. `heads`: the
    call's query heads (`gqa_tiling_args`)."""
    if slab:
        positions = jnp.maximum(positions, 0)
    return attn_work_list(positions, valid, block_size=pool_shape[1],
                          table_width=table_width, window=window,
                          **gqa_tiling_args(pool_shape, pool_dtype, q_tile,
                                            heads))


def _check_work(work: AttnWork, R: int, T: int, C: int, windowed: bool):
    if work.live.shape != (R, T) or work.row.shape != (R * T * C,) \
            or (work.first is not None) != windowed:
        raise ValueError(
            f"work list of {work.live.shape} tiles and {work.row.shape} "
            f"items ({'with' if work.first is not None else 'without'} a "
            f"window's start) does not fit a call of {(R, T)} tiles x {C} "
            f"chunks {'with' if windowed else 'without'} a window")


# ---------------------------------------------------------------------------
# GQA: K and V pools [N, bs, KV, hd]
# ---------------------------------------------------------------------------

def _rpa_kernel(*refs, bs: int, nb: int, M: int, scale: float,
                quantized: bool, suffix: bool = False, window=None,
                ring: bool = False):
    """One work item of the ragged kernel: a chunk of `nb` blocks of one
    (row, query tile).

    Every in-kernel value is 2-D with hd as its lane dim — the shapes
    the TPU compiler tiles: per kv head `kv`, G = Pt*rep query rows
    (query-major: row i is query i // rep, group member i % rep;
    `ragged_paged_attention` folds q that way around the call) against
    the chunk's [nb * bs, hd] keys, ONE strided load `kbuf[slot, :, kv]`
    of the buffer the chunk's blocks were copied into.

    Refs: tab/live/row/tile/chunk (scalar prefetch: the table and the
    work list), pos_ref/val_ref [1, 1, G, 1] int32 — this tile's per-row
    query positions / validity; q_ref [1, 1, KV, G, hd]; k_hbm/v_hbm
    [N, bs, KV, hd] — the POOLS, left where they are (`pl.ANY`); o_ref
    [1, 1, KV, G, hd]; scratch acc [KV, G, hd] f32, m/l [KV, G, 1] f32,
    kbuf/vbuf [2, nb * bs, KV, hd] (two slots of one chunk's keys and
    values, in the pool's type) and sem, DMA semaphores [K | V, slot].
    The kernel moves its own blocks: item i's `nb` K and `nb` V blocks,
    each resolved from the table here as an index map would, were
    started into slot i % 2 by the step before it (the first item's by
    itself), so a step waits for its own chunk, starts the next item's
    into the other slot and computes while that moves. (The pipeline's
    way, the pools as `nb` operands each with its own index map, moved
    the same bytes but cost the program's set-up: 16 index maps and 128
    block loads to trace and lower in each of a server's 31 programs,
    5 s of a 44 s warm start, PR 34.) A (row, tile)'s items are
    consecutive: its first starts the softmax state, its last writes the
    output. `live_ref` is per (row, tile): a tile's chain walk stops at
    ITS OWN last visible block, not the row's; a block slot of the chunk
    past it copies that last block again, whose keys the causal test
    then hides, their positions lying past every visible one.

    `quantized` adds ks_ref/vs_ref [N] f32 per-block dequant scales to
    the scalar prefetch: each block's codes dequantize under its own
    scale after the copy lands them in VMEM — the fused-dequant gather.

    `suffix` adds the speculative verify's in-register suffix slab:
    sk_ref/sv_ref [1, S, KV, hd] (this row's not-yet-committed K/V —
    the packed draft chain or tree) and svis_ref [1, 1, G, S] int32
    (per-row slab visibility: the chain's causal triangle or the tree's
    ancestor mask). A (row, tile)'s LAST item folds the slab's scores
    into the same online softmax after its pool blocks and finalizes
    there (the list gives every tile with a valid query at least one
    item, since slab visibility is independent of the pool chain
    length).

    `window` (W, a static int) bounds visibility from below as well: query
    p sees keys p - W < j <= p. The scalar prefetch then carries
    `first_ref` [R, T] after the list: the chain block that holds the
    tile's first visible key, where its walk starts; `live_ref` counts the
    blocks from there on, and chunk c block b is chain block first + c * nb
    + b (taken `% M` where the table is a `ring` of M blocks).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tab_ref, live_ref, row_ref, tile_ref, chunk_ref, *refs = refs
    first_ref = ks_ref = vs_ref = None
    if window is not None:
        first_ref, *refs = refs
    if quantized:
        ks_ref, vs_ref, *refs = refs
    pos_ref, val_ref, q_ref, k_hbm, v_hbm, *refs = refs
    if suffix:
        sk_ref, sv_ref, svis_ref, *refs = refs
    o_ref, acc_ref, m_ref, l_ref, kbuf, vbuf, sem = refs
    i = pl.program_id(0)
    r, t, c = row_ref[i], tile_ref[i], chunk_ref[i]
    nlive = live_ref[r, t]
    KV = q_ref.shape[2]
    G = pos_ref.shape[2]
    slot = i % 2

    pools = ((k_hbm, kbuf), (v_hbm, vbuf))

    def _blocks(j):
        # b -> the pool block that slot b of item j's chunk holds, as an
        # index map would resolve it: a slot past the tile's live chain
        # is its last live block
        rj, tj = row_ref[j], tile_ref[j]
        c0 = chunk_ref[j] * nb
        last = jnp.maximum(live_ref[rj, tj] - 1, 0)
        base = 0 if window is None else first_ref[rj, tj]

        def block(b):
            x = jnp.minimum(c0 + b, last) + base
            return jnp.maximum(tab_ref[rj, x % M if ring else x], 0)
        return block

    def _start(j, slot):
        # item j's 2 * nb block copies into `slot`, under way
        block = _blocks(j)

        def one(b, _):
            for s, (hbm, buf) in enumerate(pools):
                pltpu.make_async_copy(
                    hbm.at[block(b)], buf.at[slot, pl.ds(b * bs, bs)],
                    sem.at[s, slot]).start()
            return _
        jax.lax.fori_loop(0, nb, one, 0)

    pl.when(i == 0)(lambda: _start(i, slot))
    pl.when(i + 1 < pl.num_programs(0))(lambda: _start(i + 1, 1 - slot))

    def _fold(kv, k, v, vis):
        # one kv head's G query rows against T keys k/v [T, hd] f32:
        # scores, then the flash-style online-softmax update of that
        # head's running max / sum / accumulator
        q = q_ref[0, 0, kv].astype(jnp.float32) * scale       # [G, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(vis, s, _NEG_INF)                       # [G, T]
        m_prev = m_ref[kv]                                    # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # exp(s - m) alone is 1.0 for fully-masked rows (s == m ==
        # _NEG_INF) — the explicit vis select keeps them at zero
        p = jnp.where(vis, jnp.exp(s - m_new), 0.0)
        l_ref[kv] = l_ref[kv] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[kv] = acc_ref[kv] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[kv] = m_new

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # per-row causal visibility at ABSOLUTE key position (chain position,
    # not pool position), masked by query validity so padded rows
    # accumulate nothing
    kpos = c * (nb * bs) + jax.lax.broadcasted_iota(
        jnp.int32, (G, nb * bs), 1)
    if window is not None:
        kpos = kpos + first_ref[r, t] * bs
    vis = (kpos <= pos_ref[0, 0]) & (val_ref[0, 0] != 0)      # [G, nb*bs]
    if window is not None:
        vis = vis & (kpos > pos_ref[0, 0] - window)

    k_scale = v_scale = None
    if quantized:
        # each key row's dequant scale, its block's: [nb * bs, 1]
        at = jax.lax.broadcasted_iota(jnp.int32, (nb * bs, 1), 0) // bs
        block = _blocks(i)

        def scales(b, ksvs):
            blk = block(b)
            return (jnp.where(at == b, ks_ref[blk], ksvs[0]),
                    jnp.where(at == b, vs_ref[blk], ksvs[1]))
        zero = jnp.zeros((nb * bs, 1), jnp.float32)
        k_scale, v_scale = jax.lax.fori_loop(0, nb, scales, (zero, zero))

    def _wait(b, _):
        # (to wait for a copy, any block id describes the same bytes)
        for s, (hbm, buf) in enumerate(pools):
            pltpu.make_async_copy(hbm.at[0], buf.at[slot, pl.ds(b * bs, bs)],
                                  sem.at[s, slot]).wait()
        return _
    jax.lax.fori_loop(0, nb, _wait, 0)

    def _keys(buf, scales, kv):
        # the chunk's keys (or values) of one kv head, [nb * bs, hd] f32;
        # an int8 pool's codes dequantize under their blocks' prefetched
        # scales: the same quantization.kv math the XLA path applies
        # after its gather
        x = buf[slot, :, kv, :]
        return kvq.dequantize(x, scales) if quantized \
            else x.astype(jnp.float32)

    for kv in range(KV):
        _fold(kv, _keys(kbuf, k_scale, kv), _keys(vbuf, v_scale, kv), vis)

    @pl.when(c == (nlive + nb - 1) // nb - 1)
    def _finalize():
        if suffix:
            # the tile's last item: fold the suffix slab's scores into
            # the SAME online softmax. Slab rows are full precision
            # (verify-then-commit: these K/V have not been quantized or
            # committed yet), visibility is the per-row slab mask AND
            # query validity.
            svis = (svis_ref[0, 0] != 0) & (val_ref[0, 0] != 0)  # [G, S]
            for kv in range(KV):
                _fold(kv, sk_ref[0, :, kv, :].astype(jnp.float32),
                      sv_ref[0, :, kv, :].astype(jnp.float32), svis)
        l = l_ref[...]
        o = acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = o.astype(o_ref.dtype)


def _shard_specs(mesh_axis: str, nwork: int, quantized: bool, suffix: bool):
    """PartitionSpecs for `shard_map`-wrapping the kernel on a 1-D mesh.

    Positional layout mirrors the pallas_call argument order: scalar
    prefetch first (table, the `nwork` arrays of the work list[, k_scale,
    v_scale] — all REPLICATED: every shard walks the same items of the
    same block chains under the same per-block dequant scales), then the
    list's count and positions/val (replicated), then the head-carrying
    operands q, k_pool, v_pool[, suffix_k, suffix_v] split on their head
    axis (dim 2 for all five), then suffix_vis (replicated — visibility
    is a per-query/per-slab-row fact, not a per-head one). The output
    activation [R, P, H, hd] splits on the same head axis.
    """
    from jax.sharding import PartitionSpec as P

    repl = P()
    head = P(None, None, mesh_axis, None)
    specs = (repl,) * (1 + nwork + (2 if quantized else 0) + 3)
    specs += (head, head, head)
    if suffix:
        specs += (head, head, repl)
    return specs, head


# jitted under its own name: the kernel's instruction, and so its event
# in a device trace, is named after the function that encloses the custom
# call (kernels/naming.py), and one jitted object lets every step program
# that calls it at the same shapes share one trace of the kernel
@functools.partial(jax.jit, static_argnames=("q_tile", "interpret", "mesh",
                                             "mesh_axis", "window", "ring"))
def ragged_paged_attention(q, k_pool, v_pool, table, positions, valid=None,
                           *, k_scale=None, v_scale=None,
                           suffix_k=None, suffix_v=None, suffix_vis=None,
                           q_tile: int = 128, interpret=None,
                           mesh=None, mesh_axis: str = "mp",
                           window=None, ring: bool = False, work=None):
    """Paged GQA attention walking only each request's live block chain.

    Drop-in twin of the XLA `_paged_gqa_attention` gather path
    (nlp/paged.py) with the same per-query-causal semantics:

      q [R, P, H, hd]; k_pool/v_pool [N, bs, KV, hd]; table [R, M] int32
      pool block ids per row; positions [R, P] int32 absolute query
      positions (query p sees chain keys j <= positions[r, p]);
      valid [R, P] bool query mask (None = all valid). Returns
      [R, P, H, hd] in q's dtype; INVALID queries return zeros (the XLA
      path leaves never-read garbage there).

    The grid is a WORK LIST (`attn_work_list`, built here unless the
    caller hands in the one it built for these positions, valid and
    window: `work=`; `gqa_work_list` builds it as this call would): one
    step for every chunk of `nb` blocks of every (row, query tile) that
    has a valid query, as many steps as the list is long (a grid bound
    read on the device), every index map resolving row, tile and chunk
    through the prefetched list. A row or tile with no valid query
    (padded slot, inactive decode row of the fused batch, all-pad bucket
    tail) is never visited: its q block is not moved, its output not
    written (the select below zeroes it), and a call with nothing live
    walks no step at all. The pools stay in HBM (`pl.ANY`): the kernel
    copies each item's `nb` K and `nb` V blocks itself, resolved from
    the prefetched table, into one of two VMEM slots, the next item's
    while it computes on this one's, so a step moves that many blocks
    and its overhead is paid once for them (`_attn_tiling`: 8 blocks a
    decode step, 16 a prefill tile's, fewer where the table is narrower
    or a block larger); past a
    tile's live chain a slot holds its last live block again.

    k_scale/v_scale [N] f32 mark an int8 pool (kv_dtype="int8"): the
    per-block abs-max scales ride scalar prefetch next to the table and
    each block's codes dequantize INSIDE the step, right after the
    copy lands them — the gather moves int8 bytes, the dequant is fused
    compute, so a quantized request's HBM traffic is ~half its fp block
    bytes.

    The query dimension tiles at the largest divisor of P that is
    <= `q_tile` rows per grid step (q_tile itself for the serving
    path's power-of-two buckets, held to `_TILE_HEAD_ROWS` rows of query
    heads: 64 queries a tile at 48 or 72 heads; worst case 1 for a prime P,
    which trades grid overhead for the VMEM bound), bounding VMEM residency
    — scratch + q/o blocks scale with the TILE, not the full prefill
    bucket width, so a 512-wide bucket at production head counts still
    fits a core's VMEM. Per (row, tile) live chain lengths —
    ceil((max valid position in the tile + 1) / bs) — make an early
    tile of a long suffix stop at its own last visible block.

    suffix_k/suffix_v [R, S, KV, hd] add the speculative verify's
    in-register suffix slab (the packed draft chain or tree — K/V that
    exist ONLY in registers until the accepted path commits) as a
    kernel operand: a (row, tile)'s last item folds the slab's scores
    into the same online softmax after its pool blocks, so the pool
    sweep stays the int8-gathered block loop instead of falling back to
    the XLA concat path; a row whose pool chain is still empty gets one
    item all the same. suffix_vis [R, P, S] (bool/int) gives each query
    its visible slab rows — the chain's causal triangle or the tree's
    ancestor mask; invalid queries still emit zeros. The XLA formulation
    in `paged._spec_gqa_attention` stays the bit-stable parity reference.

    `mesh` (a 1-D jax.sharding.Mesh over axis `mesh_axis`) runs the
    kernel tensor-parallel: GSPMD cannot partition a pallas_call, so
    the call is wrapped in `shard_map` with q/k_pool/v_pool (and the
    suffix slab) split on their head axis and everything else — block
    table, work list, positions, validity, dequant scales, slab
    visibility — replicated. Each device runs THIS kernel on its
    contiguous head shard: per-shard H/tp query heads keep the same
    GQA group size rep = H/KV, and local head h maps to local kv head
    h // rep exactly as the global mapping does (the serving mesh's
    contiguous-shard convention, serving/tp.py), so every head's math
    is untouched and the head-axis concatenation makes the sharded
    result BIT-identical to the mesh-off kernel (the chunk, too, is cut
    from the GLOBAL pool's block, so every shard walks the list the
    mesh-off call walks). Requires H and KV divisible by the mesh axis
    size.

    `window` (W, static; None = a layer that keeps every key) makes a
    layer a SLIDING-WINDOW layer: query p sees keys p - W < j <= p, W
    keys with its own. A (row, tile)'s chain walk then starts at the
    block that holds its first visible key, not at block 0, and spans
    the blocks from there to its last one, both in the list. `ring` says
    that the row's table is a RING of M blocks: chain block m lives in
    `table[r, m % M]`, so a sequence holds M blocks however long it
    grows (the caller keeps M * bs at least the window plus the widest
    chunk it writes before attending). Not built with an int8 pool, a
    suffix slab or a mesh. In a device trace the window form's events
    read `%ragged_window_attention.N`.

    `interpret=None` auto-selects Pallas interpret mode off-TPU — the
    CPU CI parity path. Tolerance vs XLA is tight-but-not-bitwise: the
    online softmax reassociates the reduction.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    R, P, H, hd = q.shape
    N, bs, KV, _ = k_pool.shape
    M = table.shape[1]
    if valid is None:
        valid = jnp.ones((R, P), bool)
    positions = positions.astype(jnp.int32)
    table = table.astype(jnp.int32)

    quantized = k_scale is not None
    suffix = suffix_k is not None
    windowed = window is not None
    if (windowed or ring) and (quantized or suffix or mesh is not None):
        raise NotImplementedError(
            "ragged_paged_attention: a window or a ring table with an int8 "
            "pool, a suffix slab or a mesh is not built")
    if ring and not windowed:
        raise ValueError("a ring table holds a window's keys: give `window`")
    Pt, T, nb, C = _attn_tiling(
        P, M, **gqa_tiling_args(k_pool.shape, k_pool.dtype, q_tile, H))
    if work is None:
        work = gqa_work_list(positions, valid, M, k_pool.shape, k_pool.dtype,
                             window=window, slab=suffix, q_tile=q_tile,
                             heads=H)
    _check_work(work, R, T, C, windowed)

    def _tile_map(i, tab, live, row, tile, chunk, *rest):
        return (row[i], tile[i], 0, 0)

    def _tile_head_map(i, tab, live, row, tile, chunk, *rest):
        return (row[i], tile[i], 0, 0, 0)

    def _suffix_map(i, tab, live, row, *rest):
        # the row's whole slab, fetched once per (row, tile)
        return (row[i], 0, 0, 0)

    scal = [table, work.live, work.row, work.tile, work.chunk]
    if windowed:
        scal.append(work.first)
    nwork = len(scal) - 1
    if quantized:
        scal += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    nscal = len(scal)
    args = scal + [work.count, positions, valid.astype(jnp.int32), q,
                   k_pool, v_pool]
    if suffix:
        S = suffix_k.shape[1]
        args += [suffix_k, suffix_v, suffix_vis.astype(jnp.int32)]

    def _kernel_call(*ops):
        # per-device body: head counts come from the LOCAL operand
        # shapes — under shard_map each device sees its contiguous head
        # shard (H/tp query heads, KV/tp kv heads, same rep = H/KV), so
        # the kernel body and every index map run unchanged; mesh-off,
        # the local shapes ARE the global ones
        scal, (count, pos_l, val_l, q_l, kp_l, vp_l, *suf) = \
            ops[:nscal], ops[nscal:]
        Hl, KVl = q_l.shape[2], kp_l.shape[2]
        rep = Hl // KVl
        G = Pt * rep

        # The TPU compiler tiles the two minor dims of every block and
        # refuses the reshape that would split heads into (KV, rep)
        # inside the kernel, so q is folded here: each kv head's rep
        # query heads ride the query ROW axis ([R, T, KV, Pt*rep, hd],
        # query-major) and the per-query operands repeat rep times to
        # match. The pool keeps its own layout — no copy of it is made.
        def _rows(x):
            # [R, P, *f] per query -> [R, T, G, *f] per kernel row
            f = x.shape[2:]
            x = jnp.broadcast_to(x.reshape(R, T, Pt, 1, *f),
                                 (R, T, Pt, rep, *f))
            return x.reshape(R, T, G, *f)

        ops = [*scal, _rows(pos_l[:, :, None]), _rows(val_l[:, :, None]),
               q_l.reshape(R, T, Pt, KVl, rep, hd)
                  .transpose(0, 1, 3, 2, 4, 5).reshape(R, T, KVl, G, hd),
               kp_l, vp_l]
        in_specs = [
            pl.BlockSpec((1, 1, G, 1), _tile_map),
            pl.BlockSpec((1, 1, G, 1), _tile_map),
            pl.BlockSpec((1, 1, KVl, G, hd), _tile_head_map),
            # the pools stay in HBM: the kernel copies its own blocks
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        if suffix:
            sk_l, sv_l, svis_l = suf
            ops += [sk_l, sv_l, _rows(svis_l)]
            in_specs += [
                pl.BlockSpec((1, S, KVl, hd), _suffix_map),
                pl.BlockSpec((1, S, KVl, hd), _suffix_map),
                pl.BlockSpec((1, 1, G, S), _tile_map),
            ]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            # the table, the work list (and an int8 pool's per-block
            # dequant scales) are prefetched so the index maps and the
            # kernel body read them from SMEM
            num_scalar_prefetch=nscal,
            grid=(count,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, KVl, G, hd), _tile_head_map),
            scratch_shapes=[
                pltpu.VMEM((KVl, G, hd), jnp.float32),
                pltpu.VMEM((KVl, G, 1), jnp.float32),
                pltpu.VMEM((KVl, G, 1), jnp.float32),
                pltpu.VMEM((2, nb * bs, KVl, hd), kp_l.dtype),
                pltpu.VMEM((2, nb * bs, KVl, hd), vp_l.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        )
        call = pl.pallas_call(
            functools.partial(_rpa_kernel, bs=bs, nb=nb, M=M,
                              scale=1.0 / math.sqrt(hd),
                              quantized=quantized, suffix=suffix,
                              window=window, ring=ring),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((R, T, KVl, G, hd), q.dtype),
            interpret=interpret,
            # the window form under a name of its own, which its events
            # in a device trace carry: the two kinds of layer of one
            # decoder are told apart at a glance
            name="ragged_window_attention" if windowed
            else "ragged_paged_attention",
        )
        # the package enables jax_enable_x64 globally; traced with it on,
        # the weak-typed float constants and index math lower as 64-bit,
        # which the TPU compiler refuses
        with jax.enable_x64(False):
            o = call(*ops)
        o = o.reshape(R, T, KVl, Pt, rep, hd) \
            .transpose(0, 1, 3, 2, 4, 5).reshape(R, P, Hl, hd)
        # what the list never visited was never written
        return jnp.where(val_l[:, :, None, None] != 0, o,
                         jnp.zeros((), q.dtype))

    if mesh is None:
        return _kernel_call(*args)
    size = mesh.shape[mesh_axis]
    if H % size or KV % size:
        raise ValueError(
            f"head counts (H={H}, KV={KV}) must divide the mesh axis "
            f"{mesh_axis!r} size {size} to shard the ragged kernel")
    # check_vma=False: pallas_call has no replication rule; the specs
    # above are the ground truth
    in_specs, out_spec = _shard_specs(mesh_axis, nwork, quantized, suffix)
    return jax.shard_map(_kernel_call, mesh=mesh, in_specs=in_specs,
                         out_specs=out_spec, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# latent (MLA) mode: one KV head, every query head over it, keys the whole
# cached row, values its first columns, each row read once; the same kind
# of list as above
# ---------------------------------------------------------------------------

def _mla_kernel(*refs, bs: int, nb: int, scale: float, v_width: int):
    """One work item of the latent kernel: a chunk of `nb` blocks of one
    (row, query tile). Refs: tab/live/row/tile/chunk (scalar prefetch),
    pos_ref/val_ref [1, 1, G, 1] int32, q_ref [1, 1, G, W] (G = Pt*H
    query rows, query-major), `nb` k_refs [1, bs, W] (the chunk's pool
    blocks, each resolved from the table by its own index map: the SAME
    pool array `nb` times over), o_ref [1, 1, G, v_width]; scratch acc
    [G, v_width], m/l [G, 1] f32. A (row, tile)'s items are consecutive:
    its first starts the softmax state, its last writes the output.
    Scores contract the full row width W; values are columns
    [0, v_width) of the same block in VMEM: keys and values alias, so a
    cached row crosses HBM once. Both dots take the operands in their
    stored type and accumulate in float32."""
    import jax.experimental.pallas as pl

    tab_ref, live_ref, row_ref, tile_ref, chunk_ref = refs[:5]
    pos_ref, val_ref, q_ref = refs[5:8]
    k_refs = refs[8:8 + nb]
    o_ref, acc_ref, m_ref, l_ref = refs[8 + nb:]
    i = pl.program_id(0)
    c = chunk_ref[i]
    nlive = (live_ref[row_ref[i], tile_ref[i]] + nb - 1) // nb  # live CHUNKS

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    G = pos_ref.shape[2]
    k = jnp.concatenate([kr[0] for kr in k_refs], axis=0)    # [nb*bs, W]
    # a block slot past the tile's live chain re-reads the last live
    # block (the index map clamps): its key positions lie past every
    # visible one, so the causal test hides it
    kpos = c * (nb * bs) + jax.lax.broadcasted_iota(
        jnp.int32, (G, nb * bs), 1)
    vis = (kpos <= pos_ref[0, 0]) & (val_ref[0, 0] != 0)
    s = jax.lax.dot_general(q_ref[0, 0], k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(vis, s, _NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(vis, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(k.dtype), k[:, :v_width],
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(c == nlive - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "v_width", "q_tile",
                                             "blocks_per_step", "interpret"))
def mla_paged_attention(q, pool, table, positions, valid=None, *,
                        scale: float, v_width: int, q_tile: int = 16,
                        blocks_per_step=None, interpret=None, work=None):
    """Latent paged attention, the absorbed form of MLA (nlp/mla.py):

      q [R, P, H, W] (each head's query carried into the latent space,
      its rope columns last); pool [N, bs, W] one row `[c | k_r]` a
      cached token; table [R, M]; positions / valid [R, P] as in
      `ragged_paged_attention`. Returns [R, P, H, v_width]: softmax over
      the visible keys of `scale * q . row`, times the rows' first
      `v_width` columns. Invalid queries return zeros.

    The grid is a WORK LIST (`attn_work_list`, built here unless the
    caller hands in the one it built for these positions and valid:
    `work=`): one step for every chunk of `blocks_per_step` blocks of
    every (row, query tile) that has a valid query, as many steps as the
    list is long (a grid bound read on the device), every index map
    resolving row, tile and chunk through the prefetched list. A row or
    tile with no valid query is never visited: its q block is not moved,
    its output not written (the select below zeroes it), and a call with
    nothing live walks no step at all. The one KV head is shared by all
    H query heads, so a tile of Pt queries is Pt*H kernel rows against
    each block (`q_tile` = Pt bounds VMEM: scratch and the q/o blocks
    grow with Pt*H). The pool goes in `blocks_per_step` times, each
    operand's index map resolving one block of the chunk from the
    prefetched table, so a step moves that many blocks and the per-step
    overhead is paid once for them; past a tile's live chain the maps
    clamp to its last live block. From the chip: a grid step costs its
    operands, about 50 ns each (PR 30: a `[64, 1]` call walking all 256
    steps of 35 operands took 0.455 ms with no row live, 1.8 us a step,
    and half and a quarter of that at 32 and 16 slots; PR 26: 1024
    steps of 11 operands 0.47 ms), so a decode call (P = 1) takes 32
    blocks a step and, since PR 30, only its live steps: 4 us with none
    live, then 3 us an item (a step of 32 blocks), 19 us at the served
    cell's 3 rows live where it was 455; with every row live and at
    full context it costs what the full grid did (0.744 against 0.737
    ms). Prefill rows take 16 queries x 16 blocks (32 x 16 x H rows of
    float32 scores do not fit the kernel's VMEM)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    R, P, H, W = q.shape
    N, bs, _ = pool.shape
    M = table.shape[1]
    Pt, T, nb, C = _attn_tiling(P, M, q_tile, blocks_per_step)
    G = Pt * H
    if valid is None:
        valid = jnp.ones((R, P), bool)
    positions = positions.astype(jnp.int32)
    table = table.astype(jnp.int32)
    if work is None:
        work = attn_work_list(positions, valid, block_size=bs,
                              table_width=M, q_tile=q_tile,
                              blocks_per_step=blocks_per_step)
    _check_work(work, R, T, C, False)

    def _rows(x):
        # [R, P] per query -> [R, T, G, 1] per kernel row (query-major)
        x = jnp.broadcast_to(x.reshape(R, T, Pt, 1), (R, T, Pt, H))
        return x.reshape(R, T, G, 1)

    def _tile_map(i, tab, live, row, tile, chunk):
        return (row[i], tile[i], 0, 0)

    def _kv_map(b):
        def index(i, tab, live, row, tile, chunk):
            r = row[i]
            j = jnp.minimum(chunk[i] * nb + b,
                            jnp.maximum(live[r, tile[i]] - 1, 0))
            return (jnp.maximum(tab[r, j], 0), 0, 0)
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(work.count,),
        in_specs=[pl.BlockSpec((1, 1, G, 1), _tile_map),
                  pl.BlockSpec((1, 1, G, 1), _tile_map),
                  pl.BlockSpec((1, 1, G, W), _tile_map)]
        + [pl.BlockSpec((1, bs, W), _kv_map(b)) for b in range(nb)],
        out_specs=pl.BlockSpec((1, 1, G, v_width), _tile_map),
        scratch_shapes=[pltpu.VMEM((G, v_width), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32)])
    call = pl.pallas_call(
        functools.partial(_mla_kernel, bs=bs, nb=nb, scale=float(scale),
                          v_width=v_width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, T, G, v_width), q.dtype),
        interpret=interpret, name="mla_paged_attention")
    with jax.enable_x64(False):
        o = call(table, work.live, work.row, work.tile, work.chunk,
                 _rows(positions), _rows(valid.astype(jnp.int32)),
                 q.reshape(R, T, G, W), *([pool] * nb))
    # what the list never visited was never written
    return jnp.where(valid[:, :, None, None], o.reshape(R, P, H, v_width),
                     jnp.zeros((), q.dtype))
