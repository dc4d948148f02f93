"""Paged KV-cache serving: block-table cache + ragged batch admission.

Reference analog: the fused block_multihead_attention op
(paddle.incubate.nn.functional — upstream-canonical, unverified,
SURVEY.md §0) and PaddleNLP serving's block-table KV cache, which admit
ragged request batches against one shared block pool instead of padding
every request to T_max (VERDICT r4 missing 2).

TPU-native design: everything on device is STATIC-shape —
  * the pool is one [L, N_blocks, block_size, KV, hd] tensor pair shared
    by every request; a request holds ceil(len/block_size) blocks, so
    pool memory tracks the SUM of actual lengths, not B x T_max;
  * the block table [B, M] (M = table width) and per-request lengths [B]
    are device arrays; cache reads gather pool blocks through the table,
    cache writes scatter through it (drop-mode for padded slots);
  * per-request positions ride the whole compiled path — requests at
    DIFFERENT lengths decode in one batch (the dense nlp.generation path
    requires a common position);
  * block allocation/free is host-side (BlockAllocator below) — the
    reference does the same (its block tables are built by the serving
    layer, not the kernel);
  * the indirection makes KV sharing free: with prefix caching on
    (RefcountingBlockAllocator + serving.cache.PrefixCacheIndex),
    several requests' table rows name the same pool blocks for a shared
    prompt prefix, and prefill runs only on each request's suffix.
The attention here is the exact grouped-GQA formulation (generation.
_gqa_cached_attention's paged twin) with TWO interchangeable backends:
the XLA gather path below (reference — gathers the full table width,
bit-stable, the CPU default) and the Pallas ragged paged-attention
kernel (ragged_attention.py — walks only each request's LIVE block
chain, the TPU default; `attention_impl=` selects, "auto" resolves per
backend).
"""
from __future__ import annotations

import contextlib
import math
import time
from collections import OrderedDict
from typing import (TYPE_CHECKING, Any, Callable, Dict, List, NamedTuple,
                    Optional, Sequence, Tuple)

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

if TYPE_CHECKING:  # annotation-only: the nlp -> serving edge stays lazy
    from ..serving.cache import PrefixCacheIndex

from ..core.compile_cache import compile_log, program_name
from ..kernels.rms_norm import rms_norm_ref
from ..kernels.rope import rope_freqs, apply_rope_half
from ..profiler import RecordEvent
from ..quantization import kv as kvq
from . import llama
from .generation import (_wq, _mlp_cached, _final_head_cached, _sample,
                         quantize_for_serving)
from .ragged_attention import resolve_attention_impl


class PagedKVCache(NamedTuple):
    """k/v: [L, N_blocks, block_size, KV, hd]; table: [B, M] int32 block
    ids (-1 = unassigned); lengths: [B] int32 tokens currently cached.
    k_scale/v_scale: [L, N_blocks] f32 per-(layer, block) abs-max
    dequant scales when the pool stores int8 codes (kv_dtype="int8",
    quantization.kv has the math), None for the fp pool.

    A LATENT pool (`mla.MlaMoeConfig`) is ONE array: k is
    [L, N_blocks, block_size, kv_lora_rank + qk_rope_head_dim], a row
    `[c | k_r]` per token and layer that the attention reads once for
    keys and values alike, and v is None (None adds no leaves to a step
    program's signature).

    A KINDED pool (a configuration whose GQA layers are of two kinds,
    `KVLayout`): k and v are [1, blocks, block_size, KV, hd], every
    layer's blocks side by side in one array, the full layers' first and
    the window layers' after them, and a table row is [M + R] wide: the
    sequence's block chain for the full layers, then its ring of blocks
    for the window layers."""
    k: jax.Array
    v: Optional[jax.Array]
    table: jax.Array
    lengths: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def pools(self):
        """(k, v, k_scale, v_scale): the pool's device buffers, what a
        step program that writes the pool is handed as ONE donated
        argument and returns (`ContinuousBatcher._step_jit`); the table and
        the lengths beside them are never donated."""
        return self.k, self.v, self.k_scale, self.v_scale

    def with_pools(self, pools) -> "PagedKVCache":
        k, v, ks, vs = pools
        return self._replace(k=k, v=v, k_scale=ks, v_scale=vs)

    @classmethod
    def of(cls, pools, table, lengths) -> "PagedKVCache":
        k, v, ks, vs = pools
        return cls(k, v, table, lengths, ks, vs)


class BlockAllocator:
    """Host-side free-list allocator over the pool's block ids.

    Mirrors the serving layer's block manager in the reference stack:
    admission takes blocks from the free list, completion returns them —
    `stats()` exposes the reuse evidence (blocks_in_use / high_water /
    reuse_count)."""

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks))
        self._free_set: set = set(self._free)
        self._ever_used: set = set()
        self.reused_blocks = 0
        self.high_water = 0

    def allocate(self, n: int) -> List[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"pool exhausted: need {n} blocks, {len(self._free)} free")
        blocks = self._free[:n]
        del self._free[:n]
        self._free_set.difference_update(blocks)
        self._note_allocated(blocks)
        return blocks

    def _note_allocated(self, blocks: List[int]) -> None:
        self.reused_blocks += sum(1 for b in blocks if b in self._ever_used)
        self._ever_used.update(blocks)
        self.high_water = max(self.high_water,
                              self.num_blocks - self.free_blocks)

    def _check_returnable(self, b: int, seen: set, what: str) -> None:
        """A returned block id must be in range and not already free —
        a silent double free splices one block into the free list twice
        and two later requests end up writing the same KV block."""
        if not 0 <= b < self.num_blocks:
            raise ValueError(
                f"{what}: block id {b} out of range "
                f"[0, {self.num_blocks})")
        if b in self._free_set or b in seen:
            raise ValueError(
                f"{what}: block {b} is already free (double free)")

    def free(self, blocks: List[int]) -> None:
        """Return blocks to the free list. Raises ValueError on
        out-of-range or already-free ids (double-free detection) before
        mutating anything."""
        seen: set = set()
        for b in blocks:
            self._check_returnable(b, seen, "free()")
            seen.add(b)
        self._free.extend(blocks)
        self._free_set.update(blocks)

    def release(self, blocks: List[int]) -> None:
        """Alias of free() so callers can be allocator-agnostic — the
        refcounting subclass gives release() decref semantics."""
        self.free(blocks)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def stats(self) -> Dict[str, int]:
        return {
            "capacity_blocks": self.num_blocks,
            "blocks_in_use": self.num_blocks - len(self._free),
            "high_water_blocks": self.high_water,
            "reused_blocks": self.reused_blocks,
        }


class RefcountingBlockAllocator(BlockAllocator):
    """Refcounted allocator for prefix-cache block sharing.

    Three block states instead of two:

      * free        — on the free list, contents dead;
      * referenced  — refcount >= 1: held by one or more in-flight
        requests' block tables (several tables may name the same id);
      * cached      — refcount 0 but registered in the prefix index
        (`mark_cached`): contents preserved on an LRU list, reclaimable
        under pool pressure but revivable by `share()` until then.

    `allocate` prefers truly-free blocks and evicts LRU cached blocks
    only when it must (calling `on_evict(block)` so the prefix index
    unlinks them); `release` decrefs with double-free detection and
    parks cacheable blocks instead of freeing them; `share` bumps a
    live block or revives a cached one. `free_blocks` counts free AND
    cached — both are available to admission — which is exactly what
    the batcher's defer-on-no-blocks logic should see."""

    def __init__(self, num_blocks: int,
                 on_evict: Optional[Callable[[int], None]] = None):
        super().__init__(num_blocks)
        self._refs: List[int] = [0] * num_blocks
        self._cached: "OrderedDict[int, None]" = OrderedDict()  # LRU order
        self._cacheable: set = set()
        self._on_evict = on_evict
        self.evicted_blocks = 0

    def refcount(self, block: int) -> int:
        """Current refcount of `block` (0 for free AND cached blocks —
        check `is_cached` to tell them apart)."""
        return self._refs[block]

    def is_cached(self, block: int) -> bool:
        """True when `block` sits on the refcount-0 LRU cached list."""
        return block in self._cached

    @property
    def free_blocks(self) -> int:
        return len(self._free) + len(self._cached)

    def allocate(self, n: int) -> List[int]:
        if n > self.free_blocks:
            raise RuntimeError(
                f"pool exhausted: need {n} blocks, {len(self._free)} "
                f"free + {len(self._cached)} cached")
        blocks: List[int] = []
        for _ in range(n):
            if self._free:
                b = self._free.pop(0)
                self._free_set.discard(b)
            else:
                # reclaim the least-recently-parked cached block; the
                # index must forget it before its contents are reused
                b, _ = self._cached.popitem(last=False)
                self._cacheable.discard(b)
                self.evicted_blocks += 1
                if self._on_evict is not None:
                    self._on_evict(b)
            self._refs[b] = 1
            blocks.append(b)
        self._note_allocated(blocks)
        return blocks

    def share(self, blocks: List[int]) -> None:
        """Add one reference per block: bump a live block's refcount or
        revive a cached one (pulling it off the eviction list). Raises
        ValueError for a block that is neither — sharing a free block
        would hand out dead contents. Validates the WHOLE list before
        mutating anything (no half-applied bumps on error)."""
        for b in blocks:
            if not 0 <= b < self.num_blocks:
                raise ValueError(
                    f"share(): block id {b} out of range "
                    f"[0, {self.num_blocks})")
            if self._refs[b] <= 0 and b not in self._cached:
                raise ValueError(
                    f"share(): block {b} is neither referenced nor "
                    f"cached — its contents are gone")
        for b in blocks:
            if self._refs[b] > 0:
                self._refs[b] += 1
            else:
                del self._cached[b]
                self._refs[b] = 1

    def release(self, blocks: List[int]) -> None:
        """Drop one reference per block. At refcount 0 a block parks on
        the LRU cached list when the prefix index still names it
        (`mark_cached`), else returns to the free list. Raises
        ValueError on out-of-range ids and on releasing a block whose
        refcount is already 0 (double free) — validated over the WHOLE
        list (duplicates counted) before any refcount moves, so a
        failed call never half-applies."""
        pending: Dict[int, int] = {}
        for b in blocks:
            if not 0 <= b < self.num_blocks:
                raise ValueError(
                    f"release(): block id {b} out of range "
                    f"[0, {self.num_blocks})")
            pending[b] = pending.get(b, 0) + 1
            if pending[b] > self._refs[b]:
                raise ValueError(
                    f"release(): block {b} has refcount "
                    f"{self._refs[b]} (double free)")
        for b in blocks:
            self._refs[b] -= 1
            if self._refs[b] == 0:
                if b in self._cacheable:
                    self._cached[b] = None      # newest end of the LRU
                else:
                    self._free.append(b)
                    self._free_set.add(b)

    def free(self, blocks: List[int]) -> None:
        """Refcount-aware: free() IS release() here, so allocator-
        agnostic callers (the batcher's retire path) behave correctly
        whichever allocator they hold."""
        self.release(blocks)

    def mark_cached(self, blocks: List[int]) -> None:
        """Blocks the prefix index registered: when their refcount hits
        0 they park on the cached LRU instead of the free list."""
        self._cacheable.update(blocks)

    def drop_cached(self) -> None:
        """Every cached block goes back to the free list and no block is
        cacheable any more: the pool was rebuilt and their contents are
        gone (the caller empties the prefix index). No eviction is
        counted: this is not pool pressure."""
        self._free.extend(self._cached)
        self._free_set.update(self._cached)
        self._cached.clear()
        self._cacheable.clear()

    def stats(self) -> Dict[str, int]:
        in_use = self.num_blocks - len(self._free) - len(self._cached)
        return {
            "capacity_blocks": self.num_blocks,
            "blocks_in_use": in_use,            # referenced only
            "cached_blocks": len(self._cached),  # reclaimable, not dead
            "high_water_blocks": self.high_water,
            "reused_blocks": self.reused_blocks,
            "evicted_blocks": self.evicted_blocks,
        }


def _refuse_latent(weight_dtype=None, kv_dtype=None, speculative=False,
                   mesh=None, hc_mult: int = 1) -> None:
    """What the served path cannot do yet for a latent (MLA) mixer with
    expert layers, refused at construction, each by its mechanism."""
    if hc_mult > 1:
        raise NotImplementedError(
            f"hc_mult={hc_mult}: the served layer stack carries ONE "
            f"residual stream; the multi-stream (hyper-connection) path "
            f"of nlp/hyper.py runs under nlp/mla_train.py's training step "
            f"only")
    if weight_dtype not in (None, "fp"):
        raise NotImplementedError(
            f"weight_dtype={weight_dtype!r}: weight-only quantization "
            f"(generation.quantize_for_serving) knows the dense decoder's "
            f"projections, not the latent projections or stacked experts")
    if kvq.resolve_kv_dtype(kv_dtype) != "fp":
        raise NotImplementedError(
            f"kv_dtype={kv_dtype!r}: the latent (MLA) pool has no int8 "
            f"form yet (per-block scales over a normalised latent and a "
            f"rotated key)")
    if speculative:
        raise NotImplementedError(
            "speculative=True: the draft and verify programs "
            "(_forward_spec, _spec_gqa_attention) are written for the GQA "
            "block and its K/V suffix slab, not for the latent pool")
    if mesh is not None:
        raise NotImplementedError(
            "mesh: serving.tp's sharding table splits GQA heads and the "
            "dense MLP; latent attention under TP and experts over a mesh "
            "(with their exchange) are not built")


def _refuse_kinded(weight_dtype=None, kv_dtype=None, speculative=False,
                   mesh=None, prefix_cache=False) -> None:
    """What the served path cannot do yet for GQA layers of two kinds
    (window and full) over a kinded pool, or for experts on the GQA
    mixer, refused at construction, each by its mechanism."""
    if weight_dtype not in (None, "fp"):
        raise NotImplementedError(
            f"weight_dtype={weight_dtype!r}: weight-only quantization "
            f"(generation.quantize_for_serving) knows the dense decoder's "
            f"projections, not the router or the stacked experts")
    if kvq.resolve_kv_dtype(kv_dtype) != "fp":
        raise NotImplementedError(
            f"kv_dtype={kv_dtype!r}: the kinded pool (window rings beside "
            f"full chains, addressed in place) has no int8 form: a ring "
            f"block's grow-only scale would outlive the keys it was set by")
    if speculative:
        raise NotImplementedError(
            "speculative=True: the draft and verify programs "
            "(_forward_spec, _spec_gqa_attention) read one uniform pool "
            "with no lower bound on visibility; a window layer's suffix "
            "slab and ring are not built")
    if mesh is not None:
        raise NotImplementedError(
            "mesh: serving.tp's sharding table splits GQA heads of one "
            "uniform pool and the dense MLP; the kinded pool, the window "
            "form of the ragged kernel under shard_map and experts over a "
            "mesh (with their exchange) are not built")
    if prefix_cache:
        raise NotImplementedError(
            "prefix_cache=True: a hit shares a prefix's blocks of the full "
            "layers, but a window layer's ring holds only the last W keys "
            "of its own sequence; sharing needs the prefix's last W keys "
            "copied into the new ring, which is not built")


class KVLayout(NamedTuple):
    """How the ONE pool of a configuration with two kinds of GQA layer is
    laid out, and how wide each kind's part of a table row is. A FULL
    layer keeps every key of a sequence: a chain of blocks from the
    allocator, `width` (M) table entries. A WINDOW layer needs the last
    W keys and keeps no more: a RING of `ring` (R) blocks a sequence, the
    block of position p being `ring[(p // block_size) % R]`. Every
    layer's blocks lie in one array, addressed in place (no layer slice
    is copied out and back): full layer f's block b is row
    `f * full_blocks + b`, window layer w's is row `full_layers *
    full_blocks + w * window_blocks + b`."""
    full_layers: int
    window_layers: int
    full_blocks: int        # per full layer: the allocator's capacity
    window_blocks: int      # per window layer: the ring allocator's
    width: int              # M: a row's chain entries
    ring: int               # R: a row's ring entries

    @property
    def total_blocks(self) -> int:
        return (self.full_layers * self.full_blocks
                + self.window_layers * self.window_blocks)

    def base(self, kind: str, index):
        """First pool row of the `index`-th layer of its kind (`index`
        may be traced)."""
        if kind == "full":
            return index * self.full_blocks
        return self.full_layers * self.full_blocks \
            + index * self.window_blocks

    def table(self, kind: str, table):
        """A kind's part of a table row [.., M + R]."""
        return table[..., :self.width] if kind == "full" \
            else table[..., self.width:]


def ring_blocks(window: int, widest_chunk: int, block_size: int) -> int:
    """Blocks of a window layer's ring: every key the earliest query of a
    chunk may see (its own and the W - 1 before it) must survive the
    chunk's own writes, which all land before any row attends: W +
    chunk - 1 tokens, rounded up to blocks, plus one because neither end
    need lie on a block boundary."""
    return -(-(window + widest_chunk - 1) // block_size) + 1


def _layer_kinds(cfg) -> Optional[Tuple[str, ...]]:
    """One period of the configuration's layer kinds ("full" | "window")
    where it declares them (`window_moe.WindowMoeConfig`), None for a
    decoder whose layers are all alike and keep every key."""
    return getattr(cfg, "period_kinds", None)


def _has_experts(cfg) -> bool:
    """Whether the GQA decoder's FFN is the held experts' share (a latent
    decoder says it by its layer groups, `_layer_groups`)."""
    return bool(getattr(cfg, "num_experts", 0))


def _pow2_ceil(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << max(0, (int(n) - 1).bit_length())


class _Admission(NamedTuple):
    """One prepared-but-not-yet-activated admission: blocks are already
    allocated/shared (and the COW clone applied to the pool), the prompt's
    full blocks are registered in the prefix index so same-burst siblings
    hit, but the slot is not active until `_commit` — `_rollback` can
    still undo everything if the prefill fails."""
    slot: int
    rid: int
    toks: List[int]
    stop: int
    mn: int
    need: int
    matched: List[int]
    cached_len: int
    cow_src: Optional[int]
    fresh: List[int]
    inserted: List[int]
    chunks: List[Tuple[int, int, int]]   # (start, end, bucket) per chunk
    ring: Sequence[int] = ()             # window layers' ring blocks


def _is_latent(cfg) -> bool:
    """Whether the configuration's mixer is latent attention (MLA): a
    `mla.MlaMoeConfig`, told by what it declares, not by its class (the
    nlp -> mla import stays lazy)."""
    return hasattr(cfg, "kv_lora_rank")


def init_pool(cfg, num_blocks: int, block_size: int,
              kv_dtype: str = "fp", layout: Optional[KVLayout] = None):
    """Zeroed K/V pools → (k, v, k_scale, v_scale). The fp pool stores
    the compute dtype with no scales (None); kv_dtype="int8" stores
    int8 codes plus zero-initialized [L, N] per-(layer, block) abs-max
    scales — scale 0 is the never-written sentinel that dequantizes to
    the same exact zeros a fresh fp pool holds. `layout` (a kinded
    configuration): one [1, layout.total_blocks, ...] array each."""
    if _is_latent(cfg):
        _refuse_latent(kv_dtype=kv_dtype)
        return jnp.zeros((cfg.num_hidden_layers, num_blocks, block_size,
                          cfg.kv_row_width), cfg.dtype), None, None, None
    L, KV, hd = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                 cfg.head_dim)
    # K and V (and their scales) are buffers of their OWN from the start:
    # a step program is handed all of them donated, and one buffer cannot
    # be donated twice in a call
    if layout is not None:
        _refuse_kinded(kv_dtype=kv_dtype)
        shape = (1, layout.total_blocks, block_size, KV, hd)
        return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype), \
            None, None
    shape = (L, num_blocks, block_size, KV, hd)
    if kvq.resolve_kv_dtype(kv_dtype) == "int8":
        return (jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                jnp.zeros((L, num_blocks), jnp.float32),
                jnp.zeros((L, num_blocks), jnp.float32))
    return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype), \
        None, None


def build_table(allocator: BlockAllocator, lengths, max_len: int,
                block_size: int):
    """Allocate each request's blocks for up to max_len tokens → ([B, M]
    table array, per-request block lists for later free())."""
    M = -(-max_len // block_size)
    rows, owned = [], []
    for _ in lengths:
        blocks = allocator.allocate(M)
        owned.append(blocks)
        rows.append(blocks)
    return jnp.asarray(rows, jnp.int32), owned


def _write_pool(pool, table, positions, new, valid, ring: bool = False):
    """Scatter new [B, P, KV, hd] rows into pool [N, bs, KV, hd] at
    per-request absolute positions [B, P] through the block table;
    valid [B, P] masks padded slots (their writes drop). `ring`: the
    table is a ring, position p's block is `table[(p // bs) % width]`."""
    N, bs = pool.shape[0], pool.shape[1]
    B, P = positions.shape
    at = positions // bs
    if ring:
        at = at % table.shape[1]
    blk = jnp.take_along_axis(table, at, axis=1)
    flat = blk * bs + positions % bs
    flat = jnp.where(valid, flat, N * bs)          # dropped by mode="drop"
    poolf = pool.reshape(N * bs, *pool.shape[2:])
    poolf = poolf.at[flat.reshape(-1)].set(
        new.reshape(B * P, *new.shape[2:]).astype(pool.dtype), mode="drop")
    return poolf.reshape(pool.shape)


def _write_pool_int8(pool, scale, table, positions, new, valid):
    """int8 twin of `_write_pool`: quantize new [B, P, KV, hd] rows into
    the int8 pool [N, bs, KV, hd] through the block table, maintaining
    ONE per-block abs-max scale [N] (the sibling scale pool, over the
    same blocks; quantization.kv holds the math). Grow-only scale
    discipline: when this call's writes raise a block's abs-max, the
    block's EXISTING codes rescale once under the new scale — only the
    TOUCHED blocks gather/rescale/scatter (a full-pool pass would cost
    O(pool) HBM every decode step). Returns (pool', scale', dq): dq is
    the just-written rows dequantized at the committed scales, so the
    cold-prefill flash path attends over exactly what the pool now
    stores (warm reads of the same blocks see the same values —
    warm == cold by construction, not by tolerance)."""
    N, bs = pool.shape[0], pool.shape[1]
    B, P = positions.shape
    blk = jnp.take_along_axis(table, positions // bs, axis=1)   # [B, P]
    new32 = new.astype(jnp.float32)
    amax_w = jnp.where(valid, jnp.max(jnp.abs(new32), axis=(2, 3)), 0.0)
    tgt = jnp.where(valid, blk, N)            # invalid writes drop at N
    amax = jnp.zeros((N,), jnp.float32).at[tgt.reshape(-1)].max(
        amax_w.reshape(-1), mode="drop")
    scale2 = jnp.maximum(scale, kvq.scale_of(amax))
    touched = jnp.clip(blk, 0)

    def _rescale_touched(p):
        # duplicate targets all scatter the same rescaled contents
        sub = kvq.rescale_codes(p[touched],
                                scale[touched][:, :, None, None, None],
                                scale2[touched][:, :, None, None, None])
        return p.at[tgt.reshape(-1)].set(
            sub.reshape(B * P, bs, *p.shape[2:]), mode="drop")

    # rescale the touched blocks ONLY when some scale actually grew:
    # the steady-state decode step (no growth) would otherwise read and
    # rewrite every slot's full block just to store identical codes —
    # 2*block_size x the fp path's one-row write, eroding the gather-
    # bytes win. The no-growth branch is an exact no-op by the rescale
    # identity, so skipping it never changes pool contents.
    pool = lax.cond(jnp.any(scale2 > scale), _rescale_touched,
                    lambda p: p, pool)
    # quantize + scatter the new rows at the committed block scales
    s_tok = scale2[touched][:, :, None, None]                 # [B, P, 1, 1]
    codes = kvq.quantize(new32, s_tok)
    flat = jnp.where(valid, blk * bs + positions % bs, N * bs)
    poolf = pool.reshape(N * bs, *pool.shape[2:])
    poolf = poolf.at[flat.reshape(-1)].set(
        codes.reshape(B * P, *codes.shape[2:]), mode="drop")
    return poolf.reshape(pool.shape), scale2, kvq.dequantize(codes, s_tok)


def _paged_gqa_attention(q, k_pool, v_pool, table, positions, valid=None,
                         impl: str = "xla", k_scale=None, v_scale=None,
                         mesh=None, mesh_axis: str = "mp", window=None,
                         ring: bool = False, work=None):
    """q [B, P, H, hd] against pool blocks gathered through the table.
    positions [B, P]: query p sees pool keys at absolute positions
    j <= positions[b, p] — per-query causal, so this one path serves
    both single-token decode (P=1, position = current length) AND the
    cached-prefix suffix prefill (P>1 suffix tokens attending to the
    shared prefix blocks plus their own, never to their future).
    Cold prefill uses the in-batch flash path instead.

    impl="xla" (default) is THE reference: full-table-width gather,
    unchanged bit-for-bit from before the backend switch existed (it
    ignores `valid` — padded rows compute never-read garbage).
    impl="pallas" dispatches to the ragged Pallas kernel, which walks
    only each request's live block chain and zeroes invalid rows;
    parity is tight-tolerance, not bitwise (online softmax).

    k_scale/v_scale [N] f32 (the pool's per-block scales) mark an
    int8 pool: the XLA path dequantizes AFTER the gather (the bit-
    stable reference formulation), the Pallas kernel dequantizes inside
    its block-chunk loop with the scales riding scalar prefetch — so
    the quantized gather moves int8 bytes, not fp bytes.

    `mesh`/`mesh_axis` (pallas only) run the kernel shard_map-wrapped
    over the KV-head-sharded pool — the XLA path never needs them: its
    einsums partition under plain GSPMD.

    `window` (W, static; None = the program as it was) makes this a
    sliding-window layer's attention: query p sees keys p - W < j <= p.
    `ring`: the table is a ring of M blocks, chain block m in
    `table[:, m % M]` (`KVLayout`); the gather then takes the M chain
    blocks from the row's first visible one on.

    `work` (pallas only): the kernel's work list for these positions,
    valid and window (`ragged_attention.gqa_work_list`), the same for
    every layer of its kind, so built by the caller once a forward; the
    kernel builds its own where None."""
    if impl == "pallas":
        from .ragged_attention import ragged_paged_attention
        return ragged_paged_attention(q, k_pool, v_pool, table, positions,
                                      valid, k_scale=k_scale,
                                      v_scale=v_scale, mesh=mesh,
                                      mesh_axis=mesh_axis, window=window,
                                      ring=ring, work=work)
    B, P, H, hd = q.shape
    N, bs, KV, _ = k_pool.shape
    M = table.shape[1]
    tb = jnp.clip(table, 0)
    kpos = jnp.arange(M * bs)[None, None, :]            # a key's position
    if ring:
        # gathered slot i holds chain block lo + i, lo the block of the
        # row's first visible key (a slot past the last written block
        # holds an older block's keys: its positions lie in the future
        # of every query and the causal bound masks them)
        from .ragged_attention import first_visible_block
        lo = first_visible_block(
            positions, jnp.ones((B, P), bool) if valid is None else valid,
            window, bs, axis=1)
        chain = lo[:, None] + jnp.arange(M)[None, :]             # [B, M]
        tb = jnp.take_along_axis(tb, chain % M, axis=1)
        kpos = (chain[:, :, None] * bs + jnp.arange(bs)[None, None, :]
                ).reshape(B, 1, M * bs)
    if k_scale is not None:
        # dequantize after the gather: [B, M] block scales broadcast
        # over each gathered block's [bs, KV, hd] codes (the reference
        # the in-kernel dequant is pinned against)
        k = kvq.dequantize(k_pool[tb],
                           k_scale[tb][:, :, None, None, None])
        v = kvq.dequantize(v_pool[tb],
                           v_scale[tb][:, :, None, None, None])
        k = k.reshape(B, M * bs, KV, hd)
        v = v.reshape(B, M * bs, KV, hd)
    else:
        k = k_pool[tb].reshape(B, M * bs, KV, hd)
        v = v_pool[tb].reshape(B, M * bs, KV, hd)
    rep = H // KV
    qg = q.reshape(B, P, KV, rep, hd)
    s = jnp.einsum("bpkrd,btkd->bkrpt", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    # [B, P, T] key-visibility per query → broadcast over (KV, rep)
    vis = kpos <= positions[:, :, None]
    if window is not None:
        vis = vis & (kpos > positions[:, :, None] - window)
    vis = vis[:, None, None, :, :]
    s = jnp.where(vis, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkrpt,btkd->bpkrd", p, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, P, H, hd).astype(q.dtype)


def _spec_queries(base_len, P: int):
    """(positions, valid) [B, P] of the speculative score path's kernel
    call: pool visibility j < base_len == positions j <= base_len - 1,
    every query valid (inactive slots score garbage the caller discards
    — same as the XLA formulation)."""
    B = base_len.shape[0]
    return (jnp.broadcast_to((base_len - 1)[:, None], (B, P)),
            jnp.ones((B, P), bool))


def _spec_work_list(base_len, P: int, table_width: int, pool_shape,
                    pool_dtype, impl: str):
    """The suffix-slab kernel's work list for a `[B, P]` score call over
    a layer's pool `pool_shape` [N, bs, KV, hd]: one list a forward,
    every layer walks it. None for the XLA formulation, which walks no
    grid."""
    if impl != "pallas":
        return None
    from .ragged_attention import gqa_work_list
    return gqa_work_list(*_spec_queries(base_len, P), table_width,
                         pool_shape, pool_dtype, slab=True)


def _spec_gqa_attention(q, pk, pv, table, base_len, sk, sv, vis,
                        k_scale=None, v_scale=None, impl: str = "xla",
                        mesh=None, mesh_axis: str = "mp", work=None):
    """The speculative score path's attention: q [B, P, H, hd] over the
    committed pool history PLUS an in-register draft/verify suffix
    slab. The pool is READ-ONLY here — visibility for pool keys is
    j < base_len[b] (the committed length; nothing speculative has
    been written), and suffix slab row s (this step's tokens plus
    previously drafted ones, sk/sv [B, S, KV, hd]) is visible to query
    p iff vis[p, s] — the chain's causal triangle, or the packed
    tree's ancestor-or-self mask (each node sees exactly its
    root-to-node path). Together a query at committed position
    base_len + r along its path sees exactly the base_len + r + 1 keys
    plain write-then-gather decode would — same key set and values
    (slab rows pass through the pool dtype), softmax over the
    concatenated score axis.

    k_scale/v_scale mark an int8 pool: dequantized after the gather
    (the XLA reference formulation). Slab rows stay full precision —
    the committed codes a LATER step reads go through the normal
    quantize-on-commit path, so spec-vs-plain parity under int8 KV is
    a documented match-rate floor, not bitwise (README
    "Speculative decoding").

    impl="pallas" routes the whole thing through the ragged Pallas
    kernel's suffix-slab operand (nlp/ragged_attention.py): the pool
    sweep stays the int8-gathered block-chunk loop and the slab folds
    into the same online softmax at each row's last work item — instead
    of this XLA concat formulation, which stays the bit-stable parity
    reference (and the CPU default). `mesh`/`mesh_axis` (pallas only)
    shard that kernel call on heads — the slab and its accept walk
    shard naturally, since slab rows carry whole KV heads. `work`
    (pallas only): the kernel's list (`_spec_work_list`), the same for
    every layer."""
    B, P, H, hd = q.shape
    N, bs, KV, _ = pk.shape
    M = table.shape[1]
    S = sk.shape[1]
    if impl == "pallas":
        from .ragged_attention import ragged_paged_attention
        return ragged_paged_attention(
            q, pk, pv, table, *_spec_queries(base_len, P),
            k_scale=k_scale, v_scale=v_scale,
            suffix_k=sk, suffix_v=sv,
            suffix_vis=jnp.broadcast_to(vis[None], (B, P, S)),
            mesh=mesh, mesh_axis=mesh_axis, work=work)
    tb = jnp.clip(table, 0)
    if k_scale is not None:
        k = kvq.dequantize(pk[tb],
                           k_scale[tb][:, :, None, None, None])
        v = kvq.dequantize(pv[tb],
                           v_scale[tb][:, :, None, None, None])
        k = k.reshape(B, M * bs, KV, hd)
        v = v.reshape(B, M * bs, KV, hd)
    else:
        k = pk[tb].reshape(B, M * bs, KV, hd)
        v = pv[tb].reshape(B, M * bs, KV, hd)
    rep = H // KV
    qg = q.reshape(B, P, KV, rep, hd)
    sp = jnp.einsum("bpkrd,btkd->bkrpt", qg, k,
                    preferred_element_type=jnp.float32) / math.sqrt(hd)
    vis_p = (jnp.arange(M * bs)[None, :] < base_len[:, None]
             )[:, None, None, None, :]
    sp = jnp.where(vis_p, sp, -1e30)
    ss = jnp.einsum("bpkrd,bskd->bkrps", qg, sk.astype(q.dtype),
                    preferred_element_type=jnp.float32) / math.sqrt(hd)
    ss = jnp.where(vis[None, None, None, :, :], ss, -1e30)
    p = jax.nn.softmax(jnp.concatenate([sp, ss], axis=-1), axis=-1)
    o = jnp.einsum("bkrpt,btkd->bpkrd", p[..., :M * bs], v,
                   preferred_element_type=jnp.float32) \
        + jnp.einsum("bkrps,bskd->bpkrd", p[..., M * bs:],
                     sv.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    return o.reshape(B, P, H, hd).astype(q.dtype)


def _forward_spec(params, layers, tokens, cache, positions, base_len,
                  slab_k, slab_v, row0, cfg, vis,
                  impl: str = "xla", mesh=None, mesh_axis: str = "mp"):
    """The speculative score-path forward: tokens [B, P] at per-request
    absolute positions, attending to the committed pool (READ-ONLY,
    visibility < base_len) plus the spec slab (previously drafted rows
    and this call's own). The new tokens' per-layer K/V land in slab
    rows [row0, row0 + P) — NEVER the pool: verify-then-commit writes
    only accepted rows afterwards, so a rejected draft token cannot
    poison the pool, the prefix cache, or an int8 block's grow-only
    scale. `layers` may be a truncated stack (the draft's) — the
    slab's leading dim matches it; embed/norm/head come from the full
    `params` either way (the self-speculative trick: the target's pool
    layers 0..d-1 ARE the d-layer draft's cache — and when the batcher
    built a draft-from-w8 stack, `layers` is that int8 tree while
    `params` stays the target's). `vis` [P, S] gives each query its
    visible slab rows (the config's ancestor mask; a chain's is the
    causal triangle); `impl` picks the score-path
    attention backend ("xla" concat reference | "pallas" suffix-slab
    kernel), with `mesh`/`mesh_axis` shard_map-wrapping the pallas
    case on the TP mesh. Returns (logits [B, P, V], slab_k', slab_v')."""
    cd = cfg.dtype
    T_rope = cache.table.shape[1] * cache.k.shape[2]
    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(cd)
    cos, sin = rope_freqs(cfg.head_dim, T_rope, cfg.rope_theta,
                          jnp.float32)
    B, P = tokens.shape
    H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    work = _spec_work_list(base_len, P, cache.table.shape[1],
                           cache.k.shape[1:], cache.k.dtype, impl)

    def body(carry, lp):
        x, sk_all, sv_all, li = carry
        pk = lax.dynamic_slice_in_dim(cache.k, li, 1, 0)[0]
        pv = lax.dynamic_slice_in_dim(cache.v, li, 1, 0)[0]
        ks = None if cache.k_scale is None else \
            lax.dynamic_slice_in_dim(cache.k_scale, li, 1, 0)[0]
        vs = None if cache.v_scale is None else \
            lax.dynamic_slice_in_dim(cache.v_scale, li, 1, 0)[0]
        sk = lax.dynamic_slice_in_dim(sk_all, li, 1, 0)[0]
        sv = lax.dynamic_slice_in_dim(sv_all, li, 1, 0)[0]
        h = rms_norm_ref(x, lp["input_layernorm"], cfg.rms_norm_eps)
        q = (h @ _wq(lp, "q_proj", cd)).reshape(B, P, H, hd)
        k = (h @ _wq(lp, "k_proj", cd)).reshape(B, P, KV, hd)
        v = (h @ _wq(lp, "v_proj", cd)).reshape(B, P, KV, hd)
        q, k = apply_rope_half(q, k, cos, sin, positions)
        # slab rows pass through the slab (== pool compute) dtype so
        # spec attention sees the same roundtrip a pool write-then-
        # gather would give plain decode
        sk = lax.dynamic_update_slice_in_dim(sk, k.astype(sk.dtype),
                                             row0, axis=1)
        sv = lax.dynamic_update_slice_in_dim(sv, v.astype(sv.dtype),
                                             row0, axis=1)
        a = _spec_gqa_attention(q, pk, pv, cache.table, base_len,
                                sk, sv, vis, ks, vs, impl=impl,
                                mesh=mesh, mesh_axis=mesh_axis, work=work)
        a = a.reshape(B, P, H * hd) @ _wq(lp, "o_proj", cd)
        sk_all = lax.dynamic_update_slice_in_dim(sk_all, sk[None], li, 0)
        sv_all = lax.dynamic_update_slice_in_dim(sv_all, sv[None], li, 0)
        x = x + a
        h = rms_norm_ref(x, lp["post_attention_layernorm"],
                         cfg.rms_norm_eps)
        x = x + _mlp_cached(h, lp, cfg)
        return (x, sk_all, sv_all, li + 1), None

    (x, slab_k, slab_v, _), _ = lax.scan(
        body, (x, slab_k, slab_v, jnp.int32(0)), layers)
    logits = _final_head_cached(params, x, cfg)
    return logits, slab_k, slab_v


class _RowGroup(NamedTuple):
    """One group of rows of a paged forward: `tokens` [G, P] at absolute
    `positions` [G, P] through block `table` [G, M]; `valid` [G, P]
    masks padding (its pool writes drop, its outputs are never read)."""
    tokens: jax.Array
    table: jax.Array
    positions: jax.Array
    valid: jax.Array


def _pack_rows(parts):
    """Each group's rows [G, P, ...] -> the ONE activation the
    per-token layers run on. Several groups are flattened and
    concatenated to [T, ...], T the groups' token counts summed, so
    that no group is padded to another's width. A single group keeps
    its [G, P, ...] shape, the shape of `_forward_spec`'s and dense
    `generate`'s bodies: the one-group paths are held BIT-identical
    to those two on the CPU (tests/test_speculative.py,
    tests/test_paged_kv.py), where a flattened [G*P, D] activation
    rounds its bf16 otherwise and parts from them at near-ties."""
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate([p.reshape(-1, *p.shape[2:]) for p in parts], 0)


def _group_rows(x, groups):
    """`_pack_rows` undone: the packed x -> each group's [G, P, ...]."""
    if len(groups) == 1:
        return [x]
    out, off = [], 0
    for g in groups:
        G, P = g.tokens.shape
        out.append(lax.slice_in_dim(x, off, off + G * P, axis=0)
                   .reshape(G, P, *x.shape[1:]))
        off += G * P
    return out


def _attention_paged(x, lp, cfg, cos, sin, pk, pv, groups, is_prefill,
                     attention_impl: str = "xla", pks=None, pvs=None,
                     mesh=None, mesh_axis: str = "mp", tables=None,
                     window=None, ring: bool = False, works=None,
                     n_heads=None, rotary_dim=None):
    """One layer's attention over x, the packed tokens of `groups`
    (`_RowGroup`s; `_pack_rows` gives x's shape). The projections are
    per token: ONE dot each over all of x. RoPE, the pool write and the
    attention itself are per row group, each at its own [G, P] shape
    through its own table; every group's KV is written before any group
    attends, so a row may read blocks that another group's rows write
    in this very call. Returns (out shaped like x, pk', pv', pks',
    pvs') with the new tokens written into the pool: quantized on the
    commit write when pks/pvs carry the pool's int8 block scales
    (None = fp pool, the unchanged path).

    pk and pv (and pks, pvs) are the WHOLE pool of every layer, flat, and
    `tables` each group's table for this layer (of a kinded pool its
    kind's part of the row) with the layer's base added: the layer's
    blocks are written and read in place; `window` / `ring` make it a
    window layer's (`_paged_gqa_attention`). A cold chunk no longer than the
    window sees all of itself and takes the flash path like any other;
    a longer one attends through the table like a warm one.
    `works`: each group's kernel work list for this kind of layer
    (`_gqa_work_lists`), made by the caller once a forward; the kernel
    builds its own where there is none. `n_heads`: this layer's query
    heads where the configuration's kinds differ in them (its q_proj and
    o_proj are that wide); `rotary_dim`: the leading dims of a head that
    this layer rotates (cos and sin that wide; None = all). A `g_proj`
    among the layer's weights is a per-head output gate: sigmoid(x Wg),
    one a head, times the head's attention output before o_proj."""
    H, KV, hd = (n_heads or cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    cd = cfg.dtype
    if tables is None:
        tables = [g.table for g in groups]
    if works is None:
        works = [None] * len(groups)

    def heads(y, n):
        return [r.reshape(*r.shape[:2], n, hd)
                for r in _group_rows(y, groups)]

    with jax.named_scope("attn_qkv"):
        q = heads(x @ _wq(lp, "q_proj", cd), H)
        k = heads(x @ _wq(lp, "k_proj", cd), KV)
        v = heads(x @ _wq(lp, "v_proj", cd), KV)
        for i, g in enumerate(groups):
            q[i], k[i] = apply_rope_half(q[i], k[i], cos, sin, g.positions,
                                         rotary_dim)
    with jax.named_scope("kv_pool_write"):
        kq, vq = list(k), list(v)
        for i, g in enumerate(groups):
            if pks is None:
                pk = _write_pool(pk, tables[i], g.positions, k[i], g.valid,
                                 ring)
                pv = _write_pool(pv, tables[i], g.positions, v[i], g.valid,
                                 ring)
            else:
                pk, pks, kq[i] = _write_pool_int8(
                    pk, pks, tables[i], g.positions, k[i], g.valid)
                pv, pvs, vq[i] = _write_pool_int8(
                    pv, pvs, tables[i], g.positions, v[i], g.valid)
                # every consumer sees the quantize→dequantize roundtrip
                # of this call's own writes — a later cached-prefix read
                # of the same blocks sees the same KV values (warm ==
                # cold by construction)
                kq[i], vq[i] = kq[i].astype(cd), vq[i].astype(cd)
    with jax.named_scope("attn_kernel"):
        if is_prefill and (window is None or q[0].shape[1] <= window):
            # the prompt attends only to itself: plain causal
            # self-attention over the right-padded batch (rows past
            # each request's length produce garbage that is never
            # read — their pool writes are dropped and their logits
            # never selected)
            from ..kernels import flash_attention as fa
            assert len(groups) == 1, "a cold prefill is one row group"
            if mesh is not None and fa._pallas_available():
                # GSPMD cannot partition the Mosaic kernel: each
                # device runs it on its head shard, like the ragged
                # kernel below
                outs = [fa.flash_attention_sharded(
                    q[0], kq[0], vq[0], mesh,
                    PartitionSpec(None, None, mesh_axis, None))]
            else:
                outs = [fa._flash_impl(q[0], kq[0], vq[0], True, None)]
        else:
            # decode AND cached-prefix suffix prefill: gather through
            # the table with per-query causal visibility (j <= position)
            outs = [_paged_gqa_attention(
                q[i], pk, pv, tables[i], g.positions, g.valid,
                impl=attention_impl, k_scale=pks, v_scale=pvs,
                mesh=mesh, mesh_axis=mesh_axis, window=window, ring=ring,
                work=works[i])
                for i, g in enumerate(groups)]
        outs = [o.reshape(*o.shape[:2], H * hd) for o in outs]
    if "g_proj" in lp:
        with jax.named_scope("attn_gate"):
            gates = _group_rows(jax.nn.sigmoid(
                (x @ _wq(lp, "g_proj", cd)).astype(jnp.float32)), groups)
            outs = [(o.reshape(*o.shape[:2], H, hd) * g[..., None])
                    .astype(cd).reshape(o.shape)
                    for o, g in zip(outs, gates)]
    with jax.named_scope("attn_out"):
        o = _pack_rows(outs) @ _wq(lp, "o_proj", cd)
    return o, pk, pv, pks, pvs


def _attention_latent(x, lp, cfg, cos, sin, pool, groups, is_prefill,
                      attention_impl: str, base, works):
    """`_attention_paged` for a latent (MLA) mixer: one layer's attention
    over x, the packed tokens of `groups`, against the layer's latent
    pool [N, bs, R + rope]. Projections are per token, one dot each over
    all of x; RoPE, the pool write and the attention are per row group;
    every group's rows are written before any group attends. A cold
    prefill attends in the EXPANDED form (per-head keys and values made
    from its own rows, the flash kernel); everything that reads through
    the block table in the ABSORBED form, over the pool's rows as they
    are (nlp/mla.py). `pool` may be the WHOLE stack over layers,
    flattened to [L*N, bs, R + rope], with `base` = layer * N added to
    every block id: the layer's blocks are then written and read in
    place, and no layer slice of the pool is copied out and back.
    `works`: each group's kernel work list (`mla.latent_work_list`; None
    where the backend walks none or the kernel is to build its own), the
    same for every layer, so made by the caller once a forward.
    Returns (out shaped like x, pool')."""
    from . import mla
    with jax.named_scope("mla_q"):
        q = _group_rows(mla.project_q(x, lp, cfg), groups)
    with jax.named_scope("mla_kv_latent"):
        c, k_r = mla.project_latent(x, lp, cfg)
        c, k_r = _group_rows(c, groups), _group_rows(k_r, groups)
        rows = []
        for i, g in enumerate(groups):
            q[i], kr = mla.rotate(q[i], k_r[i], cos, sin, g.positions, cfg)
            rows.append(jnp.concatenate([c[i], kr.astype(c[i].dtype)], -1))
    tables = [g.table + base for g in groups]
    if works is None:
        works = [None] * len(groups)
    with jax.named_scope("kv_pool_write"):
        for i, g in enumerate(groups):
            pool = _write_pool(pool, tables[i], g.positions, rows[i],
                               g.valid)
    if is_prefill:
        assert len(groups) == 1, "a cold prefill is one row group"
        with jax.named_scope("attn_kernel"):
            outs = [mla.attend_expanded(q[0], rows[0], lp, cfg)]
    else:
        with jax.named_scope("mla_absorb"):
            q = [mla.absorb_q(qi, lp, cfg) for qi in q]
        with jax.named_scope("attn_kernel"):
            outs = [mla.latent_paged_attention(
                q[i], pool, tables[i], g.positions, g.valid, cfg,
                impl=attention_impl, work=works[i])
                for i, g in enumerate(groups)]
        with jax.named_scope("mla_absorb"):
            outs = [mla.unabsorb_o(o, lp, cfg) for o in outs]
    with jax.named_scope("attn_out"):
        o = _pack_rows(outs) @ _wq(lp, "o_proj", cfg.dtype)
    return o, pool


_EXPERT_STACKS = ("experts_gate", "experts_up", "experts_down")


def _ffn_experts(x, h, lp, cfg, groups, stats, stacks, layer):
    """The FFN of a sparse-expert layer on the packed tokens: the share of
    the routed experts held here (`moe.expert_share_ffn`: dropless, the
    padding rows masked out of routing, the router's scoring function the
    configuration's) plus the shared expert where the configuration has
    one, onto the residual. `stacks` holds the held experts' matrices for
    ALL expert layers (`layer` picks this one inside the grouped GEMM:
    the scan does not slice them). `stats` adds up the layer's routing
    counters."""
    from . import moe
    D = h.shape[-1]
    tok_valid = _pack_rows([g.valid for g in groups]).reshape(-1)
    y, st = moe.expert_share_ffn(
        h.reshape(-1, D), {"router": lp["router"], **stacks},
        k=cfg.num_experts_per_tok, first=cfg.experts_first,
        scale=cfg.routed_scaling_factor, normalize=cfg.norm_topk_prob,
        valid=tok_valid, layer=layer, score=cfg.scoring_func)
    with jax.named_scope("moe_shared"):
        x = x + y.reshape(h.shape)
        if cfg.n_shared_experts:
            x = x + _mlp_cached(h, lp, cfg)
    return x, _merge_stats(stats, st)


def _merge_stats(a, b):
    """Counters of two pieces of work: pairs, hit experts, overflowed
    sorted buffers, the grouped GEMM's and the attention kernel's work
    items add up, the largest load on one expert is a maximum."""
    return {k: (jnp.maximum(v, b[k]) if k == "moe_load_max" else v + b[k])
            for k, v in a.items()}


def _gqa_work_lists(groups, cfg, pool_shape, pool_dtype, is_prefill: bool,
                    attention_impl: str, layout):
    """The ragged kernel's work lists of one forward: {layer kind: one
    list a row group} (`ragged_attention.gqa_work_list`). A list depends
    on positions, valid, the table's width and the window, not on the
    layer or the pool's contents, so every layer of a kind walks the
    same one: the key is None where the layers are all alike, "full" and
    "window" over a kinded pool (`layout`), each cut for its own kind's
    query heads. A kind whose layers call no
    kernel in this forward has no entry: the gather reference walks no
    grid, and a cold prefill attends by the flash kernel unless its
    chunk is longer than the window (`_attention_paged`)."""
    if attention_impl != "pallas":
        return {}
    from .ragged_attention import gqa_work_list
    kinds = {None: (None, None, cfg.num_attention_heads)} \
        if layout is None else {
            "full": (None, layout.width, cfg.heads("full")),
            "window": (cfg.sliding_window, layout.ring, cfg.heads("window"))}
    P = groups[0].tokens.shape[1]
    return {
        kind: [gqa_work_list(g.positions, g.valid,
                             width or g.table.shape[1], pool_shape,
                             pool_dtype, window=window, heads=heads)
               for g in groups]
        for kind, (window, width, heads) in kinds.items()
        if not (is_prefill and (window is None or P <= window))}


def _layer_groups(params, cfg):
    """The decoder as the configuration describes it: (stacked layers,
    FFN kind, one period of layer kinds or None) in order, each a scan
    of one body. Mixer and FFN are independent: a dense GQA decoder is
    one group; a GQA decoder with expert layers is one too, and where
    its layers are of several kinds (window and full) a scan step is one
    whole PERIOD of them, the stack reshaped to [periods, period, ...],
    after its leading dense layers where it declares some (a group of
    their own, one "period" of their kinds); an MLA + sparse-expert
    decoder is its leading dense layers, then its expert layers."""
    if not _is_latent(cfg):
        out = [(params["layers"], "moe" if _has_experts(cfg) else "dense",
                _layer_kinds(cfg))]
        if getattr(cfg, "mlp_only_layers", ()):
            out.insert(0, (params["lead_layers"], "dense", cfg.lead_kinds))
        return out
    out = []
    if cfg.first_k_dense_replace:
        out.append((params["dense_layers"], "dense", None))
    if cfg.num_moe_layers:
        out.append((params["moe_layers"], "moe", None))
    return out


# A kinded decoder whose kinds differ in head count holds the attention
# matrices that follow the count stacked BY KIND, each at its own shape,
# under these keys of a layer group (`window_moe.init_params`).
_BY_KIND = {"attn_full": "full", "attn_window": "window"}


def _fold_periods(layers, kinds, periods: int):
    """A group's stacked layers [L, ...] -> [periods, period, ...], one
    scan step a period; a subtree stacked by kind [n, ...] ->
    [periods, that kind's layers a period, ...]."""
    def fold(tree, n):
        return jax.tree_util.tree_map(
            lambda w: w.reshape(periods, n, *w.shape[1:]), tree)
    return {k: fold(v, kinds.count(_BY_KIND[k]) if k in _BY_KIND
                    else len(kinds)) for k, v in layers.items()}


def _period_layer(lp, kinds, at: int):
    """The `at`-th layer's leaves of one period's (`_fold_periods`)."""
    return {k: jax.tree_util.tree_map(
        lambda w: w[kinds[:at].count(_BY_KIND[k]) if k in _BY_KIND else at],
        v) for k, v in lp.items()}


def _kind_leaves(lp, kind: str):
    """One layer's leaves with its kind's attention matrices among them."""
    own = lp.get("attn_" + kind)
    if own is None:
        return lp
    return {**{k: v for k, v in lp.items() if k not in _BY_KIND}, **own}


def _forward_groups(params, groups, pools, cfg, is_prefill: bool,
                    attention_impl: str = "xla", mesh=None,
                    mesh_axis: str = "mp", layout: Optional[KVLayout] = None):
    """THE paged layer stack, over the packed tokens of one or more row
    groups (`_RowGroup`s sharing one pool and one table width). What is
    per token — embedding, RMSNorm, the projections, the FFN — runs on
    ONE packed activation (`_pack_rows`: [G, P, D] for one group, [T, D]
    for several, T = sum of G*P: each weight is read once and each
    projection is one dot per layer, whatever the groups' shapes);
    what is per row — RoPE positions, the pool write, the attention —
    runs once per group at the group's own [G, P] shape
    (`_attention_paged`, `_attention_latent`). The block is read from
    the configuration, each choice on its own: the MIXER (`_is_latent`:
    MLA over one latent pool, or GQA over K and V pools; GQA layers all
    alike over a pool stacked by layer, or of two kinds, window and
    full, over the kinded pool that `layout` describes, `_layer_kinds`),
    and, per group of layers (`_layer_groups`), the FFN (a dense MLP, or
    the held experts' share with or without a shared expert). `pools` is
    (k, v, k_scale, v_scale) stacked over layers (a latent pool: (rows,
    None, None, None); a kinded pool: (k, v, None, None), each [1,
    blocks, ...]). Whatever the mixer, a layer's blocks are written and
    read IN the pool the scan carries, through block ids offset by the
    layer's base (`li * N` into the stacked pool viewed flat, or
    `KVLayout.base`): no layer's pool is sliced out of the carry or
    written back. Returns (x, the packed hidden states before the final
    norm; pools'; the forward's counters: the expert layers' routing
    and, where an attention kernel walks a work list, the items ONE
    layer's calls walked (`attn_work_steps`; over a kinded pool one
    layer of each kind, summed); None where there are none)."""
    cd = cfg.dtype
    latent = _is_latent(cfg)
    k_all, v_all, ks_all, vs_all = pools
    # rope spans the per-request table width (max reachable position),
    # NOT the whole pool — the pool is ~B x larger by construction
    T_rope = (groups[0].table.shape[1] if layout is None else layout.width) \
        * k_all.shape[2]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed_tokens"],
                     _pack_rows([g.tokens for g in groups]),
                     axis=0).astype(cd)
    if latent:
        cos, sin = cfg.rope_tables(T_rope)
    elif layout is not None:
        # one (cos, sin) pair a layer kind, built once a forward
        ropes = cfg.rope_tables(T_rope)
    else:
        cos, sin = rope_freqs(cfg.head_dim, T_rope, cfg.rope_theta,
                              jnp.float32)
    # the kernels' grids: one list a row group (and kind of GQA layer),
    # from positions and valid alone, so built here and not once a layer
    # in the scan (none where the backend walks no grid)
    works = {}
    if latent and not is_prefill and attention_impl == "pallas":
        from . import mla
        works = {None: [
            mla.latent_work_list(g.positions, g.valid, g.table.shape[1],
                                 k_all.shape[2], attention_impl)
            for g in groups]}
    elif not latent:
        works = _gqa_work_lists(groups, cfg, k_all.shape[1:], k_all.dtype,
                                is_prefill, attention_impl, layout)

    def mix_gqa(x, pools, li, lp):
        # GQA layers all alike: the layer's blocks are written and read
        # IN the pool stacked over layers, viewed flat (block ids offset
        # by li * N, the scales' [L, N] likewise), as `mix_latent` does:
        # no layer slice is copied out and back. An unassigned column
        # (-1) becomes li * N - 1, which no valid row reaches: writes
        # are masked by `valid`, reads by position.
        # ks_all/vs_all are the [L, N] scale pools in int8-KV mode and
        # None for fp, as stats is for a decoder without expert
        # layers — a None traces to the exact jaxpr without it (None
        # adds no carry leaves), keeping the fp GQA path byte-identical
        pk_all, pv_all, ks_all, vs_all = pools
        L, N = pk_all.shape[:2]

        def flat(p):
            return None if p is None else p.reshape(L * N, *p.shape[2:])

        with jax.named_scope("attn_qkv"):
            h = rms_norm_ref(x, lp["input_layernorm"], cfg.rms_norm_eps)
        a, *flats = _attention_paged(
            h, lp, cfg, cos, sin, flat(pk_all), flat(pv_all), groups,
            is_prefill, attention_impl, flat(ks_all), flat(vs_all),
            mesh=mesh, mesh_axis=mesh_axis,
            tables=[g.table + li * N for g in groups],
            works=works.get(None))
        return a, tuple(None if p is None else p.reshape(was.shape)
                        for p, was in zip(flats, pools))

    def mix_gqa_kinded(x, pools, li, kinds, at, lp, before):
        # GQA layers of two kinds over the kinded pool, this one the
        # `at`-th of its period and `li`-th of its group: the layer's
        # blocks are written and read IN the one flat pool (block ids
        # offset by the layer's base, the layer counted among those of
        # its kind, `before[kind]` of them in the groups before); a
        # window layer through its ring with its own RoPE table and the
        # window's bound, each kind at its own head count and rotary share
        kind = kinds[at]
        pk, pv = pools[0][0], pools[1][0]
        base = layout.base(kind, before[kind]
                           + (li // len(kinds)) * kinds.count(kind)
                           + kinds[:at].count(kind))
        window = cfg.sliding_window if kind == "window" else None
        with jax.named_scope("attn_" + kind):
            with jax.named_scope("attn_qkv"):
                h = rms_norm_ref(x, lp["input_layernorm"], cfg.rms_norm_eps)
            a, pk, pv, _, _ = _attention_paged(
                h, lp, cfg, *ropes[kind], pk, pv, groups, is_prefill,
                attention_impl,
                tables=[layout.table(kind, g.table) + base for g in groups],
                window=window, ring=window is not None,
                works=works.get(kind), n_heads=cfg.heads(kind),
                rotary_dim=cfg.rotary_dim(kind))
        return a, (pk[None], pv[None], None, None)

    def mix_latent(x, pools, li, lp):
        # the layer's blocks are written and read IN the stacked pool
        # (block ids offset by li * N): no layer slice is copied out
        # and back, and no V pool or scales exist
        pool_all = pools[0]
        L, N = pool_all.shape[:2]
        with jax.named_scope("mla_q"):
            h = rms_norm_ref(x, lp["input_layernorm"], cfg.rms_norm_eps)
        a, pool = _attention_latent(
            h, lp, cfg, cos, sin,
            pool_all.reshape(L * N, *pool_all.shape[2:]), groups,
            is_prefill, attention_impl, base=li * N, works=works.get(None))
        return a, (pool.reshape(pool_all.shape), None, None, None)

    def make_body(ffn, stacks=None, first_layer=0, kinds=None, before=None):
        def layer(x, pools, li, stats, lp, at=None):
            # one layer: its mixer, then its FFN; `at`: its place in the
            # period where the layers are of several kinds
            if latent:
                a, pools = mix_latent(x, pools, li, lp)
            elif layout is None:
                a, pools = mix_gqa(x, pools, li, lp)
            else:
                a, pools = mix_gqa_kinded(x, pools, li - first_layer, kinds,
                                          at, _kind_leaves(lp, kinds[at]),
                                          before)
            with jax.named_scope("mlp"):
                x = x + a
                h = rms_norm_ref(x, lp["post_attention_layernorm"],
                                 cfg.rms_norm_eps)
                if ffn == "dense" and layout is not None:
                    # a kinded decoder's leading dense layers
                    with jax.named_scope("mlp_lead"):
                        x = x + _mlp_cached(h, lp, cfg)
                elif ffn == "dense":
                    x = x + _mlp_cached(h, lp, cfg)
            if ffn == "moe":
                x, stats = _ffn_experts(x, h, lp, cfg, groups, stats,
                                        stacks, li - first_layer)
            return x, pools, stats

        def body(carry, lp):
            x, pk_all, pv_all, ks_all, vs_all, li, stats = carry
            pools = (pk_all, pv_all, ks_all, vs_all)
            if kinds is None or len(kinds) == 1:
                x, pools, stats = layer(x, pools, li, stats, lp, 0)
                return (x, *pools, li + 1, stats), None
            # one whole period a scan step, its layers in order
            for at in range(len(kinds)):
                x, pools, stats = layer(
                    x, pools, li + at, stats,
                    _period_layer(lp, kinds, at), at)
            return (x, *pools, li + len(kinds), stats), None

        return body

    stats = None
    if (latent and cfg.num_moe_layers) or (not latent and _has_experts(cfg)):
        z = jnp.zeros((), jnp.int32)
        stats = {"moe_pairs": z, "moe_experts_hit": z, "moe_load_max": z,
                 "moe_full_passes": z, "moe_gemm_items": z}
    carry = (x, k_all, v_all, ks_all, vs_all, jnp.int32(0), stats)
    first_layer = 0
    before = {"full": 0, "window": 0}   # layers of each kind so far
    for layers, ffn, kinds in _layer_groups(params, cfg):
        stacks = None
        if ffn == "moe":
            # the experts' stacks stay whole, outside the scanned leaves
            stacks = {k: layers[k] for k in _EXPERT_STACKS}
            layers = {k: v for k, v in layers.items()
                      if k not in _EXPERT_STACKS}
        n_layers = layers["input_layernorm"].shape[0]
        if kinds is not None and len(kinds) > 1:
            layers = _fold_periods(layers, kinds, n_layers // len(kinds))
        carry, _ = lax.scan(
            make_body(ffn, stacks, first_layer, kinds, dict(before)),
            carry, layers)
        first_layer += n_layers
        for kind in kinds or ():
            before[kind] += n_layers // len(kinds) * kinds.count(kind)
    x, pk, pv, ks, vs, _, stats = carry
    if works:
        # the items ONE layer's kernel calls walked (every layer of a
        # kind walks the same lists), beside the routing counters
        stats = {**(stats or {}), "attn_work_steps": sum(
            w.count for ws in works.values() for w in ws)}
    return x, (pk, pv, ks, vs), stats


def forward_paged(params, tokens, cache: PagedKVCache, positions, valid,
                  cfg, is_prefill: bool, attention_impl: str = "xla",
                  mesh=None, mesh_axis: str = "mp",
                  layout: Optional[KVLayout] = None):
    """tokens [B, P] at per-request absolute `positions` [B, P] →
    (logits [B, P, V] f32, cache'): the ONE-group call of
    `_forward_groups` (the plain decode chunk, the standalone prefill,
    `paged_generate`), with the LM head on every position.
    visible_len for decode = position+1 (the just-written token
    included). `attention_impl` selects the paged-attention backend
    ("xla" reference gather | "pallas" ragged kernel) for the
    non-prefill path; cold prefill keeps flash. `mesh`/`mesh_axis`
    shard_map-wrap the pallas kernel on the TP mesh (no-op for "xla",
    which shards under plain GSPMD)."""
    logits, cache, _ = _forward_paged_stats(
        params, tokens, cache, positions, valid, cfg, is_prefill,
        attention_impl, mesh=mesh, mesh_axis=mesh_axis, layout=layout)
    return logits, cache


def _forward_paged_stats(params, tokens, cache: PagedKVCache, positions,
                         valid, cfg, is_prefill: bool,
                         attention_impl: str = "xla", mesh=None,
                         mesh_axis: str = "mp",
                         layout: Optional[KVLayout] = None):
    """`forward_paged` with the expert layers' routing counters as a third
    result (None for a decoder without expert layers): what the step
    programs call."""
    x, (pk, pv, ks, vs), stats = _forward_groups(
        params, (_RowGroup(tokens, cache.table, positions, valid),),
        (cache.k, cache.v, cache.k_scale, cache.v_scale), cfg, is_prefill,
        attention_impl, mesh=mesh, mesh_axis=mesh_axis, layout=layout)
    with jax.named_scope("lm_head"):
        logits = _final_head_cached(params, x, cfg)
    new_len = jnp.maximum(cache.lengths, positions[:, -1] + 1)
    return logits, PagedKVCache(pk, pv, cache.table, new_len, ks, vs), stats


def paged_generate(params, tokens, lengths, cfg: llama.LlamaConfig,
                   max_new_tokens: int = 32, block_size: int = 64,
                   allocator: Optional[BlockAllocator] = None,
                   num_blocks: Optional[int] = None,
                   temperature: float = 1.0, top_k: int = 0,
                   top_p: float = 1.0, greedy: bool = True,
                   pad_token_id: int = 0,
                   key: Optional[jax.Array] = None,
                   attention_impl: str = "auto"):
    """Ragged batched generation over one shared block pool.

    tokens [B, P_max] right-padded prompts; lengths [B] real prompt
    lengths (REQUESTS MAY DIFFER — the dense generate() cannot).
    Returns (ids [B, max_new_tokens], allocator, owned) — `owned` is the
    per-request block lists; free them back to the allocator when each
    request completes so later admissions reuse the pool.
    `attention_impl` picks the decode attention backend ("xla"
    reference | "pallas" ragged kernel | "auto" per backend).
    """
    attention_impl = resolve_attention_impl(attention_impl)
    B, P = tokens.shape
    lengths_np = np.asarray(lengths)
    max_total = int(lengths_np.max()) + max_new_tokens
    if allocator is None:
        n = num_blocks or (B * -(-max_total // block_size))
        allocator = BlockAllocator(n)
    table, owned = build_table(allocator, lengths_np, max_total, block_size)
    kp, vp, ksc, vsc = init_pool(cfg, allocator.num_blocks, block_size)
    cache = PagedKVCache(kp, vp, table,
                         jnp.zeros((B,), jnp.int32), ksc, vsc)
    if key is None:
        key = jax.random.PRNGKey(0)
    lengths = jnp.asarray(lengths, jnp.int32)

    # prefill at per-request positions; padded rows write nothing
    positions = jnp.broadcast_to(jnp.arange(P)[None], (B, P))
    valid = positions < lengths[:, None]
    logits, cache = forward_paged(params, tokens, cache, positions, valid,
                                  cfg, is_prefill=True)
    # ragged last-token logits: position lengths[b] - 1 per request
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    key, sub = jax.random.split(key)
    first = _sample(last, sub, temperature, top_k, top_p, greedy)
    # the prefill wrote only the prompt; fix lengths to the real ones
    cache = cache._replace(lengths=lengths)

    def step(carry, _):
        tok, cache, key = carry
        pos = cache.lengths[:, None]                       # [B, 1]
        logits, cache = forward_paged(
            params, tok[:, None], cache, pos,
            jnp.ones_like(pos, bool), cfg, is_prefill=False,
            attention_impl=attention_impl)
        key, sub = jax.random.split(key)
        nxt = _sample(logits[:, 0], sub, temperature, top_k, top_p, greedy)
        return (nxt, cache, key), nxt

    (last_tok, cache, _), rest = lax.scan(
        step, (first, cache, key), None, length=max_new_tokens - 1)
    out = jnp.concatenate([first[:, None], rest.T.astype(jnp.int32)],
                          axis=1)
    return out, allocator, owned


def _sum_steps(stats, first=None):
    """A chunk's counters from its steps' (stacked on a leading axis by
    the scan; `first`: the fused forward's, outside the scan): summed as
    `_merge_stats` sums two. None for a decoder whose forward counts
    nothing."""
    if stats is None:
        return None
    out = {k: (jnp.max(v) if k == "moe_load_max"
               else jnp.sum(v, dtype=jnp.int32)) for k, v in stats.items()}
    return out if first is None else _merge_stats(out, first)


class _Tick:
    """One device-call tick of the batcher, described once.

    Every kind of tick (decode, fused, prefill, spec_draft, spec_verify)
    runs inside `with _Tick(batcher, mode, ...) as tick:` and names its
    phases with `tick.phase("pack" | "dispatch" | "wait" | "commit")`.
    From that one description come

      * the flight record, written BEFORE the call (the tick that raises
        stays the ring's last record, unclosed) and closed after it with
        the phase times (`pack_s`, `dispatch_s`, `wait_s`, `commit_s`),
        where on the clock dispatch began and the read-back returned
        (`t_dispatch`, `t_synced`), whether the tick synced at all, and
        the slots still decoding when it ended (`live_after`);
      * `RecordEvent` spans `serve.tick` (tagged with the record's `seq`
        and the mode) and `serve.<phase>`, which a running jax profiler
        trace puts on its host plane, on the device events' clock;
      * the fault injector's gate, after the record is written;
      * for a tick that synced, the profiler's per-shape sample
        (`device_s = dispatch_s + wait_s`) and the sink's device-lane
        span: the time from issue to read-back, measured by the sync
        the tick makes anyway.

    One code path yields span and stamp, so the two cannot drift. Host
    values only (SYNC001 polices it): the one device touch is `fence`,
    which runs only inside an armed capture window."""

    def __init__(self, cb: "ContinuousBatcher", mode: str, touched,
                 shape: Tuple[int, int], **fields):
        """`touched`: every rid the call touches (the gate's view);
        `shape`: the (bucket, units) of the profiler's shape key;
        `fields` go on the flight record as they are."""
        self.cb, self.mode = cb, mode
        self.rids = [int(r) for r in touched]
        self.bucket, self.units = int(shape[0]), int(shape[1])
        self.fields = fields
        self.stamps = {"pack": 0.0, "dispatch": 0.0, "wait": 0.0,
                       "commit": 0.0}
        self.t_dispatch: Optional[float] = None
        self.t_synced: Optional[float] = None
        self.adopted = False
        self.noted: Dict[str, Any] = {}

    def note(self, fields: Optional[Dict[str, Any]]) -> None:
        """Host values the call itself produced (read back with its
        tokens), for the record's close: the expert layers' routing
        counters, the latent kernel's `attn_work_steps` (and beside it
        `attn_grid_steps`, which the host knows: `_note_counters`). None
        (a decoder without them) notes nothing."""
        if fields:
            self.noted.update({k: int(v) for k, v in fields.items()})

    def __enter__(self) -> "_Tick":
        cb = self.cb
        seq = cb.flight.record(
            self.mode, active_slots=sum(cb.active),
            queue_depth=len(cb.queue), pending=len(cb._pending),
            free_slots=cb.free_slots(),
            free_blocks=cb.alloc.free_blocks, **self.fields)
        # the fault injector's seam at the device-call boundary: a no-op
        # in production, after the record so that an injected failure's
        # tick is the ring's last record, like a real one's
        if cb._fault is not None:
            cb._fault.check(self.mode, self.rids)
        self.captured = cb.profiler.should_fence()
        self.recording = self.captured or cb.profiler.sample_every > 0
        self._span = RecordEvent("serve.tick", seq=seq, mode=self.mode)
        self._span.begin()
        return self

    @contextlib.contextmanager
    def phase(self, name: str):
        """Span `serve.<name>` and the stamp `<name>_s`, from one pair
        of clock reads. A phase entered twice adds up."""
        span = RecordEvent("serve." + name)
        span.begin()
        t0 = time.perf_counter()
        if name == "dispatch" and self.t_dispatch is None:
            self.t_dispatch = t0
        try:
            yield
        finally:
            t1 = time.perf_counter()
            self.stamps[name] += t1 - t0
            span.end()
        if name == "wait":
            self.t_synced = t1

    def adopt(self, pools) -> None:
        """The pool the call just issued returns becomes the batcher's
        AT DISPATCH, whatever the kind of tick: its argument was donated
        and is deleted by now, so nobody may hold the old one while the
        tokens travel. `lengths` and the slot state commit after the
        wait, as ever; rows the call wrote past a slot's committed length
        are dead data until then."""
        self.cb.cache = self.cb.cache.with_pools(pools)
        self.adopted = True

    def fence(self, outputs) -> None:
        """Inside an armed capture window (`arm_capture`,
        `capture_profile()`, `POST /debug/profile`) drain the call just
        issued, so that a tick that reads nothing back is measured too.
        THE DOCUMENTED SYNC001 CAPTURE-WINDOW EXCEPTION: an operator
        asked for these ticks to be fenced; outside a window this is
        one attribute test and no tick ever blocks on the device here
        (tests/test_tick.py counts the calls)."""
        if self.captured:
            with self.phase("wait"):
                jax.block_until_ready(outputs)

    @property
    def call_s(self) -> float:
        """Host wall of the call so far: packing, issue, read-back."""
        return (self.stamps["pack"] + self.stamps["dispatch"]
                + self.stamps["wait"])

    @property
    def device_s(self) -> Optional[float]:
        """Issue to read-back, for a synced tick the profiler records;
        None otherwise."""
        if self.t_synced is None or not self.recording:
            return None
        return self.stamps["dispatch"] + self.stamps["wait"]

    def __exit__(self, etype, exc, tb) -> bool:
        self._span.end()
        cb, st = self.cb, self.stamps
        if etype is not None:
            # the record stays unclosed. A call that failed between its
            # dispatch and its read-back leaves a pool that is the failed
            # program's result: nothing in it is kept
            cb._drop_lost_pool(self.adopted and self.t_synced is None)
            return False
        cb.flight.close(
            pack_s=st["pack"], dispatch_s=st["dispatch"],
            wait_s=st["wait"], commit_s=st["commit"],
            t_dispatch=self.t_dispatch, t_synced=self.t_synced,
            synced=self.t_synced is not None,
            live_after=sum(cb.active), **cb._kv_blocks_in_use(),
            **self.noted)
        device_s = self.device_s
        if device_s is not None:
            cb.profiler.record(
                mode=self.mode, bucket=self.bucket, units=self.units,
                impl=cb.attention_impl, weight_dtype=cb.weight_dtype,
                kv_dtype=cb.kv_dtype, device_s=device_s,
                host_s=st["dispatch"],
                detail={"rids": self.rids, **self.noted})
            if cb._trace is not None:
                cb._trace.span(
                    "device." + self.mode, dur=device_s, lane="device",
                    t1=self.t_synced, mode=self.mode, bucket=self.bucket,
                    units=self.units, host_s=round(st["dispatch"], 6),
                    impl=cb.attention_impl, replica_id=cb.replica_id)
        return False


class ContinuousBatcher:
    """Continuous batching over the shared block pool (reference analog:
    PaddleNLP serving's in-flight batching over the block cache — pulled
    forward from the VERDICT r4 next-8 'r6 follow-up').

    Host-side scheduler over compiled device steps: a fixed set of B
    batch slots decodes in lock-step chunks; when a request finishes
    (eos or budget) its blocks return to the allocator and queued
    requests are admitted into the free slots by a bucketed prefill —
    decode of the other slots never re-pads or re-compiles (shapes are
    static: the chunk step compiles once per (B, M)).

    Prefill is bucketed, chunked, and batched: the suffix pads to a
    power-of-two bucket ladder (masked through valid/positions), longer
    suffixes split into sequential largest-bucket chunks through the
    per-query-causal paged path, and same-bucket admissions in one burst
    prefill in a single compiled call. Every shape comes from a finite
    (group, bucket, phase) set memoized in `_prefill_exe`, so
    steady-state admission NEVER recompiles (`prefill_compile_count`
    goes flat after `warmup_prefill()`); `prefill_pad_tokens` counts the
    padding overhead bucketing trades for that.

    Prefill is FUSED with decode (`fused_prefill=True`): when an
    admission lands while slots are decoding, one compiled call carries
    `max_batch` decode rows PLUS up to one bucket-sized chunk of prefill
    rows, as two row groups ([B, 1] and [Gp, Pb]) whose packed
    B + Gp*Pb tokens share every dense layer (`_forward_groups`: no
    decode row is padded to the bucket), so in-flight
    decoding advances by its chunk in the same device program that
    prefills the admission, instead of stalling while a standalone
    prefill monopolizes the device. Prepared admissions wait in a
    pending pipeline; `step()` decides each tick whether to piggyback
    the next prefill unit on the decode chunk (fused), run it standalone
    (nothing decoding — nothing to stall), or decode only. Chunked long
    prompts stream ONE fused chunk per step. `fused_steps` counts
    piggybacked calls, `decode_stall_steps` counts standalone prefill
    calls that ran while slots were decoding (the unfused cost), and
    fused shapes are memoized/AOT-warmed exactly like standalone ones.
    A fused step carries up to `fused_units` CONSECUTIVE pending units
    when they share this step's chunk bucket and no cross-unit block
    dependency forces ordering — admission bursts and co-pending
    chunked long prompts drain up to `fused_units` x faster under
    sustained decode load, with shapes still drawn from the finite
    warmed ladder (total prefill rows = units x group pad).

    Attention backend (`attention_impl=`): "xla" is the reference
    full-table-width gather (bit-stable, the CPU default); "pallas" is
    the ragged paged-attention kernel (ragged_attention.py) that walks
    only each request's LIVE block chain (the TPU default — decode HBM
    traffic tracks live pool bytes, not table width); "auto" resolves
    per backend at construction. Every compiled-shape memo keys on the
    resolved impl.

    Quantized serving (`weight_dtype=`, `kv_dtype=`): "int8" weights
    route params through generation.quantize_for_serving (int8 codes +
    per-output-channel scales, dequantized in-register at the consuming
    dot); "int8" KV
    stores the block pools as int8 codes with per-(layer, block)
    abs-max scales in a sibling scale pool (quantization.kv is the
    single-source math), quantized on every prefill/decode commit
    write, dequantized after the gather on the XLA path and inside the
    kernel's block-chunk loop on the Pallas path — per-request decode
    HBM traffic drops to ~half of fp block bytes (kv_bytes_per_token()
    quantifies it, scale overhead included). Defaults ("fp") keep both
    paths byte-identical to the pre-quantization behavior; every
    compiled-shape memo keys on (weight_dtype, kv_dtype) next to the
    attention impl.

    Self-speculative decoding (`speculative=`, `spec_k=`,
    `draft_layers=`): decode is memory-bound — every plain step sweeps
    the weights + live KV to emit ONE token per slot. With spec on, a
    cheap draft (the SAME model truncated to `draft_layers`; the
    committed pool's layers 0..d-1 ARE its KV cache, so no second
    weight set or pool exists) proposes `spec_k` tokens, and the
    target scores all k+1 positions in ONE call — the per-query
    causal mask is exactly the multi-token-suffix primitive — then
    accepts the longest prefix matching its own greedy tokens plus
    one corrected token. Verify-then-commit: scoring never writes the
    pool (proposal K/V ride an in-register slab); only accepted rows
    commit, row-sequentially, so rejection never poisons the pool /
    prefix cache / int8 scales and greedy output is identical to
    plain decode by construction. Admission pressure keeps using the
    fused plain-decode tick; `submit(speculative=False)` opts one
    request out (the engine quarantine's fallback); the spec config
    rides every memo/warmup key and `warmup_prefill` compiles the
    draft/verify pair. `spec_stats()` reports acceptance accounting.

    Observability (`trace=`, `flight_recorder_cap=`): an optional
    `serving.trace.TraceSink` collects per-request timelines (prepared
    / prefill_chunk / retired events carrying bucket, pad,
    cached-token and fused-vs-standalone annotations, keyed by rid);
    the always-on `flight` FlightRecorder keeps a bounded ring of one
    record per step tick — mode chosen, unit composition, bucket /
    group pad, free slots / blocks, compile-memo hit or miss —
    written BEFORE the device call so a failing step is the ring's
    last record. Both are host-side bookkeeping only: no device
    syncs, and the compiled-shape memo keys never see them.

    Tensor-parallel serving (`mesh=`): a serving.tp.MeshConfig shards
    the weights (every projection output-split — never a contracted
    dim, so sharded matmuls keep the unsharded summation order), the
    paged KV pool (head axis) and the w8 scale leaves across a 1-D
    device mesh; GSPMD partitions the same compiled step programs
    from sharded avals, the host-side scheduler is untouched, and
    greedy output is BIT-identical to the single-device batcher. The
    mesh key rides every compiled-shape memo key after the qkey
    (() when off — keys stay byte-identical). export_kv gathers the
    sharded pool to full host blocks and import_kv's scatter
    preserves the pool sharding, so KV migration (and disaggregated
    prefill→decode handoff) works across replicas of DIFFERENT mesh
    shapes — snapshots are mesh-agnostic by construction.

    Usage:
        cb = ContinuousBatcher(params, cfg, max_batch=2, block_size=16,
                               max_total_len=256, max_new_tokens=16)
        rid = cb.submit([tok, tok, ...])
        cb.run()              # drain queue + in-flight
        out = cb.outputs[rid] # list of generated ids
    """

    def __init__(self, params, cfg, max_batch: int, block_size: int,
                 max_total_len: int, max_new_tokens: int,
                 eos_token_id: Optional[int] = None,
                 num_blocks: Optional[int] = None, chunk: int = 8,
                 prefix_cache: bool = False,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 max_prefill_bucket: int = 512,
                 fused_prefill: bool = True, fused_units: int = 1,
                 attention_impl: str = "auto",
                 weight_dtype: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 speculative: bool = False, spec_k: int = 4,
                 draft_layers: Optional[int] = None,
                 spec_tree: Optional[Sequence[int]] = None,
                 spec_draft_w8: bool = False,
                 spec_attention_impl: Optional[str] = None,
                 trace=None, flight_recorder_cap: int = 64,
                 profile_sample_every: int = 64,
                 fault_injector=None, replica_id: str = "r0",
                 mesh=None, max_prefill_group: Optional[int] = None):
        kinds = _layer_kinds(cfg)       # None for a latent decoder too
        if _is_latent(cfg):
            _refuse_latent(weight_dtype=weight_dtype, kv_dtype=kv_dtype,
                           speculative=speculative, mesh=mesh,
                           hc_mult=getattr(cfg, "hc_mult", 1))
        elif kinds is not None or _has_experts(cfg):
            _refuse_kinded(weight_dtype=weight_dtype, kv_dtype=kv_dtype,
                           speculative=speculative, mesh=mesh,
                           prefix_cache=prefix_cache and kinds is not None
                           and "window" in kinds)
        # multi-replica attribution: stamped on every `prepared` trace
        # event so a Router's merged trace artifact (and
        # tools/trace_report.py's per-replica grouping) can tell which
        # replica's batcher admitted each request
        self.replica_id = str(replica_id)
        # quantized serving (ROADMAP direction 4): weight_dtype="int8"
        # routes params through generation.quantize_for_serving
        # (idempotent on already-quantized trees, so a caller that
        # pre-quantized for mesh placement via
        # generation.quantized_specs, the way inference/llm.py does,
        # passes through); kv_dtype="int8" stores the K/V
        # pools as int8 codes with per-(layer, block) abs-max scales in
        # a sibling scale pool (quantization.kv holds the single-source
        # math), quantized on every prefill/decode commit write and
        # dequantized after the gather (xla) or inside the kernel's
        # block-chunk loop (pallas). Defaults keep the fp path
        # byte-identical to the pre-quantization behavior.
        self.weight_dtype = "fp" if weight_dtype in (None, "fp") \
            else weight_dtype
        if self.weight_dtype not in ("fp", "int8"):
            raise ValueError(
                f"weight_dtype must be 'fp'/'int8' (or None), "
                f"got {weight_dtype!r}")
        if self.weight_dtype == "int8" and not any(
                k.endswith(":scale") for k in params["layers"]):
            params = quantize_for_serving(params, bits=8)
        self.kv_dtype = kvq.resolve_kv_dtype(kv_dtype)
        # ptlint: memo-invariant(weights and model config never change for a live batcher)
        self.params, self.cfg = params, cfg
        # chaos harness: an optional serving.faults.FaultInjector
        # consulted at every device-call boundary (_gate) — fail /
        # hang / pass, deterministically. None in production. The
        # attach notification lets an injector that follows a replica
        # slot across supervisor respawns re-arm per-incarnation rules
        # (hasattr-guarded: any object with a check() works here).
        self._fault = fault_injector
        if fault_injector is not None and hasattr(fault_injector,
                                                  "attach"):
            fault_injector.attach(replica_id)
        # ptlint: memo-invariant(pool geometry is fixed at construction)
        self.B, self.bs = max_batch, block_size
        # resolved once: every traced fn closes over the concrete
        # backend and every compiled-shape memo keys on it — and on the
        # resolved (weight_dtype, kv_dtype) pair, so the warmup ladder
        # a quantized batcher compiles can never be confused with an fp
        # one's (the zero-post-warmup-recompiles gate covers both)
        # ptlint: trace-config
        self.attention_impl = resolve_attention_impl(attention_impl)
        # ptlint: trace-config
        self._qkey = (self.weight_dtype, self.kv_dtype)
        # tensor-parallel serving (ROADMAP direction 1): `mesh` is a
        # serving.tp.MeshConfig — projections output-split (never a
        # contracted dim: bit-identical greedy decode, see tp.py),
        # the paged KV pool sharded on its head axis, scheduler
        # state replicated; GSPMD partitions the SAME step programs
        # from sharded avals, so the host-side scheduler and the AOT
        # warmup ladder are untouched. Every compiled-shape memo key
        # carries the mesh key AFTER the qkey (() when mesh is off —
        # a single-device batcher's keys are byte-identical to a
        # pre-mesh build's, the _skey convention).
        # ptlint: trace-config
        self._mkey = () if mesh is None else mesh.key()
        # ptlint: memo-invariant(fixed at construction; its key() IS _mkey, which rides every memo key)
        self._mesh_cfg = mesh
        # ptlint: memo-invariant(built once from _mesh_cfg — mesh identity rides every memo key via _mkey)
        self._mesh = None
        self._shard_params = None
        self._shard_pool = None
        self._shard_repl = None
        if mesh is not None:
            # attention_impl="pallas" composes: the step programs call
            # the ragged kernel shard_map-wrapped over the head-sharded
            # pool (ragged_attention._shard_specs), so each device runs
            # the per-device Pallas program on its head shard and GSPMD
            # stitches the head axis — no XLA-gather fallback under TP
            from ..serving.tp import build_shardings
            (self._mesh, self._shard_params, self._shard_pool,
             self._shard_repl) = build_shardings(mesh, cfg, self.params)
            self.params = jax.device_put(self.params, self._shard_params)
        # self-speculative decoding (ROADMAP direction 5(b)): a cheap
        # draft — the SAME model truncated to `draft_layers` (None =
        # full depth) — proposes spec_k tokens autoregressively off
        # the committed pool (layer l's KV depends only on layers < l,
        # so the target's pool layers 0..d-1 ARE the d-layer draft's
        # cache: no second weight set, no second pool); the target
        # then scores all k+1 positions in ONE call and accepts the
        # longest greedy-matching prefix plus one corrected token.
        # Verify-then-commit: scoring never writes the pool — accepted
        # rows commit afterwards, row-sequentially, so rejection never
        # poisons the pool / prefix cache / int8 scales and greedy
        # output is identical to plain decode by construction.
        # serving.speculative holds the config/stat types (lazy import
        # below, like trace/profiling — dependency-free module).
        # The draft is a token TREE (spec_tree=[b0, b1, ...]: b0
        # candidates for the next token, b1 children each, ... —
        # spec_k is then DERIVED as the node count; spec_k alone is
        # the chain (1,) * spec_k), optionally reads the draft sweep's
        # weights from an int8 quantization of the truncated stack
        # (spec_draft_w8 — draft bytes halve, verification still runs
        # the target's weights so tokens are unchanged), and can route
        # the verify's score path through the ragged kernel's
        # suffix-slab operand (spec_attention_impl="pallas"; None
        # inherits the batcher's resolved backend: CPU stays on XLA).
        from ..serving.speculative import SpecConfig, SpecStats
        self.speculative = bool(speculative)
        # ptlint: memo-invariant(frozen at construction; its key() rides _skey)
        self._spec_cfg = SpecConfig(spec_k, draft_layers,
                                    num_layers=cfg.num_hidden_layers,
                                    tree=spec_tree,
                                    draft_w8=spec_draft_w8)
        self.spec_k = self._spec_cfg.k
        self._draft_depth = self._spec_cfg.depth(cfg.num_hidden_layers)
        # ptlint: memo-invariant(resolved once at construction; rides _skey)
        self.spec_attention_impl = self.attention_impl \
            if spec_attention_impl is None \
            else resolve_attention_impl(spec_attention_impl)
        # draft-from-w8: quantize the truncated layer stack ONCE at
        # construction (int8 codes + per-channel scales — the same
        # weight-only math weight_dtype="int8" serves) so every draft
        # sweep streams int8 weight bytes. Only built when the target
        # itself serves fp weights: an int8 target's layers already
        # ARE the quantized tree and slicing them is free.
        self._spec_dlayers = None
        if self.speculative and self._spec_cfg.draft_w8 \
                and self.weight_dtype == "fp":
            trunc = jax.tree_util.tree_map(
                lambda x: x[:self._draft_depth], params["layers"])
            self._spec_dlayers = quantize_for_serving(
                {"layers": trunc}, bits=8)["layers"]
        # every compiled-shape memo key carries the spec config BEFORE
        # the trailing qkey (() when spec is off — plain batchers' keys
        # are byte-identical to before), so a spec batcher's warmed
        # ladder can never be confused with a plain one's
        # ptlint: trace-config
        self._skey = ((self._spec_cfg.key(cfg.num_hidden_layers)
                       + (self.spec_attention_impl,))
                      if self.speculative else ())
        self.spec = SpecStats()
        self._spec_cache: Dict[Tuple, Any] = {}
        self._spec_draft_fn = None
        self._spec_verify_fn = None
        # per-request spec opt-out (engine quarantine's plain-decode
        # fallback for victims of a failed spec tick) + the [B] device
        # mirror of per-slot participation, invalidated on admit/retire
        self._no_spec: set = set()
        self._spec_ok_dev = None
        self.max_total = max_total_len
        # the most rows one prefill call batches (None = the batch
        # width): bounds the widest step program's activations and the
        # warm-up ladder, where admissions never come max_batch at once
        # ptlint: memo-invariant(fixed at construction)
        self._group_cap = max_batch if max_prefill_group is None \
            else max(1, min(int(max_prefill_group), max_batch))
        # ptlint: memo-invariant(pool geometry is fixed at construction)
        self.M = -(-max_total_len // block_size)
        self.max_new = max_new_tokens
        # ptlint: memo-invariant(eos id is fixed at construction)
        self.eos = eos_token_id
        # ptlint: memo-invariant(decode chunk length is fixed at construction)
        self.chunk = chunk
        # prefill bucket ladder: suffixes pad to the smallest bucket that
        # fits and longer ones split into largest-bucket chunks, so every
        # admission hits one of a FIXED set of compiled shapes instead of
        # tracing per prompt length. None = auto power-of-two ladder
        # (8, 16, ... capped by max_prefill_bucket and the table span);
        # an empty sequence disables bucketing (exact shapes — one
        # compile per distinct suffix length, the pre-bucketing behavior)
        if prefill_buckets is None:
            # the top bucket never exceeds the table span — no suffix
            # can be longer than max_total_len, so a bigger bucket would
            # only buy pad tokens (the cap itself may be non-pow2)
            cap = max(1, min(int(max_total_len), int(max_prefill_bucket)))
            ladder, b = [], 8
            while b < cap:
                ladder.append(b)
                b *= 2
            ladder.append(cap)
            self._buckets: Tuple[int, ...] = tuple(sorted(set(ladder)))
        else:
            self._buckets = tuple(sorted({int(x) for x in prefill_buckets}))
            if any(x < 1 for x in self._buckets):
                raise ValueError("prefill_buckets must be positive")
        self._prefill_fns: Dict[bool, Any] = {}     # cold -> jitted fn
        self._prefill_cache: Dict[Tuple[int, int, bool, str], Any] = {}
        self.prefill_pad_tokens = 0
        # fused prefill+decode: admissions landing mid-decode piggyback
        # up to `fused_units` prefill units on the decode chunk call
        # instead of stalling every in-flight slot behind a standalone
        # prefill
        self._fused = bool(fused_prefill)
        if int(fused_units) < 1:
            raise ValueError("fused_units must be >= 1")
        self.fused_units = int(fused_units)
        self._fused_fn = None
        self._fused_cache: Dict[Tuple[int, int, str], Any] = {}
        # the plain decode chunk, AOT-compiled like the prefill shapes
        # (warmup covers it, so a decode-only stretch after a fused
        # stretch never pays a first-call compile)
        self._chunk_cache: Dict[Tuple[int, str], Any] = {}
        # prepared-but-not-fully-prefilled admissions: [record, chunks
        # done] — the record's slot and blocks are reserved for the
        # whole mid-stream prefill (free_slots counts them taken)
        self._pending: List[List] = []
        self.fused_steps = 0          # piggybacked prefill calls
        self.fused_unit_count = 0     # prefill units those calls carried
        self.decode_stall_steps = 0   # standalone prefills that stalled
        # observed real chunk lengths (len -> count): the data a
        # workload-specific bucket ladder is fitted from (bucket_tuner)
        self.prefill_suffix_hist: Dict[int, int] = {}
        # KV-transfer accounting (serving/kvtransfer.py): snapshots
        # exported/imported through this batcher plus a host count of
        # prefill rows actually computed — the disaggregation tests'
        # "decode replica ran ZERO prefill chunks" gate reads these
        self.pool_rebuilds = 0      # `_drop_lost_pool`
        self.exported_kv = 0
        self.imported_kv = 0
        self.imported_kv_bytes = 0
        self.prefill_chunk_calls = 0
        # observability: `trace` is an optional serving.trace.TraceSink
        # (per-request timelines — prefill chunk / retire events emit
        # through it, keyed by rid); the flight recorder is ALWAYS on —
        # one bounded host-side record per step tick, written BEFORE
        # the device call so a failing tick is the last record in the
        # ring. Imported lazily like the prefix cache: trace.py is
        # dependency-free but lives in serving/, and nlp must not pull
        # the serving package eagerly.
        from ..serving.profiling import StepProfiler
        from ..serving.trace import FlightRecorder, TraceSink
        # device-time attribution: every tick that reads its result back
        # hands the time from issue to read-back to bounded per-shape
        # histograms (profile_sample_every=0 turns that off; no value
        # costs a sync) — see _Tick; only an armed capture window fences
        self.profiler = StepProfiler(sample_every=profile_sample_every)
        if trace is True:
            # mirror the engine's bool API: True means "a default sink"
            trace = TraceSink()
        elif trace is False:
            trace = None
        elif trace is not None and not hasattr(trace, "emit"):
            # reject now, not as an AttributeError mid-step that would
            # surface as a device failure and abort in-flight requests
            raise TypeError(
                f"trace must be a serving.trace.TraceSink, True/False, "
                f"or None — got {type(trace).__name__}")
        self._trace = trace
        self.flight = FlightRecorder(cap=flight_recorder_cap)
        nb = num_blocks or (max_batch * self.M)
        # two kinds of GQA layer (window and full) share ONE pool and ONE
        # table row a slot (`KVLayout`): the full layers' chains come
        # from `alloc` as ever, the window layers' rings from `walloc`,
        # whose capacity gives every slot a whole ring, so that only the
        # full kind can ever defer an admission
        layout = None
        if kinds is not None:
            if not self._buckets:
                raise ValueError(
                    "layers of several kinds need a prefill bucket ladder: "
                    "the widest chunk sizes the window layers' ring")
            L = cfg.num_hidden_layers
            n_win = cfg.layer_kinds.count("window")
            ring = ring_blocks(cfg.sliding_window, self._buckets[-1],
                               block_size) if n_win else 0
            ring = min(ring, self.M)    # no sequence outgrows its table
            layout = KVLayout(
                full_layers=L - n_win, window_layers=n_win, full_blocks=nb,
                window_blocks=max_batch * ring,
                width=self.M, ring=ring)
        # ptlint: memo-invariant(pool geometry is fixed at construction)
        self._layout = layout
        self.walloc = BlockAllocator(layout.window_blocks) \
            if layout is not None and layout.window_layers else None
        # ptlint: memo-invariant(pool geometry is fixed at construction)
        self._table_width = self.M + (layout.ring if layout else 0)
        if prefix_cache:
            # vLLM-style automatic prefix caching: a trie over full-block
            # token contents + a refcounted pool, so admissions sharing a
            # prompt prefix reuse its KV blocks and prefill only their
            # suffix (serving/cache.py has the subsystem overview).
            # Imported here, not at module top: cache.py is dependency-
            # free but lives in serving/, and this module must not pull
            # the serving package eagerly (serving -> nlp is the lazy
            # direction the engine already relies on)
            from ..serving.cache import PrefixCacheIndex
            self._pcache: "Optional[PrefixCacheIndex]" = \
                PrefixCacheIndex(block_size)
            self.alloc: BlockAllocator = RefcountingBlockAllocator(
                nb, on_evict=self._pcache.evict)
        else:
            self._pcache = None
            self.alloc = BlockAllocator(nb)
        self.cache = self._empty_kv()
        self.active = [False] * max_batch
        self.slot_req: List[Optional[int]] = [None] * max_batch
        self.slot_blocks: List[Optional[List[int]]] = [None] * max_batch
        self.slot_ring: List[Optional[List[int]]] = [None] * max_batch
        self.slot_tokens: List[Optional[List[int]]] = [None] * max_batch
        self.budget = [0] * max_batch
        self.stop = [-1] * max_batch          # per-slot stop id (-1 = none)
        # device mirrors of (active, budget, stop): the decode chunk both
        # consumes and RETURNS them, so steady-state decoding re-uploads
        # nothing (SYNC001) — admission/retirement null the mirror and the
        # next step refreshes it from the host lists
        self._dev_state = None
        self.cur_tok = jnp.zeros((max_batch,), jnp.int32)
        self.queue: List = []
        self.outputs: Dict[int, List[int]] = {}
        self._next_rid = 0
        self._chunk_fn = None
        self._delivered: Dict[int, int] = {}   # rid -> tokens handed out
        self._just_finished: List[int] = []

    def submit(self, tokens, stop_token_id: Optional[int] = None,
               max_new_tokens: Optional[int] = None,
               speculative: Optional[bool] = None) -> int:
        """Queue a request. `stop_token_id` finishes THIS request early
        when emitted (in addition to the batcher-wide eos); the slot's
        blocks return to the pool on finish. `max_new_tokens` caps this
        request's budget (must be <= the batcher-wide max — the block
        table width is sized for it). `speculative=False` opts THIS
        request out of the spec pipeline (its verify rows ride along
        with acceptance forced to 0, i.e. plain greedy decode — the
        engine's quarantine fallback for victims of a failed spec
        tick); None inherits the batcher default."""
        toks = list(map(int, tokens))
        mn = self.validate(len(toks), max_new_tokens)
        rid = self._next_rid
        self._next_rid += 1
        stop = -1 if stop_token_id is None else int(stop_token_id)
        if speculative is False:
            self._no_spec.add(rid)
        self.queue.append((rid, toks, stop, mn))
        self.outputs[rid] = []
        self._delivered[rid] = 0
        return rid

    def validate(self, prompt_len: int,
                 max_new_tokens: Optional[int] = None) -> int:
        """Check a request's shape against this batcher's static sizing;
        returns the resolved max_new budget. The ONE place the sizing
        rules live — submit() and the serving layer both use it."""
        mn = self.max_new if max_new_tokens is None else int(max_new_tokens)
        if not 1 <= mn <= self.max_new:
            raise ValueError(
                f"max_new_tokens {mn} out of range [1, {self.max_new}]")
        if prompt_len + mn > self.max_total:
            raise ValueError(
                f"prompt of {prompt_len} + max_new {mn} exceeds "
                f"max_total_len {self.max_total}")
        return mn

    def blocks_needed(self, prompt_len: int,
                      max_new_tokens: Optional[int] = None,
                      tokens: Optional[Sequence[int]] = None) -> int:
        """Pool blocks a request of this shape takes FROM the pool while
        in flight. With `tokens` and prefix caching on, blocks the cache
        already holds live (refcount >= 1, pinned by another in-flight
        request) don't count — admission shares them instead of
        allocating. Cached refcount-0 matches DO still count: reviving
        one consumes a unit of `free_blocks` (free + cached) just like a
        fresh allocation, so the defer logic's `needed <= free_blocks`
        comparison stays exact either way."""
        mn = self.max_new if max_new_tokens is None else int(max_new_tokens)
        need = -(-(prompt_len + mn) // self.bs)
        # a kinded pool: this is the FULL layers' chain, the kind whose
        # pool can run out; the window layers' ring is
        # `ring_blocks_needed`, bounded a slot and provisioned for every
        # slot, and `_drain_queue` holds an admission to both
        if tokens is not None and self._pcache is not None:
            matched, _, _ = self._match_cached(list(tokens))
            need -= sum(1 for b in matched if self.alloc.refcount(b) > 0)
        # NOTE: block COUNTS are kv_dtype-invariant by construction —
        # the int8 scale pool is indexed by the same block ids (one
        # scale slot per pool block, allocated and freed with it), so
        # cached-aware deferral admits identically under "fp" and
        # "int8". What changes is bytes per block: kv_block_bytes()
        # below is the single source for that, scale overhead included.
        return need

    def ring_blocks_needed(self, prompt_len: int,
                           max_new_tokens: Optional[int] = None) -> int:
        """Blocks of the window layers' ring a request of this shape
        holds while in flight: its whole length in blocks, at most the
        ring (`KVLayout.ring`), however long it grows; 0 where no layer
        has a window."""
        if self.walloc is None:
            return 0
        mn = self.max_new if max_new_tokens is None else int(max_new_tokens)
        return min(-(-(prompt_len + mn) // self.bs), self._layout.ring)

    def alloc_stats(self) -> Dict[str, int]:
        """The allocator's `stats()`, of both kinds of block where the
        pool is kinded: the full layers' chains under the usual names,
        the window layers' rings under `window_*`."""
        out = dict(self.alloc.stats())
        if self.walloc is not None:
            out.update({"window_" + k: v
                        for k, v in self.walloc.stats().items()})
        return out

    def _kv_blocks_in_use(self) -> Dict[str, int]:
        """Blocks in use of each kind, for a tick's flight record; empty
        where the pool is of one kind."""
        if self._layout is None:
            return {}
        return {"kv_full_blocks": self.alloc.stats()["blocks_in_use"],
                "kv_window_blocks": 0 if self.walloc is None
                else self.walloc.stats()["blocks_in_use"]}

    # -- quantized-serving byte accounting --------------------------------
    def _kv_row_bytes(self) -> int:
        """K and V of one token in one GQA layer, as the pool holds them."""
        cfg = self.cfg
        return (2 * cfg.num_key_value_heads * cfg.head_dim
                * jnp.dtype(cfg.dtype).itemsize)

    def kv_block_bytes(self) -> int:
        """HBM bytes ONE pool block occupies (all layers, K+V pools,
        int8 scale-pool overhead included) — quantization.kv's
        kv_block_bytes under this batcher's geometry and kv_dtype. In a
        kinded pool: one block of a sequence's CHAIN, over the full
        layers that keep one (a ring's bytes: `kv_ring_bytes`)."""
        cfg = self.cfg
        if _is_latent(cfg):
            from . import mla
            return mla.kv_block_bytes(cfg, self.bs)
        if self._layout is not None:
            return self._layout.full_layers * self.bs * self._kv_row_bytes()
        return kvq.kv_block_bytes(
            cfg.num_hidden_layers, self.bs, cfg.num_key_value_heads,
            cfg.head_dim, self.kv_dtype,
            fp_itemsize=jnp.dtype(cfg.dtype).itemsize)

    def kv_ring_bytes(self) -> int:
        """HBM bytes the window layers hold for ONE sequence at most, its
        whole ring over those layers; 0 where no layer has a window."""
        if self.walloc is None:
            return 0
        lay = self._layout
        return lay.window_layers * lay.ring * self.bs * self._kv_row_bytes()

    def kv_pool_bytes(self) -> int:
        """Total KV pool footprint: capacity blocks x kv_block_bytes()
        (a kinded pool: both kinds' blocks) — equals the device arrays'
        nbytes sum (asserted in tests)."""
        if self._layout is not None:
            return self._layout.total_blocks * self.bs * self._kv_row_bytes()
        return self.alloc.num_blocks * self.kv_block_bytes()

    def kv_cached_bytes(self) -> int:
        """Bytes held by reclaimable (refcount-0, prefix-cached) blocks
        — the reusable-KV share of the pool a router/dashboard reads."""
        return self.alloc.stats().get("cached_blocks", 0) \
            * self.kv_block_bytes()

    def kv_bytes_per_token(self) -> float:
        """HBM bytes one cached token costs (and one decode-step gather
        moves per live token): kv_block_bytes / block_size.
        tests/test_quantized_serving.py holds int8 <= 0.55x fp on this
        number. In a kinded pool: what a token costs once the sequence
        is longer than the ring, the full layers' rows alone."""
        return self.kv_block_bytes() / self.bs

    def weight_bytes(self) -> int:
        """Resident parameter bytes (codes + scales for a w8 tree) —
        host-side .nbytes sum, no device sync."""
        return sum(x.nbytes for x in jax.tree_util.tree_leaves(self.params))

    def _match_cached(self, toks: List[int]
                      ) -> Tuple[List[int], int, Optional[int]]:
        """Prefix-cache lookup for a prompt: (matched block chain,
        cached token count, copy-on-write source block or None).

        Full-block matches are shared as-is. When the match covers the
        WHOLE prompt there is no suffix left to prefill, yet sampling
        needs the last position's logits — so the final matched block is
        demoted to a copy-on-write source: admission copies its KV into
        a private block and recomputes only the prompt's last token
        there (cached length P-1), instead of recomputing the whole
        block. The partially-filled tail is thus never shared."""
        if self._pcache is None:
            return [], 0, None
        matched = self._pcache.match(toks)
        cached_len = len(matched) * self.bs
        cow_src = None
        if matched and cached_len == len(toks):
            cow_src = matched[-1]
            matched = matched[:-1]
            cached_len = len(toks) - 1
        return matched, cached_len, cow_src

    def prefix_cached_tokens(self, tokens: Sequence[int]) -> int:
        """Prompt tokens the prefix cache can serve RIGHT NOW (0 with the
        cache off). Cheap trie walk, no refcount moves — the scheduler's
        cache-aware admission preference reads this."""
        if self._pcache is None:
            return 0
        _, cached_len, _ = self._match_cached(list(tokens))
        return cached_len

    @property
    def prefill_buckets(self) -> Tuple[int, ...]:
        """The prefill bucket ladder (empty = bucketing disabled)."""
        return self._buckets

    @property
    def prefill_compile_count(self) -> int:
        """Distinct prefill shapes compiled so far — standalone (group,
        bucket, phase) AND fused (rows, bucket) executables. Flat after
        warmup is the whole point of bucketing: each shape compiles
        exactly once for the batcher's lifetime."""
        return len(self._prefill_cache) + len(self._fused_cache)

    @property
    def compile_count(self) -> int:
        """EVERY compiled device-step shape: the prefill/fused ladder
        plus the plain decode chunk executable plus the speculative
        draft/verify pair: the memos `_aot` fills, a `compile_log`
        record each. The zero-post-warmup-recompiles gate reads this
        one (a decode-only stretch after a fused stretch must not
        compile either)."""
        return (self.prefill_compile_count + len(self._chunk_cache)
                + len(self._spec_cache))

    def prefix_stats(self) -> Dict[str, Any]:
        """Prefix-cache counters for the serving metrics surface:
        hits/misses/hit_tokens/hit_rate from the index plus the
        allocator's cached-block and eviction counts. `enabled` False
        (and nothing else) when the batcher runs without the cache."""
        if self._pcache is None:
            return {"enabled": False}
        d: Dict[str, Any] = {"enabled": True}
        d.update(self._pcache.stats())
        astats = self.alloc.stats()
        d["cached_blocks"] = astats.get("cached_blocks", 0)
        d["evictions"] = astats.get("evicted_blocks", 0)
        return d

    def release(self, rid: int) -> None:
        """Drop a finished/aborted request's retained output list. The
        long-lived serving engine calls this once tokens are delivered —
        without it `outputs` grows with every request ever served.
        (Standalone run() callers read outputs afterwards, so the
        batcher never drops entries on its own.)"""
        self.outputs.pop(rid, None)
        self._delivered.pop(rid, None)

    def free_slots(self) -> int:
        """Batch slots available to new admissions. Queued-but-not-yet-
        prefilled requests count as taken, and so do slots reserved by
        a prepared admission whose (possibly multi-chunk, mid-stream)
        prefill has not committed yet — without the pending term a
        fused admission landing during a chunked prefill could
        oversubscribe max_batch. Never negative: callers may queue past
        capacity directly via submit(), but a slot deficit still means
        zero slots for anyone new."""
        return max(0, self.active.count(False) - len(self.queue)
                   - len(self._pending))

    def abort(self, rid: int) -> bool:
        """Cancel a request: drop it from the queue, or retire its slot
        mid-decode so its blocks return to the pool immediately. Already-
        generated tokens stay in `outputs`. Returns False when rid is
        unknown or already finished."""
        for i, entry in enumerate(self.queue):
            if entry[0] == rid:
                del self.queue[i]
                self._delivered.pop(rid, None)
                self._no_spec.discard(rid)
                return True
        for i, (rec, _done) in enumerate(self._pending):
            if rec.rid == rid:
                # prepared (possibly mid-stream chunked prefill): undo
                # like a failed prefill — unlink index registrations and
                # return the blocks; any KV already written there is
                # dead content in freed blocks
                self._rollback([rec])
                del self._pending[i]
                self._delivered.pop(rid, None)
                self._no_spec.discard(rid)
                self._requeue_poisoned(rec)
                return True
        for slot in range(self.B):
            if self.active[slot] and self.slot_req[slot] == rid:
                self._retire(slot)
                # an abort is the caller's bookkeeping, not a completion
                self._just_finished.remove(rid)
                self._delivered.pop(rid, None)
                return True
        return False

    def _requeue_poisoned(self, rec: "_Admission") -> None:
        """Aborting the pending `rec` unlinked and freed `rec.inserted`
        before anyone wrote their KV; a co-pending record whose matched
        chain (or COW source) leans on those blocks would skip
        prefilling a prefix NO ONE will ever compute — silent garbage
        tokens. Roll back the pending tail from the first such record
        and push the requests back onto the queue front (original
        order), so the next drain re-prepares them against the real
        index state. Requeueing the whole tail keeps admission order
        and absorbs cascades (a rolled-back record's own insertions
        poison later matches too). Safe to fully undo: only the head
        record can be mid-stream, and the head was prepared before
        `rec`, so every tail record's prefill has not started."""
        poisoned = set(rec.inserted)
        cut = None
        for i, (sib, _done) in enumerate(self._pending):
            refs = set(sib.matched)
            if sib.cow_src is not None:
                refs.add(sib.cow_src)
            if refs & poisoned:
                cut = i
                break
        if cut is None:
            return
        victims = [e[0] for e in self._pending[cut:]]
        self._rollback(victims)
        del self._pending[cut:]
        for v in victims:
            # timeline visibility for the cascade: without this event a
            # rolled-back sibling's re-preparation looks like a second
            # unexplained "prepared" in trace_report
            self._trace_emit(v.rid, "requeued",
                             reason="poisoned_sibling")
        self.queue[:0] = [(v.rid, v.toks, v.stop, v.mn) for v in victims]

    # -- KV transfer (serving/kvtransfer.py holds the container) ----------
    def _refuse_latent_transfer(self, what: str) -> None:
        if _is_latent(self.cfg):
            raise NotImplementedError(
                f"{what}: serving.kvtransfer.KVSnapshot carries a K and a "
                f"V pool slice per block; the latent (MLA) pool is one "
                f"array and has no snapshot form yet")
        if self._layout is not None:
            raise NotImplementedError(
                f"{what}: serving.kvtransfer.KVSnapshot carries one chain "
                f"of blocks over every layer; a kinded pool's window "
                f"rings have no snapshot form yet")

    def kv_fingerprint(self) -> Dict[str, Any]:
        """Model/pool-shape identity a KVSnapshot must match to be
        importable here — kvtransfer.check_compatible compares these
        key-for-key so a cross-topology mistake (different model,
        kv_dtype or block size) fails at the handoff boundary instead
        of scattering misinterpreted codes into the pool."""
        return {
            "num_layers": int(self.cfg.num_hidden_layers),
            "num_key_value_heads": int(
                getattr(self.cfg, "num_key_value_heads", 1)),
            "head_dim": int(getattr(self.cfg, "kv_row_width", 0)
                            or self.cfg.head_dim),
            "block_size": self.bs,
            "kv_dtype": self.kv_dtype,
            "pool_dtype": str(self.cache.k.dtype),
        }

    def export_kv(self, rid: int):
        """Snapshot an in-flight request's paged KV into a portable
        host container (serving.kvtransfer.KVSnapshot): ONE coalesced
        device_get over exactly the blocks its chain has written —
        never the whole pool — plus the matching int8 scale entries
        and the host bookkeeping (tokens, remaining budget, stop id)
        an `import_kv` needs to resume decode elsewhere.

        Only an ACTIVE decode slot is exportable: queued/pending
        requests have no KV worth moving (re-submitting the prompt is
        strictly cheaper), and finished ones have released their
        blocks — ValueError for both. Migration boundary, not the
        decode hot path: the device pull below IS the transfer."""
        self._refuse_latent_transfer("export_kv")
        slot = None
        for s in range(self.B):
            if self.active[s] and self.slot_req[s] == rid:
                slot = s
                break
        if slot is None:
            raise ValueError(
                f"request {rid} holds no active decode slot — only "
                f"in-flight decode state is exportable")
        gen = list(self.outputs.get(rid, []))
        prompt = list(self.slot_tokens[slot] or [])
        # the last emitted token's KV is not written yet (decode writes
        # token t's KV while producing t+1) — the same arithmetic
        # _retire uses when registering the prefix
        written = len(prompt) + len(gen) - 1
        # ptlint: disable=SYNC001 — one guard readback at the migration boundary, never per step
        if written != int(self.cache.lengths[slot]):
            raise RuntimeError(
                f"slot {slot} device length diverged from host "
                f"bookkeeping — mid-commit state is not exportable")
        nw = -(-written // self.bs)
        chain = list(self.slot_blocks[slot][:nw])
        idx = jnp.asarray(chain)
        pulls = [self.cache.k[:, idx], self.cache.v[:, idx]]
        if self.cache.k_scale is not None:
            pulls += [self.cache.k_scale[:, idx],
                      self.cache.v_scale[:, idx]]
        # ptlint: disable=SYNC001 — the coalesced chain gather IS the export
        host = jax.device_get(tuple(pulls))
        ks, vs = (host[2], host[3]) if len(host) == 4 else (None, None)
        from ..serving.kvtransfer import KVSnapshot
        snap = KVSnapshot(
            k=host[0], v=host[1], k_scale=ks, v_scale=vs,
            tokens=prompt + gen, prompt_len=len(prompt),
            budget=int(self.budget[slot]),
            stop_token_id=int(self.stop[slot]),
            tail_valid=written - (nw - 1) * self.bs,
            fingerprint=self.kv_fingerprint(),
            src_blocks=chain, src_replica=self.replica_id)
        self.exported_kv += 1
        self._trace_emit(rid, "exported", slot=slot, blocks=nw,
                         bytes=snap.nbytes, tokens=len(snap.tokens))
        return snap

    def import_blocks_needed(self, snap) -> int:
        """Pool blocks `import_kv(snap)` will draw — the head-of-line
        check an engine's import queue runs before popping. Matches the
        source batcher's own sizing: written + the unwritten last token
        + the remaining budget is exactly P + max_new there."""
        return -(-(len(snap.tokens) + int(snap.budget)) // self.bs)

    def import_kv(self, snap, speculative: bool = False,
                  on_rid=None) -> int:
        """Adopt a KVSnapshot: allocate a fresh chain, scatter the
        block codes AND their int8 scales (transferred entries keep
        their exact scales; the unwritten tail blocks get the 0.0
        never-written sentinel, exactly like _prepare_admission's
        fresh-block reset — grow-only rescale discipline intact),
        register the written full blocks in the prefix index so
        siblings hit, and activate a slot that resumes decode at
        len(tokens) with ZERO prefill chunks. Host-side .at[].set pool
        edits only — no compiled-shape memo key moves, so post-warmup
        recompiles stay 0. Returns the new rid; its outputs list is
        pre-seeded with the snapshot's generated tokens and
        `_delivered` already covers them, so nothing re-emits.

        `speculative=False` (default) opts the imported request out of
        the spec pipeline: the draft state did not travel, and plain
        greedy decode keeps cross-hop bitwise parity unconditionally
        (spec is greedy-identical by construction, so True is safe too
        — the default just removes the reasoning burden).

        `on_rid` (optional) is called with the assigned rid before any
        trace event fires — the engine uses it to alias the rid onto
        the request's trace timeline.

        Raises ValueError on fingerprint/shape mismatch and
        RuntimeError when no slot or blocks are free — callers gate on
        `free_slots()` / `import_blocks_needed()` first."""
        self._refuse_latent_transfer("import_kv")
        from ..serving import kvtransfer
        problems = kvtransfer.check_compatible(snap.fingerprint,
                                               self.kv_fingerprint())
        if problems:
            raise ValueError(
                "KV snapshot incompatible with this batcher: "
                + "; ".join(problems))
        toks = [int(t) for t in snap.tokens]
        P = int(snap.prompt_len)
        gen = toks[P:]
        budget = int(snap.budget)
        if not gen:
            raise ValueError(
                "snapshot carries no generated token — export happens "
                "at or after the first decode commit")
        if budget < 1:
            raise ValueError(
                "snapshot budget exhausted — the source should have "
                "retired this request, nothing to resume")
        written = len(toks) - 1
        nw = -(-written // self.bs)
        if nw != int(snap.k.shape[1]):
            raise ValueError(
                f"snapshot carries {int(snap.k.shape[1])} blocks but "
                f"its {written} written tokens span {nw}")
        total = written + 1 + budget      # == P + max_new at the source
        if total > self.max_total:
            raise ValueError(
                f"resumed request needs {total} total tokens, over "
                f"this batcher's max_total_len {self.max_total}")
        need = -(-total // self.bs)
        reserved = {e[0].slot for e in self._pending}
        slot = None
        for s in range(self.B):
            if not self.active[s] and s not in reserved:
                slot = s
                break
        if slot is None:
            raise RuntimeError("no free batch slot for KV import")
        if need > self.alloc.free_blocks:
            raise RuntimeError(
                f"KV import needs {need} blocks, pool has "
                f"{self.alloc.free_blocks} free")
        fresh = self.alloc.allocate(need)
        # scatter the chain's codes into the fresh blocks — the same
        # host-side .at[].set idiom as _apply_cow, nothing traced
        hk, hv = snap.k, snap.v
        idx = jnp.asarray(fresh[:nw])
        cache = self.cache._replace(
            k=self.cache.k.at[:, idx].set(
                jnp.asarray(hk, self.cache.k.dtype)),
            v=self.cache.v.at[:, idx].set(
                jnp.asarray(hv, self.cache.v.dtype)))
        if cache.k_scale is not None:
            # fingerprint equality guarantees the snapshot carries
            # scales whenever the local pool is quantized
            hks, hvs = snap.k_scale, snap.v_scale
            sks = jnp.zeros((cache.k_scale.shape[0], need), jnp.float32)
            sks = sks.at[:, :nw].set(jnp.asarray(hks, jnp.float32))
            svs = jnp.zeros((cache.v_scale.shape[0], need), jnp.float32)
            svs = svs.at[:, :nw].set(jnp.asarray(hvs, jnp.float32))
            fidx = jnp.asarray(fresh)
            cache = cache._replace(
                k_scale=cache.k_scale.at[:, fidx].set(sks),
                v_scale=cache.v_scale.at[:, fidx].set(svs))
        row = fresh + [0] * (self.M - need)
        self.cache = cache._replace(
            table=cache.table.at[slot].set(jnp.asarray(row, jnp.int32)),
            lengths=cache.lengths.at[slot].set(written))
        rid = self._next_rid
        self._next_rid += 1
        if on_rid is not None:
            # caller hook fired the moment the rid exists — the engine
            # aliases rid→trace timeline here so the "imported" emit
            # below lands on the request's timeline instead of
            # auto-opening a phantom rid lane
            on_rid(rid)
        self.outputs[rid] = list(gen)
        self._delivered[rid] = len(gen)
        self.active[slot] = True
        self.slot_req[slot] = rid
        self.slot_blocks[slot] = list(fresh)
        self.slot_tokens[slot] = toks[:P]
        self.budget[slot] = budget
        self.stop[slot] = int(snap.stop_token_id)
        self.cur_tok = self.cur_tok.at[slot].set(gen[-1])
        self._dev_state = None           # slot occupancy changed
        self._spec_ok_dev = None
        if not speculative:
            self._no_spec.add(rid)
        if self._pcache is not None:
            # the written prefix's full blocks (prompt AND generated,
            # like _retire's registration) become visible to siblings
            # immediately; their KV is already written, so mark_cached
            # now — the post-_commit discipline, not the prepared one
            n_full = written // self.bs
            if n_full:
                self.alloc.mark_cached(self._pcache.insert(
                    toks[:n_full * self.bs], fresh[:n_full]))
        self.imported_kv += 1
        self.imported_kv_bytes += snap.nbytes
        self._trace_emit(rid, "imported", slot=slot, blocks=need,
                         bytes=snap.nbytes, resumed_tokens=len(gen),
                         src_replica=snap.src_replica)
        return rid

    # -- internals --------------------------------------------------------
    def _upload_slot_state(self):
        """Host slot lists → device arrays. Deliberately OUTSIDE step()'s
        hot path: it runs only when admission/retirement invalidated the
        mirror, so lock-step decode pays zero host→device uploads."""
        # ptlint: disable=SYNC001 — this IS the cached-mirror refresh
        # the rule asks for: it uploads only when admission/retirement
        # invalidated `_dev_state`, never per decode step
        return (jnp.asarray(self.active),
                jnp.asarray(self.budget, jnp.int32),  # ptlint: disable=SYNC001 — mirror refresh (see above)
                jnp.asarray(self.stop, jnp.int32))  # ptlint: disable=SYNC001 — mirror refresh (see above)

    # -- observability (host-side bookkeeping ONLY: no device values,
    #    no syncs — SYNC001's derived hot set covers them) ----------------
    def _trace_emit(self, rid: int, kind: str, dur=None, **attrs) -> None:
        """Emit one per-request trace event (no-op without a sink).
        Every attr must already be a plain host value — a jax array
        here would be a hidden device sync on the hot path."""
        if self._trace is not None:
            self._trace.emit(rid, kind, dur=dur, **attrs)

    def _trace_chunks(self, items, bucket: int, fused: bool,
                      dur: float, device_dur=None) -> None:
        """Emit one prefill_chunk event per packed row: which suffix
        span ran, at which bucket (and what padding that cost), fused
        onto the decode chunk or standalone, cold or continuing — and,
        on the FIRST chunk, how many prompt tokens the prefix cache
        skipped (the cached-prefix skip the timeline makes visible).
        `device_dur` (seconds) rides along when the tick synced and the
        profiler records: the call's DEVICE wall (issue to read-back)
        next to its host wall, so timelines attribute regressions to
        the kernel vs host scheduling."""
        self.prefill_chunk_calls += len(items)
        if self._trace is None:
            return
        for rec, start, end in items:
            extra = {} if device_dur is None \
                else {"device_dur": round(device_dur, 6)}
            self._trace.emit(
                rec.rid, "prefill_chunk", dur=dur, slot=rec.slot,
                start=start, end=end, bucket=bucket,
                pad=bucket - (end - start), fused=fused, cold=start == 0,
                cached_tokens=rec.cached_len if start == rec.cached_len
                else 0, **extra)

    def _decode_ctx(self, slots) -> List[int]:
        """Keys each of `slots` attends to in this tick's first decode
        step: its prompt, its tokens so far, the one being written."""
        return [len(self.slot_tokens[s]) + len(self.outputs[self.slot_req[s]])
                for s in slots]

    def _decode_fields(self, slots) -> Dict[str, Any]:
        """A decode or fused tick's record of its decoding rows: their
        contexts (`decode_ctx`) and, where window layers keep rings, how
        many of them no longer fit theirs, which has wrapped under them
        (`ring_wrapped_rows`)."""
        ctx = self._decode_ctx(slots)
        if self.walloc is None:
            return {"decode_ctx": ctx}
        cap = self._layout.ring * self.bs
        return {"decode_ctx": ctx,
                "ring_wrapped_rows": sum(c > cap for c in ctx)}

    def _note_counters(self, tick: "_Tick", stats, prefill_rows: int = 0,
                       bucket: int = 0) -> None:
        """A decode or fused tick's counters, read back with its tokens,
        onto its record; and, where the latent kernel counted the items
        its calls of ONE layer walked (`attn_work_steps`), beside them
        the full grid, live or not, that those calls span
        (`attn_grid_steps`: `chunk` decode calls `[B, 1]` and, on a fused
        tick, the `[prefill_rows, bucket]` call)."""
        tick.note(stats)
        if stats and "attn_work_steps" in stats:
            steps = self.chunk * self._attn_grid_steps(self.B, 1)
            if prefill_rows:
                steps += self._attn_grid_steps(prefill_rows, bucket)
            tick.note({"attn_grid_steps": steps})

    def _attn_grid_steps(self, R: int, P: int) -> int:
        """The full grid of ONE layer's `[R, P]` kernel call over this
        batcher's table (over a kinded pool one layer of each kind,
        summed), in the steps its work list counts (chunks of `nb`
        blocks)."""
        from .ragged_attention import attn_grid_steps, gqa_tiling_args
        if _is_latent(self.cfg):
            return attn_grid_steps(R, P, self.M)
        cfg, pool = self.cfg, self.cache.k
        calls = ((self.M, cfg.num_attention_heads),) \
            if self._layout is None else (
                (self._layout.width, cfg.heads("full")),
                (self._layout.ring, cfg.heads("window")))
        return sum(attn_grid_steps(R, P, M, **gqa_tiling_args(
            pool.shape[1:], pool.dtype, heads=H)) for M, H in calls)

    def _probe_gate(self, rid: int) -> None:
        """Fault-injection hook of the quarantine probes (a tick's own
        gate is in `_Tick.__enter__`): a no-op without an injector."""
        if self._fault is not None:
            self._fault.check("probe", [rid], probe=True)

    # -- bucketed / chunked / batched prefill -----------------------------
    def _bucket_for(self, S: int) -> int:
        """Smallest ladder bucket that fits a suffix of S tokens; with
        bucketing disabled (empty ladder) the bucket IS the exact length."""
        for b in self._buckets:
            if b >= S:
                return b
        return S

    def _suffix_chunks(self, cached_len: int,
                       P: int) -> List[Tuple[int, int, int]]:
        """Split the still-to-prefill suffix [cached_len, P) into
        (start, end, bucket) chunks: largest-bucket-sized pieces first,
        then one bucketed remainder — bounding per-chunk latency and
        lifting the effective prompt length past one flash pass."""
        out: List[Tuple[int, int, int]] = []
        start = cached_len
        cap = self._buckets[-1] if self._buckets else P - cached_len
        while P - start > cap:
            out.append((start, start + cap, cap))
            start += cap
        out.append((start, P, self._bucket_for(P - start)))
        return out

    def _group_pad(self, G: int) -> int:
        """Pad an admission group to the next power of two (capped at the
        batch width) so burst sizes draw from a fixed shape ladder."""
        return min(_pow2_ceil(max(1, G)), self._group_cap)

    def _mesh_axis(self) -> str:
        """The TP mesh axis name the step builders hand to the
        shard_map-wrapped kernel ("mp" when mesh is off — the kwarg is
        dead then, since `self._mesh` is None)."""
        return "mp" if self._mesh_cfg is None else self._mesh_cfg.axis

    def _build_prefill(self, cold: bool):
        """The one traced prefill: rows [G, Pb] at per-row absolute
        positions against the shared pool. Pure — compile bookkeeping
        lives host-side in `_prefill_exe` (TRACE001)."""
        cfg, impl = self.cfg, self.attention_impl
        mesh, max_, layout = self._mesh, self._mesh_axis(), self._layout

        def serve_prefill_step(params, pools, rows, table, positions,
                               valid, lengths):
            sub = PagedKVCache.of(pools, table, lengths)
            logits, sub = forward_paged(params, rows, sub, positions,
                                        valid, cfg, is_prefill=cold,
                                        attention_impl=impl, mesh=mesh,
                                        mesh_axis=max_, layout=layout)
            return sub.pools, logits

        return self._step_jit(serve_prefill_step)

    def _prefill_exe(self, G: int, Pb: int, cold: bool):
        """Memoized COMPILED prefill per (group, bucket, phase) shape,
        from abstract avals (`_aot`): `warmup_prefill` populates the
        whole ladder without a FLOP and steady-state admission never
        retraces."""
        key = (G, Pb, cold, self.attention_impl) + self._skey \
            + self._qkey + self._mkey
        exe = self._prefill_cache.get(key)
        if exe is None:
            fn = self._prefill_fns.get(cold)
            if fn is None:
                fn = self._build_prefill(cold)
                self._prefill_fns[cold] = fn
            sds, i32 = self._aval, jnp.int32
            exe = self._aot(
                key, fn, sds((G, Pb), i32),
                sds((G, self._table_width), i32), sds((G, Pb), i32),
                sds((G, Pb), jnp.bool_), sds((G,), i32))
            self._prefill_cache[key] = exe
        return exe

    # -- mesh-aware AOT lowering avals ------------------------------------
    def _aval(self, shape, dtype, sharding=None):
        """ShapeDtypeStruct for AOT lowering. With a serving mesh on,
        every aval carries a committed sharding (`sharding` None =
        replicated) so the compiled executable's input layout is
        pinned; mesh off lowers the plain aval — identical programs,
        byte-identical memo keys."""
        if self._mesh is None:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jax.ShapeDtypeStruct(
            shape, dtype,
            sharding=self._shard_repl if sharding is None else sharding)

    def _step_jit(self, fn, writes_pool: bool = True):
        """The jit of step program `fn(params, pools, *rest)`, and THE
        place that decides donation, for every step program alike (no
        builder writes `donate_argnums`): a program that writes the pool
        returns it first among its results and is compiled with `pools`
        (`PagedKVCache.pools`: K, V and the int8 pool's scales) DONATED,
        so the compiler aliases the returned pool onto the argument and
        the scatter writes in the caller's buffer: no whole-pool copy at
        the program's start, one generation of the pool live. Its
        caller's handle is deleted at dispatch (`_Tick.adopt`). The
        table, the lengths and the slot state are never donated; a
        program that only reads the pool (`writes_pool=False`: the
        speculative draft) donates nothing. No option turns this off."""
        return jax.jit(fn, donate_argnums=(1,) if writes_pool else ())

    def _aot(self, key: Tuple, fn, *avals):
        """The executable of step program `fn` (`_step_jit`'s) at the
        parameters' avals, the pool's and `avals`, for the memo under
        `key`: ahead-of-time compilation's three public stages run apart
        in ONE `serve.compile` span and timed into the program's
        `compile_log` record (its `key`: what precedes the backend in the
        memo's, shapes and phase). The record's `alias_bytes` says that
        the donation engaged: the bytes of the results that live in an
        argument's buffer, at least the pool's for a program that writes
        it (under a mesh a device's share of it; 0 where the backend
        gives no analysis)."""
        name, clock = program_name(fn.__name__), compile_log.clock
        short = "/".join(map(str, key[:key.index(self.attention_impl)]))
        with RecordEvent("serve.compile", program=name, key=short), \
                compile_log.program(name, key=short) as rec:
            t0 = clock()
            traced = fn.trace(self._pstruct(), self._pools_aval(), *avals)
            t1 = clock()
            lowered = traced.lower()
            t2 = clock()
            exe = lowered.compile()
            t3 = rec["t"] = clock()
            rec.update(trace_s=t1 - t0, lower_s=t2 - t1, executable_s=t3 - t2,
                       alias_bytes=int(getattr(
                           exe.memory_analysis(), "alias_size_in_bytes", 0)))
        return exe

    def _pstruct(self):
        """Param aval tree for lowering — per-leaf TP shardings when
        the mesh is on (serving.tp's table)."""
        if self._mesh is None:
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype),
                self.params)
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype,
                                              sharding=s),
            self.params, self._shard_params)

    def _pools_aval(self):
        """Aval tree of `PagedKVCache.pools`: K and V on the head axis,
        the int8 scale pools replicated."""
        c = self.cache
        return (self._pool_aval(c.k), self._pool_aval(c.v),
                self._scale_aval(c.k_scale), self._scale_aval(c.v_scale))

    def _empty_kv(self) -> PagedKVCache:
        """A zeroed pool with an empty table (construction, and
        `_drop_lost_pool`'s rebuild), pinned to the serving mesh's
        shardings where there is one."""
        cache = PagedKVCache.of(
            init_pool(self.cfg, self.alloc.num_blocks, self.bs,
                      kv_dtype=self.kv_dtype, layout=self._layout),
            jnp.zeros((self.B, self._table_width), jnp.int32),
            jnp.zeros((self.B,), jnp.int32))
        return cache if self._mesh is None \
            else self._pin_cache_shardings(cache)

    def _pin_cache_shardings(self, cache: PagedKVCache) -> PagedKVCache:
        """Pin a fresh cache's leaves to their serving-mesh shardings
        (the committed layout every compiled step expects; eager pool
        edits — COW copies, import scatters — preserve it)."""
        put = jax.device_put
        return PagedKVCache(
            put(cache.k, self._shard_pool),
            put(cache.v, self._shard_pool),
            put(cache.table, self._shard_repl),
            put(cache.lengths, self._shard_repl),
            None if cache.k_scale is None
            else put(cache.k_scale, self._shard_repl),
            None if cache.v_scale is None
            else put(cache.v_scale, self._shard_repl))

    def _pool_aval(self, pool):
        """AOT-lowering aval of a pool array, None for the V pool a
        latent cache does not have."""
        return None if pool is None else \
            self._aval(pool.shape, pool.dtype, self._shard_pool)

    def _scale_aval(self, scale):
        """AOT-lowering aval for a scale pool: None (no leaves — the fp
        pool's lowered signature is unchanged) or the [L, N] f32 shape
        (replicated under a serving mesh — per-(layer, block) scales
        carry no head axis)."""
        return None if scale is None else \
            self._aval(jnp.shape(scale), scale.dtype)

    def warmup_prefill(self, buckets: Optional[Sequence[int]] = None,
                       group_sizes: Optional[Sequence[int]] = None,
                       modes: Sequence[bool] = (True, False),
                       fused: Optional[bool] = None) -> int:
        """Pre-compile every device-step shape serving can hit — each
        ladder bucket x each power-of-two group size x {cold, cached},
        plus (with fusion on) the fused decode+prefill variant per
        reachable prefill-row count (units x group pad, units up to
        `fused_units`), plus the decode chunk's executable — ahead of
        time (`_aot`: no device compute, a `compile_log` record each).
        After this, steady state never compiles. Returns the number of
        newly compiled shapes. With bucketing disabled only the decode
        chunk warms (exact prefill shapes are unbounded)."""
        ladder = self._buckets if buckets is None else tuple(buckets)
        if group_sizes is None:
            # exactly the shapes _group_pad can ever produce
            group_sizes = {self._group_pad(g)
                           for g in range(1, self._group_cap + 1)}
        n0 = self.compile_count
        for Pb in ladder:
            for G in sorted(set(group_sizes)):
                for cold in modes:
                    self._prefill_exe(int(G), int(Pb), bool(cold))
        warm_fused = self._fused if fused is None else fused
        if warm_fused:
            # total prefill rows a fused call can carry: U consecutive
            # same-bucket units, each padded to the SAME power-of-two
            # group size — the memo normalizes (units, group) to the
            # row count U*G, so coinciding shapes compile once. Only
            # REACHABLE shapes warm: every pending record holds a slot
            # and a fused step needs >= 1 ACTIVE decode slot besides,
            # so a call whose widest unit pads to G (> G//2 records)
            # riding with u-1 more units (>= 1 record each) exists only
            # when that minimum record count fits in max_batch - 1
            rows = set()
            for G in sorted(set(int(g) for g in group_sizes)):
                need_widest = G // 2 + 1 if G > 1 else 1
                for u in range(1, self.fused_units + 1):
                    if need_widest + (u - 1) <= self.B - 1:
                        rows.add(u * G)
            for Pb in ladder:
                for Gt in sorted(rows):
                    self._fused_exe(Gt, int(Pb))
        # the standalone-decode chunk is reachable from ANY workload
        # (incl. a decode-only stretch after a fused stretch) — warm it
        # regardless of ladder/fusion configuration
        self._chunk_exe()
        if self.speculative:
            # the spec draft/verify pair runs every non-fused decode
            # tick — warm both so a spec stretch never retraces
            self._spec_draft_exe()
            self._spec_verify_exe()
        return self.compile_count - n0

    def _prepare_admission(self, slot: int, rid: int, toks: List[int],
                           stop: int, max_new: Optional[int],
                           quiet: bool = False) -> _Admission:
        """Blocks + prefix-cache bookkeeping for one admission, NO model
        compute: share the matched chain, allocate the rest, apply the
        COW clone, and register the prompt's full blocks so same-burst
        siblings hit. The slot stays inactive until `_commit`."""
        P = len(toks)
        mn = self.max_new if max_new is None else max_new
        need = -(-(P + mn) // self.bs)
        # prefix cache: share the matched chain (bumping refcounts — and
        # pinning the COW source so allocate() can't evict it before the
        # copy), then allocate only what the cache didn't supply
        matched, cached_len, cow_src = self._match_cached(toks)
        if cow_src is not None and self.alloc.refcount(cow_src) == 0:
            # a cached (refcount-0) COW source is transiently revived
            # ALONGSIDE its fresh clone — one pool unit more than
            # blocks_needed() promises the defer check. When the pool
            # can't afford it, degrade to recomputing the final block
            # cold instead of blowing up an admission that was told it
            # fits (a live source costs nothing extra: sharing it takes
            # no unit from the pool). Peak draw = fresh allocations +
            # every refcount-0 match revived off the cached list + the
            # transient source.
            draw = (need - len(matched)
                    + sum(1 for b in matched
                          if self.alloc.refcount(b) == 0))
            if self.alloc.free_blocks < draw + 1:
                cow_src = None
                cached_len = len(matched) * self.bs
        pinned = matched + ([cow_src] if cow_src is not None else [])
        if pinned:
            self.alloc.share(pinned)
        try:
            fresh = self.alloc.allocate(need - len(matched))
        except Exception:
            if pinned:
                self.alloc.release(pinned)
            raise
        # the window layers' ring: blocks of its own kind, never shared
        # and never more than the ring however long the sequence
        ring: Sequence[int] = ()
        if self.walloc is not None:
            try:
                ring = self.walloc.allocate(self.ring_blocks_needed(P, mn))
            except Exception:
                self.alloc.release(fresh + pinned)
                raise
        if self.kv_dtype == "int8" and fresh:
            # a recycled block keeps its previous tenant's scale (free
            # is host-side bookkeeping); writing under that inflated
            # scale would quantize this request's KV coarser than a
            # fresh block would — reset to the never-written sentinel
            # so quantization depends only on what THIS request writes
            # (warm == cold stays by construction, whatever the pool's
            # reuse history). Admission path, not the decode hot path.
            idx = jnp.asarray(fresh)
            self.cache = self.cache._replace(
                k_scale=self.cache.k_scale.at[:, idx].set(0.0),
                v_scale=self.cache.v_scale.at[:, idx].set(0.0))
        # NOTE: the copy-on-write clone (fresh[0] <- pool[cow_src]) is
        # NOT applied here — a same-burst neighbor may have registered
        # the source block moments ago with its prefill still pending,
        # so the clone must wait until every earlier unit has written
        # the pool (`_apply_cow` in `_run_standalone_unit` /
        # `_step_fused`)
        inserted: List[int] = []
        if self._pcache is not None:
            # register the prompt's FULL blocks right away so requests
            # queued behind this one (same burst included) share them
            # while it is still in flight; `mark_cached` waits for
            # `_commit` so a failed prefill can't park unwritten KV on
            # the reclaimable list
            n_full = P // self.bs
            if n_full:
                owned = matched + fresh
                inserted = self._pcache.insert(toks[:n_full * self.bs],
                                               owned[:n_full])
        chunks = self._suffix_chunks(cached_len, P)
        if not quiet:       # probes re-prepare without timeline noise
            self._trace_emit(rid, "prepared", slot=slot, prompt_len=P,
                             cached_tokens=cached_len,
                             cow=cow_src is not None, blocks=need,
                             chunks=len(chunks),
                             weight_dtype=self.weight_dtype,
                             kv_dtype=self.kv_dtype,
                             kv_block_bytes=self.kv_block_bytes(),
                             replica_id=self.replica_id,
                             # fast-path attribution: resolved backend,
                             # spec score path and mesh degree — so a
                             # mixed fleet's trace artifacts say which
                             # replicas actually ran the kernel paths
                             attention_impl=self.attention_impl,
                             spec_backend=(self.spec_attention_impl
                                           if self.speculative
                                           else None),
                             mesh_tp=(1 if self._mesh_cfg is None
                                      else int(self._mesh_cfg.tp)))
        return _Admission(slot, rid, list(toks), stop, mn, need, matched,
                          cached_len, cow_src, fresh, inserted, chunks, ring)

    def _rollback(self, recs: Sequence[_Admission]) -> None:
        """Undo prepared-but-uncommitted admissions after a failed
        prefill: unlink their index registrations (nothing may match KV
        that was never written), then return their blocks. Never touches
        committed slots."""
        for rec in recs:
            if self._pcache is not None:
                for b in rec.inserted:
                    self._pcache.unlink(b)
            self.alloc.release(rec.fresh)
            if rec.ring:
                self.walloc.release(list(rec.ring))
            pinned = rec.matched + ([rec.cow_src]
                                    if rec.cow_src is not None else [])
            if pinned:
                self.alloc.release(pinned)

    def _pack_prefill_rows(self, items: Sequence[Tuple[_Admission, int,
                                                       int]],
                           Pb: int, Gp: int):
        """Pack a unit's (record, start, end) chunks into the [Gp, Pb]
        prefill-row arrays one compiled call consumes: rows pad to the
        bucket, the group pads to its power-of-two size, padding masks
        through `valid` (writes drop) and clamped positions (gathers
        stay in range). Returns (rows, pos, valid, table, last_idx) and
        accounts the pad overhead."""
        rows = np.zeros((Gp, Pb), np.int32)
        pos = np.zeros((Gp, Pb), np.int32)
        val = np.zeros((Gp, Pb), np.bool_)
        tab = np.zeros((Gp, self._table_width), np.int32)
        li = np.zeros((Gp,), np.int32)
        real = 0
        maxpos = self.M * self.bs - 1
        for g, (rec, start, end) in enumerate(items):
            S = end - start
            real += S
            rows[g, :S] = rec.toks[start:end]
            pos[g] = np.minimum(np.arange(start, start + Pb), maxpos)
            val[g, :S] = True
            tab[g, :rec.need] = rec.matched + rec.fresh
            tab[g, self.M:self.M + len(rec.ring)] = rec.ring
            li[g] = S - 1
        self.prefill_pad_tokens += Gp * Pb - real
        return rows, pos, val, tab, li

    def _prefill_call(self, packed, cold: bool):
        """Issue ONE compiled standalone prefill over a unit's packed
        rows (`_pack_prefill_rows`). Returns (the pool it wrote, which
        the caller adopts: this batcher's own is deleted by now; logits
        [Gp, Pb, V])."""
        rows, pos, val, tab, _li = packed
        Gp, Pb = rows.shape
        exe = self._prefill_exe(Gp, Pb, cold)
        return exe(
            self.params, self.cache.pools, jnp.asarray(rows),
            jnp.asarray(tab), jnp.asarray(pos), jnp.asarray(val),
            jnp.zeros((Gp,), jnp.int32))

    def _units(self,
               recs: Sequence[_Admission]) -> List[List[_Admission]]:
        """Partition a burst into execution units: single-chunk records
        with the same (bucket, phase) batch into one prefill call; a
        chunked record runs alone (its chunks are sequential by
        construction).

        Group-growing admission (the PR 4 follow-on): a record no
        longer has to be CONSECUTIVE with its bucket-mates — it joins
        the EARLIEST open same-key unit with room, provided moving it
        earlier jumps over no unit whose registered blocks it depends
        on. The dependency set is the record's shared-prefix chain
        (matched blocks) plus its COW source: dependencies only point
        at EARLIER submissions, and later records that depend on THIS
        one only ever see it move toward them, so the reorder preserves
        every write-before-read edge and greedy tokens are
        schedule-invariant (tests/test_fused_step.py pins this).

        A COW record still never shares a unit with the record that
        registered its source block: the clone reads the POOL (outside
        the compiled call), so the source's prefill has to complete in
        an earlier unit first. Matched (non-COW) blocks are safe
        in-unit — the gather sees the layer's writes inside the
        computation."""
        units: List[List[_Admission]] = []
        # per unit: the growable key (None = closed chunked unit) and
        # the pool blocks its records registered
        keys: List[Optional[Tuple]] = []
        inserted: List[set] = []
        for rec in recs:
            if len(rec.chunks) > 1:
                units.append([rec])
                keys.append(None)
                inserted.append(set(rec.inserted))
                continue
            s, _, b = rec.chunks[0]
            k = (b, s == 0)
            deps = set(rec.matched)
            if rec.cow_src is not None:
                deps.add(rec.cow_src)
            # blocks registered AFTER each candidate slot, scanned
            # back to front: joining unit i is legal iff no unit past
            # i registered a block this record depends on
            target = None
            after: set = set()
            for i in range(len(units) - 1, -1, -1):
                if keys[i] == k and len(units[i]) < self._group_cap \
                        and not (deps & after) \
                        and not (rec.cow_src is not None
                                 and rec.cow_src in inserted[i]):
                    target = i
                elif deps & after:
                    break
                after |= inserted[i]
            if target is not None:
                units[target].append(rec)
                inserted[target].update(rec.inserted)
            else:
                units.append([rec])
                keys.append(k)
                inserted.append(set(rec.inserted))
        return units

    def _apply_cow(self, unit: Sequence[_Admission]) -> None:
        """Apply a unit's copy-on-write clones right before its prefill:
        every earlier unit has written the pool by now, so the clone
        captures the source block's real KV (fresh[0] sits at chain
        position len(matched) — exactly the clone's slot in the table
        row)."""
        for rec in unit:
            if rec.cow_src is not None:
                dst = rec.fresh[0]
                self.cache = self.cache._replace(
                    k=self.cache.k.at[:, dst].set(
                        self.cache.k[:, rec.cow_src]),
                    v=None if self.cache.v is None
                    else self.cache.v.at[:, dst].set(
                        self.cache.v[:, rec.cow_src]))
                if self.cache.k_scale is not None:
                    # int8 pool: the clone's codes are meaningless
                    # without the source block's dequant scales
                    self.cache = self.cache._replace(
                        k_scale=self.cache.k_scale.at[:, dst].set(
                            self.cache.k_scale[:, rec.cow_src]),
                        v_scale=self.cache.v_scale.at[:, dst].set(
                            self.cache.v_scale[:, rec.cow_src]))

    def _commit(self, rec: _Admission, first: int) -> None:
        """Activate a successfully prefilled admission in its slot."""
        for start, end, _b in rec.chunks:
            # real (pre-padding) chunk lengths, the distribution a
            # workload-specific ladder is fitted from (bucket_tuner).
            # Recorded at commit, not prepare: rolled-back and aborted
            # admissions must not feed phantom chunks to the fit.
            self.prefill_suffix_hist[end - start] = \
                self.prefill_suffix_hist.get(end - start, 0) + 1
        if rec.cow_src is not None:
            self.alloc.release([rec.cow_src])  # pinned only for the copy
        P = len(rec.toks)
        if self._pcache is not None:
            self._pcache.note_admission(P, rec.cached_len)
            if rec.inserted:
                self.alloc.mark_cached(rec.inserted)
        owned = rec.matched + rec.fresh
        blocks = owned + [0] * (self.M - rec.need) + list(rec.ring)
        blocks += [0] * (self._table_width - len(blocks))
        self.cache = self.cache._replace(
            table=self.cache.table.at[rec.slot].set(
                jnp.asarray(blocks, jnp.int32)),
            lengths=self.cache.lengths.at[rec.slot].set(P))
        self.cur_tok = self.cur_tok.at[rec.slot].set(first)
        self.active[rec.slot] = True
        self.slot_req[rec.slot] = rec.rid
        self.slot_blocks[rec.slot] = owned
        self.slot_ring[rec.slot] = list(rec.ring)
        self.slot_tokens[rec.slot] = list(rec.toks)
        self.budget[rec.slot] = rec.mn - 1
        self.stop[rec.slot] = rec.stop
        self._dev_state = None        # host slot state diverged from device
        self._spec_ok_dev = None      # slot occupancy changed
        self.outputs[rec.rid].append(first)
        if ((self.eos is not None and first == self.eos)
                or first == rec.stop or self.budget[rec.slot] <= 0):
            self._retire(rec.slot)

    def _unit_view(self, unit, entries):
        """One pending unit as an execution view — the unit-shape logic
        shared by the standalone and fused poppers: ([pipeline entries],
        [(rec, start, end) rows], bucket, cold, final). A chunked record
        runs its CURRENT chunk (progress lives in its entry); `final` is
        False for a non-last chunk — the entry stays pending with its
        progress bumped — and True means every record in the unit
        commits when the call lands."""
        if len(unit[0].chunks) > 1:
            rec, done = entries[0]
            start, end, bucket = rec.chunks[done]
            return (entries[:1], [(rec, start, end)], bucket, start == 0,
                    done == len(rec.chunks) - 1)
        items = [(r, r.chunks[0][0], r.chunks[0][1]) for r in unit]
        _, _, bucket = unit[0].chunks[0]
        return entries, items, bucket, items[0][1] == 0, True

    def _pop_unit(self):
        """The next prefill execution unit off the pending pipeline —
        group-growing admission means a unit's records need not be a
        contiguous slice of the pending list, so entries resolve by
        record identity."""
        unit = self._units([e[0] for e in self._pending])[0]
        entry_of = {id(e[0]): e for e in self._pending}
        return self._unit_view(unit, [entry_of[id(r)] for r in unit])

    def _finish_unit(self, entries, firsts) -> None:
        """Commit a unit whose FINAL chunk just computed: activate each
        record with its first token (`firsts`: host values, read back
        once per unit by the caller's wait phase)."""
        for entry, first in zip(entries, firsts):
            self._commit(entry[0], int(first))
            self._pending.remove(entry)

    def _run_standalone_unit(self) -> None:
        """Run ONE standalone prefill call for the head pending unit —
        the PR4 path: nothing decodes while it runs, so it only ever
        executes when the decode set is empty (nothing to stall) or
        fusion is off (`decode_stall_steps` then counts the cost). The
        one kind of tick that may not sync: a non-final chunk reads
        nothing back, and its device time shows in the next tick's
        wait."""
        entries, items, bucket, cold, final = self._pop_unit()
        Gp = self._group_pad(len(items))
        unit_rids = [r.rid for r, _, _ in items]
        with _Tick(
                self, "prefill", unit_rids, (bucket, Gp), rids=unit_rids,
                bucket=bucket, group_pad=Gp, cold=cold, final=final,
                prefill_spans=[[start, end] for _, start, end in items],
                stalls_decode=any(self.active),
                compile_hit=(Gp, bucket, cold, self.attention_impl)
                + self._skey + self._qkey + self._mkey
                in self._prefill_cache) as tick:
            with tick.phase("pack"):
                self._apply_cow([e[0] for e in entries if e[1] == 0])
                packed = self._pack_prefill_rows(items, bucket, Gp)
            with tick.phase("dispatch"):
                pools, logits = self._prefill_call(packed, cold)
                tick.adopt(pools)
                if final:
                    # ragged last-token logits per row — li came packed
                    # with the rows
                    g = len(items)
                    last = jnp.argmax(
                        logits[jnp.arange(g), jnp.asarray(packed[4][:g])],
                        axis=-1)
            tick.fence((logits, pools))
            if final:
                with tick.phase("wait"):
                    # ONE readback per unit: every first token at once
                    last = np.asarray(last)  # ptlint: disable=SYNC001 — the unit's single coalesced readback, one sync per prefill unit
            with tick.phase("commit"):
                if final:
                    self._finish_unit(entries, last)
                else:
                    entries[0][1] += 1
                self._trace_chunks(items, bucket, fused=False,
                                   dur=tick.call_s,
                                   device_dur=tick.device_s)

    def _fail_pending(self) -> None:
        """A failed prefill/fused call must not leak blocks OR silently
        drop work: every still-pending record rolls back (the slots
        were never activated, so nothing else would ever free them) and
        requeues at the FRONT of the batcher queue in original order —
        the caller decides who actually dies (the engine's quarantine
        probes the requeued records and re-admits the innocent; its
        fail-all fallback aborts them, which pops queue entries too).
        All-or-nothing on purpose — later records may lean on the
        failed unit's registered blocks, so partial survival would
        strand never-written KV."""
        victims = [e[0] for e in self._pending]
        self._rollback(victims)
        self._pending.clear()
        # no "requeued" trace event here: the DECISION about these
        # records (quarantine victim / culprit / fail-all) belongs to
        # the caller, which emits exactly one event per request — a
        # second one from the rollback would double trace_report's
        # requeue counts against health()["requests_requeued"]
        self.queue[:0] = [(v.rid, v.toks, v.stop, v.mn) for v in victims]

    def _drop_lost_pool(self, in_flight: bool) -> None:
        """What the batcher does when a device call fails AFTER dispatch
        (`in_flight`: the call's returned pool was adopted and its result
        never read back; or the pool it holds is deleted, a call having
        raised once it had the donated buffers): the pool it holds is a
        failed program's result and there is no older one, the argument
        having been donated. No attempt is made to save it. The pool is
        rebuilt empty (`init_pool`), the prefix index emptied, every
        pending admission rolled back onto the queue (`_fail_pending`)
        and every decoding slot dropped with its blocks, unregistered:
        `export_kv` then finds no slot, so the engine's quarantine takes
        its requeue path for every live request, which re-prefills
        `prompt + tokens` and decodes on to the tokens an unfaulted run
        gives. A failure BEFORE dispatch (the fault injector's gate, a
        builder that raises) leaves the pool live and does nothing
        here."""
        if not (in_flight or self.cache.k.is_deleted()):
            return
        self._fail_pending()
        for slot in range(self.B):
            if self.active[slot]:
                self._retire(slot, lost=True)
        if self._pcache is not None:
            self._pcache.clear()
            self.alloc.drop_cached()
        self.cache = self._empty_kv()
        self.pool_rebuilds += 1

    def _prefill_pending(self) -> None:
        """Drain the pending pipeline with standalone prefill calls
        (chunked records stream their remaining chunks back to back).
        With fusion ON the drain stops the moment a commit activates a
        decode slot — running the rest standalone would stall that
        fresh decoder exactly the way fusion exists to avoid, so the
        remaining units piggyback on the following fused steps instead.
        With fusion off everything drains (the PR4 path) and each call
        made while slots decode counts a stall. A failed call must not
        leak blocks: every still-pending record rolls back — the slots
        were never activated, so nothing else would ever free them."""
        try:
            while self._pending:
                if any(self.active):
                    if self._fused:
                        break          # the fused step takes it from here
                    # every in-flight slot stalls behind this call — the
                    # cost fusion exists to remove
                    self.decode_stall_steps += 1
                self._run_standalone_unit()
        except Exception:
            self._fail_pending()
            raise

    # -- quarantine probes (engine-thread only, failure path only) --------
    @contextlib.contextmanager
    def _probing(self):
        """A probe's device call, from dispatch to its drained result: a
        failure in between loses the pool like a tick's (`_Tick.__exit__`,
        `_drop_lost_pool`)."""
        try:
            yield
        except Exception:
            self._drop_lost_pool(True)
            raise

    def probe_decode_slot(self, slot: int) -> None:
        """Re-run the failed tick's decode chunk for ONE slot in
        isolation: the chunk executable runs with every other slot
        masked inactive, so only this slot's computation can raise.
        Commits NOTHING but the pool the run returns, which it must
        keep (the pool it probed was donated to the run): the rows the
        probe wrote lie past the slot's committed length, dead data that
        the slot's next decode writes again, and the returned tokens are
        discarded (the engine requeues the innocent for a warm
        re-prefill instead). Per-request paged attention makes the masked
        run exercise exactly this slot's math. Raises whatever the device
        (or the fault injector, before any dispatch) raises; returning
        means the slot is clean. Failure-path only: never called on the
        hot path."""
        rid = self.slot_req[slot]
        self._probe_gate(rid)
        act = [False] * self.B
        act[slot] = True
        with self._probing():
            pools, *out = self._chunk_exe()(
                self.params, self.cache.pools, self.cache.table,
                self.cur_tok, jnp.asarray(act), self.cache.lengths,
                jnp.asarray(self.budget, jnp.int32),
                jnp.asarray(self.stop, jnp.int32))
            self.cache = self.cache.with_pools(pools)
            # force the async dispatch so a data-dependent device failure
            # surfaces HERE, attributed to this slot (probe verdicts are
            # the one consumer of these arrays)
            jax.block_until_ready(out)

    def probe_queued(self, rid: int) -> None:
        """Re-run a QUEUED request's first prefill chunk in isolation:
        prepare its blocks, run one standalone single-record prefill
        call (a warmed (1, bucket) ladder shape), then roll everything
        back — the queue entry and the prefix index end exactly as they
        were, and the pool is the one the call returned: what it wrote
        lies in blocks that are free again. A failed prefill/fused call requeues its
        pending records (`_fail_pending`), so this is how the engine's
        quarantine re-executes the failing tick's prefill units one
        record at a time. Raises what the device raises; a pool too
        tight to re-prepare returns silently (inconclusive is NOT a
        conviction). No-op for a rid not in the queue."""
        entry = next((e for e in self.queue if e[0] == rid), None)
        if entry is None:
            return
        _, toks, stop, mn = entry
        self._probe_gate(rid)
        try:
            rec = self._prepare_admission(-1, rid, toks, stop, mn,
                                          quiet=True)
        except RuntimeError:
            return        # pool exhausted mid-quarantine: inconclusive
        try:
            start, end, bucket = rec.chunks[0]
            self._apply_cow([rec])
            with self._probing():
                pools, logits = self._prefill_call(
                    self._pack_prefill_rows([(rec, start, end)], bucket, 1),
                    cold=start == 0)
                self.cache = self.cache.with_pools(pools)
                jax.block_until_ready(logits)
        finally:
            self._rollback([rec])

    def _pop_fused_units(self):
        """Select the units ONE fused call carries, in unit order (the
        group-grown `_units` partition, which preserves every
        dependency edge): the head unit always rides; up to
        `fused_units - 1` more units join when each (a) prefills this
        step at the head unit's bucket (one compiled shape), and (b)
        holds no block reference — matched chain or COW source — that
        an earlier SELECTED unit registered but will not have fully
        written.
        In-call pool writes ARE visible to the gather (each layer
        writes every row's KV before gathering), so a later unit may
        chain onto blocks a completing co-selected unit writes this
        very call; but a chunked unit advancing a NON-final chunk
        leaves its later blocks unwritten, and the host-side COW clone
        copies the pool BEFORE the call — both force the dependent unit
        to wait for a later step. Returns (groups, bucket): groups is a
        list of (pipeline entries, (rec, start, end) items, final) per
        selected unit."""
        units = self._units([e[0] for e in self._pending])
        entry_of = {id(e[0]): e for e in self._pending}
        groups: List[Tuple[List, List, bool]] = []
        bucket0 = None
        inserted_sel: set = set()    # registered by any selected unit
        unwritten: set = set()       # ... that this call won't write
        for unit in units:
            if len(groups) >= self.fused_units:
                break
            entries, items, bucket, _cold, final = self._unit_view(
                unit, [entry_of[id(r)] for r in unit])
            if bucket0 is None:
                bucket0 = bucket
            elif bucket != bucket0:
                break
            refs = set()
            cow_refs = set()
            for rec in unit:
                refs.update(rec.matched)
                if rec.cow_src is not None:
                    cow_refs.add(rec.cow_src)
            if (refs | cow_refs) & unwritten or cow_refs & inserted_sel:
                break
            groups.append((entries, items, final))
            for rec in unit:
                inserted_sel.update(rec.inserted)
                if not final:
                    # mid-stream: blocks past this chunk stay unwritten
                    unwritten.update(rec.inserted)
        return groups, bucket0

    def _step_fused(self, decoding) -> None:
        """Piggyback up to `fused_units` pending prefill units on this
        step's decode chunk: ONE compiled call advances every active
        slot by its chunk AND prefills the selected same-bucket
        admission chunks; then deliver the `decoding` slots' tokens."""
        committed = False
        try:
            groups, bucket = self._pop_fused_units()
            # every selected unit pads to the SAME group size so the
            # call's shape is (units x Gp, bucket) — drawn from the
            # finite warmed ladder whatever mix of units rides
            Gp = max(self._group_pad(len(items))
                     for _, items, _ in groups)
            decode_rids = [self.slot_req[s] for s in decoding]
            unit_rids = [[r.rid for r, _, _ in items]
                         for _, items, _ in groups]
            with _Tick(
                    self, "fused",
                    decode_rids + [r for u in unit_rids for r in u],
                    (bucket, len(groups)), units=unit_rids,
                    decode_rids=decode_rids, bucket=bucket, group_pad=Gp,
                    rows=len(groups) * Gp,
                    gemm_tokens=self.B + len(groups) * Gp * bucket,
                    chunk=self.chunk,
                    **self._decode_fields(decoding),
                    prefill_spans=[[start, end] for _, items, _ in groups
                                   for _, start, end in items],
                    compile_hit=(len(groups) * Gp, bucket,
                                 self.attention_impl) + self._skey
                    + self._qkey + self._mkey in self._fused_cache
                    ) as tick:
                with tick.phase("pack"):
                    self._apply_cow([e[0] for entries, _, _ in groups
                                     for e in entries if e[1] == 0])
                    packs = [self._pack_prefill_rows(items, bucket, Gp)
                             for _, items, _ in groups]
                    rows, pos, val, tab, li = (
                        np.concatenate([p[i] for p in packs], axis=0)
                        for i in range(5))
                    exe = self._fused_exe(len(groups) * Gp, bucket)
                    if self._dev_state is None:
                        self._dev_state = self._upload_slot_state()
                    active, budget, stop = self._dev_state
                with tick.phase("dispatch"):
                    (pools, lengths, tok, budget, active, toks,
                     pfirst, stats) = exe(
                        self.params, self.cache.pools,
                        self.cache.table, self.cache.lengths,
                        self.cur_tok, active, budget, stop,
                        jnp.asarray(rows), jnp.asarray(pos),
                        jnp.asarray(val), jnp.asarray(tab),
                        jnp.asarray(li))
                    tick.adopt(pools)
                tick.fence((pools, toks, pfirst))
                with tick.phase("wait"):
                    # one host sync serves BOTH the decode chunk's
                    # tokens and the prefill rows' first tokens — and,
                    # dispatch being async, surfaces any device-side
                    # failure HERE, before the batcher state commits
                    got = jax.device_get((toks, pfirst, stats))  # ptlint: disable=SYNC001 — single per-step sync, decode + prefill readbacks coalesced
                    toks, pfirst, stats = got
                    self._note_counters(tick, stats, len(groups) * Gp,
                                        bucket)
                # decode state untouched up to here but for the pool,
                # adopted at dispatch: a failure rolls the pending units
                # back (below)
                committed = True
                with tick.phase("commit"):
                    self.cache = self.cache._replace(lengths=lengths)
                    self.cur_tok = tok
                    self._dev_state = (active, budget, stop)
                    self.fused_steps += 1
                    self.fused_unit_count += len(groups)
                    # commit IN ORDER: group g's real rows sit at
                    # [g*Gp, g*Gp+|items|) of the concatenated prefill
                    # batch, so pfirst slices per group
                    for g, (entries, items, final) in enumerate(groups):
                        if final:
                            self._finish_unit(
                                entries,
                                pfirst[g * Gp:g * Gp + len(items)])
                        else:
                            entries[0][1] += 1
                        self._trace_chunks(items, bucket, fused=True,
                                           dur=tick.call_s,
                                           device_dur=tick.device_s)
                    self._emit_chunk(decoding, toks)
        except Exception:
            if not committed:
                self._fail_pending()
            raise

    def _retire(self, slot: int, lost: bool = False) -> None:
        """Free a slot whose request finished or was aborted. `lost`: the
        pool was rebuilt under it (`_drop_lost_pool`), so its blocks go
        back unregistered and the request is dropped, not finished."""
        rid = self.slot_req[slot]
        blocks = self.slot_blocks[slot]
        self._trace_emit(rid, "kv_lost" if lost else "retired", slot=slot,
                         generated=len(self.outputs.get(rid, [])))
        if lost:
            self.alloc.release(blocks)
        elif self._pcache is not None:
            # register the finished sequence's FULL blocks (prompt +
            # generated) before releasing: at refcount 0 they park on
            # the cached LRU instead of dying, so the next request with
            # this prefix skips their prefill. The last emitted token's
            # KV was never written (decode writes token t's KV while
            # producing t+1), so the written length is P + m - 1.
            gen = self.outputs.get(rid, [])
            prompt = self.slot_tokens[slot] or []
            kv_len = len(prompt) + max(0, len(gen) - 1)
            n_full = kv_len // self.bs
            if n_full:
                seq = (prompt + gen)[:n_full * self.bs]
                self.alloc.mark_cached(
                    self._pcache.insert(seq, blocks[:n_full]))
            # leaf-first into the LRU: a chain's deep blocks are evicted
            # before the prefix blocks other chains may still extend
            self.alloc.release(list(reversed(blocks)))
        else:
            self.alloc.free(blocks)
        if self.slot_ring[slot]:
            self.walloc.free(self.slot_ring[slot])
        if lost:
            self._delivered.pop(rid, None)
        else:
            self._just_finished.append(rid)
        self.active[slot] = False
        self.slot_req[slot] = None
        self.slot_blocks[slot] = None
        self.slot_ring[slot] = None
        self.slot_tokens[slot] = None
        self.stop[slot] = -1
        self._dev_state = None        # host slot state diverged from device
        self._spec_ok_dev = None      # slot occupancy changed
        self._no_spec.discard(rid)

    def _drain_queue(self) -> None:
        """Prepare queued requests into the pending-prefill pipeline
        while a batch slot AND the KV blocks fit. Slots reserved by
        still-pending admissions are NOT handed out again (a mid-stream
        chunked prefill keeps its slot across steps)."""
        reserved = {e[0].slot for e in self._pending}
        free = [s for s in range(self.B)
                if not self.active[s] and s not in reserved]
        recs: List[_Admission] = []
        try:
            while free and self.queue:
                _, toks0, _, mn0 = self.queue[0]
                # cached-aware: blocks another in-flight request already
                # pins for this prompt's prefix are shared, not drawn
                # from the pool — and `free_blocks` already counts
                # reclaimable cached blocks on the refcounting allocator.
                # Earlier records in this burst already hold their blocks
                # (and registered their prompts), so the head-of-line
                # check and the trie walk both see them.
                need = self.blocks_needed(len(toks0), mn0, tokens=toks0)
                if need > self.alloc.free_blocks or (
                        self.walloc is not None
                        and self.ring_blocks_needed(len(toks0), mn0)
                        > self.walloc.free_blocks):
                    if (not any(self.active) and not recs
                            and not self._pending):
                        # nothing in flight will ever free blocks
                        raise RuntimeError(
                            f"request needs {need} blocks but the pool "
                            f"holds only {self.alloc.num_blocks} — size "
                            f"num_blocks for the largest single request")
                    break           # defer until a request retires
                rid, toks, stop, mn = self.queue.pop(0)
                recs.append(self._prepare_admission(
                    free.pop(0), rid, toks, stop, mn))
        except Exception:
            self._rollback(recs)
            raise
        for rec in recs:
            self._pending.append([rec, 0])

    def _fuse_now(self) -> bool:
        """This step's scheduling decision: piggyback the next pending
        prefill unit on the decode chunk exactly when there IS pending
        prefill work, slots are decoding (someone to stall), and fusion
        is enabled. Everything else runs standalone."""
        return bool(self._fused and self._pending and any(self.active))

    def _admit(self) -> None:
        """Pull queued requests into the pending pipeline, then prefill
        standalone unless the next chunk will piggyback them: the decode
        set is empty (nothing to stall) or fusion is off (the PR4 path,
        stalls counted). Runs before AND after the device chunk so a
        retire frees slots for the same step's queue."""
        self._drain_queue()
        if self._pending and not self._fuse_now():
            self._prefill_pending()

    def _emit_one(self, logits_row, tok, act, lengths, budget, stop):
        """Greedy-emit one token per decode row and advance the row's
        state — THE stopping rule, shared by the decode scan body and
        the fused chunk's first token so the two cannot diverge (token
        parity between them is by construction)."""
        eos = -1 if self.eos is None else int(self.eos)
        with jax.named_scope("sample"):
            nxt = jnp.argmax(logits_row, axis=-1).astype(jnp.int32)
            nxt = jnp.where(act, nxt, tok)
            lengths = lengths + act.astype(jnp.int32)
            budget = budget - act.astype(jnp.int32)
            # deactivate ON DEVICE the moment a slot's budget runs
            # out or it emits eos / its own stop id — a fixed-size
            # chunk must not keep writing past the slot's ALLOCATED
            # blocks (the table row's padding points at block 0,
            # i.e. someone else's cache)
            act = act & (budget > 0) & (nxt != eos) & (nxt != stop)
        return nxt, lengths, budget, act

    def _decode_step_body(self, params, stop):
        """The one traced single-token decode step, shared by the plain
        decode chunk AND the fused chunk's post-first-token scan."""
        cfg, impl = self.cfg, self.attention_impl
        mesh, max_, layout = self._mesh, self._mesh_axis(), self._layout

        def step(carry, _):
            cache, tok, lengths, budget, act = carry
            pos = lengths[:, None]
            logits, cache, stats = _forward_paged_stats(
                params, tok[:, None], cache, pos, act[:, None],
                cfg, is_prefill=False, attention_impl=impl, mesh=mesh,
                mesh_axis=max_, layout=layout)
            nxt, lengths, budget, act = self._emit_one(
                logits[:, 0], tok, act, lengths, budget, stop)
            # inactive slots must not drift: pin lengths ourselves
            cache = cache._replace(lengths=lengths)
            # stats: the expert layers' routing counters of this step,
            # None (no leaves) for a decoder without expert layers
            return (cache, nxt, lengths, budget, act), (nxt, stats)

        return step

    def _build_chunk(self):
        chunk = self.chunk

        def serve_decode_step(params, pools, table, tok, active, lengths,
                              budget, stop):
            cache = PagedKVCache.of(pools, table, lengths)
            step = self._decode_step_body(params, stop)
            (cache, tok, lengths, budget, act), (toks, stats) = \
                jax.lax.scan(step, (cache, tok, lengths, budget, active),
                             None, length=chunk)
            # act/budget go back to the caller so the next chunk can feed
            # them in again without a host round-trip
            return (cache.pools, tok, lengths, budget, act,
                    toks.T,                                # [B, chunk]
                    _sum_steps(stats))

        return self._step_jit(serve_decode_step)

    def _chunk_exe(self):
        """Memoized COMPILED plain decode chunk, warmup-covered like
        the prefill shapes: a decode-only stretch AFTER a fused stretch
        (whose steps all ran `_fused_exe`) pays no compile."""
        key = (self.chunk, self.attention_impl) + self._skey \
            + self._qkey + self._mkey
        exe = self._chunk_cache.get(key)
        if exe is None:
            if self._chunk_fn is None:
                self._chunk_fn = self._build_chunk()
            sds, i32 = self._aval, jnp.int32
            B = self.B
            exe = self._aot(
                key, self._chunk_fn, sds((B, self._table_width), i32),
                sds((B,), i32), sds((B,), jnp.bool_), sds((B,), i32),
                sds((B,), i32), sds((B,), i32))
            self._chunk_cache[key] = exe
        return exe

    def _build_fused(self):
        """The fused prefill+decode chunk: ONE compiled call that
        advances `max_batch` decode rows by their chunk AND prefills
        `Gp` bucket-wide chunk rows. Its first forward runs TWO row
        groups through `_forward_groups`: the decode rows as [B, 1]
        (token, `lengths`, `active`, `table`) and the prefill rows as
        [Gp, Pb]. The dense layers see the B + Gp*Pb packed tokens and
        nothing else — no decode row is padded to the bucket — so each
        weight is read once for both groups; the pool write and the
        paged attention run once per group, at the shapes the plain
        decode step and a warm standalone prefill already use. COLD
        prefill rows take the per-query-causal paged attention too
        (standalone cold prefill uses the flash path): the two compute
        the same softmax attention and greedy-token parity with the
        unfused path is asserted in tests/test_fused_step.py, but
        logits are not bit-for-bit. The LM head runs on the B + Gp rows
        whose logits are read: each decode row's token and each prefill
        row's last valid position. The remaining chunk-1 decode tokens
        scan the shared decode step body. `fused_units` > 1 is the same
        program with a larger `Gp`."""
        cfg, chunk, B = self.cfg, self.chunk, self.B
        impl = self.attention_impl
        mesh, max_, layout = self._mesh, self._mesh_axis(), self._layout

        def serve_fused_step(params, pools, table, lengths, tok,
                             active, budget, stop, prows, ppos, pval, ptab,
                             plast):
            Gp, Pb = prows.shape
            x, pools, stats0 = _forward_groups(
                params,
                (_RowGroup(tok[:, None], table, lengths[:, None],
                           active[:, None]),
                 _RowGroup(prows, ptab, ppos, pval)),
                pools, cfg, is_prefill=False,
                attention_impl=impl, mesh=mesh, mesh_axis=max_,
                layout=layout)
            with jax.named_scope("lm_head"):
                # x is [B + Gp*Pb, D]: the decode rows' tokens, then
                # each prefill row's bucket; ragged last-token rows
                last = B + jnp.arange(Gp) * Pb + plast
                logits = _final_head_cached(
                    params, jnp.concatenate([x[:B], x[last]], 0), cfg)
            with jax.named_scope("sample"):
                pfirst = jnp.argmax(logits[B:], axis=-1).astype(jnp.int32)
            nxt, lengths, budget, active = self._emit_one(
                logits[:B], tok, active, lengths, budget, stop)
            cache = PagedKVCache.of(pools, table, lengths)
            step = self._decode_step_body(params, stop)
            (cache, tok, lengths, budget, active), (toks, stats) = \
                jax.lax.scan(step, (cache, nxt, lengths, budget, active),
                             None, length=chunk - 1)
            toks = jnp.concatenate([nxt[None], toks], 0)
            return (cache.pools, lengths, tok, budget, active,
                    toks.T, pfirst,                       # toks [B, chunk]
                    _sum_steps(stats, stats0))

        return self._step_jit(serve_fused_step)

    def _fused_exe(self, Gp: int, Pb: int):
        """Memoized COMPILED fused chunk per (prefill rows, bucket)
        shape, warmup-covered like `_prefill_exe`. `Gp` is the TOTAL
        prefill row count of the call: units x per-unit group pad for a
        multi-unit step, so (units, group) pairs with the same product
        share one executable."""
        key = (Gp, Pb, self.attention_impl) + self._skey + self._qkey \
            + self._mkey
        exe = self._fused_cache.get(key)
        if exe is None:
            if self._fused_fn is None:
                self._fused_fn = self._build_fused()
            sds, i32 = self._aval, jnp.int32
            B = self.B
            exe = self._aot(
                key, self._fused_fn,
                sds((B, self._table_width), i32), sds((B,), i32),
                sds((B,), i32),
                sds((B,), jnp.bool_), sds((B,), i32), sds((B,), i32),
                sds((Gp, Pb), i32), sds((Gp, Pb), i32),
                sds((Gp, Pb), jnp.bool_), sds((Gp, self._table_width), i32),
                sds((Gp,), i32))
            self._fused_cache[key] = exe
        return exe

    # -- self-speculative decoding (draft k tokens, verify in one call,
    #    commit only the accepted rows) ------------------------------------
    def _spec_key(self, phase: str) -> Tuple:
        """Memo key for the spec `phase` ("draft" | "verify")
        executable — spec geometry + backend + quantization config.
        Carries `_skey` like every other compiled-shape memo key, so a
        batcher whose spec config changes shape (node count, draft
        depth, tree branching, draft-w8) via the full spec tuple can
        never serve another config's executable; the resolved spec
        score-path backend rides inside `_skey` next to the geometry
        for the same reason (KEY001 enforces the convention)."""
        return (phase, self._draft_depth, self.attention_impl) \
            + self._skey + self._qkey + self._mkey

    def spec_stats(self) -> Dict[str, Any]:
        """Speculative-decoding accounting: config + the SpecStats
        counters (steps / drafted / accepted / emitted, accept_rate,
        tokens_per_step). `enabled` False (and config only) when the
        batcher decodes plain."""
        d: Dict[str, Any] = {"enabled": self.speculative,
                             "backend": self.spec_attention_impl}
        d.update(self._spec_cfg.as_dict(self.cfg.num_hidden_layers))
        d.update(self.spec.as_dict())
        return d

    def _build_spec_tree_draft(self):
        """The traced draft, a chain (`spec_k`) being the tree
        (1,) * k: level by level, one truncated-stack forward per
        level scores ALL of the level's nodes at once (each node's
        slab visibility is its ancestor path, so its logits equal the
        sequential prefix's) and lax.top_k proposes tree[j] children
        per node — child 0 is the node's argmax, so the tree always
        contains the greedy chain. The committed pool is read, never
        written (layers 0..depth-1 of the target's pool ARE the
        draft's cache); the proposals ride the spec slab, level j's
        nodes in rows [offs[j], offs[j+1]) — contiguous by the
        packed-level layout; the LAST level's proposals are never
        forwarded here (the verify computes their K/V). `dlayers` is
        the draft-from-w8 quantized stack (None drafts from the
        target's own weights, sliced in-trace so XLA fuses the slice —
        no copy). Returns drafts [B, spec_k] in slab-row order (levels
        concatenated)."""
        cfg, B, depth = self.cfg, self.B, self._draft_depth
        sc = self._spec_cfg
        tree = sc.tree
        D = len(tree)
        sizes, offs = sc.level_sizes(), sc.level_offsets()
        Sd = offs[D]                 # draft slab: root + levels 1..D-1
        maxpos = self.M * self.bs - 1
        impl = self.spec_attention_impl
        mesh, max_ = self._mesh, self._mesh_axis()
        A = sc.ancestor_mask()
        # per-level query visibility: the level's rows of the ancestor
        # mask, restricted to the draft slab's columns (static consts)
        vis_lv = [jnp.asarray([row[:Sd] for row in
                               A[offs[j]:offs[j + 1]]])
                  for j in range(D)]

        def serve_spec_draft(params, pools, dlayers, table, lengths, tok,
                             active):
            cache = PagedKVCache.of(pools, table, lengths)
            layers = jax.tree_util.tree_map(
                lambda x: x[:depth], params["layers"]) \
                if dlayers is None else dlayers
            KVh, hd = cfg.num_key_value_heads, cfg.head_dim
            sk = jnp.zeros((depth, B, Sd, KVh, hd), cfg.dtype)
            sv = jnp.zeros_like(sk)
            toks = tok[:, None]                    # level 0: the root
            out_levels = []
            for j in range(D):
                w = sizes[j]
                pos = jnp.broadcast_to(
                    jnp.minimum(lengths + j, maxpos)[:, None], (B, w))
                logits, sk, sv = _forward_spec(
                    params, layers, toks, cache, pos, lengths,
                    sk, sv, offs[j], cfg, vis=vis_lv[j], impl=impl,
                    mesh=mesh, mesh_axis=max_)
                # top-b children per node: lax.top_k ties break toward
                # the lower index, same as argmax — child 0 IS the
                # greedy continuation, so a wider tree accepts at least
                # what the chain of its depth does per sweep
                _, top = lax.top_k(logits, tree[j])  # [B, w, b]
                nxt = top.reshape(B, w * tree[j]).astype(jnp.int32)
                nxt = jnp.where(active[:, None], nxt, tok[:, None])
                out_levels.append(nxt)
                toks = nxt
            return jnp.concatenate(out_levels, axis=1)   # [B, spec_k]

        return self._step_jit(serve_spec_draft, writes_pool=False)

    def _spec_dlayers_aval(self):
        """AOT-lowering aval tree for the draft-from-w8 stack (None —
        an empty pytree — when drafting from the target's weights)."""
        if self._spec_dlayers is None:
            return None
        return jax.tree_util.tree_map(
            lambda x: self._aval(jnp.shape(x), x.dtype),
            self._spec_dlayers)

    def _spec_draft_exe(self):
        """Memoized COMPILED draft step (warmup-covered)."""
        key = self._spec_key("draft")
        exe = self._spec_cache.get(key)
        if exe is None:
            if self._spec_draft_fn is None:
                self._spec_draft_fn = self._build_spec_tree_draft()
            sds, i32 = self._aval, jnp.int32
            B = self.B
            exe = self._aot(
                key, self._spec_draft_fn, self._spec_dlayers_aval(),
                sds((B, self.M), i32), sds((B,), i32), sds((B,), i32),
                sds((B,), jnp.bool_))
            self._spec_cache[key] = exe
        return exe

    def _build_spec_tree_verify(self):
        """The traced verify: score the whole packed token tree —
        root + every drafted node, slab visibility = the static
        ancestor mask — in ONE full-depth pass over the read-only pool
        + spec slab, then walk the tree level by level following the
        target's own greedy tokens: at each accepted node, the child
        whose draft token equals the target's greedy continuation
        extends the path (top-k children are distinct, so at most one
        matches: the longest matching prefix). The accepted path plus
        one corrected token emit, truncated by per-slot budget and
        eos/stop — the `_emit_one` stopping rule, vectorized over
        rows. Then COMMIT: the accepted path's slab K/V — and ONLY
        those — reach the pool, written one row at a time in order so
        the int8 pool's grow-only per-block scales evolve exactly as
        sequential decode's would. Greedy output is identical to plain
        decode by construction — speculation changes the schedule, not
        the tokens. out/n_emit are sized to the path width (tree depth
        + 1)."""
        cfg, B = self.cfg, self.B
        sc = self._spec_cfg
        tree = sc.tree
        D = len(tree)
        offs = sc.level_offsets()
        S = sc.slab_rows()
        P_out = D + 1
        eos = -1 if self.eos is None else int(self.eos)
        maxpos = self.M * self.bs - 1
        impl = self.spec_attention_impl
        mesh, max_ = self._mesh, self._mesh_axis()
        A = jnp.asarray(sc.ancestor_mask())                   # [S, S]
        lv = jnp.asarray(sc.row_levels(), jnp.int32)          # [S]

        def serve_spec_verify(params, pools, table, lengths, tok,
                              drafts, active, budget, stop, spec_ok):
            k, v, ks, vs = pools
            cache = PagedKVCache(k, v, table, lengths, ks, vs)
            toks_in = jnp.concatenate([tok[:, None], drafts], axis=1)
            # every node sits at committed position lengths + level —
            # siblings share a position; visibility (the ancestor
            # mask), not position, separates them
            pos = jnp.minimum(lengths[:, None] + lv[None, :], maxpos)
            KVh, hd = cfg.num_key_value_heads, cfg.head_dim
            sk = jnp.zeros((cfg.num_hidden_layers, B, S, KVh, hd),
                           cfg.dtype)
            sv = jnp.zeros_like(sk)
            logits, sk, sv = _forward_spec(
                params, params["layers"], toks_in, cache, pos, lengths,
                sk, sv, jnp.int32(0), cfg, vis=A, impl=impl,
                mesh=mesh, mesh_axis=max_)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, S]
            # accept walk: cur = the path head's slab row, ci = its
            # index within its level; a level with no matching child
            # kills the walk (alive)
            cur = jnp.zeros((B,), jnp.int32)
            ci = jnp.zeros((B,), jnp.int32)
            alive = spec_ok
            n_acc = jnp.zeros((B,), jnp.int32)
            path_rows = [cur]
            for j in range(1, D + 1):
                b = tree[j - 1]
                crows = offs[j] + ci[:, None] * b \
                    + jnp.arange(b)[None, :]                   # [B, b]
                ctoks = jnp.take_along_axis(toks_in, crows, axis=1)
                tgt = jnp.take_along_axis(g, cur[:, None], axis=1)
                hit = (ctoks == tgt) & alive[:, None]
                has = jnp.any(hit, axis=1)
                pick = jnp.argmax(hit, axis=1).astype(jnp.int32)
                ci2 = ci * b + pick
                cur = jnp.where(has, offs[j] + ci2, cur)
                ci = jnp.where(has, ci2, ci)
                n_acc = n_acc + has.astype(jnp.int32)
                alive = has
                path_rows.append(cur)
            path = jnp.stack(path_rows, axis=1)            # [B, D+1]
            # the emitted candidates: the target's greedy token after
            # each accepted path prefix (rows past n_acc duplicate the
            # head — masked off by emit below, never written)
            out_g = jnp.take_along_axis(g, path, axis=1)   # [B, D+1]
            idx = jnp.arange(P_out)[None, :]
            is_end = (out_g == eos) | (out_g == stop[:, None])
            ends_before = jnp.cumsum(is_end.astype(jnp.int32), axis=1) \
                - is_end.astype(jnp.int32)
            emit = (idx <= n_acc[:, None]) & (idx < budget[:, None]) \
                & (ends_before == 0) & active[:, None]
            n_emit = jnp.sum(emit, axis=1, dtype=jnp.int32)
            # verify-then-commit: the accepted path's positions are
            # sequential (lengths + r), only its rows' slab K/V reach
            # the pool, one row at a time in order — int8 scale growth
            # matches sequential decode's
            pos_path = jnp.minimum(lengths[:, None] + idx, maxpos)
            ks2, vs2 = ks, vs
            for r in range(P_out):
                rowr = path[:, r][None, :, None, None, None]
                kr = jnp.take_along_axis(sk, rowr, axis=2)
                vr = jnp.take_along_axis(sv, rowr, axis=2)
                posr = pos_path[:, r:r + 1]
                valr = emit[:, r:r + 1]
                if ks is None:
                    k = jax.vmap(_write_pool,
                                 in_axes=(0, None, None, 0, None))(
                        k, table, posr, kr, valr)
                    v = jax.vmap(_write_pool,
                                 in_axes=(0, None, None, 0, None))(
                        v, table, posr, vr, valr)
                else:
                    k, ks2, _ = jax.vmap(
                        _write_pool_int8,
                        in_axes=(0, 0, None, None, 0, None))(
                        k, ks2, table, posr, kr, valr)
                    v, vs2, _ = jax.vmap(
                        _write_pool_int8,
                        in_axes=(0, 0, None, None, 0, None))(
                        v, vs2, table, posr, vr, valr)
            last = jnp.take_along_axis(
                out_g, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
            last = jnp.where(active & (n_emit > 0), last, tok)
            budget2 = budget - n_emit
            active2 = active & (budget2 > 0) & (last != eos) \
                & (last != stop)
            return ((k, v, ks2, vs2), lengths + n_emit, last, budget2,
                    active2, jnp.where(emit, out_g, 0), n_emit, n_acc)

        return self._step_jit(serve_spec_verify)

    def _spec_verify_exe(self):
        """Memoized COMPILED verify step (AOT-lowered, warmup-covered)."""
        key = self._spec_key("verify")
        exe = self._spec_cache.get(key)
        if exe is None:
            if self._spec_verify_fn is None:
                self._spec_verify_fn = self._build_spec_tree_verify()
            sds, i32 = self._aval, jnp.int32
            B = self.B
            exe = self._aot(
                key, self._spec_verify_fn,
                sds((B, self.M), i32), sds((B,), i32), sds((B,), i32),
                sds((B, self.spec_k), i32), sds((B,), jnp.bool_),
                sds((B,), i32), sds((B,), i32),
                sds((B,), jnp.bool_))
            self._spec_cache[key] = exe
        return exe

    def _step_spec(self, decoding):
        """One speculative decode tick: the draft proposes spec_k
        tokens per active slot off the truncated stack, the target
        verifies all k+1 positions in one call and commits only the
        accepted rows, and the `decoding` slots' tokens are delivered.
        Returns (out_toks [B, k+1], n_emit [B]) as host arrays.
        Two device calls, so two ticks: the draft's reads nothing back
        (its device time shows in the verify's wait), the verify's
        makes the ONE host sync, like the fused path."""
        decode_rids = [self.slot_req[s] for s in decoding]
        decode_ctx = self._decode_ctx(decoding)
        c = self.cache
        with _Tick(
                self, "spec_draft", decode_rids, (self.spec_k, 0),
                rids=decode_rids, k=self.spec_k, decode_ctx=decode_ctx,
                compile_hit=self._spec_key("draft") in self._spec_cache
                ) as tick:
            with tick.phase("pack"):
                if self._dev_state is None:
                    self._dev_state = self._upload_slot_state()
                active, budget, stop = self._dev_state
                if self._spec_ok_dev is None:
                    # per-slot spec participation (quarantine fallback:
                    # opted-out victims decode plain through the same
                    # verify call) — refreshed only when admit/retire
                    # changes slot occupancy
                    self._spec_ok_dev = jnp.asarray(
                        [self.slot_req[s] is not None
                         and self.slot_req[s] not in self._no_spec
                         for s in range(self.B)])
            with tick.phase("dispatch"):
                # the draft reads the pool and returns none: it donates
                # nothing, and `c` stays the live pool for the verify
                drafts = self._spec_draft_exe()(
                    self.params, c.pools, self._spec_dlayers, c.table,
                    c.lengths, self.cur_tok, active)
            tick.fence(drafts)
            draft_s = tick.call_s
        with _Tick(
                self, "spec_verify", decode_rids, (self.spec_k, 0),
                rids=decode_rids, k=self.spec_k, decode_ctx=decode_ctx,
                compile_hit=self._spec_key("verify") in self._spec_cache
                ) as tick:
            with tick.phase("dispatch"):
                (pools, lengths, last, budget, active2, out,
                 n_emit, n_acc) = self._spec_verify_exe()(
                    self.params, c.pools, c.table,
                    c.lengths, self.cur_tok, drafts, active, budget, stop,
                    self._spec_ok_dev)
                tick.adopt(pools)
            tick.fence((pools, out, n_emit))
            with tick.phase("wait"):
                # one host sync serves tokens, counts AND acceptance —
                # and, dispatch being async, surfaces any device-side
                # failure HERE, before the batcher state commits below
                out, n_emit, n_acc = jax.device_get((out, n_emit, n_acc))  # ptlint: disable=SYNC001 — single per-step sync, token + acceptance readbacks coalesced
            with tick.phase("commit"):
                self.cache = self.cache._replace(lengths=lengths)
                self.cur_tok = last
                self._dev_state = (active2, budget, stop)
                spec_slots = [s for s in decoding
                              if self.slot_req[s] not in self._no_spec]
                self.spec.record_step(
                    drafted=self.spec_k * len(spec_slots),
                    accepted=int(n_acc.sum()), emitted=int(n_emit.sum()),
                    slots=len(decode_rids),
                    depths=[int(n_acc[s]) for s in spec_slots])
                if self._trace is not None:
                    self._trace.span(
                        "spec_draft", dur=draft_s, k=self.spec_k,
                        slots=len(decode_rids),
                        replica_id=self.replica_id)
                    dev_s = tick.device_s
                    extra = {} if dev_s is None \
                        else {"device_dur": round(dev_s, 6)}
                    for s in decoding:
                        self._trace_emit(
                            self.slot_req[s], "spec_verify",
                            dur=tick.call_s, accepted=int(n_acc[s]),
                            emitted=int(n_emit[s]), k=self.spec_k,
                            **extra)
                self._emit_spec(decoding, out, n_emit)
        return out, n_emit

    def _spec_any(self) -> bool:
        """True when at least one ACTIVE slot participates in the
        spec pipeline — with every active request opted out (the
        quarantine fallback), the plain chunk step is strictly better
        (one device call, `chunk` tokens per slot) than a vacuous
        draft+verify pair emitting one."""
        return any(self.active[s] and self.slot_req[s] not in
                   self._no_spec for s in range(self.B))

    def _emit_spec(self, decoding, out, n_emit) -> None:
        """Deliver one spec tick's emitted tokens (the host mirror of
        the device stopping rule) and retire finished slots."""
        for slot in decoding:
            rid = self.slot_req[slot]
            for j in range(int(n_emit[slot])):
                self.outputs[rid].append(int(out[slot, j]))
                self.budget[slot] -= 1
            o = self.outputs[rid]
            done = (self.budget[slot] <= 0
                    or (self.eos is not None and o
                        and o[-1] == self.eos)
                    or (self.stop[slot] >= 0 and o
                        and o[-1] == self.stop[slot]))
            if done:
                self._retire(slot)

    def step(self):
        """Admit what fits, then run ONE device chunk — fused with up to
        one admission-prefill unit when slots are decoding, plain decode
        otherwise.

        The serving layer's granularity: returns (emitted, finished) —
        `emitted` maps rid -> tokens newly generated since the last
        step() (the prefill's first token included), `finished` lists
        rids that completed this step (their blocks are already back in
        the pool). A step with nothing in flight is a cheap no-op."""
        with RecordEvent("serve.admit"):
            self._admit()
        if any(self.active):
            # slots committed by a fused admission AFTER the device call
            # must not read this chunk's token rows — they were inactive
            # (masked) rows during the call itself
            decoding = [s for s in range(self.B) if self.active[s]]
            if self._fuse_now():
                # admission pressure rides the PR 5 fused path even
                # under speculation (a plain chunk + piggybacked
                # prefill — greedy tokens are schedule-invariant, so
                # mixing the step kinds never changes output)
                self._step_fused(decoding)
            elif self.speculative and self._spec_any():
                # speculative tick: draft + verify emit up to spec_k+1
                # tokens per slot
                self._step_spec(decoding)
            else:
                self._step_decode(decoding)
            with RecordEvent("serve.admit"):
                self._admit()
        return self._drain_emitted()

    def _step_decode(self, decoding) -> None:
        """The plain decode chunk: `chunk` tokens for every active slot
        in one compiled call, one host sync, then delivery."""
        decode_rids = [self.slot_req[s] for s in decoding]
        with _Tick(
                self, "decode", decode_rids, (self.chunk, 0),
                rids=decode_rids, chunk=self.chunk,
                **self._decode_fields(decoding),
                compile_hit=(self.chunk, self.attention_impl)
                + self._skey + self._qkey + self._mkey
                in self._chunk_cache) as tick:
            with tick.phase("pack"):
                if self._dev_state is None:
                    self._dev_state = self._upload_slot_state()
                active, budget, stop = self._dev_state
            with tick.phase("dispatch"):
                (pools, tok, lengths, budget, active, toks,
                 stats) = self._chunk_exe()(
                    self.params, self.cache.pools, self.cache.table,
                    self.cur_tok, active, self.cache.lengths, budget, stop)
                tick.adopt(pools)
            tick.fence((pools, tok, toks))
            with tick.phase("wait"):
                # one host sync per decode chunk — the per-token loop
                # of the commit reads this numpy copy, never the device
                toks, stats = jax.device_get((toks, stats))  # ptlint: disable=SYNC001 — single per-chunk sync, hoisted out of the per-token loop
                self._note_counters(tick, stats)
            with tick.phase("commit"):
                self.cache = self.cache._replace(lengths=lengths)
                self.cur_tok = tok
                # steady state: the chunk's own outputs are next chunk's
                # inputs; _retire/_commit null this when the host diverges
                self._dev_state = (active, budget, stop)
                self._emit_chunk(decoding, toks)

    def _emit_chunk(self, decoding, toks) -> None:
        """Deliver one chunk's tokens [B, chunk] (host copy) to the
        `decoding` slots — the host mirror of the device stopping rule —
        and retire the slots that finished."""
        for slot in decoding:
            rid = self.slot_req[slot]
            for j in range(self.chunk):
                if self.budget[slot] <= 0:
                    break
                t = int(toks[slot, j])
                self.outputs[rid].append(t)
                self.budget[slot] -= 1
                if ((self.eos is not None and t == self.eos)
                        or t == self.stop[slot]):
                    break
            out = self.outputs[rid]
            done = (self.budget[slot] <= 0 or
                    (self.eos is not None and out and
                     out[-1] == self.eos) or
                    (self.stop[slot] >= 0 and out and
                     out[-1] == self.stop[slot]))
            if done:
                self._retire(slot)

    def _drain_emitted(self):
        """The step() return contract: (emitted rid -> new tokens,
        finished rids) off the delivery bookkeeping — shared by the
        chunk, fused and speculative step kinds."""
        emitted: Dict[int, List[int]] = {}
        for rid, n in list(self._delivered.items()):
            out = self.outputs.get(rid)
            if out is not None and len(out) > n:
                emitted[rid] = out[n:]
                self._delivered[rid] = len(out)
        finished, self._just_finished = self._just_finished, []
        for rid in finished:
            self._delivered.pop(rid, None)
        return emitted, finished

    def run(self) -> Dict[int, List[int]]:
        """Drain the queue and all in-flight requests (greedy decode)."""
        while True:
            self.step()
            if not (any(self.active) or self.queue or self._pending):
                break
        return self.outputs

