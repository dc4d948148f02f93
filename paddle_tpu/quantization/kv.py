"""paddle_tpu.quantization.kv — single-source int8 paged-KV math.

The serving stack can store the paged KV pool as int8 codes with ONE
per-(layer, block) abs-max scale kept in a sibling scale pool
(`nlp/paged.py` wires the commit writes; `nlp/ragged_attention.py`
fuses the dequant into the kernel's block-chunk loop, where the scales
ride scalar prefetch). Every quantize / rescale / dequantize on that
path routes through these helpers so the XLA gather reference, the
Pallas kernel and the commit-write agree on the math by construction —
the bit-stable parity the interpret-mode suite pins would be
unfalsifiable if the two backends each carried a private copy.

Scale discipline (grow-only, rescale-on-growth): a block's scale is
abs-max over every value EVER written to it divided by the int8 bound.
When a later write raises the block's abs-max, the block's existing
codes rescale ONCE under the new scale (`rescale_codes` — an exact
identity when the scale did not change, one extra rounding when it
did), so a block's codes always dequantize under the single scale its
pool slot stores. Empty blocks carry scale 0 and all-zero codes, which
dequantize to exact zeros — the same contents a fresh fp pool holds.

Hot path: pure jnp, no host syncs — SYNC001 roots these helpers
helpers (they run inside every compiled decode/prefill step when
``kv_dtype="int8"``).
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = [
    "KV_DTYPES", "BOUND", "resolve_kv_dtype", "scale_of", "quantize",
    "dequantize", "rescale_codes", "kv_block_bytes",
]

#: Supported paged-KV storage modes: "fp" stores the compute dtype
#: (the pre-quantization behavior, byte-identical); "int8" stores int8
#: codes plus per-(layer, block) f32 abs-max scales.
KV_DTYPES = ("fp", "int8")

#: Symmetric int8 code range: codes live in [-127, 127] so that
#: quantize(-absmax) == -quantize(absmax) (no -128 asymmetry).
BOUND = 127.0


def resolve_kv_dtype(kv_dtype) -> str:
    """Normalize a ``kv_dtype`` choice: None and "fp" mean the fp pool
    (store the compute dtype — the default, byte-identical to the
    pre-quantization path); "int8" selects the quantized pool. Anything
    else raises ValueError."""
    if kv_dtype is None:
        return "fp"
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"kv_dtype must be one of {KV_DTYPES} (or None), "
            f"got {kv_dtype!r}")
    return kv_dtype


def scale_of(amax):
    """Abs-max → symmetric int8 scale (amax / 127). A zero abs-max
    yields scale 0: the all-zero-block sentinel `dequantize` maps back
    to exact zeros."""
    return amax / BOUND


def quantize(x, scale):
    """Quantize `x` to int8 codes under `scale` (broadcastable).
    Scale 0 marks a block nothing was ever written to — its codes stay
    0 via the safe divisor (x is 0 wherever scale is legitimately 0)."""
    s = jnp.where(scale > 0.0, scale, 1.0)
    return jnp.clip(jnp.round(x.astype(jnp.float32) / s),
                    -BOUND, BOUND).astype(jnp.int8)


def dequantize(codes, scale):
    """int8 codes → f32 values under `scale` (broadcastable). The ONE
    dequant both attention backends and the commit write use — scale 0
    (never-written block) dequantizes to exact zeros."""
    return codes.astype(jnp.float32) * scale


def rescale_codes(codes, old_scale, new_scale):
    """Re-express existing codes under a grown scale. Exact identity
    when the scale did not change (round(q * 1.0) == q for |q| <= 127
    in f32); one extra rounding when it did — the bounded cost of the
    grow-only scale discipline."""
    safe = jnp.where(new_scale > 0.0, new_scale, 1.0)
    ratio = jnp.where(new_scale > 0.0, old_scale / safe, 1.0)
    return jnp.clip(jnp.round(codes.astype(jnp.float32) * ratio),
                    -BOUND, BOUND).astype(jnp.int8)


def kv_block_bytes(num_layers: int, block_size: int, kv_heads: int,
                   head_dim: int, kv_dtype: str,
                   fp_itemsize: int = 2) -> int:
    """HBM bytes ONE pool block occupies across all layers, K and V
    pools together, INCLUDING the sibling scale pool's per-block
    overhead in int8 mode (2 pools x num_layers x 4-byte f32 scales).
    The single source for every bytes surface — `kv_pool_bytes` /
    `kv_bytes_per_token` gauges, the tests' gather-bytes gate, and
    `bucket_tuner`'s pad-bytes accounting all derive from it."""
    elems = num_layers * block_size * kv_heads * head_dim * 2
    if resolve_kv_dtype(kv_dtype) == "int8":
        return elems + num_layers * 2 * 4
    return elems * int(fp_itemsize)
