"""Rotary position embedding (RoPE) — fused rope kernel analog.

Reference analog: paddle/phi/kernels/fusion fused_rope (upstream-canonical,
unverified — SURVEY.md §0). The jnp form fuses fine under XLA (pure
elementwise); a Pallas version buys little, so this stays XLA-native by
design — the TPU-first answer is 'let the compiler fuse it into the
surrounding matmuls'.
"""
from __future__ import annotations

import math

import jax.numpy as jnp


def rope_freqs(head_dim: int, max_seq: int, base: float = 10000.0,
               dtype=jnp.float32):
    """Precompute cos/sin tables [max_seq, head_dim//2]."""
    inv = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature m(f, a) = 0.1 a ln f + 1 (1 for f <= 1)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, factor: float, original_max: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN (Peng et al. 2023) inverse frequencies [dim//2], float32: per
    frequency a blend of the interpolated 1/(factor base^(2i/dim)) and the
    extrapolated 1/base^(2i/dim), by the linear ramp between the two
    correction dims (where beta_fast / beta_slow rotations fit into the
    original context). High frequencies keep extrapolating."""
    def correction_dim(rotations):
        return dim * math.log(original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    extra = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def yarn_freqs(dim: int, max_seq: int, base: float, factor: float,
               original_max: int, beta_fast: float = 32.0,
               beta_slow: float = 1.0, mscale: float = 1.0,
               mscale_all_dim: float = 0.0, dtype=jnp.float32,
               attention_factor=None):
    """cos/sin tables [max_seq, dim//2] at YaRN frequencies, scaled by
    m(factor, mscale) / m(factor, mscale_all_dim) as the DeepSeek-V2
    rotary embedding does (1 where the two are equal), or by
    `attention_factor` where the configuration gives one (Hugging Face's
    `_compute_yarn_parameters`: q and k both carry it, so a score carries
    its square)."""
    inv = yarn_inv_freq(dim, base, factor, original_max, beta_fast, beta_slow)
    m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim) \
        if attention_factor is None else float(attention_factor)
    freqs = jnp.outer(jnp.arange(max_seq, dtype=jnp.float32), inv)
    return (jnp.cos(freqs) * m).astype(dtype), (jnp.sin(freqs) * m).astype(dtype)


def apply_rope(q, k, cos, sin, position_ids=None):
    """q,k: [B, S, H, D] (or [B,S,D]); cos/sin: [S_max, D/2] tables.

    Rotates pairs (x[2i], x[2i+1]) — "interleaved" convention matched to the
    reference's fused_rotary_position_embedding default (use_neox=False
    equivalence is handled by the caller's weight layout).
    """
    def rot(x):
        d = x.shape[-1]
        if position_ids is None:
            c = cos[: x.shape[1], : d // 2]
            s = sin[: x.shape[1], : d // 2]
        else:
            c = jnp.take(cos, position_ids, axis=0)[..., : d // 2]
            s = jnp.take(sin, position_ids, axis=0)[..., : d // 2]
        # broadcast over head dim: [B,S,1,D/2]
        while c.ndim < x.ndim - 1:
            c = c[:, :, None] if c.ndim == 2 else c[..., None, :]
            s = s[:, :, None] if s.ndim == 2 else s[..., None, :]
        x1 = x[..., 0::2]
        x2 = x[..., 1::2]
        o1 = x1 * c - x2 * s
        o2 = x2 * c + x1 * s
        return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)

    return rot(q), rot(k)


def apply_rope_half(q, k, cos, sin, position_ids=None, rotary_dim=None):
    """NeoX/Llama 'rotate_half' convention: split head dim in halves.
    `rotary_dim` (r, even, at most the head dim; None = all of it)
    rotates the FIRST r dims of a head, rotate-half inside them, from
    cos/sin tables [S_max, >= r/2], and passes dims r.. through (a
    partial rotary factor r / head_dim)."""
    def rot(x):
        d = x.shape[-1] if rotary_dim is None else int(rotary_dim)
        rest = None
        if d != x.shape[-1]:
            x, rest = x[..., :d], x[..., d:]
        if position_ids is None:
            c = jnp.concatenate([cos[: x.shape[1], : d // 2]] * 2, axis=-1)
            s = jnp.concatenate([sin[: x.shape[1], : d // 2]] * 2, axis=-1)
        else:
            cc = jnp.take(cos, position_ids, axis=0)[..., : d // 2]
            ss = jnp.take(sin, position_ids, axis=0)[..., : d // 2]
            c = jnp.concatenate([cc, cc], axis=-1)
            s = jnp.concatenate([ss, ss], axis=-1)
        while c.ndim < x.ndim:
            c = c[:, :, None, :] if c.ndim == 3 else c[None]
            s = s[:, :, None, :] if s.ndim == 3 else s[None]
        half = d // 2
        rot_x = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
        out = (x * c + rot_x * s).astype(x.dtype)
        return out if rest is None else jnp.concatenate([out, rest], axis=-1)

    return rot(q), rot(k)


def apply_rope_half_bhsd(q, k, cos, sin):
    """rotate_half over HEAD-MAJOR [B, H, S, D] tensors (the einsum-form
    attention layout — r5; cos/sin broadcast over the head axis instead
    of transposing activations into [B, S, H, D] and back)."""
    def rot(x):
        d = x.shape[-1]
        c = jnp.concatenate([cos[: x.shape[2], : d // 2]] * 2,
                            axis=-1)[None, None]
        s = jnp.concatenate([sin[: x.shape[2], : d // 2]] * 2,
                            axis=-1)[None, None]
        half = d // 2
        rx = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
        return (x * c + rx * s).astype(x.dtype)

    return rot(q), rot(k)
