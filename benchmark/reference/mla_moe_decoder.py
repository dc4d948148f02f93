"""Plain reference of the mla_moe_decoder family: float32 jax.numpy, matmul
precision "highest", no kernels, no cache, the EXPANDED form of the
attention only, a Python loop over the held experts, one layer at a time
so that it fits beside nothing. It imports nothing of paddle_tpu and takes
nothing the program made: each layer's weights are drawn again from the
seed (benchmark/models/mla_moe_decoder.py), in the type the configuration
serves them in, and upcast.

Follows the published block (DeepSeek-V2, Liu et al. 2024, section 2.1 and
its Hugging Face implementation, which A.X-K1's `axk1` model type repeats
key for key). Per token x, eps from the configuration:

  h = RMSNorm(x);  c_q = RMSNorm(h W_dq);  [q_nope | q_rope] = c_q W_uq
  [c | k_r] = h W_dkv;  c = RMSNorm(c);  k_r = RoPE(k_r);  q_rope = RoPE(.)
  [k_nope | v] = c W_ukv per head
  score = (q_nope . k_nope + q_rope . k_r) * s, causal softmax, o = P v
  x += concat(o) W_o
  layer < first_k_dense_replace:  x += MLP(RMSNorm(x))
  else: s = sigmoid(h' W_g) in float32 over ALL routed experts, I = top-k,
        g_i = scale * s_i / sum_{j in I} s_j,
        x += sum_{i in I, i held here} g_i E_i(h') + E_shared(h')

YaRN: per frequency a blend of 1/(factor theta^(2i/d)) and 1/theta^(2i/d)
by the linear ramp between the two correction dims; cos and sin times
m(factor, mscale) / m(factor, mscale_all_dim); s = (dn + dr)^-0.5 *
m(factor, mscale_all_dim)^2, m(f, a) = 0.1 a ln f + 1.

Departures from the published model, all noted in the configuration file:
RoPE in the rotate-half layout (the published interleaved one differs by a
fixed permutation of the rope columns of W_uq and W_dkv, which random
weights do not see); `topk_method: "none"` read as plain top-k with no
group limit and no correction bias; the experts held here only (what
absent experts would add is left out, as in the program); the vocabulary's
slice.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import mla_moe_decoder as family

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mscale(factor: float, a: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * a * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, y: Dict[str, Any]):
    """[dim/2] inverse frequencies, numpy float64 then float32."""
    factor, orig = float(y["factor"]), int(y["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(y["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(y["beta_slow"]))), dim - 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def softmax_scale(d: Dict[str, Any]) -> float:
    y = d["yarn"]
    s = (d["dn"] + d["dr"]) ** -0.5
    if y.get("mscale_all_dim"):
        s *= mscale(float(y["factor"]), float(y["mscale_all_dim"])) ** 2
    return s


def _rope(x, d):
    """x [B, T, N, dr], positions 0..T-1, rotate-half, YaRN frequencies."""
    T, hd = x.shape[1], x.shape[-1]
    y = d["yarn"]
    inv = jnp.asarray(yarn_inv_freq(hd, d["theta"], y))
    m = mscale(float(y["factor"]), float(y["mscale"])) \
        / mscale(float(y["factor"]), float(y.get("mscale_all_dim", 0.0)))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]         # [T, hd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None] * m
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None] * m
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def route(h, router_w, d):
    """h [..., D] float32 -> (idx [..., k], gates [..., k]): sigmoid scores
    over all routed experts, top-k, normalised over the k chosen, scaled."""
    s = jax.nn.sigmoid(jnp.matmul(h, router_w,
                                  precision=jax.lax.Precision.HIGHEST))
    top, idx = jax.lax.top_k(s, d["k"])
    if d["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return idx, top * d["route_scale"]


def _mlp(h, gate, up, down, mm):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def layer(x, w, d, moe: bool, lower: Optional[Callable] = None,
          act: Optional[Callable] = None, drop_shared: bool = False,
          route_scale: Optional[float] = None):
    """One decoder layer, x [B, T, D] float32. `lower` rounds the rows a
    cache would hold, `act` both operands of every matmul (the controls).
    `drop_shared` and `route_scale` are the broken-program controls: the
    shared expert left out, another factor on the gates."""
    B, T, D = x.shape
    H, R, dn, dr, dv = d["H"], d["R"], d["dn"], d["dr"], d["dv"]
    w = jax.tree.map(lambda a: a.astype(F32), w)
    r = (lambda a: a) if act is None else act

    def mm(a, b):
        return r(a) @ r(b)

    h = _rms(x, w["input_layernorm"], d["eps"])
    cq = _rms(mm(h, w["q_a_proj"]), w["q_a_layernorm"], d["eps"])
    q = mm(cq, w["q_b_proj"]).reshape(B, T, H, dn + dr)
    ckv = mm(h, w["kv_a_proj_with_mqa"])
    c = _rms(ckv[..., :R], w["kv_a_layernorm"], d["eps"])
    k_r = _rope(ckv[..., None, R:], d)                         # [B, T, 1, dr]
    q_r = _rope(q[..., dn:], d)
    if lower is not None:
        c, k_r = lower(c), lower(k_r)
    kv = mm(c, w["kv_b_proj"]).reshape(B, T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    s = (jnp.einsum("bthd,bshd->bhts", r(q[..., :dn]), r(k_nope))
         + jnp.einsum("bthd,bsd->bhts", r(q_r), r(k_r[:, :, 0]))) \
        * softmax_scale(d)
    mask = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", r(p), r(v)).reshape(B, T, H * dv)
    x = x + mm(o, w["o_proj"])
    h = _rms(x, w["post_attention_layernorm"], d["eps"])
    if not moe:
        x = x + _mlp(h, w["gate_proj"], w["up_proj"], w["down_proj"], mm)
    else:
        dd = d if route_scale is None else {**d, "route_scale": route_scale}
        idx, gates = route(h, w["router"], dd)
        y = jnp.zeros_like(x)
        for j in range(d["n"]):                # the experts held here
            g = jnp.sum(jnp.where(idx == d["first"] + j, gates, 0.0), -1)
            y = y + g[..., None] * _mlp(h, w["experts_gate"][j],
                                        w["experts_up"][j],
                                        w["experts_down"][j], mm)
        if not drop_shared:
            y = y + _mlp(h, w["gate_proj"], w["up_proj"], w["down_proj"], mm)
        x = x + y
    if x.dtype != F32 or s.dtype != F32:      # the program turns x64 on
        raise TypeError(f"reference left float32: {x.dtype}, {s.dtype}")
    return x


@functools.lru_cache(maxsize=16)
def _programs(d_json: str, weight_dtype, lower, act, broken):
    """The jitted pieces of one forward, made ONCE per (sizes, type, hooks):
    a jit made afresh per block would trace and lower the 12-expert layer
    again for every block of requests."""
    d = json.loads(d_json)
    make = {moe: jax.jit(functools.partial(
        lambda k, moe: family.layer_weights(k, d, weight_dtype, moe),
        moe=moe)) for moe in (False, True)}
    step = {moe: jax.jit(functools.partial(
        layer, d=d, moe=moe, lower=lower, act=act, **dict(broken)))
        for moe in (False, True)}
    outer = jax.jit(functools.partial(family.outer_weights, d=d,
                                      dtype=weight_dtype))
    head = jax.jit(lambda x, o: _rms(x, o["norm"].astype(F32), d["eps"])
                   @ o["lm_head"].astype(F32))
    return make, step, outer, head


def logits(seed: int, d: Dict[str, Any], tokens, weight_dtype=jnp.bfloat16,
           lower: Optional[Callable] = None, act: Optional[Callable] = None,
           **broken):
    """tokens [B, T] int32 -> logits [B, T, V] float32. Layer by layer:
    only one layer's weights live at a time."""
    key = family.seed_key(seed)
    make, step, outer_of, head = _programs(
        json.dumps(d, sort_keys=True), jnp.dtype(weight_dtype), lower, act,
        tuple(sorted(broken.items())))
    with jax.default_matmul_precision("highest"):
        outer = outer_of(key)
        x = jnp.take(outer["embed_tokens"], tokens, axis=0).astype(F32)
        for i in range(d["L"]):
            moe = i >= d["Ld"]
            x = step[moe](x, make[moe](family.layer_key(key, jnp.int32(i))))
        return head(x, outer)


def served_gaps(seed: int, d: Dict[str, Any], prompts, served,
                weight_dtype=jnp.bfloat16, lower=None, act=None,
                rows: int = 2, pad: int = 256, **broken):
    """As `reference.dense_decoder.served_gaps`: for each (prompt, served
    tokens) pair run the reference once over prompt + served and read, at
    every served token, how far its logit lies below the reference's
    best. With `lower`, `act` or a broken-program control, the gaps of the
    token that forward puts first at the same positions. Blocks of `rows`
    sequences of like length (two: the float32 scores of 64 heads over
    2048 positions are 1 GiB a row), each padded to a multiple of `pad`."""
    seqs = [list(p) + list(s) for p, s in zip(prompts, served)]
    order = sorted(range(len(seqs)), key=lambda b: len(seqs[b]))
    gap_of = jax.jit(lambda ref, chosen: jnp.max(ref, -1) - jnp.take_along_axis(
        ref, chosen[..., None], axis=-1)[..., 0])
    control = lower is not None or act is not None or bool(broken)
    out = [np.zeros(0, np.float32)] * len(seqs)
    for at in range(0, len(order), rows):
        block = order[at:at + rows]
        T = -(-len(seqs[block[-1]]) // pad) * pad
        toks = np.zeros((rows, T), np.int32)
        for j, b in enumerate(block):
            toks[j, :len(seqs[b])] = seqs[b]
        ref = logits(seed, d, jnp.asarray(toks), weight_dtype)
        if not control:
            # position t answers with token t + 1
            chosen = jnp.asarray(np.roll(toks, -1, axis=1))
        else:
            chosen = jnp.argmax(logits(seed, d, jnp.asarray(toks),
                                       weight_dtype, lower, act, **broken),
                                -1)
        gaps = np.asarray(gap_of(ref, chosen))
        for j, b in enumerate(block):
            n, m = len(prompts[b]), len(served[b])
            out[b] = gaps[j, n - 1:n - 1 + m]
    return np.concatenate(out) if out else np.zeros(0, np.float32)


def int8_blocks(x, block: int = 16):
    """Round cached rows [B, T, ...] to int8 with one abs-max scale per
    block of `block` positions: what an int8 latent pool would hold (a
    `lower` control; the program has no such pool yet)."""
    B, T = x.shape[:2]
    pad = (-T) % block
    xp = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    xb = xp.reshape(B, (T + pad) // block, block, -1)
    scale = jnp.max(jnp.abs(xb), axis=(2, 3), keepdims=True) / 127.0
    q = jnp.round(xb / jnp.where(scale > 0, scale, 1.0)) * scale
    return q.reshape(xp.shape)[:, :T]


def _round_to(x, dtype, top):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * (top / amax)).astype(dtype).astype(F32) * (amax / top)


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor: the nearest
    precision below bfloat16 that the chip's matrix unit takes (the
    `lower` / `act` control)."""
    return _round_to(x, jnp.float8_e4m3fn, 448.0)
