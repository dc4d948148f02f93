"""Plain reference of the window_moe_decoder family: float32 jax.numpy,
matmul precision "highest", no kernels, no cache, no ring, every key of
the sequence with a mask, a loop over the experts, one layer at a time so
that it fits beside nothing. It imports nothing of paddle_tpu and takes
nothing the program made: each layer's weights are drawn again from the
seed (benchmark/models/window_moe_decoder.py), in the type the
configuration serves them in, and upcast.

Follows the published configuration key for key (`model_type: mellum`).
Per token x of layer l, kind t_l = layer_types[l], eps from the
configuration:

  h = RMSNorm(x);  q, k, v = h W_q [D, H hd], h W_k [D, KV hd], h W_v
  q, k = RoPE_t(q, k) over all hd dims
  s_ij = q_i . k_j / sqrt(hd); visible iff j <= i and (t_l full or j > i - W)
  o_i = sum_j softmax_j(s_ij) v_j; query head n reads KV head n // (H / KV)
  x += o W_o;  h' = RMSNorm(x)
  p = softmax(h' W_r) in float32 over ALL experts;  S = top-k(p)
  g_e = p_e / sum_{e in S} p_e;  x += sum_{e in S} g_e W_d,e(silu(W_g,e h') W_u,e h')

RoPE_sliding: inv_freq_i = theta^(-2i/hd). RoPE_full: YaRN as Hugging
Face's `_compute_yarn_parameters`: per frequency a blend of the
interpolated 1/(factor theta^(2i/hd)) and the extrapolated one by the
linear ramp between the two correction dims (beta_fast, beta_slow over the
original context), cos and sin times `attention_factor`, so that a full
layer's scores carry its square.

Departures from the published model, all noted in the configuration file:
the rotate-half layout; the router read as softmax, top-k, renormalised;
no q/k norm; the multi-token-prediction head left out; random weights.

Attention is computed in blocks of queries against all keys, so that the
float32 scores of a 12,800-token sequence (21 GB whole) fit.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import window_moe_decoder as family

F32 = jnp.float32
Q_BLOCK = 256                       # queries a block of the attention

# the broken-program controls `served_gaps` takes beside `lower` and `act`
# (tools/control_family.py reads this): each has to miss one of a cell's
# limits
CONTROLS = {"no_window": {"no_window": True},
            "no_attention_factor": {"no_attention_factor": True},
            "no_renorm": {"no_renorm": True}}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def inv_freq(hd: int, rp: Dict[str, Any]):
    """[hd/2] inverse frequencies of one layer kind, numpy float64 then
    float32: plain, or YaRN's blend."""
    theta = float(rp["rope_theta"])
    extra = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if rp["rope_type"] == "default":
        return extra.astype(np.float32)
    factor = float(rp["factor"])
    orig = int(rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return hd * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rp["beta_slow"]))), hd - 1)
    ramp = np.clip((np.arange(hd // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def attention_factor(rp: Dict[str, Any]) -> float:
    if rp["rope_type"] == "default":
        return 1.0
    if rp.get("attention_factor") is not None:
        return float(rp["attention_factor"])
    f = float(rp["factor"])
    return 1.0 if f <= 1.0 else 0.1 * math.log(f) + 1.0


def _rope(x, rp, scale: float):
    """x [B, T, N, hd], positions 0..T-1, rotate-half; cos and sin times
    `scale`."""
    T, hd = x.shape[1], x.shape[-1]
    ang = jnp.arange(T, dtype=F32)[:, None] * jnp.asarray(inv_freq(hd, rp))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None] * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None] * scale
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def route(h, router_w, d, renorm: bool = True):
    """h [..., D] float32 -> (idx [..., k], gates [..., k]): softmax over
    all experts, top-k, renormalised over the k chosen."""
    p = jax.nn.softmax(jnp.matmul(h, router_w,
                                  precision=jax.lax.Precision.HIGHEST), -1)
    top, idx = jax.lax.top_k(p, d["k"])
    if d["norm_topk"] and renorm:
        top = top / jnp.sum(top, -1, keepdims=True)
    return idx, top


def layer(x, w, d, kind: str, lower: Optional[Callable] = None,
          act: Optional[Callable] = None, no_window: bool = False,
          no_attention_factor: bool = False, no_renorm: bool = False):
    """One decoder layer of kind `kind`, x [B, T, D] float32. `lower`
    rounds the rows a cache would hold, `act` both operands of every
    matmul (the controls). The broken-program controls: the window left
    out on a window layer, YaRN's attention factor left out on a full
    one, the gates' renormalisation left out."""
    B, T, D = x.shape
    H, KV, hd = d["H"], d["KV"], d["hd"]
    rep = H // KV
    w = jax.tree.map(lambda a: a.astype(F32), w)
    r = (lambda a: a) if act is None else act
    rp = d["rope"][kind]

    def mm(a, b):
        return r(a) @ r(b)

    h = _rms(x, w["input_layernorm"], d["eps"])
    af = 1.0 if no_attention_factor else attention_factor(rp)
    q = _rope(mm(h, w["q_proj"]).reshape(B, T, H, hd), rp, af)
    k = _rope(mm(h, w["k_proj"]).reshape(B, T, KV, hd), rp, af)
    v = mm(h, w["v_proj"]).reshape(B, T, KV, hd)
    if lower is not None:
        k, v = lower(k), lower(v)
    window = d["W"] if kind == "window" and not no_window else None
    pad = (-T) % Q_BLOCK
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        B, (T + pad) // Q_BLOCK, Q_BLOCK, KV, rep, hd)
    kj = jnp.arange(T)[None, :]

    def block(args):
        i0, qi = args                                   # qi [B, Qb, KV, rep, hd]
        s = jnp.einsum("bqkrd,btkd->bkrqt", r(qi), r(k)) / math.sqrt(hd)
        qpos = i0 + jnp.arange(Q_BLOCK)[:, None]
        vis = kj <= qpos
        if window is not None:
            vis = vis & (kj > qpos - window)
        p = jax.nn.softmax(jnp.where(vis[None, None, None], s, -jnp.inf), -1)
        if s.dtype != F32:                  # the program turns x64 on
            raise TypeError(f"reference left float32: {s.dtype}")
        return jnp.einsum("bkrqt,btkd->bqkrd", r(p), r(v))

    o = jax.lax.map(block, (jnp.arange(0, T + pad, Q_BLOCK),
                            jnp.moveaxis(qb, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, T + pad, H * hd)[:, :T]
    x = x + mm(o, w["o_proj"])
    h = _rms(x, w["post_attention_layernorm"], d["eps"])
    idx, gates = route(h, w["router"], d, renorm=not no_renorm)

    def expert(y, e):
        j, gate, up, down = e
        g = jnp.sum(jnp.where(idx == d["first"] + j, gates, 0.0), -1)
        return y + g[..., None] * mm(jax.nn.silu(mm(h, gate)) * mm(h, up),
                                     down), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        jnp.arange(d["n"], dtype=jnp.int32), w["experts_gate"],
        w["experts_up"], w["experts_down"]))
    x = x + y
    if x.dtype != F32:
        raise TypeError(f"reference left float32: {x.dtype}")
    return x


@functools.lru_cache(maxsize=16)
def _programs(d_json: str, weight_dtype, lower, act, broken):
    """The jitted pieces of one forward, made ONCE per (sizes, type, hooks):
    a jit made afresh per block would trace and lower the layer again for
    every block of requests."""
    d = json.loads(d_json)
    make = jax.jit(lambda k: family.layer_weights(k, d, weight_dtype))
    step = {kind: jax.jit(functools.partial(
        layer, d=d, kind=kind, lower=lower, act=act, **dict(broken)))
        for kind in ("full", "window")}
    outer = jax.jit(functools.partial(family.outer_weights, d=d,
                                      dtype=weight_dtype))
    head = jax.jit(lambda x, o: _rms(x, o["norm"].astype(F32), d["eps"])
                   @ o["lm_head"].astype(F32))
    return make, step, outer, head


def logits(seed: int, d: Dict[str, Any], tokens, weight_dtype=jnp.bfloat16,
           lower: Optional[Callable] = None, act: Optional[Callable] = None,
           positions=None, **broken):
    """tokens [B, T] int32 -> logits [B, T, V] float32, or, with
    `positions` [B, S], the logits at those positions alone [B, S, V]
    (the head over 98,304 words at 13,312 positions is 5.2 GB). Layer by
    layer: only one layer's weights live at a time."""
    key = family.seed_key(seed)
    make, step, outer_of, head = _programs(
        json.dumps(d, sort_keys=True), jnp.dtype(weight_dtype), lower, act,
        tuple(sorted(broken.items())))
    with jax.default_matmul_precision("highest"):
        outer = outer_of(key)
        x = jnp.take(outer["embed_tokens"], tokens, axis=0).astype(F32)
        for i, kind in enumerate(d["kinds"]):
            x = step[kind](x, make(family.layer_key(key, jnp.int32(i))))
        if positions is not None:
            x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], 1)
        return head(x, outer)


def served_gaps(seed: int, d: Dict[str, Any], prompts, served,
                weight_dtype=jnp.bfloat16, lower=None, act=None,
                rows: int = 1, pad: int = 1024, **broken):
    """As `reference.dense_decoder.served_gaps`: for each (prompt, served
    tokens) pair run the reference once over prompt + served and read, at
    every served token, how far its logit lies below the reference's
    best. With `lower`, `act` or a broken-program control, the gaps of the
    token that forward puts first at the same positions. Blocks of `rows`
    sequences of like length, each padded to a multiple of `pad` (few
    distinct lengths, so few compilations); the head runs at the served
    positions alone."""
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
        S = -(-max(len(served[b]) for b in block) // 128) * 128
        at_pos = np.zeros((rows, S), np.int32)
        for j, b in enumerate(block):
            toks[j, :len(seqs[b])] = seqs[b]
            # position t answers with token t + 1
            at_pos[j] = np.minimum(len(prompts[b]) - 1 + np.arange(S), T - 1)
        ref = logits(seed, d, jnp.asarray(toks), weight_dtype,
                     positions=at_pos)
        if not control:
            chosen = jnp.asarray(np.take_along_axis(
                np.roll(toks, -1, axis=1), at_pos, 1))
        else:
            chosen = jnp.argmax(logits(seed, d, jnp.asarray(toks),
                                       weight_dtype, lower, act,
                                       positions=at_pos, **broken), -1)
        gaps = np.asarray(gap_of(ref, chosen))
        for j, b in enumerate(block):
            out[b] = gaps[j, :len(served[b])]
    return np.concatenate(out) if out else np.zeros(0, np.float32)


def int8_blocks(x, block: int = 16):
    """Round cached rows [B, T, ...] to int8 with one abs-max scale per
    block of `block` positions: what an int8 pool would hold (a `lower`
    control; the program refuses int8 KV for this family)."""
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
