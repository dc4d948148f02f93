"""Plain reference of the gated_window_moe_decoder family: float32
jax.numpy, matmul precision "highest", no kernels, no cache, no ring, every
key of the sequence with a mask, a loop over the experts, one layer at a
time so that it fits beside nothing. It imports nothing of paddle_tpu and
takes nothing the program made: each layer's weights are drawn again from
the seed (benchmark/models/gated_window_moe_decoder.py), in the type the
configuration serves them in, and upcast. It is given the chip's share:
the held experts (`first`, `n` of the router's `E`) and the vocabulary's
slice; what the absent experts would add is left out.

Follows the published configuration key for key (`model_type: laguna`).
Per token x of layer l, kind t = layer_types[l], H_t =
num_attention_heads_per_layer[l], eps from the configuration:

  h  = RMSNorm(x)
  q  = h Wq_t [D, H_t hd];  k, v = h Wk, h Wv [D, KV hd]
  q, k = RoPE_t(q, k) over the FIRST r_t = partial_rotary_factor_t hd dims
         of a head (rotate-half inside them), the rest passed through
  s_ij = q_i . k_j / sqrt(hd); visible iff j <= i and (t full or j > i - W)
  a_n  = sum_j softmax_j(s_ij) v_j; query head n reads KV head n // (H_t / KV)
  g    = sigmoid(h Wg_t) [H_t];  x += concat_n(g_n a_n) Wo_t
  h'   = RMSNorm(x)
  l in mlp_only_layers:  x += Wdown(silu(Wgate h') Wup h')
  else:  p = softmax(h' Wr) in float32 over ALL E experts;  S = top-k(p)
         w_e = p_e / sum_{e in S} p_e
         x += scale sum_{e in S, held} w_e E_e(h')  +  E_shared(h')

RoPE_sliding: inv_freq_i = theta^(-2i/r). RoPE_full: YaRN as Hugging
Face's `_compute_yarn_parameters` over dim r (the blend of interpolated
and extrapolated frequencies by the ramp between the correction dims), cos
and sin times `attention_factor`, so that the rotated dims' part of a full
layer's scores carries its square.

Departures from the published model, all noted in the configuration file
under `assumed`: where the gate sits and what it reads; the router read as
softmax, top-k, renormalised, then scaled; the shared expert ungated; no
q/k norm; the rotate-half layout; random weights.

Attention is computed in blocks of queries against all keys, so that the
float32 scores of a 4,864-token sequence at 72 heads fit.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import gated_window_moe_decoder as family
from .window_moe_decoder import (F32, Q_BLOCK, _rms, attention_factor, fp8,
                                 int8_blocks, inv_freq)

__all__ = ["CONTROLS", "attention", "ffn", "layer", "logits", "served_gaps", "fp8",
           "int8_blocks"]

# the broken-program controls `served_gaps` takes beside `lower` and `act`
# (tools/control_family.py reads this): each has to miss one of a cell's
# limits
CONTROLS = {"drop_gate": {"drop_gate": True},
            "full_rotary": {"full_rotary": True},
            "drop_shared": {"drop_shared": True},
            "route_scale": {"route_scale_one": True},
            "no_window": {"no_window": True}}


def _rope(x, rp, scale: float, r: int):
    """x [B, T, N, hd], positions 0..T-1: rotate-half over the first `r`
    dims of a head at the frequencies of a rotary of dim `r`, cos and sin
    times `scale`; dims r.. pass through."""
    T = x.shape[1]
    ang = jnp.arange(T, dtype=F32)[:, None] * jnp.asarray(inv_freq(r, rp))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None] * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None] * scale
    xr, rest = x[..., :r], x[..., r:]
    rot = jnp.concatenate([-xr[..., r // 2:], xr[..., :r // 2]], -1)
    return jnp.concatenate([xr * cos + rot * sin, rest], -1)


def route(h, router_w, d, scale: Optional[float] = None):
    """h [..., D] float32 -> (idx [..., k], gates [..., k]): softmax over
    all experts, top-k, renormalised over the k chosen, times the routed
    scale."""
    p = jax.nn.softmax(jnp.matmul(h, router_w,
                                  precision=jax.lax.Precision.HIGHEST), -1)
    top, idx = jax.lax.top_k(p, d["k"])
    if d["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return idx, top * (d["route_scale"] if scale is None else scale)


def _hooks(w, act):
    w = jax.tree.map(lambda a: a.astype(F32), w)
    r = (lambda a: a) if act is None else act
    return w, r, lambda a, b: r(a) @ r(b)


def attention(x, w, d, i: int, lower: Optional[Callable] = None,
              act: Optional[Callable] = None, drop_gate: bool = False,
              full_rotary: bool = False, no_window: bool = False):
    """Layer `i`'s attention sublayer onto the residual stream x
    [B, T, D] float32. `lower` rounds the rows a cache would hold, `act`
    both operands of every matmul (the controls). The broken-program
    controls: the output gate left out; every kind rotated over all of a
    head's dims; the window left out on a window layer."""
    B, T, D = x.shape
    kind = d["kinds"][i]
    H, KV, hd = d["H"][kind], d["KV"], d["hd"]
    rep = H // KV
    w, r, mm = _hooks(w, act)
    rp = d["rope"][kind]
    h = _rms(x, w["input_layernorm"], d["eps"])
    af = attention_factor(rp)
    rot = hd if full_rotary else int(round(
        hd * float(rp.get("partial_rotary_factor", 1.0))))
    q = _rope(mm(h, w["q_proj"]).reshape(B, T, H, hd), rp, af, rot)
    k = _rope(mm(h, w["k_proj"]).reshape(B, T, KV, hd), rp, af, rot)
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
    o = jnp.moveaxis(o, 0, 1).reshape(B, T + pad, H, hd)[:, :T]
    if not drop_gate:
        o = o * jax.nn.sigmoid(mm(h, w["g_proj"]))[..., None]
    return x + mm(o.reshape(B, T, H * hd), w["o_proj"])


def ffn(x, w, d, i: int, act: Optional[Callable] = None,
        drop_shared: bool = False, route_scale_one: bool = False,
        experts=None):
    """Layer `i`'s FFN sublayer onto x: the dense MLP of a leading layer,
    or the held experts' part of the routed sum plus the shared expert.
    The broken-program controls: the shared expert left out; the routed
    scale read as 1. `experts` (first, n): another share of the routed
    experts than the configuration's, among stacks that hold them all."""
    w, r, mm = _hooks(w, act)
    h = _rms(x, w["post_attention_layernorm"], d["eps"])

    def mlp(gate, up, down):
        return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)

    shared = mlp(w["gate_proj"], w["up_proj"], w["down_proj"])
    if i < d["Ld"]:
        return x + shared
    idx, gates = route(h, w["router"], d, 1.0 if route_scale_one else None)
    first, n = (d["first"], d["n"]) if experts is None else experts

    def expert(y, e):
        j, gate, up, down = e
        g = jnp.sum(jnp.where(idx == first + j, gates, 0.0), -1)
        return y + g[..., None] * mlp(gate, up, down), None

    lo = first - d["first"]                 # within the drawn stacks
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        jnp.arange(n, dtype=jnp.int32), w["experts_gate"][lo:lo + n],
        w["experts_up"][lo:lo + n], w["experts_down"][lo:lo + n]))
    return x + y if drop_shared else x + y + shared


def layer(x, w, d, i: int, lower: Optional[Callable] = None,
          act: Optional[Callable] = None, drop_gate: bool = False,
          full_rotary: bool = False, drop_shared: bool = False,
          route_scale_one: bool = False, no_window: bool = False):
    """Layer `i`, x [B, T, D] float32: its attention, then its FFN."""
    x = ffn(attention(x, w, d, i, lower, act, drop_gate, full_rotary,
                      no_window),
            w, d, i, act, drop_shared, route_scale_one)
    if x.dtype != F32:
        raise TypeError(f"reference left float32: {x.dtype}")
    return x


@functools.lru_cache(maxsize=16)
def _programs(d_json: str, weight_dtype, lower, act, broken):
    """The jitted pieces of one forward, made ONCE per (sizes, type, hooks):
    a jit made afresh per block would trace and lower the layer again for
    every block of requests. One program a SHAPE of layer: the leading
    dense one, then one a kind."""
    d = json.loads(d_json)
    make, step = {}, {}
    for i, kind in enumerate(d["kinds"]):
        shape = (i < d["Ld"], kind)
        if shape not in step:
            make[shape] = jax.jit(functools.partial(
                family.layer_weights, d=d, dtype=weight_dtype, i=i))
            step[shape] = jax.jit(functools.partial(
                layer, d=d, i=i, lower=lower, act=act, **dict(broken)))
    outer = jax.jit(functools.partial(family.outer_weights, d=d,
                                      dtype=weight_dtype))
    head = jax.jit(lambda x, o: _rms(x, o["norm"].astype(F32), d["eps"])
                   @ o["lm_head"].astype(F32))
    return make, step, outer, head


def logits(seed: int, d: Dict[str, Any], tokens, weight_dtype=jnp.bfloat16,
           lower: Optional[Callable] = None, act: Optional[Callable] = None,
           positions=None, **broken):
    """tokens [B, T] int32 -> logits [B, T, V] float32, or, with
    `positions` [B, S], the logits at those positions alone [B, S, V].
    Layer by layer: only one layer's weights live at a time."""
    key = family.seed_key(seed)
    make, step, outer_of, head = _programs(
        json.dumps(d, sort_keys=True), jnp.dtype(weight_dtype), lower, act,
        tuple(sorted(broken.items())))
    with jax.default_matmul_precision("highest"):
        outer = outer_of(key)
        x = jnp.take(outer["embed_tokens"], tokens, axis=0).astype(F32)
        for i, kind in enumerate(d["kinds"]):
            shape = (i < d["Ld"], kind)
            x = step[shape](x, make[shape](
                family.layer_key(key, jnp.int32(i))))
        if positions is not None:
            x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], 1)
        return head(x, outer)


def served_gaps(seed: int, d: Dict[str, Any], prompts, served,
                weight_dtype=jnp.bfloat16, lower=None, act=None,
                rows: int = 1, pad: int = 1024, **broken):
    """As `reference.window_moe_decoder.served_gaps`: for each (prompt,
    served tokens) pair run the reference once over prompt + served and
    read, at every served token, how far its logit lies below the
    reference's best. With `lower`, `act` or a broken-program control, the
    gaps of the token that forward puts first at the same positions.
    Blocks of `rows` sequences, EVERY block padded to the one length the
    longest sequence of the call needs (a multiple of `pad`) and read at
    one count of served positions (a multiple of 256): a window's sample
    always holds its longest request, so every run of a cell compiles the
    same few programs and later runs read them back. (A length a block
    wrote three layer programs a run into the machine's capped compile
    cache, which then dropped the cell's step programs: four of seven
    consecutive runs set up cold, 435-455 s for 72-77, PERF.md section 6,
    PR 43.) The head runs at the served positions alone."""
    seqs = [list(p) + list(s) for p, s in zip(prompts, served)]
    order = sorted(range(len(seqs)), key=lambda b: len(seqs[b]))
    gap_of = jax.jit(lambda ref, chosen: jnp.max(ref, -1) - jnp.take_along_axis(
        ref, chosen[..., None], axis=-1)[..., 0])
    control = lower is not None or act is not None or bool(broken)
    out = [np.zeros(0, np.float32)] * len(seqs)
    T = -(-max(map(len, seqs), default=1) // pad) * pad
    S = -(-max(map(len, served), default=1) // 256) * 256
    for at in range(0, len(order), rows):
        block = order[at:at + rows]
        toks = np.zeros((rows, T), np.int32)
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
