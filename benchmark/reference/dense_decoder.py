"""Plain reference of the dense_decoder family: float32 jax.numpy, matmul
precision "highest", no kernels, no cache, no batching tricks, one layer
at a time so that it fits beside nothing. It imports nothing of
paddle_tpu and takes nothing the program made: each layer's weights are
drawn again from the seed (benchmark/models/dense_decoder.py), in the
type the configuration serves them in, and upcast.

Follows the published block (Mistral-7B, Jiang et al. 2023, and its
Hugging Face implementation): x += Attn(RMSNorm(x)); x += MLP(RMSNorm(x));
rotary embedding in the rotate-half convention on q and k; grouped-query
attention, causal; MLP down(silu(gate(x)) * up(x)); final RMSNorm, untied
head. No sliding window (v0.3 has none).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import dense_decoder as family

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, T, N, hd], positions 0..T-1, rotate-half."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]         # [T, hd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def layer(x, w, d, lower: Optional[Callable] = None,
          act: Optional[Callable] = None):
    """One decoder layer, x [B, T, D] float32. The two hooks are used only
    by the controls: `lower` rounds the keys and values a cache would
    hold, `act` rounds both operands of every matmul."""
    B, T, D = x.shape
    H, KV, hd = d["H"], d["KV"], d["hd"]
    w = jax.tree.map(lambda a: a.astype(F32), w)
    r = (lambda a: a) if act is None else act

    def mm(a, b):
        return r(a) @ r(b)

    h = _rms(x, w["input_layernorm"], d["eps"])
    q = mm(h, w["q_proj"]).reshape(B, T, H, hd)
    k = mm(h, w["k_proj"]).reshape(B, T, KV, hd)
    v = mm(h, w["v_proj"]).reshape(B, T, KV, hd)
    q, k = _rope(q, d["theta"]), _rope(k, d["theta"])
    if lower is not None:
        k, v = lower(k), lower(v)
    rep = H // KV
    q = q.reshape(B, T, KV, rep, hd)
    s = jnp.einsum("btgrd,bsgd->bgrts", r(q), r(k)) * (float(hd) ** -0.5)
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrts,bsgd->btgrd", r(p), r(v)).reshape(B, T, H * hd)
    x = x + mm(o, w["o_proj"])
    h = _rms(x, w["post_attention_layernorm"], d["eps"])
    x = x + mm(jax.nn.silu(mm(h, w["gate_proj"])) * mm(h, w["up_proj"]),
               w["down_proj"])
    if x.dtype != F32 or s.dtype != F32:      # the program turns x64 on
        raise TypeError(f"reference left float32: {x.dtype}, {s.dtype}")
    return x


def int8_blocks(x, block: int = 16):
    """Round keys or values [B, T, KV, hd] to int8 with one abs-max scale
    per block of `block` positions: what an int8 KV cache holds."""
    B, T, KV, hd = x.shape
    pad = (-T) % block
    xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    xb = xp.reshape(B, (T + pad) // block, block, KV, hd)
    scale = jnp.max(jnp.abs(xb), axis=(2, 3, 4), keepdims=True) / 127.0
    q = jnp.round(xb / jnp.where(scale > 0, scale, 1.0)) * scale
    return q.reshape(B, T + pad, KV, hd)[:, :T]


def logits(seed: int, d: Dict[str, Any], tokens, weight_dtype=jnp.bfloat16,
           lower: Optional[Callable] = None, act: Optional[Callable] = None):
    """tokens [B, T] int32 -> logits [B, T, V] float32. Layer by layer:
    only one layer's weights live at a time."""
    key = family.seed_key(seed)
    with jax.default_matmul_precision("highest"):
        outer = jax.jit(functools.partial(
            family.outer_weights, d=d, dtype=weight_dtype))(key)
        make = jax.jit(lambda k: family.layer_weights(k, d, weight_dtype))
        step = jax.jit(functools.partial(layer, d=d, lower=lower, act=act))
        x = jnp.take(outer["embed_tokens"], tokens, axis=0).astype(F32)
        for i in range(d["L"]):
            x = step(x, make(family.layer_key(key, jnp.int32(i))))
        head = jax.jit(lambda x, o: _rms(x, o["norm"].astype(F32), d["eps"])
                       @ o["lm_head"].astype(F32))
        return head(x, outer)


def served_gaps(seed: int, d: Dict[str, Any], prompts, served,
                weight_dtype=jnp.bfloat16, lower=None, act=None,
                rows: int = 4, pad: int = 256):
    """For each (prompt, served tokens) pair run the reference once over
    prompt + served and read, at every served token, how far its logit
    lies below the reference's best. Returns per-token gaps (one array, in
    the order given), and with `lower` or `act` the gaps of the token that
    the lower-precision forward puts first at the same positions. Runs in
    blocks of `rows` sequences of like length, each block padded to a
    multiple of `pad` tokens, so that it fits beside nothing and compiles
    few shapes."""
    seqs = [list(p) + list(s) for p, s in zip(prompts, served)]
    order = sorted(range(len(seqs)), key=lambda b: len(seqs[b]))
    gap_of = jax.jit(lambda ref, chosen: jnp.max(ref, -1) - jnp.take_along_axis(
        ref, chosen[..., None], axis=-1)[..., 0])
    out = [np.zeros(0, np.float32)] * len(seqs)
    for at in range(0, len(order), rows):
        block = order[at:at + rows]
        T = -(-len(seqs[block[-1]]) // pad) * pad
        toks = np.zeros((rows, T), np.int32)
        for j, b in enumerate(block):
            toks[j, :len(seqs[b])] = seqs[b]
        ref = logits(seed, d, jnp.asarray(toks), weight_dtype)
        if lower is None and act is None:
            # position t answers with token t + 1
            chosen = jnp.asarray(np.roll(toks, -1, axis=1))
        else:
            chosen = jnp.argmax(logits(seed, d, jnp.asarray(toks),
                                       weight_dtype, lower, act), -1)
        gaps = np.asarray(gap_of(ref, chosen))
        for j, b in enumerate(block):
            n, m = len(prompts[b]), len(served[b])
            out[b] = gaps[j, n - 1:n - 1 + m]
    return np.concatenate(out) if out else np.zeros(0, np.float32)


# ---------------------------------------------------------------------------
# training: loss, gradients and AdamW, followed step by step
# ---------------------------------------------------------------------------

def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _sq(tree):
    return jax.tree.map(lambda a: jnp.sum(jnp.square(a.astype(F32))), tree)


def adam_replay(p0, grads, scales, hp):
    """AdamW (decoupled weight decay, bias-corrected, the gradient first
    scaled by its step's clip factor) applied step by step from p0 over
    the listed gradients. Returns the parameters after the last."""
    lr, wd = hp["learning_rate"], hp["weight_decay"]
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
    p = p0
    m = jax.tree.map(jnp.zeros_like, p0)
    v = jax.tree.map(jnp.zeros_like, p0)
    for j, (g, c) in enumerate(zip(grads, scales), start=1):
        g = jax.tree.map(lambda a: a * c, g)
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        p = jax.tree.map(
            lambda pp, mm, vv: pp * (1 - lr * wd) - lr * (mm / (1 - b1 ** j))
            / (jnp.sqrt(vv / (1 - b2 ** j)) + eps), p, m, v)
    return p


def _head_block(xb, tokb, outer, d):
    """Summed next-token cross entropy of one block of rows (the last
    position of each row has no target)."""
    h = _rms(xb, outer["norm"], d["eps"]) @ outer["lm_head"]
    tgt = jnp.roll(tokb, -1, axis=1)
    logz = jax.scipy.special.logsumexp(h, axis=-1)
    gold = jnp.take_along_axis(h, tgt[..., None], axis=-1)[..., 0]
    valid = (jnp.arange(tokb.shape[1]) < tokb.shape[1] - 1).astype(F32)
    return jnp.sum((logz - gold) * valid[None])


def train_follow(seed: int, d: Dict[str, Any], tokens_of, steps: int,
                 hp: Dict[str, Any], weight_dtype=jnp.bfloat16,
                 rows: int = 1, lower: Optional[Callable] = None):
    """Follow the first `steps` training steps in float32: the loss of
    each, per leaf the norm of the first gradient as the optimizer applies
    it (after the global-norm clip), and per leaf the norm of the
    parameters' change after the last step. One layer's weights and one
    block of `rows` sequences are live at a time; the gradients of earlier
    steps wait on the host. `lower`, used only by the control, rounds the
    operands of every matmul."""
    import numpy as onp
    key = family.seed_key(seed)
    L = d["L"]
    lay = functools.partial(layer, d=d, act=lower)

    with jax.default_matmul_precision("highest"):
        make = jax.jit(
            lambda k: _f32(family.layer_weights(k, d, weight_dtype)))
        outer0 = jax.jit(
            lambda k: _f32(family.outer_weights(k, d, weight_dtype)))(key)
        replay = jax.jit(lambda p0, gs, cs: adam_replay(p0, gs, cs, hp))

        def fwd_layer(x, w):
            B, S, D = x.shape
            out = jax.lax.map(lambda xb: lay(xb, w),
                              x.reshape(B // rows, rows, S, D))
            return out.reshape(B, S, D)

        def bwd_layer(x, w, dy):
            B, S, D = x.shape
            shp = (B // rows, rows, S, D)

            def body(acc, xs):
                xb, dyb = xs
                _, vjp = jax.vjp(lay, xb, w)
                dxb, dwb = vjp(dyb)
                return jax.tree.map(jnp.add, acc, dwb), dxb

            dw, dx = jax.lax.scan(body, jax.tree.map(jnp.zeros_like, w),
                                  (x.reshape(shp), dy.reshape(shp)))
            return dx.reshape(B, S, D), dw

        def head(x, toks, outer):
            B, S, D = x.shape
            shp = (B // rows, rows, S, D)
            n = B * (S - 1)

            def body(acc, xs):
                xb, tb = xs
                loss, (dxb, do) = jax.value_and_grad(
                    lambda a, o: _head_block(a, tb, o, d) / n, (0, 1))(
                        xb, {"norm": outer["norm"],
                             "lm_head": outer["lm_head"]})
                return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], do)), dxb

            zero = {"norm": jnp.zeros_like(outer["norm"]),
                    "lm_head": jnp.zeros_like(outer["lm_head"])}
            (loss, do), dx = jax.lax.scan(
                body, (jnp.zeros((), F32), zero),
                (x.reshape(shp), toks.reshape(B // rows, rows, S)))
            return loss, dx.reshape(B, S, D), do

        fwd_layer, bwd_layer, head = map(jax.jit, (fwd_layer, bwd_layer, head))
        embed_grad = jax.jit(lambda toks, dx, V: jnp.zeros(
            (V, dx.shape[-1]), F32).at[toks.reshape(-1)].add(
                dx.reshape(-1, dx.shape[-1])), static_argnums=2)

        host_g = []     # per step: {"layers": [numpy trees], "outer": tree}
        scales = []     # per step: the clip factor
        losses, first_norm, leaf_sq = [], None, None

        def grads_of(part, upto):
            """The first `upto` steps' gradients of one layer (its index)
            or of the outer leaves ("outer"), back on the device."""
            pick = (lambda g: g["outer"]) if part == "outer" else \
                (lambda g: g["layers"][part])
            return [jax.tree.map(jnp.asarray, pick(g)) for g in host_g[:upto]]

        def layer_params(i, upto):
            p0 = make(family.layer_key(key, jnp.int32(i)))
            return replay(p0, grads_of(i, upto), scales[:upto]) if upto \
                else p0

        def outer_params(upto):
            return replay(outer0, grads_of("outer", upto), scales[:upto]) \
                if upto else outer0

        for k in range(steps):
            toks = jnp.asarray(tokens_of(k), jnp.int32)
            outer = outer_params(k)
            x = jnp.take(outer["embed_tokens"], toks, axis=0)
            acts = []
            for i in range(L):
                acts.append(x)
                x = fwd_layer(x, layer_params(i, k))
            loss, dx, do = head(x, toks, outer)
            losses.append(float(loss))
            sq = None
            g_layers = [None] * L
            for i in reversed(range(L)):
                dx, dw = bwd_layer(acts.pop(), layer_params(i, k), dx)
                s = _sq(dw)
                sq = s if sq is None else jax.tree.map(jnp.add, sq, s)
                g_layers[i] = jax.tree.map(onp.asarray, dw)
                del dw
            g_outer = {"embed_tokens": embed_grad(toks, dx, d["V"]),
                       "norm": do["norm"], "lm_head": do["lm_head"]}
            leaf_sq = {**{n: float(v) for n, v in sq.items()},
                       **{n: float(v) for n, v in _sq(g_outer).items()}}
            total = float(onp.sqrt(sum(leaf_sq.values())))
            clip = hp.get("grad_clip")
            c = 1.0 if not clip else min(1.0, clip / (total + 1e-6))
            scales.append(c)
            host_g.append({"layers": g_layers,
                           "outer": jax.tree.map(onp.asarray, g_outer)})
            if k == 0:
                first_norm = {n: c * float(onp.sqrt(v))
                              for n, v in leaf_sq.items()}
                first_total = total

        # the parameters' change after the last step, per leaf
        delta_sq: Dict[str, float] = {}
        change = jax.jit(lambda p0, gs, cs: _sq(jax.tree.map(
            jnp.subtract, adam_replay(p0, gs, cs, hp), p0)))
        for i in range(L):
            p0 = make(family.layer_key(key, jnp.int32(i)))
            s = change(p0, grads_of(i, steps), scales)
            for n, v in s.items():
                delta_sq[n] = delta_sq.get(n, 0.0) + float(v)
        s = change(outer0, grads_of("outer", steps), scales)
        delta_sq.update({n: float(v) for n, v in s.items()})
    return {"loss": losses, "grad_norm": first_norm,
            "grad_norm_total": first_total, "clip": scales,
            "delta_norm": {n: float(onp.sqrt(v))
                           for n, v in delta_sq.items()}}


def _round_to(x, dtype, top):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * (top / amax)).astype(dtype).astype(F32) * (amax / top)


@jax.custom_vjp
def fp8(x):
    """Round to float8 as a scaled fp8 matmul recipe does, the nearest
    precision below bfloat16 that the chip's matrix unit takes: the operand
    to e4m3 with one scale per tensor, its cotangent to e5m2 likewise."""
    return _round_to(x, jnp.float8_e4m3fn, 448.0)


fp8.defvjp(lambda x: (_round_to(x, jnp.float8_e4m3fn, 448.0), None),
           lambda _, g: (_round_to(g, jnp.float8_e5m2, 57344.0),))
