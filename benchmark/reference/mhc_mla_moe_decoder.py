"""Plain reference of the mhc_mla_moe_decoder family: float32 jax.numpy,
matmul precision "highest", no kernels, a Python loop over the held
experts, one layer and one block of sequences at a time so that it fits
beside nothing. It imports nothing of paddle_tpu and takes nothing the
program made: every weight is drawn again from the seed
(benchmark/models/mhc_mla_moe_decoder.py), in the type the configuration
trains it in, and upcast.

Sizes: D hidden, n = hc_mult streams, H heads, the MLA widths of
reference/mla_moe_decoder.py (whose attention, YaRN tables and softmax
scale this file imports: the block is DeepSeek-V2's, section 2.1).

1. Residual path (mHC, arXiv:2512.24880, over Hyper-Connections,
   arXiv:2409.19606). The state after a layer is X in R^{n x D} a token.
   Each SUBLAYER F (attention, then FFN) has phi in R^{nD x (n^2 + 2n)},
   a bias b, three scalars a_pre, a_post, a_res and a norm scale over nD:
     x~ = RMSNorm(vec(X));  [u_pre | u_post | u_res] = x~ phi  (n, n, n^2)
     H_pre = sigmoid(a_pre u_pre + b_pre)                      (1 x n)
     H_post = 2 sigmoid(a_post u_post + b_post)                (1 x n)
     M0 = exp(clip(a_res mat(u_res) + b_res, clamp_min, clamp_max)), then
       hc_sinkhorn_iters rounds of (each column / (its sum + hc_eps), then
       each row / (its sum + hc_eps)): H_res                   (n x n)
     X' = H_res X + H_post^T F(RMSNorm_F(H_pre X))
   with RMSNorm_F the sublayer's own input_layernorm /
   post_attention_layernorm. Entry: the embedding copied into the n
   streams. Exit: the streams summed, then the final norm.
2. Attention: MLA, expanded form (no cache in training).
3. FFN. Dense layers: gated SiLU. Expert layers: s = sigmoid(h W_r) over
   all E routed experts; the top k of s + e_bias are selected; gates are s
   (without the bias) at the selected, divided by their sum, times
   routed_scaling_factor; y = shared(h) + sum_{i selected, i held here}
   g_i E_i(h). Gradient flows through g and the experts, not through the
   selection or the bias.
4. MTP (DeepSeek-V3, arXiv:2412.19437 section 2.2). With h_i the main
   model's summed streams before its final norm:
     h'_i = [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))] W_m
   entered into n streams like an embedding, one expert layer with its own
   weights, summed, a final norm of its own, the SHARED head; its logits
   at i are scored against t_{i+2}. Loss = CE_main + mtp_weight CE_mtp,
   both means over their valid positions.

Departures from the published model, all noted in the configuration file
(`assumed`): the four mHC placements (columns before rows; hc_eps in both
denominators; a learnable scale on the nD norm; entry by copy, exit by
sum), the loss weight (0.3), RoPE in the rotate-half layout, the experts
held here only and the vocabulary's slice (what absent experts would add
is left out, as in the program), random weights.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..models import mhc_mla_moe_decoder as family
# `fp8` is the TRAINING control (operands to e4m3, cotangents to a scaled
# e5m2): the served family's is a plain cast, whose transpose would round
# every cotangent to e4m3 unscaled, which is to nought
from .dense_decoder import adam_replay, fp8, _f32, _sq      # noqa: F401
from .mla_moe_decoder import _mlp, _rms, _rope, softmax_scale

F32 = jnp.float32

# the broken programs the cell's limits have to refuse: a name and the
# keywords that break this file's forward (`train_follow(lower=...)` takes
# one of them, or a function that rounds every matmul's operands)
CONTROLS = {
    "hres_identity": {"hres_identity": True},       # the streams never mix
    "sinkhorn_one_round": {"sinkhorn_iters": 1},
    "no_mtp_loss": {"mtp_weight": 0.0},
    "no_selection_bias": {"no_bias": True},
    "gates_unscaled": {"route_scale": 1.0},
}


class Broken(dict):
    """One of CONTROLS' keyword sets, told apart from a rounding function
    where `tools/control_train.py` hands either over as `lower`."""


globals().update({name: Broken(kw) for name, kw in CONTROLS.items()})


def coefficients(X, w, p: str, d, mm, hres_identity=False,
                 sinkhorn_iters: Optional[int] = None):
    """X [B, T, n, D] -> H_pre [B, T, n], H_post [B, T, n], H_res
    [B, T, n, n] of the sublayer whose leaves carry the prefix `p`."""
    B, T, n, D = X.shape
    u = mm(_rms(X.reshape(B, T, n * D), w[p + "norm"], d["eps"]),
           w[p + "phi"])
    a, b = w[p + "a"], w[p + "b"]
    h_pre = jax.nn.sigmoid(a[0] * u[..., :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * u[..., n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * u[..., 2 * n:] + b[2 * n:], *d["hc_clamp"])
                ).reshape(B, T, n, n)
    if hres_identity:
        return h_pre, h_post, jnp.broadcast_to(jnp.eye(n, dtype=F32),
                                               m.shape)
    iters = d["hc_iters"] if sinkhorn_iters is None else sinkhorn_iters
    for _ in range(iters):
        m = m / (jnp.sum(m, -2, keepdims=True) + d["hc_eps"])   # columns
        m = m / (jnp.sum(m, -1, keepdims=True) + d["hc_eps"])   # rows
    return h_pre, h_post, m


def sublayer(X, w, p: str, d, fn, mm, **hc):
    h_pre, h_post, h_res = coefficients(X, w, p, d, mm, **hc)
    y = fn(jnp.einsum("btn,btnd->btd", h_pre, X))
    return jnp.einsum("btij,btjd->btid", h_res, X) \
        + h_post[..., None] * y[:, :, None, :]


def attention(h, w, d, r):
    """MLA, expanded form, h [B, T, D] (normalised) -> [B, T, D]; one head
    at a time under jax.checkpoint, so that a backward pass holds one
    head's [T, T] scores."""
    B, T, D = h.shape
    H, R, dn, dr, dv = d["H"], d["R"], d["dn"], d["dr"], d["dv"]

    def mm(a, b):
        return r(a) @ r(b)

    cq = _rms(mm(h, w["q_a_proj"]), w["q_a_layernorm"], d["eps"])
    q = mm(cq, w["q_b_proj"]).reshape(B, T, H, dn + dr)
    ckv = mm(h, w["kv_a_proj_with_mqa"])
    c = _rms(ckv[..., :R], w["kv_a_layernorm"], d["eps"])
    k_r = _rope(ckv[..., None, R:], d)[:, :, 0]                 # [B, T, dr]
    q_r = _rope(q[..., dn:], d)
    kv = mm(c, w["kv_b_proj"]).reshape(B, T, H, dn + dv)
    mask = jnp.tril(jnp.ones((T, T), bool))
    scale = softmax_scale(d)

    @jax.checkpoint
    def head(qn, qr, kn, v):                        # [B, T, .] of one head
        s = (jnp.einsum("btd,bsd->bts", r(qn), r(kn))
             + jnp.einsum("btd,bsd->bts", r(qr), r(k_r))) * scale
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", r(p), r(v))

    by_head = lambda x: jnp.moveaxis(x, 2, 0)               # noqa: E731
    o = jax.lax.map(lambda a: head(*a), (
        by_head(q[..., :dn]), by_head(q_r), by_head(kv[..., :dn]),
        by_head(kv[..., dn:])))
    return mm(jnp.moveaxis(o, 0, 2).reshape(B, T, H * dv), w["o_proj"])


def route(h, w, d, no_bias=False, route_scale: Optional[float] = None):
    """h [..., D] -> (idx [..., k], gates [..., k]): sigmoid scores over
    all routed experts, the top k of score + bias, gates the scores alone,
    normalised over the k chosen, scaled."""
    s = jax.nn.sigmoid(jnp.matmul(h, w["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    pick = s if no_bias else s + w["e_bias"]
    _, idx = jax.lax.top_k(pick, d["k"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if d["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return idx, top * (d["route_scale"] if route_scale is None
                       else route_scale)


def layer(X, w, d, moe: bool, act: Optional[Callable] = None,
          hres_identity=False, sinkhorn_iters=None, no_bias=False,
          route_scale=None):
    """One decoder layer, X [B, T, n, D] float32. `act` rounds both
    operands of every matmul (the precision control); the other keywords
    are the broken programs of CONTROLS."""
    w = jax.tree.map(lambda a: a.astype(F32), w)
    r = (lambda a: a) if act is None else act
    hc = {"hres_identity": hres_identity, "sinkhorn_iters": sinkhorn_iters}

    def mm(a, b):
        return r(a) @ r(b)

    def attn(h):
        return attention(_rms(h, w["input_layernorm"], d["eps"]), w, d, r)

    def ffn(h):
        h = _rms(h, w["post_attention_layernorm"], d["eps"])
        y = _mlp(h, w["gate_proj"], w["up_proj"], w["down_proj"], mm)
        if not moe:
            return y
        idx, gates = route(h, w, d, no_bias, route_scale)
        for j in range(d["n"]):                # the experts held here
            g = jnp.sum(jnp.where(idx == d["first"] + j, gates, 0.0), -1)
            y = y + g[..., None] * _mlp(h, w["experts_gate"][j],
                                        w["experts_up"][j],
                                        w["experts_down"][j], mm)
        return y

    X = sublayer(X, w, "hc_attn_", d, attn, mm, **hc)
    X = sublayer(X, w, "hc_ffn_", d, ffn, mm, **hc)
    if X.dtype != F32:                        # the program turns x64 on
        raise TypeError(f"reference left float32: {X.dtype}")
    return X


def enter(x, d):
    """[B, T, D] -> [B, T, n, D]: the embedding copied into the streams."""
    return jnp.broadcast_to(x[:, :, None, :],
                            x.shape[:2] + (d["hc"], x.shape[-1]))


def ce_sum(h, norm_w, head, tok, shift: int, d, mm):
    """Summed cross entropy of h [B, T, D]: position i against token
    i + shift, over the T - shift positions that have one."""
    logits = mm(_rms(h, norm_w, d["eps"]), head)
    tgt = jnp.roll(tok, -shift, axis=1)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    T = tok.shape[1]
    return jnp.sum((logz - gold) * (jnp.arange(T) < T - shift)[None])


def mtp_ce_sum(h, e_next, wm, head, tok, d, act=None, **broken):
    """The module's summed cross entropy: h [B, T, D] the main model's
    summed streams before its final norm, e_next [B, T, D] the embedding
    of token i + 1 at i (the last position's is scored nowhere)."""
    wm = jax.tree.map(lambda a: a.astype(F32), wm)
    r = (lambda a: a) if act is None else act
    mm = lambda a, b: r(a) @ r(b)                           # noqa: E731
    x = mm(jnp.concatenate([_rms(h, wm["hnorm"], d["eps"]),
                            _rms(e_next, wm["enorm"], d["eps"])], -1),
           wm["eh_proj"])
    X = layer(enter(x, d), wm, d, True, act, **broken)
    return ce_sum(jnp.sum(X, 2), wm["norm"], head, tok, 2, d, mm)


def split_outer(outer, strip: bool = True):
    """outer_weights' flat leaves -> (embedding, norm and head; the dense
    layers' stacked leaves; the module's leaves), without the prefixes
    (`strip`) or with them."""
    cut = (lambda k, n: k[n:]) if strip else (lambda k, n: k)
    top = {k: v for k, v in outer.items()
           if not k.startswith(("dense_", "mtp_"))}
    dense = {cut(k, 6): v for k, v in outer.items()
             if k.startswith("dense_")}
    mtp = {cut(k, 4): v for k, v in outer.items() if k.startswith("mtp_")}
    return top, dense, mtp


def losses(w, tokens, d, act=None, mtp_weight=None, **broken):
    """(CE_main, CE_mtp) of the whole tree `w` (the family's layout) on
    tokens [B, T], in one piece: what `train_follow` computes in blocks,
    for small sizes. The total is CE_main + mtp_weight CE_mtp."""
    w = _f32(w)
    top, dense, mtp = split_outer(w)
    B, T = tokens.shape
    r = (lambda a: a) if act is None else act
    mm = lambda a, b: r(a) @ r(b)                           # noqa: E731
    X = enter(jnp.take(top["embed_tokens"], tokens, axis=0), d)
    for i in range(d["Ld"]):
        X = layer(X, jax.tree.map(lambda a: a[i], dense), d, False, act,
                  **broken)
    for i in range(d["L"]):
        X = layer(X, jax.tree.map(lambda a: a[i], w["layers"]), d, True, act,
                  **broken)
    h = jnp.sum(X, 2)
    main = ce_sum(h, top["norm"], top["lm_head"], tokens, 1, d, mm) \
        / (B * (T - 1))
    e_next = jnp.take(top["embed_tokens"], jnp.roll(tokens, -1, 1), axis=0)
    side = mtp_ce_sum(h, e_next, mtp, top["lm_head"], tokens, d, act,
                      **broken) / (B * (T - 2))
    return main, side


# ---------------------------------------------------------------------------
# training: loss, gradients and AdamW, followed step by step
# ---------------------------------------------------------------------------

def train_follow(seed: int, d: Dict[str, Any], tokens_of, steps: int,
                 hp: Dict[str, Any], weight_dtype=jnp.bfloat16,
                 rows: int = 1, lower=None):
    """Follow the first `steps` training steps in float32: the loss of
    each, per leaf the norm of the first gradient as the optimizer applies
    it (after the global-norm clip), and per leaf the norm of the
    parameters' change after the last step (`reference.dense_decoder.
    train_follow`'s contract and names: a stacked expert-layer leaf by its
    name, summed over the layers; every other leaf by its flat name). One
    layer's weights and one block of `rows` sequences are live on the
    device at a time beside the outer leaves; layer inputs and the
    gradients of earlier steps wait on the host. `lower`, used only by the
    controls: a function that rounds the operands of every matmul, or one
    of CONTROLS' broken programs (`Broken`)."""
    import numpy as onp
    broken = dict(lower) if isinstance(lower, Broken) else {}
    act = None if isinstance(lower, Broken) else lower
    lam = broken.pop("mtp_weight", d["mtp_weight"])
    key = family.seed_key(seed)
    L, Ld = d["L"], d["Ld"]
    r = (lambda a: a) if act is None else act
    mm = lambda a, b: r(a) @ r(b)                           # noqa: E731

    with jax.default_matmul_precision("highest"):
        make = jax.jit(
            lambda k: _f32(family.layer_weights(k, d, weight_dtype)))
        outer0 = jax.jit(
            lambda k: _f32(family.outer_weights(k, d, weight_dtype)))(key)
        replay_ = jax.jit(lambda p0, gs, cs: adam_replay(p0, gs, cs, hp))
        change_ = jax.jit(lambda p0, gs, cs: _sq(jax.tree.map(
            jnp.subtract, adam_replay(p0, gs, cs, hp), p0)))

        def replay(p0, grads, cs, fn=replay_):
            """One program a GROUP of leaves (a layer; the embedding, norm
            and head; the dense layers; the module): a whole tree's
            moments would not fit, a program a leaf compiles for minutes."""
            if not grads:
                return p0
            if "embed_tokens" not in p0:
                return fn(p0, grads, cs)
            out = {}
            for part in split_outer(p0, strip=False):
                out.update(fn(part, [{k: g[k] for k in part} for g in grads],
                              cs))
            return out

        def blocks(x):
            return x.reshape((x.shape[0] // rows, rows) + x.shape[1:])

        def fwd_layer(X, w, moe):
            out = jax.lax.map(lambda xb: layer(xb, w, d, moe, act, **broken),
                              blocks(X))
            return out.reshape(X.shape)

        def bwd_layer(X, w, dY, moe):
            def body(acc, xs):
                xb, dyb = xs
                _, vjp = jax.vjp(
                    lambda a, b: layer(a, b, d, moe, act, **broken), xb, w)
                dxb, dwb = vjp(dyb)
                return jax.tree.map(jnp.add, acc, dwb), dxb

            dw, dX = jax.lax.scan(body, jax.tree.map(jnp.zeros_like, w),
                                  (blocks(X), blocks(dY)))
            return dX.reshape(X.shape), dw

        def heads(X, toks, top, wm):
            """Both losses from the last layer's streams, and their
            gradients: with respect to X, the final norm, the head, the
            module's leaves and the embedding rows the module reads."""
            B, S = toks.shape
            n_main, n_mtp = B * (S - 1), B * (S - 2)
            nxt = jnp.roll(toks, -1, axis=1)

            def both(Xb, eb, tb, nw, head, wmod):
                h = jnp.sum(Xb, 2)
                main = ce_sum(h, nw, head, tb, 1, d, mm) / n_main
                side = mtp_ce_sum(h, eb, wmod, head, tb, d, act,
                                  **broken) / n_mtp
                return main + lam * side, (main, side)

            def body(acc, xs):
                Xb, tb, nb = xs
                eb = jnp.take(top["embed_tokens"], nb, axis=0)
                (_, (main, side)), g = jax.value_and_grad(
                    both, (0, 1, 3, 4, 5), has_aux=True)(
                        Xb, eb, tb, top["norm"], top["lm_head"], wm)
                acc = (acc[0] + main, acc[1] + side,
                       jax.tree.map(jnp.add, acc[2], g[2:]))
                return acc, (g[0], g[1])

            zero = jax.tree.map(jnp.zeros_like,
                                (top["norm"], top["lm_head"], wm))
            (main, side, (dn, dh, dwm)), (dX, de) = jax.lax.scan(
                body, (jnp.zeros((), F32), jnp.zeros((), F32), zero),
                (blocks(X), blocks(toks), blocks(nxt)))
            return main, side, dX.reshape(X.shape), \
                de.reshape(B, S, -1), dn, dh, dwm

        fwd_layer = jax.jit(fwd_layer, static_argnums=2)
        bwd_layer = jax.jit(bwd_layer, static_argnums=3)
        heads = jax.jit(heads)
        embed_grad = jax.jit(lambda toks, dx, V: jnp.zeros(
            (V, dx.shape[-1]), F32).at[toks.reshape(-1)].add(
                dx.reshape(-1, dx.shape[-1])), static_argnums=2)
        pick = jax.jit(lambda tree, i: jax.tree.map(lambda a: a[i], tree))
        sq_tree = jax.jit(lambda t: _sq(jax.tree.map(jnp.asarray, t)))

        host_g = []     # per step: {"layers": [numpy trees], "outer": tree}
        scales = []     # per step: the clip factor
        losses_, first_norm, first_total = [], None, None

        def grads_of(part, upto):
            fetch = (lambda g: g["outer"]) if part == "outer" else \
                (lambda g: g["layers"][part])
            return [jax.tree.map(jnp.asarray, fetch(g))
                    for g in host_g[:upto]]

        def layer_params(i, upto):
            p0 = make(family.layer_key(key, jnp.int32(i)))
            return replay(p0, grads_of(i, upto), scales[:upto])

        for k in range(steps):
            toks = jnp.asarray(tokens_of(k), jnp.int32)
            outer = replay(outer0, grads_of("outer", k), scales[:k])
            top, dense, wm = split_outer(outer)
            X = enter(jnp.take(top["embed_tokens"], toks, axis=0), d)
            acts = []                       # layer inputs, on the host
            stack = [(False, i) for i in range(Ld)] \
                + [(True, i) for i in range(L)]
            weights_of = lambda moe, i: (                   # noqa: E731
                layer_params(i, k) if moe else pick(dense, i))
            for moe, i in stack:
                acts.append(onp.asarray(X))
                X = fwd_layer(X, weights_of(moe, i), moe)
            main, side, dX, de, dn, dh, dwm = heads(X, toks, top, wm)
            del X
            losses_.append(float(main) + lam * float(side))
            sq: Dict[str, Any] = {}
            g_layers = [None] * L
            g_dense = [None] * Ld
            for moe, i in reversed(stack):
                dX, dw = bwd_layer(jnp.asarray(acts.pop()),
                                   weights_of(moe, i), dX, moe)
                if moe:
                    for n_, v in _sq(dw).items():
                        sq[n_] = sq.get(n_, 0.0) + float(v)
                    g_layers[i] = jax.tree.map(onp.asarray, dw)
                else:
                    g_dense[i] = jax.tree.map(onp.asarray, dw)
                del dw
            # the embedding is read twice: by the streams' entry (every
            # stream's cotangent) and by the module (the next token's row)
            d_embed = embed_grad(toks, jnp.sum(dX, 2), d["V"]) \
                + embed_grad(jnp.roll(toks, -1, axis=1), de, d["V"])
            del dX, de
            g_outer = {"embed_tokens": d_embed, "norm": dn, "lm_head": dh}
            g_outer.update({"mtp_" + n_: v for n_, v in dwm.items()})
            if Ld:
                g_outer.update({
                    "dense_" + n_: onp.stack([g[n_] for g in g_dense])
                    for n_ in g_dense[0]})
            outer_sq = {n_: float(v) for n_, v in sq_tree(g_outer).items()}
            g_outer = jax.tree.map(onp.asarray, g_outer)
            del d_embed, dn, dh, dwm
            leaf_sq = {**sq, **outer_sq}
            total = float(onp.sqrt(sum(leaf_sq.values())))
            clip = hp.get("grad_clip")
            c = 1.0 if not clip else min(1.0, clip / (total + 1e-6))
            scales.append(c)
            host_g.append({"layers": g_layers, "outer": g_outer})
            if k == 0:
                first_norm = {n_: c * float(onp.sqrt(v))
                              for n_, v in leaf_sq.items()}
                first_total = total

        # the parameters' change after the last step, per leaf
        delta_sq: Dict[str, float] = {}
        for i in range(L):
            p0 = make(family.layer_key(key, jnp.int32(i)))
            s = replay(p0, grads_of(i, steps), scales, change_)
            for n_, v in s.items():
                delta_sq[n_] = delta_sq.get(n_, 0.0) + float(v)
        s = replay(outer0, grads_of("outer", steps), scales, change_)
        delta_sq.update({n_: float(v) for n_, v in s.items()})
    return {"loss": losses_, "grad_norm": first_norm,
            "grad_norm_total": first_total, "clip": scales,
            "delta_norm": {n_: float(onp.sqrt(v))
                           for n_, v in delta_sq.items()}}
