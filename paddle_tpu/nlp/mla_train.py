"""The MLA + shared/routed-expert decoder under `jax.grad`: the layer of
nlp/mla.py trained, with the three things a published model of this
family adds on the training side.

  * The residual path may run `hc_mult` streams (nlp/hyper.py: mHC): every
    sublayer reads one mix of the streams and writes back through another.
    With `hc_mult` 1 it is the plain pre-norm residual.
  * `topk_method: "noaux_tc"`: the router selects by `sigmoid score +
    e_bias` and gates by the score alone (`moe.sigmoid_bias_top_k`); the
    bias is a leaf no gradient reaches.
  * `num_nextn_predict_layers` 1: a multi-token-prediction module
    (DeepSeek-V3, arXiv:2412.19437, section 2.2). With `h_i` the main
    model's summed streams before its final norm,
    `h'_i = [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))] W_m`, one expert layer
    with its own weights, a final norm of its own and the SHARED head,
    scored at i against `t_{i+2}`. Loss = CE_main + `mtp_loss_weight`
    CE_mtp, each a mean over its own valid positions.

Attention is `mla.attention` (the projections, rotation, YaRN tables and
flash call the server's cold prefill makes), the expert layer
`moe.expert_share_train`, the differentiable form of the served share
(this chip's: experts `experts_first` .. of the router's
`n_routed_experts`), the head
`llama.fused_head_ce`, called once for each loss over the one head.

The tree (`init_params`): `embed_tokens`, `norm`, `lm_head`; the expert
layers stacked under `layers`; the leading dense layers' leaves stacked
under their own names with the prefix `dense_`, the module's under
`mtp_`: flat beside the outer leaves, so that a per-leaf walk of the tree
meets arrays only there and one stacked group. Every 1-D leaf (norm
scales, the mHC gains, biases and norm scale, the selection bias) is
float32 whatever `param_dtype`: a bfloat16 scale near 1 has its
neighbours 0.0078 away and would never move by a 1e-4 step, and together
they are a few hundred KiB. Each layer runs under `jax.checkpoint`
(`remat`): a layer's input, [T, hc_mult D], is all that is saved of it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..kernels.rms_norm import rms_norm_train
from . import hyper, llama, mla, moe

ATTN_LEAVES = ("input_layernorm", "q_a_proj", "q_a_layernorm", "q_b_proj",
               "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj", "o_proj",
               "post_attention_layernorm")
_STATS = ("moe_pairs", "moe_experts_hit", "moe_load_max", "moe_full_passes")


@dataclasses.dataclass
class MlaTrainConfig(mla.MlaMoeConfig):
    """`mla.MlaMoeConfig` with what only the training step reads."""
    topk_method: str = "none"           # "noaux_tc": a selection bias
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    remat: bool = True                  # jax.checkpoint each layer

    def __post_init__(self):
        super().__post_init__()
        if self.topk_method not in ("none", "noaux_tc"):
            raise ValueError(
                f"topk_method {self.topk_method!r}: plain top-k (\"none\") "
                f"or the bias-corrected one (\"noaux_tc\"); group-limited "
                f"routing is not built")
        if self.topk_method == "noaux_tc" and self.scoring_func != "sigmoid":
            raise ValueError("noaux_tc corrects sigmoid scores")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one multi-token-prediction module at most")

    @property
    def router(self) -> str:
        """The expert layer's router, by its name in `moe.ROUTERS`."""
        return self.scoring_func + ("_bias" if self.topk_method == "noaux_tc"
                                    else "")

    @staticmethod
    def tiny(**over) -> "MlaTrainConfig":
        base = dict(dataclasses.asdict(mla.MlaMoeConfig.tiny()),
                    hc_mult=4, topk_method="noaux_tc", experts_first=4,
                    experts_count=8, num_nextn_predict_layers=1)
        base.update(over)
        return MlaTrainConfig(**base)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _hc_leaves(key, cfg, dt):
    if cfg.hc_mult == 1:
        return {}
    out = {}
    for k, name in zip(jax.random.split(key, 2), ("hc_attn_", "hc_ffn_")):
        sub = hyper.init_sublayer(k, cfg.hc_mult, cfg.hidden_size, dt)
        out.update({name + leaf: v for leaf, v in sub.items()})
    return out


def _gains_f32(tree, ndim: int):
    """The 1-D leaves of a group (`ndim` 1, or 2 where the group is
    stacked over layers) in float32."""
    return {k: v.astype(jnp.float32) if v.ndim == ndim else v
            for k, v in tree.items()}


def init_params(key: jax.Array, cfg: MlaTrainConfig) -> Dict[str, Any]:
    """Random parameters in the trained layout (module docstring). The
    attention, MLP and expert leaves are `mla.init_params`'s, layer for
    layer; the selection bias starts at zero."""
    served = mla.init_params(key, cfg)
    dt = cfg.param_dtype
    D = cfg.hidden_size
    kh, km = jax.random.split(jax.random.fold_in(key, 1))

    def with_hc(stack, key, moe_layer):
        n = jax.tree.leaves(stack)[0].shape[0]
        extra = jax.vmap(lambda k: _hc_leaves(k, cfg, dt))(
            jax.random.split(key, n))
        if moe_layer and cfg.topk_method == "noaux_tc":
            extra["e_bias"] = jnp.zeros((n, cfg.n_routed_experts), dt)
        return _gains_f32({**stack, **extra}, 2)

    kd, kl, kt = jax.random.split(kh, 3)
    params = _gains_f32(
        {k: served[k] for k in ("embed_tokens", "norm", "lm_head")}, 1)
    params["layers"] = with_hc(served["moe_layers"], kl, True)
    if cfg.first_k_dense_replace:
        params.update({"dense_" + k: v for k, v in with_hc(
            served["dense_layers"], kd, False).items()})
    if cfg.num_nextn_predict_layers:
        one = dataclasses.replace(cfg, num_hidden_layers=1,
                                  first_k_dense_replace=0)
        layer = with_hc(mla.init_params(km, one)["moe_layers"], kt, True)
        params.update({"mtp_" + k: v[0] for k, v in layer.items()})
        params.update({
            "mtp_enorm": jnp.ones((D,), jnp.float32),
            "mtp_hnorm": jnp.ones((D,), jnp.float32),
            "mtp_norm": jnp.ones((D,), jnp.float32),
            "mtp_eh_proj": (jax.random.normal(
                jax.random.fold_in(km, 1), (2 * D, D), jnp.float32)
                * 0.02).astype(dt)})
    return params


def param_specs(cfg: MlaTrainConfig, pp: bool = False):
    raise NotImplementedError(
        "a mesh: latent attention under TP and experts over a mesh (with "
        "their exchange) are not built; this step trains one chip's share")


def _sub(params, prefix):
    """The leaves under a flat prefix, without it."""
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def _mlp(h, lp, cd):
    g = h @ lp["gate_proj"].astype(cd)
    u = h @ lp["up_proj"].astype(cd)
    return (jax.nn.silu(g) * u) @ lp["down_proj"].astype(cd)


def _residual(X, lp, prefix, fn, cfg):
    """One sublayer on the residual path: `hc_mult` streams [T, n D]
    through `hyper.sublayer`, or the plain `X + F(X)`."""
    if cfg.hc_mult == 1:
        y = fn(X)
        return (X + y[0], y[1]) if isinstance(y, tuple) else X + y
    return hyper.sublayer(
        X, {k: lp[prefix + k] for k in hyper.LEAVES}, fn, n=cfg.hc_mult,
        iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
        clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max),
        norm_eps=cfg.rms_norm_eps)


@jax.custom_vjp
def _together(h, lp):
    """The identity, whose transpose hands back the cotangents of a
    sublayer's input and of its parameters TOGETHER: the sublayer's whole
    backward pass then runs before anything upstream of it. Without it
    the chip's scheduler leaves the expert layer's weight gradients (and
    with them the sorted rows, their cotangents and the GEMMs' outputs,
    1.5 GiB at 16k tokens) waiting while the attention sublayer's
    backward pass runs with its own buffers."""
    return h, lp


_together.defvjp(lambda h, lp: ((h, lp), None),
                 lambda _, ct: jax.lax.optimization_barrier(ct))


def _zero_stats():
    return {k: jnp.zeros((), jnp.int32) for k in _STATS}


def _merge(a, b):
    return {k: (jnp.maximum(v, b[k]) if k == "moe_load_max" else v + b[k])
            for k, v in a.items()}


def layer(X, lp, cfg: MlaTrainConfig, cos, sin, shape, expert: bool):
    """One decoder layer on X [B S, hc_mult D]: attention, then the dense
    MLP or this chip's share of the expert layer plus the shared expert.
    Returns (X', the expert layer's counters)."""
    B, S = shape
    cd, D = cfg.dtype, cfg.hidden_size
    norm = lambda h, w: rms_norm_train(h, w, cfg.rms_norm_eps, True)  # noqa: E731

    def attn(h):
        h = norm(h, lp["input_layernorm"]).reshape(B, S, D)
        return mla.attention(h, lp, cfg, cos, sin).reshape(B * S, D)

    def ffn(h):
        h, w = _together(h, {k: v for k, v in lp.items()
                             if k not in ATTN_LEAVES[:-1]
                             and not k.startswith("hc_")})
        h = norm(h, w["post_attention_layernorm"])
        if not expert:
            with jax.named_scope("mlp"):
                return _mlp(h, w, cd), _zero_stats()
        y, st = moe.expert_share_train(
            h, {k: w[k] for k in ("router", "e_bias", "experts_gate",
                                  "experts_up", "experts_down") if k in w},
            k=cfg.num_experts_per_tok, first=cfg.experts_first,
            scale=cfg.routed_scaling_factor, normalize=cfg.norm_topk_prob,
            score=cfg.router)
        with jax.named_scope("moe_shared"):
            if cfg.n_shared_experts:
                y = y + _mlp(h, w, cd)
        return y, st

    X = _residual(X, lp, "hc_attn_", attn, cfg)
    return _residual(X, lp, "hc_ffn_", ffn, cfg)


def _scan(X, stack, cfg, cos, sin, shape, expert):
    def body(carry, lp):
        X, st = carry
        X, s = layer(X, lp, cfg, cos, sin, shape, expert)
        return (X, _merge(st, s)), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    (X, st), _ = jax.lax.scan(body, (X, _zero_stats()), stack)
    return X, st


def _leave(X, cfg):
    return X if cfg.hc_mult == 1 else hyper.leave(X, cfg.hc_mult)


def _enter(x, cfg):
    return x if cfg.hc_mult == 1 else hyper.enter(x, cfg.hc_mult)


def _head_ce(h, norm_w, params, tokens, cfg, shift):
    """Final norm and the shared head's cross entropy of h [B S, D],
    position i against token i + shift."""
    B, S = tokens.shape
    with jax.named_scope("lm_head"):
        x = rms_norm_train(h, norm_w, cfg.rms_norm_eps, True)
        return llama.fused_head_ce(
            x.reshape(B, S, -1).astype(cfg.dtype),
            params["lm_head"].astype(cfg.dtype), tokens, shift)


def _mtp(params, h, tokens, cfg, cos, sin):
    """The multi-token-prediction module's loss: h [B S, D] the main
    model's summed streams before its final norm."""
    B, S = tokens.shape
    cd = cfg.dtype
    lp = _sub(params, "mtp_")
    with jax.named_scope("mtp"):
        # Emb(t_{i+1}); the last position wraps, is scored nowhere, and
        # no earlier position sees it
        nxt = jnp.take(params["embed_tokens"], jnp.roll(tokens, -1, axis=1),
                       axis=0).astype(cd).reshape(B * S, -1)
        x = jnp.concatenate(
            [rms_norm_train(h, lp["hnorm"], cfg.rms_norm_eps, True),
             rms_norm_train(nxt, lp["enorm"], cfg.rms_norm_eps, True)], -1
        ) @ lp["eh_proj"].astype(cd)
        # a stack of one layer: the same remat and counters, and the loop
        # keeps the chip's scheduler from spreading the layer's recomputed
        # forward over the rest of the step (2 GiB more at 16k tokens,
        # compiled for a described v5e, PR 36)
        X, st = _scan(_enter(x, cfg),
                      jax.tree.map(lambda a: a[None], {
                          k: v for k, v in lp.items()
                          if k not in ("hnorm", "enorm", "eh_proj", "norm")}),
                      cfg, cos, sin, (B, S), True)
        # position i holds t_{i+1} too: its logits answer with t_{i+2}
        loss = _head_ce(_leave(X, cfg), lp["norm"], params, tokens, cfg, 2)
    return loss, st


def loss_and_metrics(params, tokens, cfg: MlaTrainConfig, mesh=None):
    """(loss, metrics): next-token cross entropy of tokens [B, S], plus
    `mtp_loss_weight` times the module's where there is one. `metrics`:
    `loss_main`, `loss_mtp`, and the expert layers' counters added up
    (`moe_pairs`, `moe_experts_hit`, `moe_full_passes`; `moe_load_max` a
    maximum)."""
    if mesh is not None:
        param_specs(cfg)
    B, S = tokens.shape
    cd = cfg.dtype
    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(cd)
    X = _enter(x.reshape(B * S, -1), cfg)
    cos, sin = cfg.rope_tables(S)
    st = _zero_stats()
    if cfg.first_k_dense_replace:
        X, _ = _scan(X, _sub(params, "dense_"), cfg, cos, sin, (B, S), False)
    if cfg.num_moe_layers:
        X, st = _scan(X, params["layers"], cfg, cos, sin, (B, S), True)
    h = _leave(X, cfg)
    loss_main = _head_ce(h, params["norm"], params, tokens, cfg, 1)
    loss_mtp = jnp.zeros((), jnp.float32)
    if cfg.num_nextn_predict_layers:
        loss_mtp, s = _mtp(params, h, tokens, cfg, cos, sin)
        st = _merge(st, s)
    loss = loss_main + cfg.mtp_loss_weight * loss_mtp
    return loss, {"loss_main": loss_main, "loss_mtp": loss_mtp, **st}


def loss_fn(params, tokens, cfg: MlaTrainConfig, mesh=None):
    return loss_and_metrics(params, tokens, cfg, mesh)[0]
