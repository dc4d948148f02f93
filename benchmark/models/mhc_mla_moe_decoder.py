"""Family `mhc_mla_moe_decoder`: the `mla_moe_decoder` layer (multi-head
latent attention with YaRN rotary embedding in the rotate-half layout, a
dense gated-SiLU MLP in the `first_k_dense_replace` leading layers, then a
sparse-expert FFN with a shared expert) TRAINED, with what Xing4.0-29B-A4B
adds to it:

  * a residual path of `hc_mult` streams (mHC: manifold-constrained
    hyper-connections, arXiv:2512.24880, over arXiv:2409.19606): each
    sublayer mixes the streams into its input, and writes back through a
    doubly stochastic stream-to-stream matrix (`hc_sinkhorn_iters` rounds
    of Sinkhorn) and a per-stream output gain;
  * a bias-corrected sigmoid router (`topk_method: "noaux_tc"`): the top
    `num_experts_per_tok` of score + bias are chosen, the gates are the
    scores alone, normalised and scaled (`n_group` 1, `topk_group` 1: no
    group limit);
  * `num_nextn_predict_layers` 1 multi-token-prediction module
    (DeepSeek-V3, arXiv:2412.19437, section 2.2) with its own loss.

The equations are in benchmark/reference/mhc_mla_moe_decoder.py.

A configuration of this family is ONE CHIP'S SHARE of a deployment in
which several chips share each layer (`share` in its file): the chip holds
`n_routed_experts` of the router's `share.router_experts` experts (those
from `share.experts_first` on) and a slice of the vocabulary. The router
keeps its published width and its experts per token; what absent experts
would add is left out, here and in the reference alike.

The tree is the one the train runner walks and the program's
`mla_train.init_params` lays out: the expert layers stacked under `layers`
(`layer_weights`, one a `layer_key`), every other leaf flat beside them
(`outer_weights`): embedding, final norm, head; the leading dense layers'
leaves stacked over those layers under `dense_<leaf>`; the module's under
`mtp_<leaf>`. `d["L"]` is the number of EXPERT layers (the stacked group),
`d["Ld"]` of dense ones.

Weights come from the seed, on the device, in one jitted call, in the type
they are trained in: the matrices in the configuration's `trained_dtype`,
every 1-D leaf (norm scales, the mHC gains, biases and norm scale, the
selection bias) in float32, as the program holds them (a bfloat16 scale
near 1 cannot move by a 1e-4 step: its neighbours are 0.0078 away).
normal(0, 0.02) matrices; norm scales 1 + normal(0, 0.1); the mHC gains
`a` 0.1 + normal(0, 0.02), its biases normal(0, 1):
the streams enter as copies of one embedding and part only through the
output mix, so a narrow draw would leave them all but equal and the
stream-to-stream matrix with nothing to mix (identity in its place then
moves the loss by 2e-6 at a small size), and the matrix starts far from
doubly stochastic, so that every Sinkhorn round shows; the router's
selection bias normal(0, 0.1), the same values on every chip's block of
experts (`_selection_bias`). This file also holds the functions that
count the least operations and bytes.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .dense_decoder import (_normal, layer_key, roofline_seconds, seed_key,
                            train_tokens)
from .mla_moe_decoder import _scale as _scale_as

__all__ = ["dims", "make_params", "params_shape", "program_config",
           "layer_weights", "outer_weights", "layer_key", "seed_key",
           "roofline_seconds", "train_tokens", "train_flops_per_token",
           "causal_attention_cost", "num_params"]

_DENSE_KEY, _MTP_KEY = 1 << 16, 1 << 17
MTP_LOSS_WEIGHT = 0.3
F32 = jnp.float32


def _scale(key, n):
    """A norm scale (1 + normal(0, 0.1)): float32 whatever the matrices'
    type, like every 1-D leaf of this family."""
    return _scale_as(key, n, F32)


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the weights and the reference need, from a configuration
    file's published keys (at its top level, under the catalog row's names)
    and its `share` block. `V` is the slice of the vocabulary held here."""
    m, sh = config, config["share"]
    if m["first_k_dense_replace"] >= m["num_hidden_layers"] \
            or m["moe_layer_freq"] != 1 or m["attention_bias"]:
        raise ValueError("mhc_mla_moe_decoder: expert layers follow the "
                         "leading dense ones one for one, no attention bias")
    if m["scoring_func"] != "sigmoid" or m["topk_method"] != "noaux_tc" \
            or m["n_group"] != 1 or m["topk_group"] != 1:
        raise ValueError("mhc_mla_moe_decoder: sigmoid scores, a selection "
                         "bias (noaux_tc), no group limit")
    if m["num_nextn_predict_layers"] != 1 or m["hc_mult"] < 2:
        raise ValueError("mhc_mla_moe_decoder: one multi-token-prediction "
                         "module, a multi-stream residual path")
    rs = m["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError("mhc_mla_moe_decoder: YaRN rotary scaling")
    n, first = m["n_routed_experts"], sh["experts_first"]
    if first + n > sh["router_experts"] or sh["router_experts"] % n:
        raise ValueError("held experts: one of the equal blocks that the "
                         "router's width divides into")
    Ld = m["first_k_dense_replace"]
    return {
        "V": m["vocab_size"], "D": m["hidden_size"],
        "F": m["intermediate_size"], "Fm": m["moe_intermediate_size"],
        "L": m["num_hidden_layers"] - Ld, "Ld": Ld,
        "H": m["num_attention_heads"], "Q": m["q_lora_rank"],
        "R": m["kv_lora_rank"], "dn": m["qk_nope_head_dim"],
        "dr": m["qk_rope_head_dim"], "dv": m["v_head_dim"],
        "E": sh["router_experts"], "n": n, "first": first,
        "k": m["num_experts_per_tok"], "shared": m["n_shared_experts"],
        "route_scale": float(m["routed_scaling_factor"]),
        "norm_topk": bool(m["norm_topk_prob"]),
        "theta": float(m["rope_theta"]), "eps": float(m["rms_norm_eps"]),
        "yarn": {k: rs[k] for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim")},
        "hc": m["hc_mult"], "hc_iters": m["hc_sinkhorn_iters"],
        "hc_eps": float(m["hc_eps"]),
        "hc_clamp": [float(m["mhc_h_res_clamp_min"]),
                     float(m["mhc_h_res_clamp_max"])],
        "mtp_weight": float(config.get("mtp_loss_weight", MTP_LOSS_WEIGHT)),
    }


def _hc_weights(key: jax.Array, d: Dict[str, Any], dtype) -> Dict[str, Any]:
    """One sublayer's mHC leaves: phi [n D, n^2 + 2 n], b, a (a_pre,
    a_post, a_res), and the scale of the norm over n D."""
    n, D = d["hc"], d["D"]
    k = jax.random.split(key, 5)
    return {"phi": _normal(k[0], (n * D, n * n + 2 * n), dtype),
            "b": _normal(k[1], (n * n + 2 * n,), F32, 1.0),
            "a": 0.1 + _normal(k[3], (3,), F32, 0.02),
            "norm": _scale(k[4], n * D)}


def _selection_bias(key: jax.Array, d: Dict[str, Any]) -> jax.Array:
    """The router's selection bias [E]: normal(0, 0.1), which is half the
    spread of the scores themselves, so that it decides a good part of
    the choices and single experts are loaded several times unevenly; the
    SAME n values on each chip's block of n experts, in an order of the
    block's own. The bias exists to balance the load, and a deployment's
    chips are each routed about a quarter of the pairs; values drawn
    independently over all E give this chip 22 to 29% of them by the seed
    (73k-94k pairs a step over six seeds, PR 36), and the step's time
    follows."""
    n, blocks = d["n"], d["E"] // d["n"]
    base = _normal(key, (n,), F32, 0.1)
    return jnp.concatenate([
        jax.random.permutation(jax.random.fold_in(key, j), base)
        for j in range(blocks)])


def _layer(key: jax.Array, d: Dict[str, Any], dtype,
           moe: bool) -> Dict[str, Any]:
    D, H, Q, R = d["D"], d["H"], d["Q"], d["R"]
    k = jax.random.split(key, 19)
    w = {
        "input_layernorm": _scale(k[0], D),
        "q_a_proj": _normal(k[1], (D, Q), dtype),
        "q_a_layernorm": _scale(k[2], Q),
        "q_b_proj": _normal(k[3], (Q, H * (d["dn"] + d["dr"])), dtype),
        "kv_a_proj_with_mqa": _normal(k[4], (D, R + d["dr"]), dtype),
        "kv_a_layernorm": _scale(k[5], R),
        "kv_b_proj": _normal(k[6], (R, H * (d["dn"] + d["dv"])), dtype),
        "o_proj": _normal(k[7], (H * d["dv"], D), dtype),
        "post_attention_layernorm": _scale(k[8], D),
    }
    F = d["Fm"] * d["shared"] if moe else d["F"]
    w.update({"gate_proj": _normal(k[9], (D, F), dtype),
              "up_proj": _normal(k[10], (D, F), dtype),
              "down_proj": _normal(k[11], (F, D), dtype)})
    if moe:
        n, Fm = d["n"], d["Fm"]
        w.update({"router": _normal(k[12], (D, d["E"]), dtype),
                  "e_bias": _selection_bias(k[13], d),
                  "experts_gate": _normal(k[14], (n, D, Fm), dtype),
                  "experts_up": _normal(k[15], (n, D, Fm), dtype),
                  "experts_down": _normal(k[16], (n, Fm, D), dtype)})
    for kk, name in ((k[17], "hc_attn_"), (k[18], "hc_ffn_")):
        w.update({name + leaf: v
                  for leaf, v in _hc_weights(kk, d, dtype).items()})
    return w


def layer_weights(key: jax.Array, d: Dict[str, Any], dtype) -> Dict[str, Any]:
    """One EXPERT layer's weights from its own key (`layer_key(key, i)`,
    i < d["L"]): the attention sublayer, the router with its selection
    bias, the held experts (stacked), the shared expert under the dense
    MLP's names, and the two sublayers' mHC leaves."""
    return _layer(key, d, dtype, True)


def dense_weights(key: jax.Array, d: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The leading dense layers, stacked over them (keys of their own)."""
    return jax.vmap(lambda i: _layer(layer_key(key, _DENSE_KEY + i), d, dtype,
                                     False))(
        jnp.arange(d["Ld"], dtype=jnp.int32))


def mtp_weights(key: jax.Array, d: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The multi-token-prediction module: the two norms and the projection
    of its input, one expert layer, a final norm of its own."""
    key = layer_key(key, _MTP_KEY)
    k = jax.random.split(jax.random.fold_in(key, 1), 4)
    D = d["D"]
    return {**_layer(key, d, dtype, True),
            "hnorm": _scale(k[0], D), "enorm": _scale(k[1], D),
            "eh_proj": _normal(k[2], (2 * D, D), dtype),
            "norm": _scale(k[3], D)}


def outer_weights(key: jax.Array, d: Dict[str, Any], dtype) -> Dict[str, Any]:
    """Every leaf outside the stacked expert layers, flat: embedding, final
    norm and head over the slice of the vocabulary; `dense_<leaf>`;
    `mtp_<leaf>`."""
    k = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    out = {"embed_tokens": _normal(k[0], (d["V"], d["D"]), dtype),
           "norm": _scale(k[1], d["D"]),
           "lm_head": _normal(k[2], (d["D"], d["V"]), dtype)}
    out.update({"dense_" + n: v
                for n, v in dense_weights(key, d, dtype).items()})
    out.update({"mtp_" + n: v for n, v in mtp_weights(key, d, dtype).items()})
    return out


def _build(key, d: Dict[str, Any], dtype):
    layers = jax.vmap(lambda i: layer_weights(layer_key(key, i), d, dtype))(
        jnp.arange(d["L"], dtype=jnp.int32))
    return {**outer_weights(key, d, dtype), "layers": layers}


def make_params(seed: int, d: Dict[str, Any], dtype=jnp.bfloat16,
                shardings=None):
    """The whole parameter tree in the layout `mla_train` takes, one
    jitted call."""
    return jax.jit(functools.partial(_build, d=d, dtype=dtype),
                   out_shardings=shardings)(seed_key(seed))


def params_shape(d: Dict[str, Any], dtype=jnp.bfloat16):
    return jax.eval_shape(functools.partial(_build, d=d, dtype=dtype),
                          seed_key(0))


def program_config(config: Dict[str, Any]):
    """The program's own configuration object for these sizes."""
    from paddle_tpu.nlp import mla_train
    m, d = config, dims(config)
    if m.get("tie_word_embeddings") or m["hidden_act"] != "silu":
        raise ValueError("mhc_mla_moe_decoder: untied, gated SiLU")
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config.get("trained_dtype", "bfloat16")]
    return mla_train.MlaTrainConfig(
        vocab_size=d["V"], hidden_size=d["D"], intermediate_size=d["F"],
        moe_intermediate_size=d["Fm"],
        num_hidden_layers=d["L"] + d["Ld"], first_k_dense_replace=d["Ld"],
        num_attention_heads=d["H"], q_lora_rank=d["Q"], kv_lora_rank=d["R"],
        qk_nope_head_dim=d["dn"], qk_rope_head_dim=d["dr"],
        v_head_dim=d["dv"], n_routed_experts=d["E"],
        num_experts_per_tok=d["k"], n_shared_experts=d["shared"],
        routed_scaling_factor=d["route_scale"], norm_topk_prob=d["norm_topk"],
        scoring_func=m["scoring_func"], topk_method=m["topk_method"],
        experts_first=d["first"], experts_count=d["n"],
        hc_mult=d["hc"], hc_sinkhorn_iters=d["hc_iters"], hc_eps=d["hc_eps"],
        mhc_h_res_clamp_min=d["hc_clamp"][0],
        mhc_h_res_clamp_max=d["hc_clamp"][1],
        num_nextn_predict_layers=m["num_nextn_predict_layers"],
        mtp_loss_weight=d["mtp_weight"],
        max_position_embeddings=m["max_position_embeddings"],
        rms_norm_eps=d["eps"], rope_theta=d["theta"],
        rope_scaling=dict(d["yarn"]), dtype=dt, param_dtype=dt)


def _layer_params(d: Dict[str, Any], moe: bool) -> Dict[str, float]:
    """One layer's parameters resident here, and the multiply-adds a token
    spends in its matrices (the routed experts: in expectation)."""
    n, D = d["hc"], d["D"]
    attn = (D * d["Q"] + d["Q"] * d["H"] * (d["dn"] + d["dr"])
            + D * (d["R"] + d["dr"]) + d["R"] * d["H"] * (d["dn"] + d["dv"])
            + d["H"] * d["dv"] * D)
    norms = 2 * D + d["Q"] + d["R"]
    hc = 2 * (n * D * (n * n + 2 * n) + (n * n + 2 * n) + 3 + n * D)
    # per sublayer beyond phi: the input mix (n D), the stream mix
    # (n^2 D) and the write-back (n D)
    hc_macs = 2 * (n * D * (n * n + 2 * n) + (n * n + 2 * n) * D)
    if not moe:
        ffn = macs = 3 * D * d["F"]
    else:
        one = 3 * D * d["Fm"]
        ffn = D * d["E"] + d["E"] + one * (d["n"] + d["shared"])
        macs = D * d["E"] + one * (d["shared"]
                                   + d["k"] * d["n"] / d["E"])
    return {"params": attn + norms + hc + ffn,
            "macs": attn + hc_macs + macs}


def num_params(d: Dict[str, Any]) -> int:
    """Parameters resident on this chip."""
    D = d["D"]
    return int(2 * d["V"] * D + D
               + d["Ld"] * _layer_params(d, False)["params"]
               + d["L"] * _layer_params(d, True)["params"]
               + _layer_params(d, True)["params"] + 2 * D * D + 3 * D)


# ---------------------------------------------------------------------------
# operations and bytes: the least the algorithm needs, never what today's
# kernel happens to move
# ---------------------------------------------------------------------------

def train_flops_per_token(d: Dict[str, Any], seq_len: int) -> float:
    """Forward plus backward FLOPs a trained token needs on this chip: 6
    per multiply-add of the forward (2 forward, 4 backward). Every matrix
    a token meets: attention's projections, the mHC projection and its
    stream mixes, the dense MLP, the router, the shared expert and, of the
    routed experts, `k n / E` pairs a token a layer in expectation (n of E
    held here); the module's projection and its one expert layer; the head
    TWICE (main and module). Attention: the causal half (a query meets
    seq/2 keys on average), scores over dn + dr columns and values over dv
    (the useful widths: the kernel's zero padding of values is not
    credited). The embedding gathers are not counted, no recomputation is
    counted."""
    attn = d["H"] * (d["dn"] + d["dr"] + d["dv"]) * seq_len / 2.0
    dense = _layer_params(d, False)["macs"] + attn
    moe = _layer_params(d, True)["macs"] + attn
    mtp = 2 * d["D"] * d["D"] + moe
    return 6.0 * (d["Ld"] * dense + d["L"] * moe + mtp
                  + 2 * d["V"] * d["D"])


def causal_attention_cost(d: Dict[str, Any], batch: int, seq_len: int,
                          itemsize: int = 2, layers: int = 1,
                          backward: bool = False) -> Dict[str, float]:
    """Causal self-attention of one layer over `batch` sequences of
    seq_len in the expanded form: the causal half of QK^T over dn + dr
    columns and of PV over dv (the backward pass: 2.5 times the forward's:
    dq, dk, dv and the recomputed scores); q and k (dn + dr wide), v and
    the output (dv wide) read or written once (backward: those, the
    output's gradient and three gradients: twice the forward's)."""
    qk, v = d["dn"] + d["dr"], d["dv"]
    fwd = 2.0 * batch * d["H"] * (qk + v) * seq_len * seq_len / 2.0
    io = batch * seq_len * d["H"] * (2 * qk + 2 * v) * itemsize
    if backward:
        return {"flops": layers * 2.5 * fwd, "bytes": layers * 2.0 * io}
    return {"flops": layers * fwd, "bytes": layers * io}
