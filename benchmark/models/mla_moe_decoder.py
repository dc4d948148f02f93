"""Family `mla_moe_decoder`: pre-norm decoder blocks of multi-head latent
attention (low-rank query, one latent row `[c | k_r]` a token for keys and
values, YaRN rotary embedding in the rotate-half layout) and, after
`first_k_dense_replace` leading layers with a dense gated-SiLU MLP, a
sparse-expert FFN (sigmoid router over ALL routed experts, top-k,
normalised and scaled gates, a shared expert), RMSNorm, untied embedding
and head: the DeepSeek-V2/V3 layer. A.X-K1 is one.

A configuration of this family is ONE CHIP'S SHARE of a deployment in
which several chips share each layer (`share` in its file): the chip holds
`n_routed_experts` of the router's `share.router_experts` experts
(those from `share.experts_first` on) and a slice of the vocabulary. The
router keeps its published width and its experts per token; what absent
experts would add is left out, here and in the reference alike.

The benchmark, not the program, makes the weights (as for `dense_decoder`):
from the seed, on the device, in one jitted call, in the served type. The
plain reference (benchmark/reference/mla_moe_decoder.py) draws the same
layer from the same key, one layer at a time. This file also holds the
functions that count the new kernels' least operations and bytes.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .dense_decoder import (_normal, layer_key, roofline_seconds,
                            seed_key)

__all__ = ["dims", "make_params", "params_shape", "program_config",
           "layer_weights", "outer_weights", "layer_key", "seed_key",
           "roofline_seconds", "latent_attention_cost", "expert_ffn_cost"]


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the weights and the reference need, from a configuration
    file's published keys (at its top level, under the catalog row's names)
    and its `share` block. `V` is the slice of the vocabulary held here:
    traffic, logits and sampling are over it."""
    m, sh = config, config["share"]
    if m["first_k_dense_replace"] > m["num_hidden_layers"] \
            or m["moe_layer_freq"] != 1 or m["attention_bias"]:
        raise ValueError("mla_moe_decoder: expert layers follow the leading "
                         "dense ones one for one, no attention bias")
    if m["scoring_func"] != "sigmoid" or m["topk_method"] != "none":
        raise ValueError("mla_moe_decoder: sigmoid scores, plain top-k")
    rs = m["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError("mla_moe_decoder: YaRN rotary scaling")
    n, first = m["n_routed_experts"], sh["experts_first"]
    if first + n > sh["router_experts"]:
        raise ValueError("held experts lie outside the router's width")
    return {
        "V": m["vocab_size"], "D": m["hidden_size"],
        "F": m["intermediate_size"], "Fm": m["moe_intermediate_size"],
        "L": m["num_hidden_layers"], "Ld": m["first_k_dense_replace"],
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
    }


def _scale(key, n, dtype):
    """A norm scale that is not all ones, so that a dropped one shows."""
    return (1.0 + _normal(key, (n,), jnp.float32, 0.1)).astype(dtype)


def layer_weights(key: jax.Array, d: Dict[str, Any], dtype,
                  moe: bool) -> Dict[str, Any]:
    """One decoder layer's weights from its own key: the attention
    sublayer, then a dense MLP (`moe` false) or the router, the held
    experts (stacked) and the shared expert under the dense MLP's names."""
    D, H, Q, R = d["D"], d["H"], d["Q"], d["R"]
    k = jax.random.split(key, 16)
    w = {
        "input_layernorm": _scale(k[0], D, dtype),
        "q_a_proj": _normal(k[1], (D, Q), dtype),
        "q_a_layernorm": _scale(k[2], Q, dtype),
        "q_b_proj": _normal(k[3], (Q, H * (d["dn"] + d["dr"])), dtype),
        "kv_a_proj_with_mqa": _normal(k[4], (D, R + d["dr"]), dtype),
        "kv_a_layernorm": _scale(k[5], R, dtype),
        "kv_b_proj": _normal(k[6], (R, H * (d["dn"] + d["dv"])), dtype),
        "o_proj": _normal(k[7], (H * d["dv"], D), dtype),
        "post_attention_layernorm": _scale(k[8], D, dtype),
    }
    F = d["Fm"] * d["shared"] if moe else d["F"]
    w.update({"gate_proj": _normal(k[9], (D, F), dtype),
              "up_proj": _normal(k[10], (D, F), dtype),
              "down_proj": _normal(k[11], (F, D), dtype)})
    if moe:
        n, Fm = d["n"], d["Fm"]
        w.update({"router": _normal(k[12], (D, d["E"]), dtype),
                  "experts_gate": _normal(k[13], (n, D, Fm), dtype),
                  "experts_up": _normal(k[14], (n, D, Fm), dtype),
                  "experts_down": _normal(k[15], (n, Fm, D), dtype)})
    return w


def outer_weights(key: jax.Array, d: Dict[str, Any], dtype) -> Dict[str, Any]:
    """Embedding, final norm and head, over the slice of the vocabulary."""
    k = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {"embed_tokens": _normal(k[0], (d["V"], d["D"]), dtype),
            "norm": _scale(k[1], d["D"], dtype),
            "lm_head": _normal(k[2], (d["D"], d["V"]), dtype)}


def _build(key, d: Dict[str, Any], dtype):
    def group(lo, hi, moe):
        return jax.vmap(lambda i: layer_weights(layer_key(key, i), d, dtype,
                                                moe))(
            jnp.arange(lo, hi, dtype=jnp.int32))
    return {**outer_weights(key, d, dtype),
            "dense_layers": group(0, d["Ld"], False),
            "moe_layers": group(d["Ld"], d["L"], True)}


def make_params(seed: int, d: Dict[str, Any], dtype=jnp.bfloat16,
                shardings=None):
    """The whole parameter tree in the layout the program's MLA decoder
    takes (`dense_layers` and `moe_layers`, each stacked on a leading
    axis), one jitted call."""
    return jax.jit(functools.partial(_build, d=d, dtype=dtype),
                   out_shardings=shardings)(seed_key(seed))


def params_shape(d: Dict[str, Any], dtype=jnp.bfloat16):
    return jax.eval_shape(functools.partial(_build, d=d, dtype=dtype),
                          seed_key(0))


def program_config(config: Dict[str, Any]):
    """The program's own configuration object for these sizes."""
    from paddle_tpu.nlp import mla
    m, d = config, dims(config)
    if m.get("tie_word_embeddings") or m["hidden_act"] != "silu":
        raise ValueError("mla_moe_decoder: untied, gated SiLU")
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config.get("served_dtype", "bfloat16")]
    return mla.MlaMoeConfig(
        vocab_size=d["V"], hidden_size=d["D"], intermediate_size=d["F"],
        moe_intermediate_size=d["Fm"], num_hidden_layers=d["L"],
        first_k_dense_replace=d["Ld"], num_attention_heads=d["H"],
        q_lora_rank=d["Q"], kv_lora_rank=d["R"], qk_nope_head_dim=d["dn"],
        qk_rope_head_dim=d["dr"], v_head_dim=d["dv"],
        n_routed_experts=d["E"], num_experts_per_tok=d["k"],
        n_shared_experts=d["shared"],
        routed_scaling_factor=d["route_scale"], norm_topk_prob=d["norm_topk"],
        experts_first=d["first"], experts_count=d["n"],
        max_position_embeddings=m["max_position_embeddings"],
        rms_norm_eps=d["eps"], rope_theta=d["theta"],
        rope_scaling=dict(d["yarn"]), dtype=dt, param_dtype=dt)


def num_params(d: Dict[str, Any]) -> int:
    """Parameters resident on this chip."""
    attn = (d["D"] * d["Q"] + d["Q"] + d["Q"] * d["H"] * (d["dn"] + d["dr"])
            + d["D"] * (d["R"] + d["dr"]) + d["R"]
            + d["R"] * d["H"] * (d["dn"] + d["dv"])
            + d["H"] * d["dv"] * d["D"] + 2 * d["D"])
    moe = d["D"] * d["E"] + 3 * d["D"] * d["Fm"] * (d["n"] + d["shared"])
    return (2 * d["V"] * d["D"] + d["D"]
            + d["Ld"] * (attn + 3 * d["D"] * d["F"])
            + (d["L"] - d["Ld"]) * (attn + moe))


# ---------------------------------------------------------------------------
# operations and bytes: the least the algorithm needs, never what today's
# kernel happens to move
# ---------------------------------------------------------------------------

def latent_attention_cost(d: Dict[str, Any], pairs: float, queries: float,
                          keys: float, itemsize: int = 2,
                          layers: int = 1) -> Dict[str, float]:
    """Absorbed-form latent attention of one call: `pairs` visible
    (query, key) pairs, `queries` query tokens, `keys` cached rows the
    call's rows can see (each counted once per row that sees it: a row's
    keys are read once for all of its queries and heads). Per key one
    cached row of R + dr columns, read ONCE for scores and values alike;
    q in (H x (R + dr)) and o_lat out (H x R) once a query. FLOPs per
    pair: the score over R + dr columns and the value sum over R, 2 each
    per column and head."""
    W = d["R"] + d["dr"]
    flops = 2.0 * pairs * d["H"] * (W + d["R"])
    nbytes = itemsize * (keys * W + queries * d["H"] * (W + d["R"]))
    return {"bytes": layers * nbytes, "flops": layers * flops}


def expert_ffn_cost(d: Dict[str, Any], pairs: float, experts_hit: float,
                    itemsize: int = 2) -> Dict[str, float]:
    """The routed experts' part of a tick: `pairs` (token, expert) pairs
    computed here, `experts_hit` expert-layers that got a token (both
    summed over layers and steps). Each hit expert's three matrices are
    read once a step; a pair costs the gated MLP's three matmuls."""
    per = 3 * d["D"] * d["Fm"]
    return {"bytes": float(experts_hit) * per * itemsize,
            "flops": float(pairs) * 2.0 * per}
