"""Family `gated_window_moe_decoder`: pre-norm decoder blocks of
grouped-query attention whose layers are of two kinds in a fixed period,
sliding-window and full, that differ in more than their mask: each kind has
its own number of QUERY heads over the same KV heads (`q_proj` / `o_proj`
differ in shape by kind), its own rotary embedding over its own SHARE of a
head's dims (`partial_rotary_factor`), and every layer gates each head's
attention output with a sigmoid of the layer's normed input before
`o_proj`. The leading `mlp_only_layers` have a dense gated-SiLU MLP; every
later layer a sparse-expert FFN (softmax router over ALL experts, top-k,
renormalised, times `moe_routed_scaling_factor`) plus one ungated shared
expert. RMSNorm, untied embedding and head, rotate-half layout.
Laguna-S-2.1 is one.

A configuration of this family is ONE CHIP'S SHARE of a deployment in which
several chips share each layer (`share` in its file): the chip holds
`num_experts` of the router's `share.router_experts` routed experts (those
from `share.experts_first` on), computes their part of each token's result
and leaves the rest out, in the program and in the reference alike; it
holds `vocab_size` rows of the vocabulary. The file keeps the published
`layer_types`, `mlp_layer_types`, `gating_types` and
`num_attention_heads_per_layer` whole; the first `num_hidden_layers`
entries are what runs.

The benchmark, not the program, makes the weights: from the seed, on the
device, in one jitted call, in the served type, in the program's layout
(`nlp/window_moe.py::init_params`: `lead_layers` and `layers` stacked in
layer order, the matrices that follow a kind's head count stacked BY KIND
under `attn_full` / `attn_window`). The plain reference
(benchmark/reference/gated_window_moe_decoder.py) draws the same layer from
the same key, one layer at a time. This file also holds the functions that
count the kernels' least operations and bytes.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from .dense_decoder import (_normal, layer_key, roofline_seconds,
                            seed_key)
from . import window_moe_decoder
from .window_moe_decoder import (KINDS, _scale, expert_ffn_cost,
                                 outer_weights)

__all__ = ["dims", "make_params", "params_shape", "program_config",
           "layer_weights", "outer_weights", "layer_key", "seed_key",
           "roofline_seconds", "attention_cost", "expert_ffn_cost"]

BY_KIND = ("q_proj", "o_proj", "g_proj")    # shaped by a kind's head count


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the weights and the reference need, from a configuration
    file's published keys (at its top level, under the catalog row's
    names) and its `share` block. `V` is the slice of the vocabulary held
    here. JSON-plain: the reference keys its compiled pieces by it."""
    m, sh = config, config["share"]
    L = m["num_hidden_layers"]
    lead = len(m["mlp_only_layers"])
    per = ("layer_types", "mlp_layer_types", "gating_types",
           "num_attention_heads_per_layer")
    if any(len(m[k]) < L for k in per):
        raise ValueError("gated_window_moe_decoder: an entry for every layer")
    if list(m["mlp_only_layers"]) != list(range(lead)) or lead >= L \
            or m["mlp_layer_types"][:L] != ["dense"] * lead \
            + ["sparse"] * (L - lead) or m["decoder_sparse_step"] != 1:
        raise ValueError("gated_window_moe_decoder: leading dense layers, "
                         "then sparse ones")
    if m["gating"] != "per-head" \
            or any(g != "per_head" for g in m["gating_types"][:L]):
        raise ValueError("gated_window_moe_decoder: a gate a head")
    if m["attention_bias"] or m.get("tie_word_embeddings") \
            or m["moe_apply_router_weight_on_input"] \
            or m["moe_router_logit_softcapping"]:
        raise ValueError("gated_window_moe_decoder: no attention bias, "
                         "untied, gates on the experts' output, no softcap")
    kinds = [KINDS[t] for t in m["layer_types"][:L]]
    heads = {}
    for kind, h in zip(kinds, m["num_attention_heads_per_layer"][:L]):
        if heads.setdefault(kind, h) != h or h % m["num_key_value_heads"]:
            raise ValueError("gated_window_moe_decoder: one head count a "
                             "kind, over the KV heads")
    rope = {}
    for name, kind in KINDS.items():
        rp = m["rope_parameters"][name]
        if rp["rope_type"] not in ("default", "yarn"):
            raise ValueError("gated_window_moe_decoder: plain or YaRN rotary")
        rope[kind] = {k: rp[k] for k in sorted(rp)}
    n, first = m["num_experts"], sh["experts_first"]
    if first + n > sh["router_experts"]:
        raise ValueError("held experts lie outside the router's width")
    return {
        "V": m["vocab_size"], "D": m["hidden_size"], "L": L, "Ld": lead,
        "H": heads, "KV": m["num_key_value_heads"], "hd": m["head_dim"],
        "W": m["sliding_window"], "kinds": kinds,
        "F": m["intermediate_size"], "Fm": m["moe_intermediate_size"],
        "Fs": m["shared_expert_intermediate_size"],
        "E": sh["router_experts"], "n": n, "first": first,
        "k": m["num_experts_per_tok"],
        "route_scale": float(m["moe_routed_scaling_factor"]),
        "norm_topk": bool(m["norm_topk_prob"]),
        "eps": float(m["rms_norm_eps"]), "rope": rope,
    }


def layer_weights(key: jax.Array, d: Dict[str, Any], dtype,
                  i: int) -> Dict[str, Any]:
    """Layer `i`'s weights from its own key: the attention sublayer at its
    kind's head count with its gate, then a dense MLP (a leading layer) or
    the router, the held experts (stacked) and the shared expert under the
    dense MLP's names."""
    D, KV, hd = d["D"], d["KV"], d["hd"]
    H = d["H"][d["kinds"][i]]
    k = jax.random.split(key, 14)
    out = {
        "input_layernorm": _scale(k[0], D, dtype),
        "q_proj": _normal(k[1], (D, H * hd), dtype),
        "k_proj": _normal(k[2], (D, KV * hd), dtype),
        "v_proj": _normal(k[3], (D, KV * hd), dtype),
        "o_proj": _normal(k[4], (H * hd, D), dtype),
        # a gate's logit of spread 1: gates between 0.1 and 0.9, so that
        # a dropped gate shows
        "g_proj": _normal(k[5], (D, H), dtype, D ** -0.5),
        "post_attention_layernorm": _scale(k[6], D, dtype),
    }
    width = d["F"] if i < d["Ld"] else d["Fs"]
    out.update({"gate_proj": _normal(k[7], (D, width), dtype),
                "up_proj": _normal(k[8], (D, width), dtype),
                "down_proj": _normal(k[9], (width, D), dtype)})
    if i < d["Ld"]:
        return out
    n, Fm = d["n"], d["Fm"]
    return {**out,
            "router": _normal(k[10], (D, d["E"]), dtype),
            "experts_gate": _normal(k[11], (n, D, Fm), dtype),
            "experts_up": _normal(k[12], (n, D, Fm), dtype),
            "experts_down": _normal(k[13], (n, Fm, D), dtype)}


def _group(key, d: Dict[str, Any], dtype, layers: Sequence[int]):
    """The layers `layers` stacked as the program holds a layer group:
    per-layer leaves in layer order, the head-count-shaped ones by kind."""
    each = [layer_weights(layer_key(key, jnp.int32(i)), d, dtype, i)
            for i in layers]
    out = {name: jnp.stack([w[name] for w in each])
           for name in each[0] if name not in BY_KIND}
    for kind in sorted({d["kinds"][i] for i in layers}):
        out["attn_" + kind] = {
            name: jnp.stack([w[name] for w, i in zip(each, layers)
                             if d["kinds"][i] == kind]) for name in BY_KIND}
    return out


def _build(key, d: Dict[str, Any], dtype):
    out = {**outer_weights(key, d, dtype),
           "layers": _group(key, d, dtype, range(d["Ld"], d["L"]))}
    if d["Ld"]:
        out["lead_layers"] = _group(key, d, dtype, range(d["Ld"]))
    return out


def make_params(seed: int, d: Dict[str, Any], dtype=jnp.bfloat16,
                shardings=None):
    """The whole parameter tree in the layout the program takes, one
    jitted call."""
    return jax.jit(functools.partial(_build, d=d, dtype=dtype),
                   out_shardings=shardings)(seed_key(seed))


def params_shape(d: Dict[str, Any], dtype=jnp.bfloat16):
    return jax.eval_shape(functools.partial(_build, d=d, dtype=dtype),
                          seed_key(0))


def program_config(config: Dict[str, Any]):
    """The program's own configuration object for these sizes."""
    from paddle_tpu.nlp import window_moe
    m, d = config, dims(config)
    L = d["L"]
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config.get("served_dtype", "bfloat16")]
    return window_moe.WindowMoeConfig(
        vocab_size=d["V"], hidden_size=d["D"], num_hidden_layers=L,
        num_attention_heads=m["num_attention_heads"],
        num_attention_heads_per_layer=tuple(
            m["num_attention_heads_per_layer"][:L]),
        num_key_value_heads=d["KV"], head_dim=d["hd"],
        layer_types=tuple(m["layer_types"][:L]), sliding_window=d["W"],
        rope_parameters={k: dict(v)
                         for k, v in m["rope_parameters"].items()},
        attention_gate="per_head",
        mlp_only_layers=tuple(m["mlp_only_layers"]),
        intermediate_size=d["F"],
        num_experts=d["E"], num_experts_per_tok=d["k"],
        moe_intermediate_size=d["Fm"], norm_topk_prob=d["norm_topk"],
        scoring_func="softmax", routed_scaling_factor=d["route_scale"],
        n_shared_experts=1, shared_expert_intermediate_size=d["Fs"],
        experts_first=d["first"], experts_count=d["n"],
        max_position_embeddings=m["max_position_embeddings"],
        rms_norm_eps=d["eps"], dtype=dt, param_dtype=dt)


def num_params(d: Dict[str, Any]) -> int:
    """Parameters resident on this chip."""
    D, hd = d["D"], d["hd"]
    total = 2 * d["V"] * D + D
    for i, kind in enumerate(d["kinds"]):
        H = d["H"][kind]
        total += 2 * D * (H + d["KV"]) * hd + D * H + 2 * D
        total += 3 * D * d["F"] if i < d["Ld"] else (
            D * d["E"] + 3 * D * d["Fm"] * d["n"] + 3 * D * d["Fs"])
    return total


# ---------------------------------------------------------------------------
# operations and bytes: the least the algorithm needs, never what today's
# kernel happens to move. `expert_ffn_cost` is `window_moe_decoder`'s: each
# hit expert's three matrices read once a step, a pair's three matmuls.
# ---------------------------------------------------------------------------

def attention_cost(d: Dict[str, Any], kind: str,
                   decode_ctx: Sequence[int] = (),
                   prefill_spans: Sequence[Sequence[int]] = (),
                   itemsize: int = 2) -> Dict[str, float]:
    """ONE layer of kind `kind` ("full" | "window") for one call of the
    paged attention, as `window_moe_decoder.attention_cost` counts it
    (a full layer reads every key a row can see, a window layer at most
    the last W of each query; every such K and V element read once a row,
    q read and the output written once a query; FLOPs over the visible
    pairs only), at the kind's OWN number of query heads."""
    return window_moe_decoder.attention_cost(
        {**d, "H": d["H"][kind]}, kind, decode_ctx, prefill_spans, itemsize)
