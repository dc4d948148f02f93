"""Family `window_moe_decoder`: pre-norm decoder blocks of grouped-query
attention whose layers are of two kinds in a fixed period, sliding-window
(the last `sliding_window` keys, plain rotary embedding) and full (every
key, YaRN rotary embedding with the configuration's `attention_factor`),
head size a key of its own (H x hd need not be the hidden size), and a
sparse-expert FFN in every layer (softmax router over ALL experts, top-k,
renormalised, no shared expert, no dense MLP), RMSNorm, untied embedding
and head, rotate-half layout. Mellum 2 is one.

A configuration of this family holds every expert of a layer and the whole
vocabulary (`held experts first=0, n=num_experts`); its cut is in depth,
whole periods of the layer pattern. The file keeps the published
`layer_types` / `mlp_layer_types` whole; the first `num_hidden_layers`
entries are what runs.

The benchmark, not the program, makes the weights (as for `dense_decoder`):
from the seed, on the device, in one jitted call, in the served type. The
plain reference (benchmark/reference/window_moe_decoder.py) draws the same
layer from the same key, one layer at a time. This file also holds the
functions that count the kernels' least operations and bytes.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from .dense_decoder import (_normal, layer_key, roofline_seconds,
                            seed_key)

__all__ = ["dims", "make_params", "params_shape", "program_config",
           "layer_weights", "outer_weights", "layer_key", "seed_key",
           "roofline_seconds", "attention_cost", "expert_ffn_cost"]

KINDS = {"full_attention": "full", "sliding_attention": "window"}


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the weights and the reference need, from a configuration
    file's published keys (at its top level, under the catalog row's
    names). JSON-plain: the reference keys its compiled pieces by it."""
    m = config
    L = m["num_hidden_layers"]
    if len(m["layer_types"]) < L or len(m["mlp_layer_types"]) < L:
        raise ValueError("window_moe_decoder: a kind for every layer")
    if any(t != "sparse" for t in m["mlp_layer_types"][:L]):
        raise ValueError("window_moe_decoder: every layer's FFN is sparse")
    if m["attention_bias"] or not m["use_sliding_window"] \
            or m["hidden_act"] != "silu" or m.get("tie_word_embeddings"):
        raise ValueError("window_moe_decoder: no attention bias, a sliding "
                         "window, gated SiLU experts, untied")
    rope = {}
    for name, kind in KINDS.items():
        rp = m["rope_parameters"][name]
        if rp["rope_type"] not in ("default", "yarn"):
            raise ValueError("window_moe_decoder: plain or YaRN rotary")
        rope[kind] = {k: rp[k] for k in sorted(rp)}
    return {
        "V": m["vocab_size"], "D": m["hidden_size"], "L": L,
        "H": m["num_attention_heads"], "KV": m["num_key_value_heads"],
        "hd": m["head_dim"], "W": m["sliding_window"],
        "kinds": [KINDS[t] for t in m["layer_types"][:L]],
        "E": m["num_experts"], "n": m["num_experts"], "first": 0,
        "k": m["num_experts_per_tok"], "Fm": m["moe_intermediate_size"],
        "norm_topk": bool(m["norm_topk_prob"]),
        "eps": float(m["rms_norm_eps"]), "rope": rope,
    }


def _scale(key, n, dtype):
    """A norm scale that is not all ones, so that a dropped one shows."""
    return (1.0 + _normal(key, (n,), jnp.float32, 0.1)).astype(dtype)


def layer_weights(key: jax.Array, d: Dict[str, Any], dtype) -> Dict[str, Any]:
    """One decoder layer's weights from its own key: the attention
    sublayer, the router and the experts (stacked). Window and full layers
    have the same shapes."""
    D, H, KV, hd, n, Fm = d["D"], d["H"], d["KV"], d["hd"], d["n"], d["Fm"]
    k = jax.random.split(key, 10)
    return {
        "input_layernorm": _scale(k[0], D, dtype),
        "q_proj": _normal(k[1], (D, H * hd), dtype),
        "k_proj": _normal(k[2], (D, KV * hd), dtype),
        "v_proj": _normal(k[3], (D, KV * hd), dtype),
        "o_proj": _normal(k[4], (H * hd, D), dtype),
        "post_attention_layernorm": _scale(k[5], D, dtype),
        "router": _normal(k[6], (D, d["E"]), dtype),
        "experts_gate": _normal(k[7], (n, D, Fm), dtype),
        "experts_up": _normal(k[8], (n, D, Fm), dtype),
        "experts_down": _normal(k[9], (n, Fm, D), dtype),
    }


def outer_weights(key: jax.Array, d: Dict[str, Any], dtype) -> Dict[str, Any]:
    """Embedding, final norm and head."""
    k = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {"embed_tokens": _normal(k[0], (d["V"], d["D"]), dtype),
            "norm": _scale(k[1], d["D"], dtype),
            "lm_head": _normal(k[2], (d["D"], d["V"]), dtype)}


def _build(key, d: Dict[str, Any], dtype):
    layers = jax.vmap(lambda i: layer_weights(layer_key(key, i), d, dtype))(
        jnp.arange(d["L"], dtype=jnp.int32))
    return {**outer_weights(key, d, dtype), "layers": layers}


def make_params(seed: int, d: Dict[str, Any], dtype=jnp.bfloat16,
                shardings=None):
    """The whole parameter tree in the layout the program takes (`layers`
    stacked on a leading axis in layer order), one jitted call."""
    return jax.jit(functools.partial(_build, d=d, dtype=dtype),
                   out_shardings=shardings)(seed_key(seed))


def params_shape(d: Dict[str, Any], dtype=jnp.bfloat16):
    return jax.eval_shape(functools.partial(_build, d=d, dtype=dtype),
                          seed_key(0))


def program_config(config: Dict[str, Any]):
    """The program's own configuration object for these sizes."""
    from paddle_tpu.nlp import window_moe
    m, d = config, dims(config)
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config.get("served_dtype", "bfloat16")]
    return window_moe.WindowMoeConfig(
        vocab_size=d["V"], hidden_size=d["D"], num_hidden_layers=d["L"],
        num_attention_heads=d["H"], num_key_value_heads=d["KV"],
        head_dim=d["hd"], layer_types=tuple(m["layer_types"][:d["L"]]),
        sliding_window=d["W"],
        rope_parameters={k: dict(v)
                         for k, v in m["rope_parameters"].items()},
        num_experts=d["E"], num_experts_per_tok=d["k"],
        moe_intermediate_size=d["Fm"], norm_topk_prob=d["norm_topk"],
        scoring_func="softmax", experts_first=d["first"],
        experts_count=d["n"],
        max_position_embeddings=m["max_position_embeddings"],
        rms_norm_eps=d["eps"], dtype=dt, param_dtype=dt)


def num_params(d: Dict[str, Any]) -> int:
    """Parameters resident on this chip."""
    attn = 2 * d["D"] * (d["H"] + d["KV"]) * d["hd"] + 2 * d["D"]
    moe = d["D"] * d["E"] + 3 * d["D"] * d["Fm"] * d["n"]
    return 2 * d["V"] * d["D"] + d["D"] + d["L"] * (attn + moe)


# ---------------------------------------------------------------------------
# operations and bytes: the least the algorithm needs, never what today's
# kernel happens to move
# ---------------------------------------------------------------------------

def attention_cost(d: Dict[str, Any], kind: str,
                   decode_ctx: Sequence[int] = (),
                   prefill_spans: Sequence[Sequence[int]] = (),
                   itemsize: int = 2) -> Dict[str, float]:
    """ONE layer of kind `kind` ("full" | "window") for one call of the
    paged attention: decode rows that each see `ctx` keys (their own
    included) and prefill rows that hold the queries `[start, end)` of a
    sequence whose first `end` keys are cached. A full layer reads every
    key a row can see, a window layer at most the last W of each query:
    a decode row `min(ctx, W)` keys, a prefill row the keys from its
    first query's window start to its last query. Every such K and V
    element is read once a row, q read and the output written once a
    query; FLOPs over the VISIBLE (query, key) pairs only, QK^T and PV
    at 2 each per pair, head and head dim."""
    W = d["W"] if kind == "window" else None
    keys = pairs = queries = 0.0
    for c in decode_ctx:
        seen = c if W is None else min(c, W)
        keys, pairs, queries = keys + seen, pairs + seen, queries + 1
    for start, end in prefill_spans:
        n = end - start
        if W is None:
            keys += end
            pairs += n * start + n * (n + 1) / 2.0
        else:
            keys += end - max(0, start - W + 1)
            pairs += sum(min(p + 1, W) for p in range(start, end))
        queries += n
    return {"bytes": (2 * keys * d["KV"] + 2 * queries * d["H"]) * d["hd"]
            * itemsize,
            "flops": 4.0 * pairs * d["H"] * d["hd"]}


def expert_ffn_cost(d: Dict[str, Any], pairs: float, experts_hit: float,
                    itemsize: int = 2) -> Dict[str, float]:
    """The routed experts' part of a tick: `pairs` (token, expert) pairs
    computed here, `experts_hit` expert-layers that got a token (both
    summed over layers and steps). Each hit expert's three matrices are
    read once a step; a pair costs the gated MLP's three matmuls."""
    per = 3 * d["D"] * d["Fm"]
    return {"bytes": float(experts_hit) * per * itemsize,
            "flops": float(pairs) * 2.0 * per}
