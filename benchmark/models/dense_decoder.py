"""Family `dense_decoder`: pre-norm decoder blocks of grouped-query
attention with rotary embeddings (rotate-half) and a gated SiLU MLP,
RMSNorm, untied embedding and head. Mistral-7B is one.

The benchmark, not the program, makes the weights: from the seed, on the
device, in one jitted call, in the type they are served in. The plain
reference (benchmark/reference/dense_decoder.py) draws the same layer
from the same key, one layer at a time. This file also holds the
functions that count a kernel's least operations and bytes.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the weights and the reference need, from a configuration
    file's published keys."""
    m = config["model"]
    H = m["num_attention_heads"]
    hd = m.get("head_dim") or m["hidden_size"] // H
    return {"V": m["vocab_size"], "D": m["hidden_size"],
            "F": m["intermediate_size"], "L": m["num_hidden_layers"],
            "H": H, "KV": m["num_key_value_heads"], "hd": hd,
            "theta": float(m["rope_theta"]), "eps": float(m["rms_norm_eps"])}


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number: seeds run past 32 signed bits."""
    seed = abs(int(seed))
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _normal(key, shape, dtype, std=INIT_STD):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_weights(key: jax.Array, d: Dict[str, Any], dtype) -> Dict[str, Any]:
    """One decoder layer's weights from its own key. Norm scales are not
    all ones, so that a dropped scale shows."""
    D, F, H, KV, hd = d["D"], d["F"], d["H"], d["KV"], d["hd"]
    k = jax.random.split(key, 9)
    return {
        "input_layernorm": (1.0 + _normal(k[0], (D,), jnp.float32, 0.1)
                            ).astype(dtype),
        "q_proj": _normal(k[1], (D, H * hd), dtype),
        "k_proj": _normal(k[2], (D, KV * hd), dtype),
        "v_proj": _normal(k[3], (D, KV * hd), dtype),
        "o_proj": _normal(k[4], (H * hd, D), dtype),
        "post_attention_layernorm": (
            1.0 + _normal(k[5], (D,), jnp.float32, 0.1)).astype(dtype),
        "gate_proj": _normal(k[6], (D, F), dtype),
        "up_proj": _normal(k[7], (D, F), dtype),
        "down_proj": _normal(k[8], (F, D), dtype),
    }


def layer_key(key: jax.Array, layer) -> jax.Array:
    return jax.random.fold_in(key, layer)


def outer_weights(key: jax.Array, d: Dict[str, Any], dtype) -> Dict[str, Any]:
    """Embedding, final norm and head."""
    k = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {
        "embed_tokens": _normal(k[0], (d["V"], d["D"]), dtype),
        "norm": (1.0 + _normal(k[1], (d["D"],), jnp.float32, 0.1)
                 ).astype(dtype),
        "lm_head": _normal(k[2], (d["D"], d["V"]), dtype),
    }


def _build(key, d: Dict[str, Any], dtype):
    layers = jax.vmap(lambda i: layer_weights(layer_key(key, i), d, dtype))(
        jnp.arange(d["L"], dtype=jnp.int32))
    return {**outer_weights(key, d, dtype), "layers": layers}


def make_params(seed: int, d: Dict[str, Any], dtype=jnp.bfloat16,
                shardings=None):
    """The whole parameter tree (layers stacked on a leading axis, the
    layout the program's dense-decoder code takes), one jitted call; with
    `shardings`, each leaf made straight into its shards."""
    return jax.jit(functools.partial(_build, d=d, dtype=dtype),
                   out_shardings=shardings)(seed_key(seed))


def params_shape(d: Dict[str, Any], dtype=jnp.bfloat16):
    return jax.eval_shape(functools.partial(_build, d=d, dtype=dtype),
                          seed_key(0))


def program_config(config: Dict[str, Any]):
    """The program's own configuration object for these sizes."""
    from paddle_tpu.nlp import llama
    m = config["model"]
    d = dims(config)
    if m["hidden_size"] != d["H"] * d["hd"]:
        raise ValueError("the program derives head_dim as hidden/heads")
    if m.get("tie_word_embeddings") or m.get("sliding_window"):
        raise ValueError("dense_decoder: untied, no sliding window")
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[m["torch_dtype"]]
    return llama.LlamaConfig(
        vocab_size=d["V"], hidden_size=d["D"], intermediate_size=d["F"],
        num_hidden_layers=d["L"], num_attention_heads=d["H"],
        num_key_value_heads=d["KV"],
        max_position_embeddings=m["max_position_embeddings"],
        rms_norm_eps=d["eps"], rope_theta=d["theta"],
        tie_word_embeddings=False, dtype=dt, param_dtype=dt)


def num_params(d: Dict[str, Any]) -> int:
    per_layer = (2 * d["D"] + d["D"] * d["H"] * d["hd"] * 2
                 + 2 * d["D"] * d["KV"] * d["hd"] + 3 * d["D"] * d["F"])
    return 2 * d["V"] * d["D"] + d["L"] * per_layer + d["D"]


# ---------------------------------------------------------------------------
# operations and bytes: the least the algorithm needs, never what today's
# kernel happens to move
# ---------------------------------------------------------------------------

def train_flops_per_token(d: Dict[str, Any], seq_len: int) -> float:
    """Forward plus backward FLOPs a trained token needs: 6 per matmul
    parameter, the causal half of attention credited (QK^T and PV each
    visit seq/2 keys on average), the embedding gather not counted, no
    recomputation counted. Copied from the program's
    `llama.flops_per_token` so that a later change there cannot move MFU."""
    matmul = d["L"] * (d["D"] * (d["H"] + 2 * d["KV"]) * d["hd"]
                       + d["H"] * d["hd"] * d["D"] + 3 * d["D"] * d["F"]) \
        + d["V"] * d["D"]
    attn = d["L"] * d["H"] * d["hd"] * seq_len
    return 6.0 * (matmul + attn)


def decode_attention_cost(d: Dict[str, Any], context_lens, itemsize: int = 2,
                          layers: int = 1) -> Dict[str, float]:
    """One decode step of paged attention over live sequences of the given
    context lengths: every live K and V element read once, q read and the
    output written once. FLOPs: QK^T and PV, 2 each per key element and
    query head."""
    ctx = float(sum(context_lens))
    n = len(context_lens)
    kv_bytes = 2 * ctx * d["KV"] * d["hd"] * itemsize
    qo_bytes = 2 * n * d["H"] * d["hd"] * itemsize
    flops = 4.0 * ctx * d["H"] * d["hd"]
    return {"bytes": layers * (kv_bytes + qo_bytes), "flops": layers * flops}


def causal_attention_cost(d: Dict[str, Any], batch: int, seq_len: int,
                          itemsize: int = 2, layers: int = 1,
                          backward: bool = False) -> Dict[str, float]:
    """Causal self-attention over `batch` sequences of seq_len: the causal
    half of QK^T and PV (the backward pass: 2.5 times the forward's, dq,
    dk, dv and the recomputed scores); q, k, v read and the output written
    once (backward: those, the output's gradient, and three gradients)."""
    fwd = 4.0 * batch * d["H"] * d["hd"] * seq_len * seq_len / 2.0
    io = batch * seq_len * (2 * d["H"] + 2 * d["KV"]) * d["hd"] * itemsize
    if backward:
        return {"flops": layers * 2.5 * fwd, "bytes": layers * 2.0 * io}
    return {"flops": layers * fwd, "bytes": layers * io}


def roofline_seconds(cost: Dict[str, float], peak: Dict[str, float]):
    """(least seconds, which bound) on a chip with these peaks."""
    t_f = cost["flops"] / peak["bf16_flops"]
    t_b = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "bytes")


def train_tokens(key: jax.Array, step, batch: int, seq: int, vocab: int):
    """The batch of training step `step`: uniform random tokens from the
    seed's key, rows that all differ, a fresh batch every step."""
    k = jax.random.fold_in(jax.random.fold_in(key, 7 << 20), step)
    return jax.random.randint(k, (batch, seq), 0, vocab, jnp.int32)
