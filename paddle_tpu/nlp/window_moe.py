"""A GQA decoder whose layers are of two KINDS, sliding-window and full
attention interleaved in a fixed period, each with its own rotary
embedding, and whose FFN is a sparse-expert layer with a softmax router and
no shared expert: the Mellum 2 layer. Served through the paged path
(`nlp/paged.py`): this file holds the configuration and the parameters'
layout, nothing of the forward.

Per token x of layer l, kind t_l (`layer_types[l]`):

  h = RMSNorm(x);  q, k, v = h W_q, h W_k, h W_v      (H, KV, KV heads of hd)
  q, k = RoPE_t(q, k)                                  over all hd dims
  s_ij = q_i . k_j / sqrt(hd), visible iff j <= i and (t_l full or j > i - W)
  x += softmax(s) v W_o                                head n reads KV n // (H/KV)
  h' = RMSNorm(x);  p = softmax(h' W_r) in float32 over ALL experts
  S = top-k(p);  g_e = p_e / sum_{e in S} p_e;  x += sum_{e in S} g_e E_e(h')

`RoPE_sliding` is plain (theta), `RoPE_full` YaRN with the configuration's
`attention_factor` on cos and sin (`kernels.rope.yarn_freqs`). What the two
kinds force on the cache (a full layer keeps every key, a window layer the
last W) is `paged.KVLayout`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.rope import rope_freqs, yarn_freqs

KINDS = {"full_attention": "full", "sliding_attention": "window"}


@dataclasses.dataclass
class WindowMoeConfig:
    """`head_dim` is a field (H * hd need not be the hidden size);
    `layer_types` the published names a layer, `rope_parameters` one entry
    a name; `experts_first` / `experts_count` say which routed experts are
    held here (None = all), as in `mla.MlaMoeConfig`."""
    vocab_size: int = 32000
    hidden_size: int = 2304
    num_hidden_layers: int = 8
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 \
        + ("full_attention",)
    sliding_window: int = 1024
    rope_parameters: Optional[Dict[str, Dict[str, Any]]] = None
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    scoring_func: str = "softmax"        # the router's scores (moe.ROUTERS)
    routed_scaling_factor: float = 1.0
    n_shared_experts: int = 0
    experts_first: int = 0
    experts_count: Optional[int] = None
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        L = self.num_hidden_layers
        if len(self.layer_types) != L or any(
                t not in KINDS for t in self.layer_types):
            raise ValueError(
                f"layer_types must name each of the {L} layers as one of "
                f"{sorted(KINDS)}")
        if self.rope_parameters is None:
            self.rope_parameters = {t: {"rope_type": "default",
                                        "rope_theta": 10000.0}
                                    for t in set(self.layer_types)}
        for t in set(self.layer_types):
            kind = self.rope_parameters[t].get("rope_type", "default")
            if kind not in ("default", "yarn"):
                raise ValueError(f"rope_type {kind!r} of {t}: plain or YaRN")
        if self.experts_count is None:
            self.experts_count = self.num_experts - self.experts_first
        if not (0 <= self.experts_first and self.experts_count >= 1
                and self.experts_first + self.experts_count
                <= self.num_experts):
            raise ValueError("held experts lie outside the router's width")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide over the KV heads")
        if self.n_shared_experts or self.tie_word_embeddings:
            raise ValueError("WindowMoeConfig: no shared expert, untied")

    @property
    def period_kinds(self) -> Tuple[str, ...]:
        """The kinds ("window" | "full") of one period of the layer
        pattern: the shortest prefix that the whole stack repeats. The
        paged forward scans whole periods, one compiled body."""
        kinds = tuple(KINDS[t] for t in self.layer_types)
        L = len(kinds)
        for p in range(1, L + 1):
            if L % p == 0 and kinds == kinds[:p] * (L // p):
                return kinds[:p]
        raise AssertionError("unreachable: p = L always repeats")

    def rope_tables(self, max_seq: int) -> Dict[str, Tuple[jax.Array,
                                                           jax.Array]]:
        """kind -> (cos, sin) [max_seq, hd/2], float32: each kind's own
        frequencies; YaRN's cos and sin carry `attention_factor` where the
        configuration gives one, m(factor) otherwise."""
        out = {}
        for name, kind in KINDS.items():
            rp = self.rope_parameters.get(name)
            if rp is None:
                continue
            theta = float(rp["rope_theta"])
            if rp.get("rope_type", "default") == "default":
                out[kind] = rope_freqs(self.head_dim, max_seq, theta,
                                       jnp.float32)
            else:
                out[kind] = yarn_freqs(
                    self.head_dim, max_seq, theta, float(rp["factor"]),
                    int(rp["original_max_position_embeddings"]),
                    float(rp.get("beta_fast", 32.0)),
                    float(rp.get("beta_slow", 1.0)),
                    attention_factor=rp.get("attention_factor"))
        return out

    @staticmethod
    def tiny(**over) -> "WindowMoeConfig":
        """Test-sized: two periods, both kinds, both RoPE sets, H * hd not
        the hidden size, nothing wide."""
        base = dict(
            vocab_size=128, hidden_size=48, num_hidden_layers=8,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            layer_types=(("sliding_attention",) * 3
                         + ("full_attention",)) * 2,
            sliding_window=16,
            rope_parameters={
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                    "original_max_position_embeddings": 32, "beta_fast": 32,
                    "beta_slow": 1, "attention_factor": 1.1386294361119891},
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": 10000.0}},
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=24,
            max_position_embeddings=512, dtype=jnp.float32,
            param_dtype=jnp.float32)
        base.update(over)
        return WindowMoeConfig(**base)


def init_params(key: jax.Array, cfg: WindowMoeConfig,
                std: float = 0.02) -> Dict[str, Any]:
    """Random parameters in the served layout: `layers` stacked on a
    leading axis in layer order, the held experts' matrices among them."""
    D, H, KV, hd = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    n, F, dt = cfg.experts_count, cfg.moe_intermediate_size, cfg.param_dtype

    def nrm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    def layer(k):
        k = jax.random.split(k, 8)
        return {"input_layernorm": jnp.ones((D,), dt),
                "q_proj": nrm(k[0], (D, H * hd)),
                "k_proj": nrm(k[1], (D, KV * hd)),
                "v_proj": nrm(k[2], (D, KV * hd)),
                "o_proj": nrm(k[3], (H * hd, D)),
                "post_attention_layernorm": jnp.ones((D,), dt),
                "router": nrm(k[4], (D, cfg.num_experts)),
                "experts_gate": nrm(k[5], (n, D, F)),
                "experts_up": nrm(k[6], (n, D, F)),
                "experts_down": nrm(k[7], (n, F, D))}

    ko, kl = jax.random.split(key)
    ko = jax.random.split(ko, 2)
    return {"embed_tokens": nrm(ko[0], (cfg.vocab_size, D)),
            "norm": jnp.ones((D,), dt),
            "lm_head": nrm(ko[1], (D, cfg.vocab_size)),
            "layers": jax.vmap(layer)(
                jax.random.split(kl, cfg.num_hidden_layers))}
