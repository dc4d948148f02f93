"""A GQA decoder whose layers are of two KINDS, sliding-window and full
attention interleaved in a fixed period, each with its own rotary
embedding, and whose FFN is a sparse-expert layer with a softmax router:
the Mellum 2 layer, and, with what a configuration may declare beside it
(head counts a kind, a per-head output gate, a rotary share a kind, leading
dense layers, a shared expert, a routed scale, a held share of the
experts), the Laguna-S-2.1 layer. Served through the paged path
(`nlp/paged.py`): this file holds the configuration and the parameters'
layout, nothing of the forward.

Mellum 2. Per token x of layer l, kind t_l (`layer_types[l]`):

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

Laguna-S-2.1. Per token x of layer l, kind t, H_t =
`num_attention_heads_per_layer[l]` (48 full, 72 window), KV = 8, hd = 128:

  h  = RMSNorm(x)
  q  = h Wq_t  [H_t, hd];   k, v = h Wk, h Wv  [KV, hd]
  q, k = RoPE_t(q, k)       full:   YaRN, cos and sin times attention_factor,
                                    over the FIRST r = partial_rotary_factor
                                    * hd dims of a head (rotate-half inside
                                    them), dims r.. pass through
                            window: plain, all hd dims
  s_ij = q_i . k_j / sqrt(hd), visible iff j <= i and (t full or j > i - W)
  a_n  = softmax(s)_n v_{n // (H_t / KV)}
  g    = sigmoid(h Wg_t)  [H_t]          one gate a head, from the normed input
  x   += concat_n(g_n a_n) Wo_t
  h'   = RMSNorm(x)
  l in mlp_only_layers:  x += (silu(h' Wgate) * h' Wup) Wdown      (`intermediate_size`)
  else:  p = softmax(h' Wr) in float32 over ALL experts;  S = top-k(p)
         w_e = p_e / sum_S p
         x += scale * sum_{e in S, e held here} w_e E_e(h')  +  E_shared(h')

What the published configuration does not pin is read as its keys' family
reads it (`assumed` in `benchmark/configs/laguna-s-ep4.json`, each with its
reason): the gate is the head-wise sigmoid gate after attention and before
`o_proj`, from the layer's normed input (arXiv:2505.06708); the router's
scores are a softmax (the Qwen-MoE keys, `norm_topk_prob`, no scoring or
bias key); the scale multiplies the renormalised gates; the shared expert
is ungated and counted once; no QK norm; rotate-half layout; the window is
W keys with the query's own.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.rope import rope_freqs, yarn_freqs

KINDS = {"full_attention": "full", "sliding_attention": "window"}
NAMES = {kind: name for name, kind in KINDS.items()}


@dataclasses.dataclass
class WindowMoeConfig:
    """`head_dim` is a field (H * hd need not be the hidden size);
    `layer_types` the published names a layer, `rope_parameters` one entry
    a name (with `partial_rotary_factor`: the share of a head's dims that
    kind rotates); `experts_first` / `experts_count` say which routed
    experts are held here (None = all), as in `mla.MlaMoeConfig`.
    `num_attention_heads_per_layer` gives a layer's query heads where the
    kinds differ (one count a kind; `num_attention_heads` otherwise);
    `attention_gate` "per_head" a sigmoid gate a head on the attention's
    output; `mlp_only_layers` the LEADING layers whose FFN is a dense MLP
    `intermediate_size` wide; `n_shared_experts` a shared expert
    `shared_expert_intermediate_size` wide beside the routed ones."""
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
    shared_expert_intermediate_size: Optional[int] = None
    experts_first: int = 0
    experts_count: Optional[int] = None
    num_attention_heads_per_layer: Optional[Tuple[int, ...]] = None
    attention_gate: Optional[str] = None
    mlp_only_layers: Tuple[int, ...] = ()
    intermediate_size: int = 0
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
            r = self.rotary_dim(KINDS[t])
            if not 0 < r <= self.head_dim or r % 2:
                raise ValueError(f"partial_rotary_factor of {t}: an even "
                                 f"number of a head's {self.head_dim} dims")
        if self.experts_count is None:
            self.experts_count = self.num_experts - self.experts_first
        if not (0 <= self.experts_first and self.experts_count >= 1
                and self.experts_first + self.experts_count
                <= self.num_experts):
            raise ValueError("held experts lie outside the router's width")
        self.mlp_only_layers = tuple(self.mlp_only_layers)
        if self.mlp_only_layers != tuple(range(len(self.mlp_only_layers))) \
                or len(self.mlp_only_layers) >= L:
            raise ValueError("mlp_only_layers: the LEADING layers, and an "
                             "expert layer after them")
        if self.mlp_only_layers and self.intermediate_size < 1:
            raise ValueError("mlp_only_layers need intermediate_size")
        if self.num_attention_heads_per_layer is not None:
            per = tuple(self.num_attention_heads_per_layer)
            self.num_attention_heads_per_layer = per
            if len(per) != L or any(
                    len({h for h, t in zip(per, self.layer_types)
                         if t == name}) > 1 for name in KINDS):
                raise ValueError("num_attention_heads_per_layer: one count "
                                 "for each layer, the same within a kind")
        if any(self.heads(KINDS[t]) % self.num_key_value_heads
               for t in set(self.layer_types)):
            raise ValueError("query heads must divide over the KV heads")
        if self.attention_gate not in (None, "per_head"):
            raise ValueError(f"attention_gate {self.attention_gate!r}: "
                             f"None or per_head")
        if self.n_shared_experts not in (0, 1) or self.tie_word_embeddings:
            raise ValueError("WindowMoeConfig: at most one shared expert, "
                             "untied")
        if self.shared_expert_intermediate_size is None:
            self.shared_expert_intermediate_size = \
                self.moe_intermediate_size * self.n_shared_experts

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Every layer's kind ("window" | "full"), in layer order."""
        return tuple(KINDS[t] for t in self.layer_types)

    @property
    def lead_kinds(self) -> Tuple[str, ...]:
        """The kinds of the leading dense layers (`mlp_only_layers`): a
        layer group of their own before the periods."""
        return self.layer_kinds[:len(self.mlp_only_layers)]

    @property
    def period_kinds(self) -> Tuple[str, ...]:
        """The kinds ("window" | "full") of one period of the layer
        pattern after the leading dense layers: the shortest prefix that
        the rest of the stack repeats. The paged forward scans whole
        periods, one compiled body."""
        kinds = self.layer_kinds[len(self.mlp_only_layers):]
        L = len(kinds)
        for p in range(1, L + 1):
            if L % p == 0 and kinds == kinds[:p] * (L // p):
                return kinds[:p]
        raise AssertionError("unreachable: p = L always repeats")

    def heads(self, kind: str) -> int:
        """Query heads of a layer of `kind`."""
        per = self.num_attention_heads_per_layer or ()
        return next((h for h, k in zip(per, self.layer_kinds) if k == kind),
                    self.num_attention_heads)

    @property
    def heads_by_kind(self) -> bool:
        """Whether the kinds' attention matrices differ in SHAPE: the
        parameters then hold them stacked by kind (`init_params`)."""
        return len({self.heads(k) for k in set(self.layer_kinds)}) > 1

    def rotary_dim(self, kind: str) -> int:
        """The leading dims of a head that a layer of `kind` rotates."""
        share = self.rope_parameters.get(NAMES[kind], {}).get(
            "partial_rotary_factor", 1.0)
        return int(round(self.head_dim * float(share)))

    def rope_tables(self, max_seq: int) -> Dict[str, Tuple[jax.Array,
                                                           jax.Array]]:
        """kind -> (cos, sin) [max_seq, r/2], float32, r the kind's
        `rotary_dim`: each kind's own frequencies over the dims it
        rotates; YaRN's cos and sin carry `attention_factor` where the
        configuration gives one, m(factor) otherwise."""
        out = {}
        for name, kind in KINDS.items():
            rp = self.rope_parameters.get(name)
            if rp is None:
                continue
            theta, r = float(rp["rope_theta"]), self.rotary_dim(kind)
            if rp.get("rope_type", "default") == "default":
                out[kind] = rope_freqs(r, max_seq, theta, jnp.float32)
            else:
                out[kind] = yarn_freqs(
                    r, max_seq, theta, float(rp["factor"]),
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


def attention_shapes(cfg: WindowMoeConfig, kind: str) -> Dict[str, Tuple]:
    """The attention matrices whose shape follows a kind's head count."""
    D, Hhd = cfg.hidden_size, cfg.heads(kind) * cfg.head_dim
    out = {"q_proj": (D, Hhd), "o_proj": (Hhd, D)}
    if cfg.attention_gate:
        out["g_proj"] = (D, cfg.heads(kind))
    return out


def init_params(key: jax.Array, cfg: WindowMoeConfig,
                std: float = 0.02) -> Dict[str, Any]:
    """Random parameters in the served layout: `layers` (the expert
    layers) stacked on a leading axis in layer order, the held experts'
    matrices and the shared expert's (`gate_proj`, `up_proj`,
    `down_proj`) among them; `lead_layers` the leading dense layers
    likewise, their MLP under the same three names. Where the kinds'
    head counts differ (`cfg.heads_by_kind`), the matrices that follow
    the head count (`attention_shapes`) are stacked BY KIND, each at its
    own shape, under `attn_full` / `attn_window` of their group: the
    i-th entry is the group's i-th layer of that kind."""
    D, KV, hd = cfg.hidden_size, cfg.num_key_value_heads, cfg.head_dim
    n, F, dt = cfg.experts_count, cfg.moe_intermediate_size, cfg.param_dtype

    def nrm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    def mats(k, shapes):
        return {name: nrm(kk, shape) for kk, (name, shape) in zip(
            jax.random.split(k, len(shapes)), sorted(shapes.items()))}

    def mlp(k, width):
        return mats(k, {"gate_proj": (D, width), "up_proj": (D, width),
                        "down_proj": (width, D)})

    def layer(k, dense: bool):
        k = jax.random.split(k, 8)
        out = {"input_layernorm": jnp.ones((D,), dt),
               "k_proj": nrm(k[1], (D, KV * hd)),
               "v_proj": nrm(k[2], (D, KV * hd)),
               "post_attention_layernorm": jnp.ones((D,), dt)}
        if not cfg.heads_by_kind:
            # (every kind's shapes are the first kind's)
            shapes = attention_shapes(cfg, cfg.layer_kinds[0])
            out.update({"q_proj": nrm(k[0], shapes["q_proj"]),
                        "o_proj": nrm(k[3], shapes["o_proj"])})
            if "g_proj" in shapes:
                out["g_proj"] = nrm(jax.random.fold_in(k[0], 1),
                                    shapes["g_proj"])
        if dense:
            return {**out, **mlp(k[4], cfg.intermediate_size)}
        if cfg.n_shared_experts:
            out.update(mlp(jax.random.fold_in(k[4], 1),
                           cfg.shared_expert_intermediate_size))
        return {**out, "router": nrm(k[4], (D, cfg.num_experts)),
                "experts_gate": nrm(k[5], (n, D, F)),
                "experts_up": nrm(k[6], (n, D, F)),
                "experts_down": nrm(k[7], (n, F, D))}

    def group(k, kinds, dense: bool):
        out = jax.vmap(lambda kk: layer(kk, dense))(
            jax.random.split(k, len(kinds)))
        if cfg.heads_by_kind:
            for kind in sorted(set(kinds)):
                out["attn_" + kind] = jax.vmap(
                    lambda kk: mats(kk, attention_shapes(cfg, kind)))(
                        jax.random.split(jax.random.fold_in(
                            k, 1 + (kind == "full")), kinds.count(kind)))
        return out

    ko, kl = jax.random.split(key)
    ko = jax.random.split(ko, 2)
    lead = len(cfg.mlp_only_layers)
    out = {"embed_tokens": nrm(ko[0], (cfg.vocab_size, D)),
           "norm": jnp.ones((D,), dt),
           "lm_head": nrm(ko[1], (D, cfg.vocab_size)),
           "layers": group(kl, cfg.layer_kinds[lead:], False)}
    if lead:
        out["lead_layers"] = group(jax.random.fold_in(key, 1),
                                   cfg.lead_kinds, True)
    return out
