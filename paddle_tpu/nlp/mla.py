"""Multi-head latent attention (MLA) with a sparse-expert decoder around it:
the DeepSeek-V2/V3 layer (Liu et al. 2024), served through the paged path.

Per token x, `h = RMSNorm(x)`:

  c_q = RMSNorm(h W_dq);  [q_nope | q_rope] = c_q W_uq    per head (128 | 64)
  [c | k_r] = h W_dkv;    c = RMSNorm(c);  k_r = RoPE(k_r) one for all heads
  [k_nope | v] = c W_ukv                                  per head (128 | 128)
  score = (q_nope . k_nope + RoPE(q_rope) . k_r) * s, causal softmax, o = P v

The cache holds one row `[c | k_r]` per token and layer (normalised and
rotated): `kv_lora_rank + qk_rope_head_dim` columns where per-head K and V
would be `heads * (qk + v)`. Two forms compute the same attention:

  * EXPANDED (a cold prefill): k_nope and v are made from c and the flash
    kernel runs over per-head q, k (192 wide) and v (128);
  * ABSORBED (everything that reads through the block table):
    `q_lat = q_nope W_uk^T` (heads x 512), `score = (q_lat . c + q_rope .
    k_r) * s`, `o_lat = P c`, `o = o_lat W_uv`: one KV head, keys 576
    wide, values the first 512 columns of the same row, read once.

RoPE is the repo's rotate-half layout (`kernels.rope.apply_rope_half`);
the published interleaved layout differs by a fixed permutation of the
rope columns of W_uq and W_dkv. Frequencies are YaRN's where the
configuration scales its context (`kernels.rope.yarn_freqs`), and the
softmax scale carries YaRN's temperature squared.

The decoder: `first_k_dense` leading layers with a dense gated-SiLU MLP,
then layers whose FFN is `moe.expert_share_ffn` (sigmoid top-k router over
ALL routed experts, the experts held here, one shared expert). The served
path (nlp/paged.py) and the trained one (nlp/mla_train.py, which adds the
multi-stream residual path of nlp/hyper.py and a multi-token-prediction
module) run the SAME projections, rotation, YaRN tables and expanded
attention from here; `attention` is the sublayer as `jax.grad` takes it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..kernels.rms_norm import rms_norm_ref
from ..kernels.rope import (apply_rope_half, rope_freqs, yarn_freqs,
                            yarn_mscale)
from .generation import _wq


@dataclasses.dataclass
class MlaMoeConfig:
    """An MLA + sparse-expert decoder as one chip serves it. The router
    keeps its published width (`n_routed_experts`) and experts per token;
    `experts_first` / `experts_count` say which routed experts are HELD
    here (None = all): the layer routes over all of them and computes its
    own experts' part, what absent experts would add is left out."""
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632        # the leading dense layers' MLP
    moe_intermediate_size: int = 704     # one expert's MLP
    num_hidden_layers: int = 4
    first_k_dense_replace: int = 1
    num_attention_heads: int = 16
    q_lora_rank: int = 512
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64
    n_routed_experts: int = 16
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"        # the router's scores (moe.ROUTERS)
    experts_first: int = 0
    experts_count: Optional[int] = None
    # streams of the residual path (nlp/hyper.py); the served decoder runs
    # one and refuses more (paged._refuse_latent)
    hc_mult: int = 1
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # {"factor", "original_max_position_embeddings", "beta_fast",
    #  "beta_slow", "mscale", "mscale_all_dim"} or None (plain RoPE)
    rope_scaling: Optional[Dict[str, Any]] = None
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.experts_count is None:
            self.experts_count = self.n_routed_experts - self.experts_first
        if not (0 <= self.experts_first and self.experts_count >= 1
                and self.experts_first + self.experts_count
                <= self.n_routed_experts):
            raise ValueError(
                f"held experts [{self.experts_first}, "
                f"{self.experts_first + self.experts_count}) lie outside "
                f"the router's {self.n_routed_experts}")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace exceeds the depth")
        if self.tie_word_embeddings:
            raise ValueError("MlaMoeConfig: the head is untied")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kv_row_width(self) -> int:
        """Columns of one cached row: the latent and the shared rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        """qk_head_dim^-0.5, times YaRN's m(factor, mscale_all_dim)^2 where
        the context is scaled."""
        s = self.qk_head_dim ** -0.5
        rs = self.rope_scaling
        if rs and rs.get("mscale_all_dim"):
            s *= yarn_mscale(float(rs["factor"]),
                             float(rs["mscale_all_dim"])) ** 2
        return s

    def rope_tables(self, max_seq: int):
        rs = self.rope_scaling
        if not rs:
            return rope_freqs(self.qk_rope_head_dim, max_seq,
                              self.rope_theta, jnp.float32)
        return yarn_freqs(
            self.qk_rope_head_dim, max_seq, self.rope_theta,
            float(rs["factor"]),
            int(rs["original_max_position_embeddings"]),
            float(rs.get("beta_fast", 32.0)), float(rs.get("beta_slow", 1.0)),
            float(rs.get("mscale", 1.0)),
            float(rs.get("mscale_all_dim", 0.0)))

    @staticmethod
    def tiny(**over) -> "MlaMoeConfig":
        """Test-sized: every mechanism present, nothing wide."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                    moe_intermediate_size=32, num_hidden_layers=3,
                    first_k_dense_replace=1, num_attention_heads=4,
                    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
                    num_experts_per_tok=4, routed_scaling_factor=2.5,
                    max_position_embeddings=256,
                    rope_scaling={"factor": 4.0, "beta_fast": 32,
                                  "beta_slow": 1, "mscale": 1.0,
                                  "mscale_all_dim": 1.0,
                                  "original_max_position_embeddings": 64},
                    dtype=jnp.float32, param_dtype=jnp.float32)
        base.update(over)
        return MlaMoeConfig(**base)


def init_params(key: jax.Array, cfg: MlaMoeConfig,
                std: float = 0.02) -> Dict[str, Any]:
    """Random parameters in the served layout: `dense_layers` and
    `moe_layers`, each stacked on a leading axis."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    R, Q = cfg.kv_lora_rank, cfg.q_lora_rank
    dt = cfg.param_dtype

    def nrm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    def attn(k):
        k = jax.random.split(k, 5)
        return {
            "input_layernorm": jnp.ones((D,), dt),
            "q_a_proj": nrm(k[0], (D, Q)),
            "q_a_layernorm": jnp.ones((Q,), dt),
            "q_b_proj": nrm(k[1], (Q, H * cfg.qk_head_dim)),
            "kv_a_proj_with_mqa": nrm(k[2], (D, cfg.kv_row_width)),
            "kv_a_layernorm": jnp.ones((R,), dt),
            "kv_b_proj": nrm(k[3], (R, H * (cfg.qk_nope_head_dim
                                            + cfg.v_head_dim))),
            "o_proj": nrm(k[4], (H * cfg.v_head_dim, D)),
            "post_attention_layernorm": jnp.ones((D,), dt),
        }

    def mlp(k, F):
        k = jax.random.split(k, 3)
        return {"gate_proj": nrm(k[0], (D, F)), "up_proj": nrm(k[1], (D, F)),
                "down_proj": nrm(k[2], (F, D))}

    def dense_layer(k):
        ka, km = jax.random.split(k)
        return {**attn(ka), **mlp(km, cfg.intermediate_size)}

    def moe_layer(k):
        ka, ks, kr, ke = jax.random.split(k, 4)
        n, Fm = cfg.experts_count, cfg.moe_intermediate_size
        ke = jax.random.split(ke, 3)
        return {**attn(ka), **mlp(ks, Fm * cfg.n_shared_experts),
                "router": nrm(kr, (D, cfg.n_routed_experts)),
                "experts_gate": nrm(ke[0], (n, D, Fm)),
                "experts_up": nrm(ke[1], (n, D, Fm)),
                "experts_down": nrm(ke[2], (n, Fm, D))}

    ko, kd, km = jax.random.split(key, 3)
    ko = jax.random.split(ko, 2)
    return {
        "embed_tokens": nrm(ko[0], (cfg.vocab_size, D)),
        "norm": jnp.ones((D,), dt),
        "lm_head": nrm(ko[1], (D, cfg.vocab_size)),
        "dense_layers": jax.vmap(dense_layer)(
            jax.random.split(kd, cfg.first_k_dense_replace)),
        "moe_layers": jax.vmap(moe_layer)(
            jax.random.split(km, cfg.num_moe_layers)),
    }


# ---------------------------------------------------------------------------
# the attention sublayer, in the pieces the paged layer stack composes
# ---------------------------------------------------------------------------

def project_q(h, lp, cfg: MlaMoeConfig):
    """h [..., D] -> q [..., H, qk_head_dim], nope columns first, the rope
    columns not yet rotated."""
    cd = cfg.dtype
    cq = rms_norm_ref(h @ _wq(lp, "q_a_proj", cd), lp["q_a_layernorm"],
                      cfg.rms_norm_eps)
    q = cq.astype(cd) @ _wq(lp, "q_b_proj", cd)
    return q.reshape(*h.shape[:-1], cfg.num_attention_heads, cfg.qk_head_dim)


def project_latent(h, lp, cfg: MlaMoeConfig):
    """h [..., D] -> (c [..., R] normalised, k_r [..., rope] not yet
    rotated)."""
    cd = cfg.dtype
    ckv = h @ _wq(lp, "kv_a_proj_with_mqa", cd)
    c = rms_norm_ref(ckv[..., :cfg.kv_lora_rank], lp["kv_a_layernorm"],
                     cfg.rms_norm_eps).astype(cd)
    return c, ckv[..., cfg.kv_lora_rank:]


def rotate(q, k_r, cos, sin, positions, cfg: MlaMoeConfig):
    """RoPE on q's rope columns [G, P, H, nope+rope] and on the shared
    rope key [G, P, rope] at `positions` [G, P]."""
    n = cfg.qk_nope_head_dim
    q_r, k_r = apply_rope_half(q[..., n:], k_r[:, :, None, :], cos, sin,
                               positions)
    return jnp.concatenate([q[..., :n], q_r], -1), k_r[:, :, 0, :]


def _kv_up(lp, cfg: MlaMoeConfig):
    """kv_b_proj as (W_uk [R, H, nope], W_uv [R, H, v])."""
    w = _wq(lp, "kv_b_proj", cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def attend_expanded(q, row, lp, cfg: MlaMoeConfig):
    """Causal self-attention of one row group over ITS OWN tokens, per
    head: q [G, P, H, nope+rope] (rotated), row [G, P, R+rope] (the rows
    the pool holds). Returns [G, P, H*v]."""
    from ..kernels import flash_attention as fa
    G, P, H, _ = q.shape
    w_uk, w_uv = _kv_up(lp, cfg)
    c, k_r = row[..., :cfg.kv_lora_rank], row[..., cfg.kv_lora_rank:]
    k_nope = jnp.einsum("gpr,rhn->gphn", c, w_uk)
    v = jnp.einsum("gpr,rhv->gphv", c, w_uv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, :, None, :],
                                  (G, P, H, cfg.qk_rope_head_dim))], -1)
    # the flash kernels, forward and backward, take one head size: v rides
    # zero-padded to q's (a third of the P.V, dP and dV work at 192 / 128
    # is padding; the rooflines count the useful work only)
    pad = cfg.qk_head_dim - cfg.v_head_dim
    if pad > 0:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
    o = fa.flash_attention_fwd(q, k.astype(q.dtype), v.astype(q.dtype),
                               True, cfg.softmax_scale)
    return o[..., :cfg.v_head_dim].reshape(G, P, H * cfg.v_head_dim)


def attention(h, lp, cfg: MlaMoeConfig, cos, sin):
    """The attention sublayer as a training step runs it: h [B, S, D]
    (normalised) -> [B, S, D], every row a whole sequence from position 0,
    the EXPANDED form (no cache), on the projections, rotation and flash
    call the server's cold prefill makes; differentiable throughout."""
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    with jax.named_scope("mla_q"):
        q = project_q(h, lp, cfg)
    with jax.named_scope("mla_kv_latent"):
        c, k_r = project_latent(h, lp, cfg)
        q, k_r = rotate(q, k_r, cos, sin, positions, cfg)
        row = jnp.concatenate([c, k_r.astype(c.dtype)], -1)
    with jax.named_scope("attn_kernel"):
        o = attend_expanded(q, row, lp, cfg)
    with jax.named_scope("attn_out"):
        return o @ _wq(lp, "o_proj", cfg.dtype)


def absorb_q(q, lp, cfg: MlaMoeConfig):
    """q [G, P, H, nope+rope] -> [G, P, H, R+rope]: the nope columns
    carried into the latent space (`q_nope W_uk^T`), the rope columns as
    they are, in the order of a cached row."""
    w_uk, _ = _kv_up(lp, cfg)
    n = cfg.qk_nope_head_dim
    q_lat = jnp.einsum("gphn,rhn->gphr", q[..., :n], w_uk)
    return jnp.concatenate([q_lat.astype(q.dtype), q[..., n:]], -1)


def unabsorb_o(o_lat, lp, cfg: MlaMoeConfig):
    """o_lat [G, P, H, R] -> [G, P, H*v] (`o_lat W_uv`)."""
    _, w_uv = _kv_up(lp, cfg)
    o = jnp.einsum("gphr,rhv->gphv", o_lat, w_uv)
    return o.reshape(*o.shape[:2], -1).astype(o_lat.dtype)


def latent_paged_attention_xla(q, pool, table, positions, scale: float,
                               v_width: int):
    """The reference of the latent kernel: q [B, P, H, W] against pool
    rows [N, bs, W] gathered through the table at full table width;
    query p sees keys at positions j <= positions[b, p]; values are the
    first `v_width` columns of the same rows. Returns [B, P, H, v_width]
    (padded rows compute never-read garbage, as the GQA gather does)."""
    B = q.shape[0]
    N, bs, W = pool.shape
    M = table.shape[1]
    kv = pool[jnp.clip(table, 0)].reshape(B, M * bs, W)
    s = jnp.einsum("bphw,btw->bhpt", q, kv,
                   preferred_element_type=jnp.float32) * scale
    vis = (jnp.arange(M * bs)[None, None, :] <= positions[:, :, None]
           )[:, None]
    p = jax.nn.softmax(jnp.where(vis, s, -1e30), axis=-1)
    o = jnp.einsum("bhpt,btv->bphv", p, kv[..., :v_width],
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def latent_work_list(positions, valid, table_width: int, block_size: int,
                     impl: str = "xla"):
    """What the kernel's grid walks for one row group (`positions`,
    `valid` [G, P]): its list of live (row, query tile, chunk) items
    (`ragged_attention.attn_work_list`), the same for every layer of a
    forward, so built once and handed to each layer's
    `latent_paged_attention`. None for the gather reference, which walks
    no grid."""
    if impl != "pallas":
        return None
    from .ragged_attention import attn_work_list
    return attn_work_list(positions, valid, block_size=block_size,
                          table_width=table_width)


def latent_paged_attention(q, pool, table, positions, valid, cfg,
                           impl: str = "xla", work=None):
    """The absorbed form's attention over the latent pool, by backend:
    "xla" the gather reference above, "pallas" the kernel
    (`ragged_attention.mla_paged_attention`; `work`: its list from
    `latent_work_list`, built here if None)."""
    if impl == "pallas":
        from .ragged_attention import mla_paged_attention
        return mla_paged_attention(q, pool, table, positions, valid,
                                   scale=cfg.softmax_scale,
                                   v_width=cfg.kv_lora_rank, work=work)
    return latent_paged_attention_xla(q, pool, table, positions,
                                      cfg.softmax_scale, cfg.kv_lora_rank)


def kv_block_bytes(cfg: MlaMoeConfig, block_size: int) -> int:
    """HBM bytes one pool block holds over all layers: one row of
    `kv_row_width` a token and layer, no V pool, no scales."""
    return (cfg.num_hidden_layers * block_size * cfg.kv_row_width
            * jnp.dtype(cfg.dtype).itemsize)
