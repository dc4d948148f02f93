"""DiT (Diffusion Transformer) — the BASELINE 'DiT/SD3' workload (config 3).

Reference analog: PaddleMIX's DiT implementation (facebookresearch DiT
architecture: patchify → AdaLN-Zero transformer blocks conditioned on
timestep+class embeddings → unpatchify; out-of-repo domain suite —
SURVEY.md §1 Lx row, §0 provenance).

TPU-native design (mirrors nlp/llama.py): functional params pytree, blocks
stacked on [L] and scanned, `param_specs` TP/FSDP table, bf16 compute /
f32 params. The conv+attention mix this workload exercises (SURVEY.md §7 M7
gate) comes from the patch-embed conv plus full self-attention blocks.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass
class DiTConfig:
    image_size: int = 32            # latent spatial size
    patch_size: int = 2
    in_channels: int = 4
    hidden_size: int = 1152
    depth: int = 28
    num_heads: int = 16
    mlp_ratio: float = 4.0
    num_classes: int = 1000
    class_dropout_prob: float = 0.1
    learn_sigma: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def out_channels(self) -> int:
        return self.in_channels * (2 if self.learn_sigma else 1)

    @staticmethod
    def tiny(**over) -> "DiTConfig":
        base = dict(image_size=8, patch_size=2, in_channels=4,
                    hidden_size=64, depth=2, num_heads=4, num_classes=10)
        base.update(over)
        return DiTConfig(**base)

    @staticmethod
    def dit_xl_2(**over) -> "DiTConfig":
        base = dict(patch_size=2, hidden_size=1152, depth=28, num_heads=16)
        base.update(over)
        return DiTConfig(**base)


def init_params(key: jax.Array, cfg: DiTConfig) -> Dict[str, Any]:
    D, L = cfg.hidden_size, cfg.depth
    F = int(D * cfg.mlp_ratio)
    pc = cfg.patch_size * cfg.patch_size * cfg.in_channels
    pd = cfg.param_dtype
    ks = jax.random.split(key, 12)

    def norm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pd)

    return {
        "patch_embed_w": norm(ks[0], (pc, D)),
        "patch_embed_b": jnp.zeros((D,), pd),
        "pos_embed": norm(ks[1], (cfg.n_patches, D)),
        # timestep MLP (sinusoidal input dim 256 → D → D)
        "t_mlp1_w": norm(ks[2], (256, D)),
        "t_mlp1_b": jnp.zeros((D,), pd),
        "t_mlp2_w": norm(ks[3], (D, D)),
        "t_mlp2_b": jnp.zeros((D,), pd),
        # class embedding (+1 slot for classifier-free null label)
        "label_embed": norm(ks[4], (cfg.num_classes + 1, D)),
        "blocks": {
            # AdaLN-Zero: 6 modulation params per block from conditioning;
            # zero-init so each block starts as identity (DiT recipe)
            "ada_w": jnp.zeros((L, D, 6 * D), pd),
            "ada_b": jnp.zeros((L, 6 * D), pd),
            "qkv_w": norm(ks[5], (L, D, 3 * D)),
            "qkv_b": jnp.zeros((L, 3 * D), pd),
            "proj_w": norm(ks[6], (L, D, D)),
            "proj_b": jnp.zeros((L, D), pd),
            "mlp_in_w": norm(ks[7], (L, D, F)),
            "mlp_in_b": jnp.zeros((L, F), pd),
            "mlp_out_w": norm(ks[8], (L, F, D)),
            "mlp_out_b": jnp.zeros((L, D), pd),
        },
        "final_ada_w": jnp.zeros((D, 2 * D), pd),
        "final_ada_b": jnp.zeros((2 * D,), pd),
        "final_w": jnp.zeros(
            (D, cfg.patch_size * cfg.patch_size * cfg.out_channels), pd),
        "final_b": jnp.zeros(
            (cfg.patch_size * cfg.patch_size * cfg.out_channels,), pd),
    }


def param_specs(cfg: DiTConfig) -> Dict[str, Any]:
    return {
        "patch_embed_w": P("sharding", "mp"),
        "patch_embed_b": P("mp"),
        "pos_embed": P(None, "sharding"),
        "t_mlp1_w": P("sharding", "mp"),
        "t_mlp1_b": P("mp"),
        "t_mlp2_w": P("mp", "sharding"),
        "t_mlp2_b": P(None),
        "label_embed": P(None, "sharding"),
        "blocks": {
            "ada_w": P(None, "sharding", "mp"),
            "ada_b": P(None, "mp"),
            "qkv_w": P(None, "sharding", "mp"),
            "qkv_b": P(None, "mp"),
            "proj_w": P(None, "mp", "sharding"),
            "proj_b": P(None, None),
            "mlp_in_w": P(None, "sharding", "mp"),
            "mlp_in_b": P(None, "mp"),
            "mlp_out_w": P(None, "mp", "sharding"),
            "mlp_out_b": P(None, None),
        },
        "final_ada_w": P("sharding", "mp"),
        "final_ada_b": P("mp"),
        "final_w": P("sharding", None),
        "final_b": P(None),
    }


def batch_spec() -> P:
    """Latent batch [B, C, H, W] sharded over the data axes."""
    return P(("dp", "sharding"), None, None, None)


def timestep_embedding(t, dim=256, max_period=10000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) *
                    jnp.arange(half, dtype=jnp.float32) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def _modulate(x, shift, scale):
    return x * (1 + scale[:, None]) + shift[:, None]


def _ln(x):  # elementwise-affine-free LN (DiT uses affine in modulation)
    # plain jnp on purpose: the fused layer_norm_train kernel measured
    # neutral here (adaLN cost is in the modulate chains, not the norm)
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype)


def _block(x, c, bp, cfg: DiTConfig):
    dt = cfg.dtype
    B, N, D = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    mods = c @ bp["ada_w"].astype(dt) + bp["ada_b"].astype(dt)
    (sh_a, sc_a, g_a, sh_m, sc_m, g_m) = jnp.split(mods, 6, axis=-1)
    h = _modulate(_ln(x), sh_a, sc_a)
    # einsum-form head-major attention + the non-causal flash kernel in
    # layout='bhsd' (r5; +3.3pt MFU over r4's exact path at batch 96).
    # The r4 flash experiment measured -1pt — but that was flash ALONE
    # with bshd relayouts; einsum-only was also ~-0.5pt. Only the
    # combination wins: projections write head-major directly and the
    # custom-call folds [B,H,N,hd] for free, so the [B,H,N,N] f32 score
    # traffic disappears without adding relayout copies. The fused qkv_w
    # keeps upstream DiT's [D, 3D] shape; its (D,3,H,hd) view means mp
    # sharding does not propagate THROUGH the reshape (leading factor 3)
    # — GSPMD inserts a reshard instead, acceptable for this domain
    # model (TP serving of DiT is not a BASELINE config).
    wqkv = bp["qkv_w"].astype(dt).reshape(D, 3, H, hd)
    bqkv = bp["qkv_b"].astype(dt).reshape(3, H, hd)
    q, k, v = [jnp.einsum("bnd,dhe->bhne", h, wqkv[:, i]) +
               bqkv[i][None, :, None, :] for i in range(3)]
    from ..kernels import flash_attention as fa
    ctx = fa.flash_attention_fwd(q, k, v, False, None, "bhsd")
    ctx = jnp.einsum("bhne,hed->bnd", ctx,
                     bp["proj_w"].astype(dt).reshape(H, hd, D))
    x = x + g_a[:, None] * (ctx + bp["proj_b"].astype(dt))
    h = _modulate(_ln(x), sh_m, sc_m)
    h = jax.nn.gelu(h @ bp["mlp_in_w"].astype(dt) +
                    bp["mlp_in_b"].astype(dt), approximate=True)
    h = h @ bp["mlp_out_w"].astype(dt) + bp["mlp_out_b"].astype(dt)
    return x + g_m[:, None] * h


def patchify(x, cfg: DiTConfig):
    """[B, C, H, W] → [B, N, p*p*C]."""
    B, C, H, W = x.shape
    p = cfg.patch_size
    x = x.reshape(B, C, H // p, p, W // p, p)
    x = x.transpose(0, 2, 4, 3, 5, 1)  # B, H/p, W/p, p, p, C
    return x.reshape(B, (H // p) * (W // p), p * p * C)


def unpatchify(x, cfg: DiTConfig):
    B, N, _ = x.shape
    p, c = cfg.patch_size, cfg.out_channels
    g = int(math.sqrt(N))
    x = x.reshape(B, g, g, p, p, c).transpose(0, 5, 1, 3, 2, 4)
    return x.reshape(B, c, g * p, g * p)


def forward(params, x, t, y, cfg: DiTConfig):
    """x: [B, C, H, W] noisy latents; t: [B] timesteps; y: [B] labels
    (num_classes = null token). → [B, out_channels, H, W]."""
    dt = cfg.dtype
    h = patchify(x.astype(dt), cfg)
    h = h @ params["patch_embed_w"].astype(dt) + \
        params["patch_embed_b"].astype(dt)
    h = h + params["pos_embed"].astype(dt)[None]
    temb = timestep_embedding(t).astype(dt)
    temb = jax.nn.silu(temb @ params["t_mlp1_w"].astype(dt) +
                       params["t_mlp1_b"].astype(dt))
    temb = temb @ params["t_mlp2_w"].astype(dt) + \
        params["t_mlp2_b"].astype(dt)
    c = jax.nn.silu(temb + params["label_embed"][y].astype(dt))

    def body(carry, bp):
        fn = _block
        if cfg.remat:
            fn = jax.checkpoint(fn, static_argnums=(3,))
        return fn(carry, c, bp, cfg), None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    sh, sc = jnp.split(
        c @ params["final_ada_w"].astype(dt) +
        params["final_ada_b"].astype(dt), 2, axis=-1)
    h = _modulate(_ln(h), sh, sc)
    h = h @ params["final_w"].astype(dt) + params["final_b"].astype(dt)
    return unpatchify(h, cfg)


def diffusion_loss(params, key, x0, y, cfg: DiTConfig, n_timesteps=1000):
    """Simple DDPM epsilon-prediction MSE (the DiT training objective).
    Linear beta schedule; sigma channels (learn_sigma) are ignored in the
    loss like the reference's 'simple' loss term."""
    kb, kt, ke = jax.random.split(key, 3)
    B = x0.shape[0]
    t = jax.random.randint(kt, (B,), 0, n_timesteps)
    betas = jnp.linspace(1e-4, 0.02, n_timesteps, dtype=jnp.float32)
    alphas_bar = jnp.cumprod(1.0 - betas)
    ab = alphas_bar[t][:, None, None, None]
    eps = jax.random.normal(ke, x0.shape, jnp.float32)
    xt = jnp.sqrt(ab) * x0 + jnp.sqrt(1.0 - ab) * eps
    # classifier-free guidance dropout → null label
    drop = jax.random.bernoulli(kb, cfg.class_dropout_prob, (B,))
    y = jnp.where(drop, cfg.num_classes, y)
    pred = forward(params, xt, t, y, cfg).astype(jnp.float32)
    pred_eps = pred[:, :cfg.in_channels]
    return jnp.mean((pred_eps - eps) ** 2)
