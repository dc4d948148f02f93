"""Llama-family flagship model — the BASELINE 'Llama-3-8B (TP+DP)' workload.

Reference analog: the PaddleNLP `llm/` Llama recipes the reference's BASELINE
configs point at (out-of-repo, SURVEY.md §1 Lx row; upstream-canonical,
unverified — SURVEY.md §0). The reference builds Llama out of
ColumnParallelLinear/RowParallelLinear mpu layers + fused rope/rms_norm/flash
attention kernels and runs it under fleet hybrid parallelism.

TPU-native design (SURVEY.md §7 M5): a pure-functional transformer whose
params are one pytree; layers are STACKED (leading [L] dim) and the decoder
runs as one `lax.scan` over layer params — one XLA while-loop instead of L
unrolled blocks (compile time O(1) in depth, same MXU schedule). Parallelism
is not code: `param_specs`/`act_specs` return PartitionSpec trees for the
hybrid mesh axes (dp, sharding=FSDP/ZeRO-3, sep=context, mp=tensor) and GSPMD
partitions the one program — the reference's mpu layer zoo collapses into
these tables. Compute in bf16 on the MXU, params/master state in f32,
softmax/loss in f32.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..kernels.flash_attention import (_pallas_available,
                                       flash_attention_fwd,
                                       flash_attention_sharded)
from ..kernels.rms_norm import rms_norm_ref, rms_norm_train
from ..kernels.rope import rope_freqs, apply_rope_half


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32       # < heads → GQA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16           # compute dtype (MXU)
    param_dtype: Any = jnp.float32      # storage dtype (master weights)
    remat: bool = True                  # jax.checkpoint each layer body
    use_flash: bool = True
    # loss path: True routes loss_fn through fused_head_ce (no [B,S,V] f32
    # materialization — frees ~6GB at the 2B bench shape). Default False:
    # the dense 2B single-chip bench measures ~6pt MFU SLOWER through the
    # chunked scan (r4, consistent with r3's chunked-vocab finding); the
    # MoE model uses the fused path unconditionally for the memory headroom.
    fused_ce: bool = False
    # attention schedule: "flash" (single-device / GSPMD-sharded), or the
    # context-parallel schedules over the sep mesh axis — "ring"
    # (ppermute KV rotation, SURVEY.md §2.3 CP row) / "ulysses" (all_to_all
    # head<->seq swap, SEP row). Ignored when mesh is None or sep == 1.
    attn_impl: str = "flash"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny(**over) -> "LlamaConfig":
        """Test/dryrun-sized config."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, max_position_embeddings=128)
        base.update(over)
        return LlamaConfig(**base)

    @staticmethod
    def llama3_8b(**over) -> "LlamaConfig":
        base = dict(vocab_size=128256, hidden_size=4096,
                    intermediate_size=14336, num_hidden_layers=32,
                    num_attention_heads=32, num_key_value_heads=8,
                    max_position_embeddings=8192, rope_theta=500000.0)
        base.update(over)
        return LlamaConfig(**base)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    """Initialize the parameter pytree. Layer weights are stacked on a
    leading [L] axis for the scan. Init matches the reference recipes:
    normal(0, 0.02) for projections/embeddings, ones for norm scales."""
    D, F, V, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_hidden_layers)
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    pd = cfg.param_dtype
    ks = jax.random.split(key, 8)

    def norm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pd)

    params = {
        "embed_tokens": norm(ks[0], (V, D)),
        "layers": {
            "input_layernorm": jnp.ones((L, D), pd),
            "q_proj": norm(ks[1], (L, D, H * hd)),
            "k_proj": norm(ks[2], (L, D, KV * hd)),
            "v_proj": norm(ks[3], (L, D, KV * hd)),
            "o_proj": norm(ks[4], (L, H * hd, D)),
            "post_attention_layernorm": jnp.ones((L, D), pd),
            "gate_proj": norm(ks[5], (L, D, F)),
            "up_proj": norm(ks[6], (L, D, F)),
            "down_proj": norm(ks[7], (L, F, D)),
        },
        "norm": jnp.ones((D,), pd),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm(jax.random.fold_in(key, 99), (D, V))
    return params


def param_specs(cfg: LlamaConfig, pp: bool = False) -> Dict[str, Any]:
    """PartitionSpec tree matching init_params. This table IS the reference's
    TP layer zoo + GroupSharded stage-3 (SURVEY.md §2.3 TP/sharding rows):
      mp       = Megatron TP: qkv/gate/up column-split, o/down row-split,
                 embeddings vocab-split (VocabParallelEmbedding).
      sharding = ZeRO-3/FSDP: the *other* matmul dim, so every big weight is
                 2D-sharded and all-gathers ride ICI.
    Layer stack dim [L]: unsharded when pp=False (it is scanned); sharded
    over 'pp' when pp=True — contiguous L/pp layer blocks per stage, which
    IS the pipeline stage partition (reference: PipelineLayer LayerDesc
    partition-by-layer, SURVEY.md §2.3 PP row)."""
    lspec = "pp" if pp else None
    return {
        "embed_tokens": P("mp", "sharding"),
        "layers": {
            "input_layernorm": P(lspec, None),
            "q_proj": P(lspec, "sharding", "mp"),
            "k_proj": P(lspec, "sharding", "mp"),
            "v_proj": P(lspec, "sharding", "mp"),
            "o_proj": P(lspec, "mp", "sharding"),
            "post_attention_layernorm": P(lspec, None),
            "gate_proj": P(lspec, "sharding", "mp"),
            "up_proj": P(lspec, "sharding", "mp"),
            "down_proj": P(lspec, "mp", "sharding"),
        },
        "norm": P(None),
        "lm_head": P("sharding", "mp"),
    } if not cfg.tie_word_embeddings else {
        "embed_tokens": P("mp", "sharding"),
        "layers": param_specs(
            dataclasses.replace(cfg, tie_word_embeddings=False), pp)["layers"],
        "norm": P(None),
    }


def infer_param_specs(cfg: LlamaConfig) -> Dict[str, Any]:
    """Serving-side PartitionSpec tree: Megatron TP ONLY (mp on the
    head/ffn dims; qkv/gate/up column-split, o/down row-split, lm_head
    vocab-split), everything else replicated. Unlike param_specs there is
    no ZeRO 'sharding' axis — weights must stay resident so decode steps
    insert no per-step param all-gathers (the reference's PaddleNLP llm/
    predict mp>1 layout; SURVEY.md §3.5, VERDICT r2 missing item 1)."""
    specs = {
        "embed_tokens": P(None, None),
        "layers": {
            "input_layernorm": P(None, None),
            "q_proj": P(None, None, "mp"),
            "k_proj": P(None, None, "mp"),
            "v_proj": P(None, None, "mp"),
            "o_proj": P(None, "mp", None),
            "post_attention_layernorm": P(None, None),
            "gate_proj": P(None, None, "mp"),
            "up_proj": P(None, None, "mp"),
            "down_proj": P(None, "mp", None),
        },
        "norm": P(None),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, "mp")
    return specs


def act_spec() -> P:
    """Activation sharding [B, S, D]: batch over (dp, sharding) — ZeRO data
    axes — and sequence over sep (context parallel). Megatron-SP falls out of
    GSPMD: XLA converts the surrounding collectives (SURVEY.md §2.3 SP row)."""
    return P(("dp", "sharding"), "sep", None)


def batch_spec() -> P:
    """Token batch [B, S]."""
    return P(("dp", "sharding"), "sep")


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _attention(x, lp, cfg: LlamaConfig, cos, sin, mesh=None):
    """x: [B,S,D] (compute dtype); lp: this layer's param slice."""
    B, S, D = x.shape
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    cd = cfg.dtype
    q = (x @ lp["q_proj"].astype(cd)).reshape(B, S, H, hd)
    k = (x @ lp["k_proj"].astype(cd)).reshape(B, S, KV, hd)
    v = (x @ lp["v_proj"].astype(cd)).reshape(B, S, KV, hd)
    q, k = apply_rope_half(q, k, cos, sin)
    if (cfg.attn_impl in ("ring", "ulysses") and mesh is not None
            and "sep" in mesh.axis_names and mesh.shape["sep"] > 1):
        from ..kernels.ring_attention import sep_attention
        o = sep_attention(q, k, v, mesh, impl=cfg.attn_impl, causal=True)
    elif cfg.use_flash and mesh is not None and _pallas_available() \
            and not in_manual_axis("pp"):
        # GSPMD cannot partition the Mosaic kernel: run it per
        # (batch, head) shard. Off-TPU meshes keep the global call below
        # (the exact path partitions under plain GSPMD), as _make_norm does
        o = flash_attention_sharded(
            q, k, v, mesh, P(("dp", "sharding"), None, "mp", None))
    elif cfg.use_flash:
        o = flash_attention_fwd(q, k, v, True, None)
    else:
        from .. kernels.flash_attention import mha_ref
        o = mha_ref(q, k, v, causal=True)
    o = o.reshape(B, S, H * hd)
    return o @ lp["o_proj"].astype(cd)


def _mlp(x, lp, cfg: LlamaConfig):
    cd = cfg.dtype
    g = x @ lp["gate_proj"].astype(cd)
    u = x @ lp["up_proj"].astype(cd)
    return (jax.nn.silu(g) * u) @ lp["down_proj"].astype(cd)


def _decoder_layer(x, lp, cfg: LlamaConfig, cos, sin, mesh=None):
    # fused-backward norm everywhere (XLA's autodiff of the ref emits
    # ~7x-slower backward fusions — the round-4 dense-2B profile's
    # largest non-GEMM cost): bare pallas_call on one chip, shard_mapped
    # over the activation shards under a mesh (r5 — previously the mesh
    # path dropped to jnp because pallas is opaque to GSPMD)
    norm = _make_norm(cfg, mesh)
    h = norm(x, lp["input_layernorm"])
    x = x + _attention(h, lp, cfg, cos, sin, mesh)
    h = norm(x, lp["post_attention_layernorm"])
    x = x + _mlp(h, lp, cfg)
    return x


def in_manual_axis(*names) -> bool:
    """True when tracing inside a shard_map MANUAL over any of `names`
    (e.g. the compiled-pipeline stage body, manual over 'pp') — a nested
    shard_map over the remaining auto axes is unsupported there, so the
    mesh-aware fused kernels must fall back to their jnp formulations."""
    manual = jax.sharding.get_abstract_mesh().manual_axes
    return any(n in manual for n in names)


def _make_norm(cfg: LlamaConfig, mesh):
    """RMSNorm closure: single-chip fused kernel, or the shard_mapped
    fused kernel over act_spec shards under a mesh (off-TPU meshes fall
    through to jnp inside the shard, as before). Inside a pipeline
    stage (manual over pp) the jnp path keeps GSPMD partitioning the
    remaining axes."""
    from ..kernels.rms_norm import rms_norm_train_sharded
    if mesh is None:
        return lambda h, w: rms_norm_train(h, w, cfg.rms_norm_eps, True)
    if in_manual_axis("pp") or not _pallas_available():
        # CPU meshes keep the GLOBAL jnp formulation (bit-identical to
        # the mesh=None reference — shard_mapping the same math changes
        # bf16 fusion rounding enough to trip tight parity tests)
        return lambda h, w: rms_norm_train(h, w, cfg.rms_norm_eps, False)
    return lambda h, w: rms_norm_train_sharded(h, w, cfg.rms_norm_eps,
                                               mesh, act_spec())


def _backbone(params, tokens, cfg: LlamaConfig, mesh=None):
    """Embed + decoder stack → pre-norm hidden states [B, S, D].

    The decoder is one lax.scan over the stacked layer params; each body is
    optionally jax.checkpoint-ed (the reference's recompute_sequential,
    SURVEY.md §2.4 recompute row, as a remat policy instead of a PyLayer).
    With a mesh, activations carry sharding constraints (act_spec)."""
    cd = cfg.dtype
    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(cd)
    cos, sin = rope_freqs(cfg.head_dim, tokens.shape[1], cfg.rope_theta, jnp.float32)

    def maybe_constrain(h):
        if mesh is not None:
            from jax.sharding import NamedSharding
            h = jax.lax.with_sharding_constraint(
                h, NamedSharding(mesh, act_spec()))
        return h

    x = maybe_constrain(x)

    def body(h, lp):
        h = _decoder_layer(h, lp, cfg, cos, sin, mesh)
        return maybe_constrain(h), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return x


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig,
            mesh=None) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, V] (f32)."""
    return _final_head(params, _backbone(params, tokens, cfg, mesh), cfg)


def _head_weights(params, cfg: LlamaConfig):
    """The LM head matrix [D, V] — ONE selection point for the tied /
    untied choice (shared by the logits and fused-CE paths)."""
    return (params["embed_tokens"].T if cfg.tie_word_embeddings
            else params["lm_head"])


def _final_head(params, x, cfg: LlamaConfig):
    """Final RMSNorm + LM head: x [B,S,D] → logits [B,S,V] (f32)."""
    cd = cfg.dtype
    x = rms_norm_ref(x, params["norm"], cfg.rms_norm_eps)
    logits = x.astype(cd) @ _head_weights(params, cfg).astype(cd)
    return logits.astype(jnp.float32)


def forward_pp(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig,
               mesh, num_microbatches: int,
               virtual_pp: int = 1) -> jax.Array:
    """Pipeline-parallel forward: the decoder stack runs as a compiled GPipe
    schedule over the mesh's `pp` axis (parallel.pipeline), embed/head stay
    GSPMD (replicated compute over pp, sharded over mp/sharding).

    virtual_pp > 1 selects the interleaved (virtual-pp) circular schedule:
    each device holds virtual_pp non-contiguous layer chunks, shrinking the
    fill/drain bubble by that factor (reference: PipelineParallel's
    interleaved mode). Note the [v, p, L/(v*p)] chunk layout differs from
    param_specs' contiguous-P('pp') blocks, so GSPMD reshards the layer
    stack at entry — init with a matching sharding for production runs.

    Reference analog: PipelineParallel.train_batch's forward half
    (SURVEY.md §3.3) — here the microbatch loop is a lax.scan and the stage
    hops are ppermute, all inside one XLA program."""
    from ..parallel.pipeline import (interleaved, pipelined,
                                     stack_virtual_chunks)

    n, stage_params, stage_fn = _pp_stage_setup(
        params, tokens.shape, cfg, mesh, num_microbatches,
        need_stage_params=(virtual_pp == 1))
    B, S = tokens.shape
    M = num_microbatches
    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(cfg.dtype)
    mb = x.reshape((M, B // M) + x.shape[1:])
    if virtual_pp > 1:
        chunks = stack_virtual_chunks(
            params["layers"], n, virtual_pp, mesh=mesh)
        chunk_fn = interleaved(stage_fn, mesh, v=virtual_pp,
                               remat=cfg.remat)
        outs = chunk_fn(chunks, mb)
    else:
        outs = pipelined(stage_fn, mesh, remat=cfg.remat)(stage_params, mb)
    x = outs.reshape(B, S, -1)
    return _final_head(params, x, cfg)


def _pp_stage_setup(params, tokens_shape, cfg: LlamaConfig, mesh,
                    num_microbatches: int, need_stage_params: bool = True):
    """Shared pipeline-partition plumbing for the GPipe and 1F1B paths:
    validates divisibility, reshapes [L, ...] layer params into
    [n, L/n, ...] stage slices (a LOCAL no-op when layers are sharded
    P('pp') — contiguous blocks, i.e. param_specs(cfg, pp=True), the
    reference's LayerDesc partition-by-layer), and builds the stage body.
    Returns (n_stages, stage_params, stage_fn). The interleaved/virtual-pp
    callers pass need_stage_params=False — they build their own
    [v, p, L/(v·p)] chunk layout and must not pay this reshape (ADVICE r2)."""
    n = mesh.shape["pp"]
    B, S = tokens_shape
    if B % num_microbatches:
        raise ValueError(
            f"batch {B} not divisible by {num_microbatches} microbatches")
    L = cfg.num_hidden_layers
    if L % n:
        raise ValueError(
            f"{L} decoder layers not divisible by pp={n} stages")
    cos, sin = rope_freqs(cfg.head_dim, S, cfg.rope_theta, jnp.float32)
    stage_params = None
    if need_stage_params:
        stage_params = jax.tree.map(
            lambda p: p.reshape((n, L // n) + p.shape[1:]), params["layers"])

    def stage_fn(local_layers, h):
        def body(h, lp):
            return _decoder_layer(h, lp, cfg, cos, sin, mesh), None
        h, _ = jax.lax.scan(body, h, local_layers)
        return h

    return n, stage_params, stage_fn


def _mb_loss(logits, tokens):
    """Per-microbatch next-token loss — same normalization as loss_fn, so
    the mean over microbatches equals the global loss."""
    targets = jnp.roll(tokens, -1, axis=1)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    seq = tokens.shape[1]
    valid = (jnp.arange(seq) < seq - 1).astype(logits.dtype)
    return jnp.sum((logz - gold) * valid[None]) / (
        tokens.shape[0] * (seq - 1))


_CE_CHUNKS = 8


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_head_ce(x, head, tokens, shift=1):
    """LM head + next-token CE WITHOUT materializing [B, S, V] f32 logits.

    The straightforward `_final_head + _mb_loss` makes autodiff save the
    full f32 logits (4.2 GB at the bench shape) and the bwd rebuild a
    bf16 copy — ~100 ms/step of the MoE bench was this head/loss block
    (xplane profile, VERDICT r3 task 1). Here the forward scans S-chunks
    keeping only logsumexp + the gold logit (residuals [B, S] f32), and
    the backward recomputes each chunk's logits in bf16 and feeds
    (softmax − onehot) straight into the dx/dhead GEMMs. Chunking is over
    SEQUENCE — the vocab-chunked variant measured slower on the dense
    bench (r3 notes).

    x: post-RMSNorm activations [B, S, D] (compute dtype); head [D, V];
    tokens [B, S] int32. Returns the scalar mean loss. `shift`: position
    i is scored against token i + shift, over the S - shift positions
    that have one (1: the next token; 2: a multi-token-prediction
    module's)."""
    loss, _ = _fused_head_ce_fwd(x, head, tokens, shift)
    return loss


def _ce_scan_chunks(x, tokens, shift=1):
    B, S, D = x.shape
    # largest chunk count <= _CE_CHUNKS dividing S — never silently fall
    # back to one chunk (nc=1 would materialize the full [B, S, V] f32
    # logits this function exists to avoid)
    nc = next(n for n in range(_CE_CHUNKS, 0, -1) if S % n == 0)
    c = S // nc
    xs = x.reshape(B, nc, c, D).swapaxes(0, 1)           # [nc, B, c, D]
    tg = jnp.roll(tokens, -shift, axis=1).reshape(B, nc, c).swapaxes(0, 1)
    return xs, tg, nc, c


def _fused_head_ce_fwd(x, head, tokens, shift):
    B, S, D = x.shape
    xs, tg, nc, c = _ce_scan_chunks(x, tokens, shift)

    def chunk(_, xt):
        xc, tc = xt
        logits = (xc @ head).astype(jnp.float32)         # [B, c, V] transient
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return None, (logz, gold)

    _, (logz, gold) = lax.scan(chunk, None, (xs, tg))
    logz = logz.swapaxes(0, 1).reshape(B, S)
    gold = gold.swapaxes(0, 1).reshape(B, S)
    valid = (jnp.arange(S) < S - shift).astype(jnp.float32)
    loss = jnp.sum((logz - gold) * valid[None]) / (B * (S - shift))
    return loss, (x, head, tokens, logz)


def _fused_head_ce_bwd(shift, res, g):
    x, head, tokens, logz = res
    B, S, D = x.shape
    V = head.shape[1]
    xs, tg, nc, c = _ce_scan_chunks(x, tokens, shift)
    lz = logz.reshape(B, nc, c).swapaxes(0, 1)
    valid = (jnp.arange(S) < S - shift).astype(jnp.float32).reshape(nc, 1, c)
    scale = g / (B * (S - shift))

    def chunk(dhead, args):
        xc, tc, lzc, vc = args
        logits = (xc @ head).astype(jnp.float32)
        p = jnp.exp(logits - lzc[..., None])
        d = p - jax.nn.one_hot(tc, V, dtype=jnp.float32)
        d = (d * (vc[..., None] * scale)).astype(x.dtype)   # [B, c, V]
        dx_c = d @ head.T
        dhead = dhead + jnp.einsum("bcd,bcv->dv", xc, d).astype(jnp.float32)
        return dhead, dx_c

    # dhead accumulates in f32: a bf16 carry saves ~17 ms/step of
    # convert_add traffic on the MoE bench but rounds per chunk — measured
    # only +0.08pt MFU, not worth the longer-seq gradient-precision risk
    dhead, dxs = lax.scan(
        chunk, jnp.zeros((D, V), jnp.float32),
        (xs, tg, lz, jnp.broadcast_to(valid, (nc, B, c))))
    dx = dxs.swapaxes(0, 1).reshape(B, S, D)
    return (dx, dhead.astype(head.dtype),
            _np.zeros(tokens.shape, jax.dtypes.float0))


fused_head_ce.defvjp(_fused_head_ce_fwd, _fused_head_ce_bwd)


def _head_ce(params, x, cfg: LlamaConfig, tokens):
    """Final norm + fused head/CE (the loss-path twin of _final_head)."""
    cd = cfg.dtype
    x = rms_norm_ref(x, params["norm"], cfg.rms_norm_eps)
    return fused_head_ce(x.astype(cd),
                         _head_weights(params, cfg).astype(cd), tokens)


def loss_and_grad_pp(params: Dict[str, Any], tokens: jax.Array,
                     cfg: LlamaConfig, mesh, num_microbatches: int,
                     virtual_pp: int = 1):
    """Fused loss + grads through the compiled 1F1B pipeline schedule.

    Reference analog: PipelineParallel.train_batch with its default 1F1B
    scheduler (fleet/meta_parallel/pipeline_parallel.py, SURVEY.md §3.3).
    Unlike the GPipe path (loss_fn + jax.grad, which transposes the forward
    scan and therefore keeps O(M) microbatch activations live), this runs
    parallel.pipeline.one_f_one_b: embedding at stage 0, decoder slices per
    stage, final norm + head + loss at the last stage, O(pp) activation
    residency. Returns (loss, grads) with grads matching the params tree.

    virtual_pp > 1 selects interleaved_one_f_one_b (the reference's
    interleaved/virtual-pp mode IS a 1F1B schedule): v layer chunks per
    device, bubble shrunk by v, activation residency O(v·pp) —
    still independent of num_microbatches (VERDICT r2 missing 2).
    """
    from ..parallel.pipeline import run_1f1b

    n, _, stage_fn = _pp_stage_setup(
        params, tokens.shape, cfg, mesh, num_microbatches,
        need_stage_params=False)
    B, S = tokens.shape
    M = num_microbatches
    L = cfg.num_hidden_layers
    cd = cfg.dtype
    first_params = params["embed_tokens"]
    last_params = {"norm": params["norm"]}
    if cfg.tie_word_embeddings:
        last_params["embed_tokens"] = params["embed_tokens"]
    else:
        last_params["lm_head"] = params["lm_head"]

    def first_fn(embed, tok_mb):
        return jnp.take(embed, tok_mb, axis=0).astype(cd)

    def last_fn(lp, y, tok_mb):
        x = rms_norm_ref(y, lp["norm"], cfg.rms_norm_eps)
        head = (lp["embed_tokens"].T if cfg.tie_word_embeddings
                else lp["lm_head"])
        logits = (x.astype(cd) @ head.astype(cd)).astype(jnp.float32)
        return _mb_loss(logits, tok_mb)

    toks_mb = tokens.reshape((M, B // M) + tokens.shape[1:])
    loss, g_layers, g_f, g_l = run_1f1b(
        stage_fn, first_fn, last_fn, mesh, params["layers"], first_params,
        last_params, toks_mb, n_stages=n, virtual_pp=virtual_pp)

    d_embed = g_f
    if cfg.tie_word_embeddings:
        d_embed = d_embed + g_l["embed_tokens"]
    grads = {
        "embed_tokens": d_embed,
        "layers": g_layers,
        "norm": g_l["norm"],
    }
    if not cfg.tie_word_embeddings:
        grads["lm_head"] = g_l["lm_head"]
    grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
    return loss, grads


def loss_fn(params, tokens, cfg: LlamaConfig, mesh=None,
            pp_microbatches: Optional[int] = None, pp_virtual: int = 1):
    """Next-token cross entropy, masked at the final position. f32 softmax.

    Shapes stay [B, S] throughout (targets via roll + mask, not slicing):
    S-1 is generally not divisible by the sep axis, and uneven seq sharding
    of the embedding-grad scatter aborts XLA's SPMD partitioner
    (PadBaseShapeBeforeUnevenTiledSharding CHECK) — beyond being slower.

    pp_microbatches: with a mesh whose pp axis > 1, run the decoder through
    the compiled GPipe schedule with this many microbatches."""
    if (pp_microbatches and mesh is not None
            and "pp" in mesh.axis_names and mesh.shape["pp"] > 1):
        logits = forward_pp(params, tokens, cfg, mesh, pp_microbatches,
                            pp_virtual)
        return _mb_loss(logits, tokens)
    if cfg.fused_ce:
        return _head_ce(params, _backbone(params, tokens, cfg, mesh), cfg,
                        tokens)
    return _mb_loss(forward(params, tokens, cfg, mesh), tokens)


def num_params(cfg: LlamaConfig) -> int:
    D, F, V, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_hidden_layers)
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    per_layer = 2 * D + D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    total = V * D + L * per_layer + D
    if not cfg.tie_word_embeddings:
        total += D * V
    return total


def flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Approx. train FLOPs/token (fwd+bwd = 6·params_matmul + attention)."""
    D, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    # vocab term: only the OUTPUT projection is a matmul (the input
    # embedding is a gather — ~zero MXU FLOPs, tied or not)
    matmul = L * (D * (H + 2 * KV) * hd + H * hd * D + 3 * D * F) \
        + cfg.vocab_size * D
    # causal attention MACs/token: QK^T + PV visit ~seq/2 keys each →
    # 2 * H*hd*seq/2 = H*hd*seq (the flash kernels really skip the masked
    # half, so crediting full attention would overstate MFU)
    attn = L * H * hd * seq_len
    return 6.0 * (matmul + attn)
