"""Benchmark: flagship Llama training step on one chip → MFU + tokens/sec.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline"}.
vs_baseline is measured MFU / 40% (the BASELINE.json north-star floor;
the reference publishes no numbers — BASELINE.md).

Two configs, both sized for a single chip's HBM (the driver benches on one
real TPU), same arch as the 8B flagship (GQA + SwiGLU + RoPE + Pallas
flash attention + remat):
  headline — 2.0B params: bf16 params + f8 blockwise Adam moments
  (optimizer.quant_state), the flagship-class measurement (VERDICT r1
  item 6); keys mfu/value.
  comparison — 0.5B params, f32 params + f32 Adam (the round-1 config);
  keys mfu_05b/tok_s_05b.
"""
from __future__ import annotations

import gc
import json
import time

import numpy as np

# bf16 peak TFLOP/s per chip by TPU generation (public spec sheets),
# matched as a substring of jax's `device_kind`. A device that is not in
# the table has no MFU: `peak_for` raises rather than invent a peak.
PEAK_TFLOPS = {
    "v6": 918.0, "v5p": 459.0, "v5 lite": 197.0, "v5e": 197.0,
    "v4": 275.0, "v3": 123.0, "v2": 46.0,
}


def peak_for(device) -> float:
    kind = device.device_kind.lower()
    for k, v in PEAK_TFLOPS.items():
        if k in kind:
            return v * 1e12
    raise ValueError(
        f"no published bf16 peak for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}): MFU is only defined on a chip "
        f"in PEAK_TFLOPS")


def _free():
    """Force collection AFTER the caller has del'd its big references —
    lingering HBM buffers measurably slow the following config
    (fragmentation). Usage: `del state, step, tx, tokens; _free()`."""
    gc.collect()


def _timed_steps(step, state, tokens, warmup, timed):
    """Shared timing protocol: warmup, drain the device, then the timed
    loop ending in `block_until_ready` on the whole step output. HBM
    cleanup is the CALLER's job (_free) — it holds the big references."""
    import jax
    for _ in range(max(warmup, 1)):
        state, m = step(state, tokens)
    jax.block_until_ready((state, m))
    t0 = time.perf_counter()
    for _ in range(timed):
        state, m = step(state, tokens)
    jax.block_until_ready((state, m))
    dt = time.perf_counter() - t0
    return dt, float(m["loss"])


def run_config(cfg, batch, seq, timed_steps, state_quant=None,
               warmup_steps=2, grad_clip=1.0):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nlp import llama, train

    dev = jax.devices()[0]
    tx = train.make_optimizer(1e-4, state_quant=state_quant,
                              grad_clip=grad_clip)
    state = train.init_state(jax.random.key(0), cfg, tx, mesh=None)
    step = train.make_train_step(cfg, tx, mesh=None)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)

    dt, loss_val = _timed_steps(step, state, tokens, warmup_steps,
                                timed_steps)
    tok_s = batch * seq * timed_steps / dt
    mfu = tok_s * llama.flops_per_token(cfg, seq) / peak_for(dev)
    del state, step, tx, tokens
    _free()
    return {"tok_s": tok_s, "mfu": mfu, "loss": loss_val,
            "params": llama.num_params(cfg)}


def run_moe(batch=20, seq=2048, timed_steps=10):
    """BASELINE config 4 (DeepSeekMoE/Qwen2-MoE-class EP workload) on one
    chip: a ~1.6B-total / ~0.5B-active DeepSeek-style MoE (16 experts
    top-2 + 1 shared, index-form GShard routing with the Pallas ragged
    gather) trained with bf16 params + 8-bit Adam. MFU counts ACTIVE
    FLOPs (the MoE convention — only routed experts do work)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nlp import moe, train

    dev = jax.devices()[0]
    cfg = moe.MoeConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        moe_intermediate_size=1024, num_experts=16, num_experts_per_tok=2,
        num_shared_experts=1, num_hidden_layers=12,
        num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=2048, param_dtype=jnp.bfloat16)
    tx = train.make_optimizer(1e-4, state_quant="8bit", grad_clip=1.0)
    state = train.init_state(jax.random.key(0), cfg, tx, mesh=None,
                             model=moe)
    step = train.make_train_step(cfg, tx, mesh=None, model=moe)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    dt_total, _ = _timed_steps(step, state, tokens, 2, timed_steps)
    dt = dt_total / timed_steps
    mfu = moe.flops_per_token(cfg, seq) * batch * seq / dt / peak_for(dev)
    del state, step, tx, tokens
    _free()
    return {"mfu": mfu, "tok_s": batch * seq / dt,
            "params": moe.num_params(cfg)}


def flagship_2b_cfg(max_position_embeddings=2048):
    """The ~2.1B bf16 flagship Llama config — ONE definition shared by the
    training bench (main) and the serving prefill bench so both always
    measure the same stack."""
    import jax.numpy as jnp
    from paddle_tpu.nlp import llama
    return llama.LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=9472,
        num_hidden_layers=11, num_attention_heads=32,
        num_key_value_heads=8,
        max_position_embeddings=max_position_embeddings,
        param_dtype=jnp.bfloat16)


def build_ernie_step(batch=64, seq=512):
    """ERNIE train-step builder shared by run_ernie and
    tools/profile_step.py (one definition so the profiler always measures
    the benched step)."""
    import jax
    import jax.numpy as jnp
    import optax
    from paddle_tpu.nlp import ernie

    # finetune recipe: no remat (118M params; activations fit HBM and the
    # recompute measured -0.2pt), fully unrolled layer scan (+0.8pt: the
    # backward's per-layer grad stacking becomes static writes)
    cfg = ernie.ErnieConfig.ernie3_base(num_labels=2, remat=False,
                                        scan_unroll=True)
    params = ernie.init_params(jax.random.key(0), cfg)
    tx = optax.adamw(2e-5)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.num_labels, (batch,)), jnp.int32)

    @jax.jit
    def step(state, batch_):
        params, opt = state
        loss, g = jax.value_and_grad(ernie.finetune_loss)(
            params, batch_[0], batch_[1], cfg)
        upd, opt = tx.update(g, opt, params)
        return (optax.apply_updates(params, upd), opt), {"loss": loss}

    return step, (params, tx.init(params)), (ids, labels), cfg


def run_ernie(batch=64, seq=512, timed_steps=10):
    """BASELINE config 1 (ERNIE-3.0-base finetune): sequence-classification
    step at seq 512 on one chip — bidirectional encoder, f32 params + f32
    Adam (the small-model finetune recipe; 118M params need no quantized
    state). MFU uses the bidirectional attention accounting
    (ernie.flops_per_token)."""
    import jax
    from paddle_tpu.nlp import ernie

    dev = jax.devices()[0]
    step, state, batch_xy, cfg = build_ernie_step(batch, seq)
    dt, _ = _timed_steps(step, state, batch_xy, 2, timed_steps)
    tok_s = batch * seq * timed_steps / dt
    mfu = tok_s * ernie.flops_per_token(cfg, seq) / peak_for(dev)
    del state, batch_xy, step
    _free()
    return {"mfu": mfu, "tok_s": tok_s, "params": ernie.num_params(cfg)}


def build_dit_step(batch=96):
    """DiT train-step builder shared by run_dit and tools/profile_step.py
    (one definition so the profiler always measures the benched step)."""
    import jax
    import jax.numpy as jnp
    import optax
    from paddle_tpu.mix import dit
    from paddle_tpu.optimizer.quant_state import adamw_q

    cfg = dit.DiTConfig.dit_xl_2()
    params = dit.init_params(jax.random.key(0), cfg)
    tx = adamw_q(1e-4)
    rng = np.random.default_rng(0)
    x0 = jnp.asarray(rng.standard_normal(
        (batch, cfg.in_channels, cfg.image_size, cfg.image_size)),
        jnp.float32)
    y = jnp.asarray(rng.integers(0, cfg.num_classes, (batch,)), jnp.int32)
    key = jax.random.key(1)

    @jax.jit
    def step(state, batch_):
        params, opt = state
        loss, g = jax.value_and_grad(
            lambda p: dit.diffusion_loss(p, key, batch_[0], batch_[1],
                                         cfg))(params)
        upd, opt = tx.update(g, opt, params)
        return (optax.apply_updates(params, upd), opt), {"loss": loss}

    return step, (params, tx.init(params)), (x0, y), cfg


def run_dit(batch=96, timed_steps=10):
    """BASELINE config 3 (DiT-XL/2-class diffusion): epsilon-prediction
    train step on 32x32x4 latents, depth-28 DiT (675M params), bf16
    compute + 8-bit Adam moments. MFU per dit.flops_per_image.

    batch 96 (r5; 64 measured 38.1%, 112 thrashes HBM at 36.4%, 128
    OOMs): the backward-scan grad stacking is batch-independent, so the
    bigger batch amortizes it."""
    import jax
    from paddle_tpu.mix import dit

    dev = jax.devices()[0]
    step, state, batch_xy, cfg = build_dit_step(batch)
    dt, _ = _timed_steps(step, state, batch_xy, 2, timed_steps)
    img_s = batch * timed_steps / dt
    mfu = img_s * dit.flops_per_image(cfg) / peak_for(dev)
    del state, batch_xy, step
    _free()
    return {"mfu": mfu, "img_s": img_s, "params": dit.num_params(cfg)}


def run_prefill(prompt_len=8192, timed=4):
    """Serving prefill throughput (VERDICT r3 missing 2): 8k-token prompt
    through the flash-prefill path of nlp.generation on the 2B flagship
    layer stack — the O(S^2)-mask-free path; the r3 masked-cache path
    could not even allocate this shape's per-head masks."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nlp import llama, generation

    cfg = flagship_2b_cfg(max_position_embeddings=prompt_len + 256)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    T = prompt_len + 64
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, prompt_len)), jnp.int32)

    @jax.jit
    def prefill(params, prompt):
        cache = generation.init_cache(cfg, 1, T)
        logits, cache = generation.forward_cached(params, prompt, cache, 0,
                                                  cfg)
        return logits[:, -1]

    lg = prefill(params, prompt)
    float(lg[0, 0])
    t0 = time.perf_counter()
    for _ in range(timed):
        lg = prefill(params, prompt)
    float(lg[0, 0])
    dt = (time.perf_counter() - t0) / timed
    del params, prompt, prefill
    _free()
    return {"prefill_tok_s": prompt_len / dt}


def run_decode(batch=8, prompt_len=512, new_tokens=128, timed=3,
               weight_only=None):
    """Serving decode throughput: greedy batched decode on the 2B flagship
    stack (prefill + ONE compiled lax.scan of cached single-token steps —
    nlp.generation.generate). Reported as generated tokens/s across the
    batch, steady-state-dominated (prompt work amortized over new_tokens;
    SURVEY.md §3.5 serving stack).

    weight_only=8: int8 weight-only decode (generation.quantize_for_serving
    — VERDICT r4 next-2; the reference ecosystem's serving default). The
    int8 codes halve the per-step weight read, roughly doubling the
    bandwidth roofline."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nlp import generation, llama

    cfg = flagship_2b_cfg(max_position_embeddings=prompt_len + new_tokens)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    if weight_only:
        params = generation.quantize_for_serving(params, bits=weight_only)

    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)

    gen = jax.jit(lambda p, ids: generation.generate(
        p, ids, cfg, max_new_tokens=new_tokens, greedy=True))
    out = gen(params, prompt)
    int(out[0, -1])
    t0 = time.perf_counter()
    for _ in range(timed):
        out = gen(params, prompt)
    int(out[0, -1])
    dt = (time.perf_counter() - t0) / timed
    del params, prompt, gen, out
    _free()
    return {"decode_tok_s": batch * new_tokens / dt}


def run_8b_layer(seq, batch=1, timed_steps=8):
    """One Llama-3-8B-dimension decoder layer (d=4096, ffn=14336, GQA
    32/8, bf16), flash fwd+bwd — the north-star LAYER SHAPE measured on
    the chip that cannot hold the full 8B (VERDICT r2 missing 7). The 8B
    model is this layer x32 + embeddings, so its per-layer compute
    efficiency is the load-bearing number for the v5p-64 projection."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nlp import llama
    from paddle_tpu.kernels.rope import rope_freqs

    dev = jax.devices()[0]
    cfg = llama.LlamaConfig.llama3_8b(
        num_hidden_layers=1, param_dtype=jnp.bfloat16, remat=False)
    D, F = cfg.hidden_size, cfg.intermediate_size
    H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    key = jax.random.PRNGKey(0)
    lp = {k: v[0] for k, v in
          llama.init_params(key, cfg)["layers"].items()}
    cos, sin = rope_freqs(hd, seq, cfg.rope_theta, jnp.float32)
    x = (jax.random.normal(key, (batch, seq, D), jnp.float32) * 0.1
         ).astype(cfg.dtype)

    def loss(lp, x):
        y = llama._decoder_layer(x, lp, cfg, cos, sin, None)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    step = jax.jit(jax.grad(loss))
    g = step(lp, x)
    float(jax.tree.leaves(g)[0].reshape(-1)[0])
    t0 = time.perf_counter()
    for _ in range(timed_steps):
        g = step(lp, x)
    float(jax.tree.leaves(g)[0].reshape(-1)[0])
    dt = (time.perf_counter() - t0) / timed_steps

    matmul = D * (H + 2 * KV) * hd + H * hd * D + 3 * D * F
    attn = H * hd * seq    # causal: QK^T + PV at ~seq/2 visible keys each
    flops = 6.0 * (matmul + attn) * batch * seq
    mfu = flops / dt / peak_for(dev)
    del lp, x, g, step
    _free()
    return mfu


def main():
    import jax
    from paddle_tpu.nlp import llama

    dev = jax.devices()[0]
    # every number below is a device metric: a run that finds no chip
    # fails here instead of timing a toy model on the CPU
    peak_for(dev)
    # flagship-class ~2.1B Llama: bf16 params + f8 blockwise Adam moments
    # (optimizer.quant_state) fit one chip's 16GB HBM; wide layers keep
    # the MXU fed
    cfg2b = flagship_2b_cfg()
    # grad_clip=1.0 rides the STREAMED clip fused into the 8-bit Adam
    # chunk stream (optimizer/quant_state.py clip_norm) — no second
    # grad tree, so the flagship recipe's clip is ON
    batch, seq = 8, 2048
    big = run_config(cfg2b, batch=batch, seq=seq, timed_steps=8,
                     state_quant="8bit", grad_clip=1.0)
    # round-1 config (~0.5B, f32 Adam state) for cross-round comparison
    cfg05 = llama.LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=8, num_attention_heads=16,
        num_key_value_heads=8, max_position_embeddings=2048)
    small = run_config(cfg05, batch=16, seq=2048, timed_steps=10)
    # the 8B layer shape at north-star sequence lengths
    layer8b_4k = run_8b_layer(seq=4096)
    layer8b_8k = run_8b_layer(seq=8192)
    # FULL 2B model long-context step (combined streamed flash bwd)
    long8k = run_config(flagship_2b_cfg(max_position_embeddings=8192),
                        batch=2, seq=8192, timed_steps=4,
                        state_quant="8bit", grad_clip=1.0)
    moe_res = run_moe()
    ernie_res = run_ernie()
    dit_res = run_dit()
    prefill_res = run_prefill()
    decode_res = run_decode()
    decode_w8_res = run_decode(weight_only=8)
    # serving-throughput scaling point: the same int8 stack at batch
    # 32 (weight reads amortize across the batch; the b8 key stays
    # the cross-round comparison)
    decode_w8_b32_res = run_decode(batch=32, weight_only=8)

    print(json.dumps({
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(big["tok_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(big["mfu"] / 0.40, 4),
        "mfu": round(big["mfu"], 4),
        "device": dev.device_kind,
        "model_params": big["params"],
        "batch": batch, "seq": seq,
        "loss": round(big["loss"], 4),
        "mfu_05b": round(small["mfu"], 4),
        "tok_s_05b": round(small["tok_s"], 1),
        "mfu_8b_layer": round(layer8b_4k, 4),
        "mfu_8b_layer_s8k": round(layer8b_8k, 4),
        "mfu_2b_seq8k": round(long8k["mfu"], 4),
        "tok_s_2b_seq8k": round(long8k["tok_s"], 1),
        "mfu_moe": round(moe_res["mfu"], 4),
        "tok_s_moe": round(moe_res["tok_s"], 1),
        "moe_params": moe_res["params"],
        "mfu_ernie": round(ernie_res["mfu"], 4),
        "tok_s_ernie": round(ernie_res["tok_s"], 1),
        "mfu_dit": round(dit_res["mfu"], 4),
        "img_s_dit": round(dit_res["img_s"], 2),
        "prefill_tok_s": round(prefill_res["prefill_tok_s"], 1),
        "decode_tok_s": round(decode_res["decode_tok_s"], 1),
        "decode_tok_s_w8": round(decode_w8_res["decode_tok_s"], 1),
        "decode_tok_s_w8_b32": round(decode_w8_b32_res["decode_tok_s"], 1),
    }))


if __name__ == "__main__":
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
