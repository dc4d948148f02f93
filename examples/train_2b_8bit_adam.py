"""Single-chip flagship-class training: bf16 params + 8-bit Adam moments.

The configuration: a 2.0B-param Llama (d 4096, ffn 9472, 32/8 heads,
11 layers, vocab 32000, bf16 params) whose ENTIRE train state fits
one 16GB v5e chip because the Adam moments are stored as blockwise
float8 codes (~2 bytes/param instead of 8 —
optimizer/quant_state.py). Run small anywhere:

  JAX_PLATFORMS=cpu python examples/train_2b_8bit_adam.py

On a chip `main()` builds that shape; on the CPU a tiny one.
"""
import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.nlp import llama, train


def main(steps=5):
    on_tpu = jax.devices()[0].platform != "cpu"
    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=4096, intermediate_size=9472,
            num_hidden_layers=11, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=2048,
            param_dtype=jnp.bfloat16)
        batch, seq = 4, 2048
    else:
        cfg = llama.LlamaConfig.tiny(num_hidden_layers=2, use_flash=False)
        batch, seq = 8, 64

    # the 8-bit path streams clip-by-global-norm through its chunked
    # update (no second grad tree), so the recipe's clip stays on at 2B
    tx = train.make_optimizer(1e-4, state_quant="8bit", grad_clip=1.0)
    state = train.init_state(jax.random.key(0), cfg, tx, mesh=None)
    step = train.make_train_step(cfg, tx, mesh=None)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)),
        jnp.int32)
    for i in range(steps):
        state, metrics = step(state, tokens)
        print(f"step {i}: loss {float(metrics['loss']):.4f}  "
              f"params {llama.num_params(cfg)/1e9:.2f}B")


if __name__ == "__main__":
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
