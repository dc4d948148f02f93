"""KV-cache generation with the flagship Llama family (round 2).

The decode loop is ONE compiled lax.scan (nlp/generation.py) — no host
round-trip per token, unlike the reference's PaddleNLP predict loop.

Run anywhere:
  JAX_PLATFORMS=cpu python examples/generate_llama.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.nlp import llama, generation


def main():
    cfg = llama.LlamaConfig.tiny(num_hidden_layers=2, use_flash=False)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8)),
        jnp.int32)

    greedy = jax.jit(lambda p, t: generation.generate(
        p, t, cfg, max_new_tokens=16))(params, prompt)
    print("greedy      :", np.asarray(greedy).tolist())

    sampled = generation.generate(
        params, prompt, cfg, max_new_tokens=16, greedy=False,
        temperature=0.8, top_k=40, top_p=0.95, key=jax.random.PRNGKey(7))
    print("top-k/top-p :", np.asarray(sampled).tolist())


if __name__ == "__main__":
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
