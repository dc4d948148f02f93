"""TP/DP-sharded LLM serving through inference.create_predictor (round 3).

The serving analog of the reference's PaddleNLP `llm/` predict with
--tensor_parallel_degree: save a generation-ready checkpoint (.pdllm),
point an inference.Config at it, pick mp/dp degrees, and the Predictor
runs the whole prefill + decode scan as ONE compiled TP/DP-sharded
program — KV cache resident and mp-sharded across the loop
(nlp/generation.cache_spec), weights placed per llama.infer_param_specs.

Run anywhere (sized to the host):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/serve_llm.py
On a real v5e chip this serves a 2B-class Llama (d 4096, ffn 9472,
32/8 heads, 11 layers, vocab 32000, bf16) single-chip;
with 8 devices it runs mp=2 x dp=2.
"""
import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu import inference
from paddle_tpu.inference import llm as illm
from paddle_tpu.nlp import llama


def main():
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        # the 2B-class single-chip config from examples/train_2b_8bit_adam
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=4096, intermediate_size=9472,
            num_hidden_layers=11, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=2048,
            param_dtype=jnp.bfloat16)
        batch, plen, new = 4, 128, 64
    else:
        cfg = llama.LlamaConfig.tiny(num_hidden_layers=2, use_flash=False)
        batch, plen, new = 2, 8, 16

    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prefix = "/tmp/paddle_tpu_llm_demo"
    illm.save_llm(prefix, params, cfg)
    print(f"saved {llama.num_params(cfg)/1e9:.2f}B-param checkpoint "
          f"-> {prefix}{illm.LLM_SUFFIX}")

    config = inference.Config(prefix)
    config.enable_llm_generation(max_new_tokens=new, decode_strategy="sampling",
                                 temperature=0.8, top_k=40, top_p=0.95)
    ndev = len(jax.devices())
    if ndev >= 4:
        config.set_llm_parallel(mp=2, dp=2)
        print("serving with mp=2 dp=2")
    elif ndev >= 2:
        config.set_llm_parallel(mp=2)
        print("serving with mp=2")
    predictor = inference.create_predictor(config)

    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, plen)).astype(np.int32)
    predictor.get_input_handle("input_ids").copy_from_cpu(prompt)
    import time
    predictor.run()  # warm-up trace+compile
    t0 = time.perf_counter()
    (out,) = predictor.run()
    dt = time.perf_counter() - t0
    toks = out.shape[0] * out.shape[1]
    print(f"generated {out.shape} in {dt*1e3:.1f} ms "
          f"({toks/dt:.0f} tok/s)")
    print("first row:", out[0, :12].tolist())


if __name__ == "__main__":
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
