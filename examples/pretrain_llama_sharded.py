"""Pretrain a small Llama over the full hybrid mesh (dp/sharding/sep/mp) —
the flagship GSPMD path (SURVEY.md §7 M4-M5).

Run single-host (virtual devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/pretrain_llama_sharded.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.nlp import llama, train
from paddle_tpu.parallel import topology


def main(steps=5):
    n = len(jax.devices())
    mp = 2 if n % 2 == 0 else 1
    sharding = 2 if n % 4 == 0 else 1
    mesh = topology.build_mesh(dp=n // (mp * sharding), sharding=sharding,
                               mp=mp)
    cfg = llama.LlamaConfig.tiny(num_hidden_layers=4)
    tx = train.make_optimizer(3e-4)
    state = train.init_state(jax.random.key(0), cfg, tx, mesh=mesh)
    step = train.make_train_step(cfg, tx, mesh=mesh)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 128)), jnp.int32)
    for i in range(steps):
        state, metrics = step(state, tokens)
        print(f"step {i}: loss {float(metrics['loss']):.4f}")


if __name__ == "__main__":
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
