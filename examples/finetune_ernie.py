"""Finetune the ERNIE encoder on a synthetic classification task
(BASELINE config-1 shape).

Run: python examples/finetune_ernie.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.nlp import ernie


def main(steps=20):
    cfg = ernie.ErnieConfig.tiny(num_labels=2)
    params = ernie.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (16, 32)))
    labels = jnp.asarray(rng.integers(0, 2, (16,)))

    step = jax.jit(jax.value_and_grad(
        lambda p: ernie.finetune_loss(p, ids, labels, cfg)))
    for i in range(steps):
        loss, grads = step(params)
        params = jax.tree.map(lambda p, g: p - 5e-2 * g, params, grads)
        if i % 5 == 0:
            print(f"step {i}: loss {float(loss):.4f}")


if __name__ == "__main__":
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
