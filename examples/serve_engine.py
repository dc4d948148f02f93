"""Async request serving through paddle_tpu.serving.ServingEngine (PR 1).

Where examples/serve_llm.py serves one fixed batch per Predictor.run(),
the ServingEngine serves a STREAM of requests: a background thread keeps
the paged-KV continuous batcher saturated from a priority queue, tokens
flow back through per-request channels (blocking or streaming), requests
carry deadlines / stop tokens / cancellation, and the engine exports a
metrics snapshot (TTFT, queue wait, KV-block utilization).

Run anywhere:
  JAX_PLATFORMS=cpu python examples/serve_engine.py
"""
import numpy as np
import jax

from paddle_tpu import serving
from paddle_tpu.nlp import llama


def main():
    cfg = llama.LlamaConfig.tiny(num_hidden_layers=2, use_flash=False)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompt = lambda n: rng.integers(1, cfg.vocab_size, n).tolist()

    eng = serving.ServingEngine(params, cfg, max_batch=2, block_size=8,
                                max_total_len=64, max_new_tokens=16,
                                chunk=4)

    # blocking one-shot
    out = eng.generate(prompt(6))
    print("generate:", out)

    # streaming consumption
    print("stream:  ", end="", flush=True)
    for tok in eng.stream(prompt(9), max_new_tokens=8):
        print(tok, end=" ", flush=True)
    print()

    # async handles: mixed priorities + a cancellation
    hi = eng.submit(prompt(5), priority=0)
    lo = eng.submit(prompt(5), priority=5)
    doomed = eng.submit(prompt(5), priority=9)
    doomed.cancel()
    print("hi-prio: ", hi.result())
    print("lo-prio: ", lo.result())
    doomed.wait()
    print("doomed:  ", doomed.state.name)

    snap = eng.snapshot()
    print("counters:", snap["counters"])
    print("ttft_s:  ", {k: round(v, 4) for k, v in
                        snap["histograms"]["ttft_s"].items()})
    print("pool:    ", snap["allocator"])
    eng.shutdown()     # graceful drain


if __name__ == "__main__":
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
