"""Every step program that returns a KV pool is compiled with the pool
donated (`ContinuousBatcher._step_jit`), and the batcher holds the returned
pool from the moment of dispatch (`_Tick.adopt`): one case a tick kind
(standalone prefill, decode chunk, fused step, speculative verify) and
pool family (uniform fp, uniform int8, latent, kinded), on the CPU,
whose backend honours donation on this JAX: a donated array
`is_deleted()` and a later use raises, so a stale holder of the pool
fails a test here before it fails on the chip.

Each family's scenario runs ONCE (`_scenario`), ticks of every kind in a
row through the batcher's own tick methods, and keeps what each tick did
to the handles; the cases read their tick's slice of it. The tokens are
compared with the PARENT's programs': the same batcher with `_step_jit`
told that no program writes the pool, which donates nothing (the parent
commit's compile, argument for argument).
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.models import mla_moe_decoder, window_moe_decoder  # noqa: E402
from paddle_tpu.nlp import llama, paged                           # noqa: E402

LATENT = {
    "family": "mla_moe_decoder", "served_dtype": "float32",
    "share": {"router_experts": 16, "experts_first": 4},
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 160,
    "kv_lora_rank": 32, "max_position_embeddings": 256,
    "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_routed_experts": 6,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 2, "q_lora_rank": 48,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_method": "none", "v_head_dim": 16,
    "vocab_size": 128}
KINDED = {
    "family": "window_moe_decoder", "served_dtype": "float32",
    "attention_bias": False, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 48, "intermediate_size": 96,
    "layer_types": ["sliding_attention", "full_attention"],
    "mlp_layer_types": ["sparse"] * 2, "max_position_embeddings": 512,
    "moe_intermediate_size": 24, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
            "original_max_position_embeddings": 32, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000.0}},
    "sliding_window": 16, "tie_word_embeddings": False,
    "use_sliding_window": True, "vocab_size": 128}

FAMILIES = ("uniform-fp", "uniform-int8", "latent", "kinded")
KINDS = ("prefill", "decode", "fused", "spec_verify")
# the draft and verify programs are written for the uniform GQA pool
# (`_refuse_latent`, `_refuse_kinded` refuse the rest at construction)
CASES = [(k, f) for f in FAMILIES for k in KINDS
         if k != "spec_verify" or f.startswith("uniform")]
# which memoized executable a kind of tick calls
EXE_OF = {"prefill": "_prefill_exe", "decode": "_chunk_exe",
          "fused": "_fused_exe", "spec_verify": "_spec_verify_exe"}


class _ParentBatcher(paged.ContinuousBatcher):
    """The parent commit's programs: nothing is donated."""

    def _step_jit(self, fn, writes_pool=True):
        return super()._step_jit(fn, writes_pool=False)


def _build(family, cls):
    kw = dict(max_batch=2, block_size=4, max_total_len=48,
              max_new_tokens=7, chunk=2, prefill_buckets=(8,),
              attention_impl="xla")
    if family.startswith("uniform"):
        cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        return cls(params, cfg, speculative=True, spec_k=2,
                   kv_dtype="int8" if family.endswith("int8") else None,
                   **kw)
    fam, config = (mla_moe_decoder, LATENT) if family == "latent" \
        else (window_moe_decoder, KINDED)
    cfg = fam.program_config(config)
    params = fam.make_params(3, fam.dims(config), jnp.float32)
    return cls(params, cfg, **kw)


def _spy(cb, name, seen):
    """Wrap the memoized executable behind `cb.<name>()`: every call's
    (results, whether the pool it was handed is alive on its return) go
    on `seen[name]`."""
    getter = getattr(cb, name)

    def spied(*shape):
        exe = getter(*shape)

        def call(params, pools, *rest):
            out = exe(params, pools, *rest)
            seen.setdefault(name, []).append((out, not any(
                p.is_deleted() for p in pools if p is not None)))
            return out
        return call
    setattr(cb, name, spied)


def _drive(cb, seen=None):
    """One tick of every kind the batcher has, through its own tick
    methods, then the rest through `run()`. With `seen`, what each tick
    did to the pool's handles: {kind: (every old handle deleted, the
    batcher's pool live, the batcher's pool IS the program's result)}."""
    rng = np.random.RandomState(5)
    a, b = (list(map(int, rng.randint(1, 120, n))) for n in (6, 11))
    observed = {}

    def tick(kind, run):
        before = [p for p in cb.cache.pools if p is not None]
        run()
        if seen is None:
            return
        out, _ = seen[EXE_OF[kind]][-1]
        after = [p for p in cb.cache.pools if p is not None]
        observed[kind] = (
            all(p.is_deleted() for p in before),
            not any(p.is_deleted() for p in after),
            all(x is y for x, y in zip(
                after, [p for p in out[0] if p is not None])))
        # the live pool reads (a stale handle raises "deleted")
        np.asarray(cb.cache.k[0, 0])

    def decoding():
        return [s for s in range(cb.B) if cb.active[s]]

    ra = cb.submit(a)
    cb._drain_queue()
    tick("prefill", cb._run_standalone_unit)
    tick("decode", lambda: cb._step_decode(decoding()))
    if cb.speculative:
        tick("spec_verify", lambda: cb._step_spec(decoding()))
    rb = cb.submit(b)                   # lands while `a` decodes: fused
    cb._drain_queue()
    tick("fused", lambda: cb._step_fused(decoding()))
    out = cb.run()
    assert cb.alloc.stats()["blocks_in_use"] == 0
    return observed, [out[ra], out[rb]]


@functools.lru_cache(maxsize=None)
def _scenario(family):
    cb = _build(family, paged.ContinuousBatcher)
    seen = {}
    for name in (*EXE_OF.values(), "_spec_draft_exe"):
        _spy(cb, name, seen)
    observed, toks = _drive(cb, seen)
    _, parent_toks = _drive(_build(family, _ParentBatcher))
    # the draft reads the pool and returns none: what it was handed is
    # alive when it returns (its verify deletes it afterwards)
    draft_kept = [alive for _, alive in seen.get("_spec_draft_exe", [])]
    return observed, toks, parent_toks, draft_kept


@pytest.mark.parametrize("kind,family", CASES,
                         ids=[f"{k}-{f}" for k, f in CASES])
def test_tick_donates_its_pool_and_the_batcher_holds_the_result(kind,
                                                                family):
    observed, toks, parent_toks, draft_kept = _scenario(family)
    old_deleted, new_live, is_result = observed[kind]
    assert old_deleted, "a handle taken before the tick outlived it"
    assert new_live and is_result
    # the same tokens as the parent's undonated programs, to the last
    assert toks == parent_toks and all(len(t) == 7 for t in toks)
    if kind == "spec_verify":
        assert draft_kept and all(draft_kept)


def test_parent_batcher_donates_nothing():
    """The comparison's other side is what it says: with no program
    told to write the pool, no handle is ever deleted."""
    cb = _build("uniform-fp", _ParentBatcher)
    k0 = cb.cache.k
    cb.submit([3, 4, 5])
    cb.run()
    assert not k0.is_deleted()
