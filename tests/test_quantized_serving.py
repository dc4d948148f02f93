"""Quantized serving end-to-end: w8 weights + int8 paged KV through
the whole stack (ROADMAP direction 4).

The PR's acceptance matrix:

  * kv math units — quantize/dequantize/rescale invariants from
    quantization.kv (exact identity on an unchanged scale, exact zeros
    for never-written blocks, byte accounting matching device nbytes);
  * batcher — warm==cold token parity under every (weight_dtype,
    kv_dtype) combination (cached-prefix reads reproduce the cold
    prefill exactly, COW full-hit included), zero post-warmup
    recompiles with memo keys carrying the quantized config, block
    COUNT accounting invariant across kv_dtype (cached-aware deferral
    admits identically), and quantized-vs-fp greedy divergence within
    the documented bound;
  * engine — snapshot()/prometheus expose the resolved quantization
    config and the byte gauges; quarantine/probe parity under
    weight_dtype="int8" (a poisoned fused batch convicts the culprit
    alone, innocents BIT-identical to the fault-free quantized run,
    probes reuse the warmed quantized executables — 0 recompiles).
"""
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.nlp import llama
from paddle_tpu.nlp.paged import ContinuousBatcher
from paddle_tpu.quantization import kv as kvq
from paddle_tpu import serving
from paddle_tpu.serving import RequestState
from paddle_tpu.serving.faults import FaultInjector


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


_RNG = np.random.RandomState(7)
PROMPTS = [list(map(int, _RNG.randint(1, 200, L)))
           for L in (5, 11, 8, 19)]


def _batcher(params, cfg, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_total_len", 64)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("prefix_cache", True)
    return ContinuousBatcher(params, cfg, **kw)


def _serve_round(cb, prompts):
    rids = [cb.submit(p) for p in prompts]
    out = cb.run()
    return [out[r] for r in rids]


# Quantized-vs-fp greedy divergence floor on the tiny random-init model:
# across the workload, at least this fraction of the fp run's greedy
# tokens must match the quantized run position-for-position up to each
# request's first divergence. int8 weight/KV error flips the argmax on a
# small minority of steps; a collapse below the floor means the
# quantized math broke, not that rounding moved a borderline logit.
QUANT_MATCH_FLOOR = 0.60


def _prefix_match(base, quant) -> float:
    """Fraction of baseline greedy tokens the quantized run reproduces
    up to each request's first divergence (1.0 = bit-identical)."""
    total = sum(len(b) for b in base)
    lcp = 0
    for b, t in zip(base, quant):
        for x, y in zip(b, t):
            if x != y:
                break
            lcp += 1
    return lcp / total if total else 1.0


# ---- quantization.kv math units ----------------------------------------
class TestKvMath:
    def test_resolve_kv_dtype(self):
        assert kvq.resolve_kv_dtype(None) == "fp"
        assert kvq.resolve_kv_dtype("fp") == "fp"
        assert kvq.resolve_kv_dtype("int8") == "int8"
        with pytest.raises(ValueError):
            kvq.resolve_kv_dtype("int4")

    def test_quant_dequant_roundtrip_error_bound(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 8), jnp.float32)
        scale = jnp.max(jnp.abs(x)) / kvq.BOUND
        err = np.abs(np.asarray(kvq.dequantize(kvq.quantize(x, scale),
                                               scale) - x))
        # symmetric rounding: at most half a quantization step
        assert err.max() <= float(scale) / 2 + 1e-7

    def test_zero_scale_dequantizes_to_exact_zeros(self):
        codes = jnp.zeros((3, 4), jnp.int8)
        assert (np.asarray(kvq.dequantize(codes, 0.0)) == 0.0).all()

    def test_rescale_identity_when_scale_unchanged(self):
        codes = jnp.arange(-127, 128, dtype=jnp.int8)
        s = jnp.float32(0.37)
        out = kvq.rescale_codes(codes, s, s)
        assert (np.asarray(out) == np.asarray(codes)).all()

    def test_rescale_growth_halves_codes(self):
        codes = jnp.asarray([100, -50, 3], jnp.int8)
        out = kvq.rescale_codes(codes, jnp.float32(1.0), jnp.float32(2.0))
        assert list(np.asarray(out)) == [50, -25, 2]

    def test_block_bytes_includes_scale_overhead(self):
        fp = kvq.kv_block_bytes(2, 4, 2, 16, "fp", fp_itemsize=2)
        q = kvq.kv_block_bytes(2, 4, 2, 16, "int8")
        assert fp == 2 * 4 * 2 * 16 * 2 * 2
        assert q == 2 * 4 * 2 * 16 * 2 + 2 * 2 * 4
        assert q / fp < 0.55


# ---- batcher: parity, accounting, memo keys ----------------------------
QUANT_CONFIGS = [
    {"weight_dtype": "int8"},
    {"kv_dtype": "int8"},
    {"weight_dtype": "int8", "kv_dtype": "int8"},
]


class TestQuantizedBatcher:
    @pytest.mark.parametrize("qkw", QUANT_CONFIGS)
    def test_warm_equals_cold_with_zero_recompiles(self, setup, qkw):
        """The headline batcher gate: a second round of the SAME
        prompts (cached-prefix warm, COW full-hits included) emits
        token-identical output to the cold round, with every shape —
        probe, prefill, fused, chunk — on the warmed quantized
        ladder."""
        cfg, params = setup
        cb = _batcher(params, cfg, **qkw)
        cb.warmup_prefill()
        n0 = cb.compile_count
        cold = _serve_round(cb, PROMPTS)
        hits0 = cb.prefix_stats()["hit_tokens"]
        warm = _serve_round(cb, PROMPTS)
        assert warm == cold, "cached-prefix reads diverged from the " \
            "cold prefill under quantization"
        assert cb.prefix_stats()["hit_tokens"] > hits0, \
            "warm round never hit the cache — the parity was vacuous"
        assert cb.compile_count - n0 == 0

    def test_cow_full_hit_under_int8(self, setup):
        """A block-aligned full-prompt hit takes the COW path: the
        clone must copy the source block's CODES AND SCALES, so the
        re-served prompt decodes token-identically."""
        cfg, params = setup
        cb = _batcher(params, cfg, kv_dtype="int8")
        prompt = PROMPTS[0][:4] * 2          # 8 tokens = 2 full blocks
        cold = _serve_round(cb, [prompt])
        warm = _serve_round(cb, [prompt])    # full-prompt hit → COW
        assert warm == cold
        assert cb.prefix_stats()["hit_tokens"] > 0

    def test_quantized_vs_fp_divergence_bound(self, setup):
        """Greedy outputs under quantization track the fp run within
        the documented bound (QUANT_MATCH_FLOOR): the matched-prefix
        fraction across the workload stays above the floor for every
        quantized configuration."""
        cfg, params = setup
        base = _serve_round(_batcher(params, cfg), PROMPTS)
        for qkw in QUANT_CONFIGS:
            got = _serve_round(_batcher(params, cfg, **qkw), PROMPTS)
            m = _prefix_match(base, got)
            assert m >= QUANT_MATCH_FLOOR, \
                f"{qkw}: match {m:.3f} below the documented floor"

    def test_memo_keys_carry_quant_config(self, setup):
        cfg, params = setup
        cb = _batcher(params, cfg, weight_dtype="int8", kv_dtype="int8")
        cb.warmup_prefill()
        keys = (list(cb._prefill_cache) + list(cb._fused_cache)
                + list(cb._chunk_cache))
        assert keys and all(k[-2:] == ("int8", "int8") for k in keys)

    def test_w8_params_quantized_and_idempotent(self, setup):
        """weight_dtype="int8" routes params through
        quantize_for_serving (codes + per-channel scales) and accepts
        an already-quantized tree unchanged."""
        cfg, params = setup
        cb = _batcher(params, cfg, weight_dtype="int8")
        assert cb.params["layers"]["q_proj"].dtype == jnp.int8
        assert "q_proj:scale" in cb.params["layers"]
        cb2 = _batcher(cb.params, cfg, weight_dtype="int8")
        assert cb2.params["layers"]["q_proj"] is \
            cb.params["layers"]["q_proj"]
        with pytest.raises(ValueError):
            _batcher(params, cfg, weight_dtype="int4")

    def test_block_count_accounting_invariant_across_kv_dtype(self, setup):
        """The admission/deferral fix's proof: block COUNTS (and so
        cached-aware defer decisions) are identical under fp and int8 —
        the scale pool rides the same block ids. Only BYTES change."""
        cfg, params = setup
        fp = _batcher(params, cfg)
        q8 = _batcher(params, cfg, kv_dtype="int8")
        for p in PROMPTS:
            assert fp.blocks_needed(len(p), tokens=p) == \
                q8.blocks_needed(len(p), tokens=p)
        assert fp.alloc.num_blocks == q8.alloc.num_blocks
        assert q8.kv_block_bytes() < fp.kv_block_bytes()

    def test_byte_accounting_matches_device_nbytes(self, setup):
        """kv_pool_bytes (quantization.kv.kv_block_bytes x capacity)
        equals the actual device arrays' nbytes, scales included — the
        single-source math and the real pool cannot drift."""
        cfg, params = setup
        for qkw in ({}, {"kv_dtype": "int8"}):
            cb = _batcher(params, cfg, **qkw)
            c = cb.cache
            nbytes = c.k.nbytes + c.v.nbytes
            if c.k_scale is not None:
                nbytes += c.k_scale.nbytes + c.v_scale.nbytes
            assert cb.kv_pool_bytes() == nbytes
        ratio = (_batcher(params, cfg, kv_dtype="int8").kv_bytes_per_token()
                 / _batcher(params, cfg).kv_bytes_per_token())
        assert ratio <= 0.55

    def test_reused_blocks_reset_stale_scales(self, setup):
        """free() is host-side bookkeeping, so a recycled block keeps
        its previous tenant's scale — admission must reset fresh
        blocks to the never-written sentinel or this request's KV
        quantizes coarser than a fresh pool's would. Poisoning every
        scale as if a huge-range tenant had used the pool must not
        change a single output token."""
        cfg, params = setup
        cb = _batcher(params, cfg, kv_dtype="int8", prefix_cache=False)
        base = _serve_round(cb, [PROMPTS[1]])
        cb2 = _batcher(params, cfg, kv_dtype="int8", prefix_cache=False)
        cb2.cache = cb2.cache._replace(
            k_scale=cb2.cache.k_scale + 100.0,
            v_scale=cb2.cache.v_scale + 100.0)
        assert _serve_round(cb2, [PROMPTS[1]]) == base

    def test_abort_and_rollback_clean_under_int8(self, setup):
        """The rollback/abort machinery is dtype-agnostic: aborting a
        mid-decode quantized request returns every block."""
        cfg, params = setup
        cb = _batcher(params, cfg, kv_dtype="int8", chunk=2)
        rid = cb.submit(PROMPTS[3])
        cb.step()
        assert any(cb.active)
        assert cb.abort(rid)
        assert cb.alloc.stats()["blocks_in_use"] == 0


# ---- engine: config surface + quarantine parity under w8 ---------------
class TestQuantizedEngine:
    def _engine(self, setup, inj=None, **kw):
        cfg, params = setup
        return serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=64,
            max_new_tokens=16, chunk=2, prefill_buckets=(8,),
            start=False, fault_injector=inj, **kw)

    def test_snapshot_and_prometheus_expose_quant_config(self, setup):
        eng = self._engine(setup, weight_dtype="int8", kv_dtype="int8")
        snap = eng.snapshot()
        q = snap["quantization"]
        assert q["weight_dtype"] == "int8" and q["kv_dtype"] == "int8"
        assert q["kv_pool_bytes"] == eng.batcher.kv_pool_bytes()
        assert q["weight_bytes"] == eng.batcher.weight_bytes()
        assert q["kv_bytes_per_token"] == eng.batcher.kv_bytes_per_token()
        prom = eng.metrics.to_prometheus()
        assert f"paddle_tpu_kv_pool_bytes {float(q['kv_pool_bytes'])!r}" \
            in prom
        assert "paddle_tpu_weight_bytes" in prom
        assert "paddle_tpu_kv_cached_bytes" in prom
        eng.shutdown()

    def test_w8_pool_smaller_and_weights_smaller(self, setup):
        fp = self._engine(setup)
        q = self._engine(setup, weight_dtype="int8", kv_dtype="int8")
        sfp, sq = fp.snapshot()["quantization"], \
            q.snapshot()["quantization"]
        assert sq["weight_bytes"] < sfp["weight_bytes"]
        assert sq["kv_pool_bytes"] < sfp["kv_pool_bytes"] * 0.55
        fp.shutdown()
        q.shutdown()

    def test_kv_cached_bytes_gauge_tracks_retirement(self, setup):
        """Retired requests park their blocks on the cached LRU — the
        kv_cached_bytes gauge must price exactly those blocks."""
        eng = self._engine(setup, kv_dtype="int8").start()
        eng.generate(PROMPTS[0], timeout=300)
        eng.shutdown()
        cached = eng.batcher.alloc.stats()["cached_blocks"]
        assert cached > 0
        g = eng.metrics.gauge("kv_cached_bytes").value
        assert g == cached * eng.batcher.kv_block_bytes()

    def test_prepared_event_carries_quant_config(self, setup):
        eng = self._engine(setup, kv_dtype="int8").start()
        r = eng.submit(PROMPTS[0])
        r.result(timeout=300)
        tl = eng.trace.timeline(r.trace_id)
        prep = next(e for e in tl["events"] if e["kind"] == "prepared")
        assert prep["attrs"]["kv_dtype"] == "int8"
        assert prep["attrs"]["weight_dtype"] == "fp"
        assert prep["attrs"]["kv_block_bytes"] == \
            eng.batcher.kv_block_bytes()
        eng.shutdown()

    def _serve_all(self, eng, prompts, budgets, culprit_idx=None,
                   inj=None):
        """test_fault_tolerance's harness under quantization: warmed
        lifecycle, optional first-streamed-token poison on the
        culprit. Returns (requests, post-warmup recompiles)."""
        eng.warmup()
        eng.start()
        eng.generate(prompts[0], timeout=300)
        warm = eng.batcher.compile_count
        armed = threading.Event()

        def arm(tok):
            if not armed.is_set():
                armed.set()
                inj.fail_on_rid(culprit_req.request_id)

        culprit_req = None if culprit_idx is None else \
            serving.GenerationRequest(prompts[culprit_idx],
                                      max_new_tokens=budgets[culprit_idx],
                                      on_token=arm)
        reqs = []
        for i, (p, mn) in enumerate(zip(prompts, budgets)):
            reqs.append(eng.submit(culprit_req) if i == culprit_idx
                        else eng.submit(p, max_new_tokens=mn))
        assert eng.drain(timeout=300)
        return reqs, eng.batcher.compile_count - warm

    def test_quarantine_convicts_culprit_under_w8(self, setup):
        """PR 8's headline gate re-run under weight_dtype="int8" +
        kv_dtype="int8": probe_decode_slot/probe_queued must reuse the
        warmed QUANTIZED executables — the poisoned fused batch
        convicts the culprit alone, innocents finish BIT-identical to
        the fault-free quantized run, zero post-warmup recompiles,
        clean pool."""
        budgets = [8, 5, 7, 6]
        qkw = {"weight_dtype": "int8", "kv_dtype": "int8"}
        eng0 = self._engine(setup, **qkw)
        base, _ = self._serve_all(eng0, PROMPTS, budgets)
        base_toks = [r.result(timeout=5) for r in base]
        eng0.shutdown()

        inj = FaultInjector(seed=0)
        eng = self._engine(setup, inj, **qkw)
        reqs, recompiles = self._serve_all(eng, PROMPTS, budgets,
                                           culprit_idx=1, inj=inj)
        culprit = reqs[1]
        assert [r.state for r in reqs].count(RequestState.FAILED) == 1
        assert culprit.state is RequestState.FAILED
        assert culprit.tokens
        assert culprit.tokens == base_toks[1][:len(culprit.tokens)]
        for i in (0, 2, 3):
            assert reqs[i].state is RequestState.FINISHED
            assert reqs[i].result(timeout=5) == base_toks[i], \
                f"innocent {i} lost token parity under quantization"
        assert recompiles == 0, \
            "quarantine probes left the warmed quantized ladder"
        assert eng.batcher.alloc.stats()["blocks_in_use"] == 0
        assert eng.health()["quarantines"] >= 1
        eng.shutdown()


# ---- tools: tuner pad-bytes + trace_report bytes columns ---------------
class TestQuantizedTools:
    def test_bucket_tuner_prices_pad_in_kv_bytes(self):
        import importlib
        tuner = importlib.import_module("tools.bucket_tuner")
        bench = {"prefill_suffix_hist": {"3": 2, "7": 1},
                 "prefill_buckets": [8], "kv_dtype": "int8",
                 "kv_bytes_per_token": 130.0}
        out = tuner.tune(bench, max_buckets=1)
        # ladder (7,): pads 2x(7-3)=8 tokens; observed (8,): 11 tokens
        assert out["pad_tokens_current_ladder"] == 11
        assert out["pad_kv_bytes_current_ladder"] == int(11 * 130.0)
        assert out["pad_kv_bytes_recommended"] == \
            int(out["pad_tokens_recommended"] * 130.0)
        assert out["kv_dtype"] == "int8"

    def test_trace_report_bytes_columns(self, setup, tmp_path):
        import importlib
        import json
        rep = importlib.import_module("tools.trace_report")
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=64,
            max_new_tokens=4, chunk=2, prefill_buckets=(8,),
            kv_dtype="int8")
        eng.generate(PROMPTS[0], timeout=300)
        eng.shutdown()
        path = tmp_path / "trace.json"
        path.write_text(json.dumps(eng.trace.to_chrome_trace()))
        summary = rep.summarize(rep.load_events(str(path)))
        assert summary["total"]["kv_dtype"] == "int8"
        assert summary["total"]["kv_bytes_total"] > 0
        row = summary["requests"][0]
        assert row["kv_bytes"] > 0
        assert "kv_bytes" in rep.render(summary)
