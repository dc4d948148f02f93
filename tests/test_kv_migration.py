"""Portable KV-block snapshots + live migration (serving.kvtransfer).

Deterministic CPU coverage of the disaggregated prefill/decode tier:
snapshot round-trip bit-identity at the batcher level (fp AND int8-KV,
scale write-set discipline intact), fingerprint-mismatch rejection at
the import boundary, prefix-index registration visible to siblings on
the importing pool, mid-decode export under fused prefill+decode
steps, speculative-destination parity across the hop, the affinity
index re-pointing migrated chains at the destination replica, the
Router's disaggregated end-to-end path (prefill-role surrender →
snapshot migration → decode-role resume, bit-identical to a
monolithic engine with ZERO decode-replica prefill chunks), warm
failover from an exported snapshot, and the supervisor's
drain-export → respawn → resume cycle.
"""
import threading

import numpy as np
import pytest
import jax

from paddle_tpu.nlp import llama, paged
from paddle_tpu import serving
from paddle_tpu.serving import RequestState
from paddle_tpu.serving.router import Router, _AffinityIndex, _DECODE_ROLES

_RNG = np.random.RandomState(23)
PROMPTS = [list(map(int, _RNG.randint(1, 200, n))) for n in (6, 9, 5)]
MAX_NEW = 8


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _batcher(setup, **kw):
    cfg, params = setup
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_total_len", 48)
    kw.setdefault("max_new_tokens", MAX_NEW)
    kw.setdefault("chunk", 2)
    return paged.ContinuousBatcher(params, cfg, **kw)


def _export_mid_decode(cb, rid, min_tokens=2):
    """Step until `rid` holds at least `min_tokens` generated tokens
    but is still decoding, then export + surrender its slot (the
    engine's `_surrender` sequence: export, abort, release)."""
    for _ in range(64):
        if len(cb.outputs.get(rid, [])) >= min_tokens:
            break
        cb.step()
    active = {cb.slot_req[s] for s in range(cb.B) if cb.active[s]}
    assert rid in active, "request finished before the export point"
    snap = cb.export_kv(rid)
    cb.abort(rid)
    cb.release(rid)
    return snap


class TestSnapshotRoundTrip:
    def _roundtrip(self, setup, **dtypes):
        ref_cb = _batcher(setup, **dtypes)
        r_ref = ref_cb.submit(PROMPTS[0])
        ref = ref_cb.run()[r_ref]

        src = _batcher(setup, **dtypes)
        rid = src.submit(PROMPTS[0])
        snap = _export_mid_decode(src, rid)
        assert snap.prompt_len == len(PROMPTS[0])
        assert snap.tokens[snap.prompt_len:] == ref[:len(snap.tokens)
                                                    - snap.prompt_len]
        dst = _batcher(setup, **dtypes)
        return snap, dst, ref

    def test_fp_bit_identity(self, setup):
        snap, dst, ref = self._roundtrip(setup)
        rid2 = dst.import_kv(snap)
        out = dst.run()
        assert out[rid2] == ref          # resumed decode is bit-exact
        assert dst.prefill_chunk_calls == 0
        assert dst.imported_kv == 1
        # blocks drain clean after the resumed request retires
        assert dst.alloc.stats()["blocks_in_use"] == 0

    def test_int8_kv_bit_identity_and_scales(self, setup):
        snap, dst, ref = self._roundtrip(
            setup, weight_dtype="int8", kv_dtype="int8")
        assert snap.k_scale is not None and snap.v_scale is not None
        rid2 = dst.import_kv(snap)
        # scale write-set discipline BEFORE decode resumes: the
        # transferred blocks carry the source's exact scales, the
        # unwritten tail keeps the 0.0 never-written sentinel
        slot = dst.slot_req.index(rid2)
        chain = dst.slot_blocks[slot]
        nw = snap.n_blocks
        ks = np.array(dst.cache.k_scale)    # a copy: the ticks donate the pool
        np.testing.assert_array_equal(ks[:, chain[:nw]],
                                      np.asarray(snap.k_scale))
        assert np.all(ks[:, chain[nw:]] == 0.0)
        out = dst.run()
        assert out[rid2] == ref          # int8 codes+scales round-trip
        assert dst.prefill_chunk_calls == 0

    def test_fingerprint_mismatch_rejected(self, setup):
        src = _batcher(setup)
        rid = src.submit(PROMPTS[0])
        snap = _export_mid_decode(src, rid)
        # wrong block size: codes would scatter misaligned
        with pytest.raises(ValueError, match="incompatible"):
            _batcher(setup, block_size=8).import_kv(snap)
        # wrong pool dtype: int8 codes are not fp values
        with pytest.raises(ValueError, match="incompatible"):
            _batcher(setup, kv_dtype="int8").import_kv(snap)

    def test_import_registers_prefix_for_siblings(self, setup):
        src = _batcher(setup, prefix_cache=True)
        rid = src.submit(PROMPTS[1])         # len 9: 2 full blocks
        snap = _export_mid_decode(src, rid)
        dst = _batcher(setup, prefix_cache=True)
        rid2 = dst.import_kv(snap)
        # registration is the IMPORT's move (pre-retire): the written
        # full blocks are already matchable on the destination index
        written = len(snap.tokens) - 1
        n_full = written // dst.bs
        assert n_full >= 1
        assert len(dst._pcache.match(snap.tokens)) == n_full
        dst.run()
        # a sibling sharing the prompt prefix admits with cached
        # tokens — prefill work it would otherwise redo
        sib = PROMPTS[1][:dst.bs] + [7, 8, 9]
        r3 = dst.submit(sib)
        out = dst.run()
        assert len(out[r3]) == MAX_NEW
        assert dst._pcache.hits >= 1
        assert dst._pcache.hit_tokens >= dst.bs

    def test_mid_decode_export_under_fused_steps(self, setup):
        dtypes = dict(fused_units=2)
        ref_cb = _batcher(setup, **dtypes)
        ra, rb = ref_cb.submit(PROMPTS[0]), ref_cb.submit(PROMPTS[2])
        refs = ref_cb.run()

        src = _batcher(setup, **dtypes)
        r0 = src.submit(PROMPTS[0])
        src.step()                       # r0 decoding
        r1 = src.submit(PROMPTS[2])      # admission lands mid-decode
        for _ in range(64):
            if src.outputs.get(r1):      # r1's prefill piggybacked
                break
            src.step()
        assert src.fused_steps >= 1      # the fused path actually ran
        assert len(src.outputs.get(r0, [])) >= 2
        snap = src.export_kv(r0)
        src.abort(r0)
        src.release(r0)
        out_src = src.run()
        assert out_src[r1] == refs[rb]   # the co-batched request is
        dst = _batcher(setup, **dtypes)  # untouched by the export
        rid2 = dst.import_kv(snap)
        assert dst.run()[rid2] == refs[ra]
        assert dst.prefill_chunk_calls == 0


class TestEngineHop:
    def test_speculative_destination_parity(self, setup):
        """An imported request on a speculative decode engine stays
        bit-identical to plain greedy: the import opts it out of the
        spec pipeline (the draft state did not travel), and spec is
        greedy-identical for native requests anyway."""
        cfg, params = setup
        ref_cb = _batcher(setup)
        r_ref = ref_cb.submit(PROMPTS[0])
        ref = ref_cb.run()[r_ref]

        src = _batcher(setup)
        rid = src.submit(PROMPTS[0])
        snap = _export_mid_decode(src, rid)
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=48,
            max_new_tokens=MAX_NEW, chunk=2, prefill_buckets=(8,),
            speculative=True, spec_k=2, start=False)
        eng.warmup()
        eng.start()
        req = eng.submit_import(snap)    # fresh pre-seeded handle
        out = req.result(timeout=300)
        eng.shutdown()
        assert out == ref
        assert eng.batcher.prefill_chunk_calls == 0
        assert eng.batcher.imported_kv == 1


class TestAffinity:
    def test_observe_repoints_migrated_chain(self, setup):
        """Unit: re-observing a chain moves every block's credit to the
        new replica — the `_place` call a snapshot import runs, so a
        migrated prefix stops steering siblings at the source."""
        idx = _AffinityIndex(4)
        toks = list(range(100, 112))     # 3 full blocks
        idx.observe(toks, 0)
        assert idx.match(toks) == {0: 12}
        idx.observe(toks, 1)             # the migration re-point
        assert idx.match(toks) == {1: 12}


class TestDisaggRouter:
    @pytest.mark.parametrize("dtypes", [
        {}, {"weight_dtype": "int8", "kv_dtype": "int8"}],
        ids=["fp", "int8"])
    def test_end_to_end_parity_and_zero_prefill(self, setup, dtypes):
        cfg, params = setup
        kw = dict(max_batch=2, block_size=4, max_total_len=48,
                  max_new_tokens=MAX_NEW, chunk=2,
                  prefill_buckets=(8,), max_queue_depth=16, **dtypes)
        eng = serving.ServingEngine(params, cfg, start=False, **kw)
        eng.warmup()
        eng.start()
        ref = [eng.generate(p, timeout=300) for p in PROMPTS]
        eng.shutdown()

        r = Router(params, cfg, replicas=2, disaggregated=True,
                   per_replica=[{"role": "prefill"}, {"role": "decode"}],
                   start=False, **kw)
        r.warmup()
        r.start()
        warm = [e.batcher.compile_count for e in r.engines]
        streamed = [[] for _ in PROMPTS]
        reqs = [r.submit(p, on_token=streamed[i].append)
                for i, p in enumerate(PROMPTS)]
        out = [q.result(timeout=300) for q in reqs]
        pre, dec = r.engines
        # exports and imports stay on the warmed ladder, and the hop
        # leaks no pool block on either side
        assert [e.batcher.compile_count for e in r.engines] == warm
        assert r.drain(timeout=60)
        assert [e.batcher.alloc.stats()["blocks_in_use"]
                for e in r.engines] == [0, 0]
        health = r.health()
        snap = r.snapshot()
        assert out == ref                    # bit-identical across hop
        # the client stream is strictly append-only across the hop:
        # every token arrived exactly once, in order
        assert streamed == out
        # MAX_NEW > 1 + chunk, so every request crosses the surrender
        # boundary and migrates exactly once
        assert health["migrations"] == len(PROMPTS)
        assert health["migration_bytes"] > 0
        assert dec.batcher.imported_kv == len(PROMPTS)
        assert dec.batcher.prefill_chunk_calls == 0
        assert pre.batcher.exported_kv == len(PROMPTS)
        assert all(e["via"] == "kv_import" and e["handoff_s"] >= 0
                   for e in snap["migration_log"])
        # prefill-role health surfaces the handoffs; the role itself
        # rides health() and load() for operators and the policy
        assert pre.health()["role"] == "prefill"
        assert dec.health()["role"] == "decode"
        # the affinity index re-pointed every migrated chain to the
        # decode replica: a decode-capable placement of a sibling
        # (what warm failover runs) now lands on replica 1
        eff = PROMPTS[0] + out[0]
        views = r._views(eff, exclude=(), roles=_DECODE_ROLES)
        assert views and views[0][1] == 1
        assert views[0][2]["affinity_tokens"] > 0
        prom = r.to_prometheus()
        assert "migrations" in prom and "migration_bytes" in prom
        r.shutdown()


class TestWarmFailover:
    def test_failover_imports_exported_kv(self, setup):
        """A replica drained for restart attaches each in-flight
        request's snapshot to the FAILED handle ("respawn_failed" when
        resume is impossible) — the router's failover predicate must
        re-place it on a survivor via `submit_import`, keeping every
        streamed token and re-prefilling nothing."""
        cfg, params = setup
        kw = dict(max_batch=2, block_size=4, max_total_len=48,
                  max_new_tokens=24, chunk=2,
                  prefill_buckets=(8,), max_queue_depth=16)
        eng = serving.ServingEngine(params, cfg, start=False, **kw)
        eng.warmup()
        eng.start()
        ref = eng.generate(PROMPTS[0], timeout=300)
        eng.shutdown()

        r = Router(params, cfg, replicas=2, start=False, **kw)
        r.warmup()
        r.start()
        got, go = threading.Event(), threading.Event()

        def on_token(_):
            got.set()
            go.wait(timeout=10.0)

        req = r.submit(PROMPTS[0], on_token=on_token)
        assert got.wait(timeout=60.0)
        victim = next(i for i, e in enumerate(r.engines)
                      if e.replica_id == req.replica_id)
        survivor = r.engines[1 - victim]
        chunks0 = survivor.batcher.prefill_chunk_calls
        go.set()
        # the supervisor's drain-and-export contract, driven by hand:
        # the victim surrenders its in-flight KV, and a respawn that
        # cannot resume fails the handle with the snapshot attached
        pairs = r.engines[victim].drain_export(timeout=10.0)
        assert len(pairs) == 1
        for s, inner in pairs:
            inner.kv_snapshot = s
            inner._finish(RequestState.FAILED, "respawn_failed")
        out = req.result(timeout=300)
        health = r.health()
        snap = r.snapshot()
        r.shutdown()
        assert out == ref                     # warm resume is bit-exact
        assert health["failovers"] == 1
        assert health["migrations"] == 1      # the warm import counted
        fo = snap["failover_log"][-1]
        assert fo["via"] == "kv_import"
        assert fo["tokens_kept"] >= 1         # streamed tokens all kept
        assert survivor.batcher.imported_kv == 1
        # zero re-prefilled tokens: the survivor never prefilled for it
        assert survivor.batcher.prefill_chunk_calls == chunks0


class TestSupervisorResume:
    def test_restart_slot_drains_exports_and_resumes(self, setup):
        """Planned rolling restart: `restart_slot` drains the serving
        engine's KV before teardown and the respawned engine adopts it
        via `submit_import` — the in-flight stream completes
        bit-identically with ZERO re-prefilled tokens (the fresh
        engine's only prefill is the readiness probe's)."""
        cfg, params = setup
        kw = dict(max_batch=2, block_size=4, max_total_len=64,
                  max_new_tokens=32, chunk=2,
                  prefill_buckets=(8,), max_queue_depth=16)
        eng = serving.ServingEngine(params, cfg, start=False, **kw)
        eng.warmup()
        eng.start()
        ref = eng.generate(PROMPTS[0], timeout=300)
        eng.shutdown()

        r = Router(params, cfg, replicas=2, auto_restart=True,
                   start=False, **kw)
        r.warmup()
        r.start()
        got, go = threading.Event(), threading.Event()

        def on_token(_):
            got.set()
            go.wait(timeout=10.0)

        req = r.submit(PROMPTS[0], on_token=on_token)
        assert got.wait(timeout=60.0)
        victim = next(i for i, e in enumerate(r.engines)
                      if e.replica_id == req.replica_id)
        old = r.engines[victim]
        go.set()
        assert r._supervisor.restart_slot(victim)
        out = req.result(timeout=300)
        # wait for the slot to finish rejoining before inspecting it
        deadline = 60.0
        while r._supervisor.states()[victim] != "SERVING" and deadline:
            threading.Event().wait(0.05)
            deadline -= 0.05
        fresh = r.engines[victim]
        health = r.health()
        r.shutdown()
        assert out == ref                     # resumed stream bit-exact
        assert fresh is not old               # the slot was respawned
        assert health["replica_restarts"] == 1
        assert fresh.batcher.imported_kv >= 1
        # the fresh engine's ONLY prefill is the readiness probe's
        # single chunk — the resumed request re-prefilled nothing
        assert fresh.batcher.prefill_chunk_calls == 1
