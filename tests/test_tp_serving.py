"""Tensor-parallel serving (serving.tp) — mesh-sharded paged decode.

Deterministic CPU coverage over the conftest's 8 forced host devices:
MeshConfig validation/key units, sharding-spec derivation for the
llama/paged state (column/row weight splits, head-axis KV pool,
replicated scales), memo-key mesh-element placement (the KEY001
convention: `.key()` rides the tail of every compiled-shape cache key;
mesh-off keys stay byte-identical to the unsharded batcher), greedy
bit-identity of a TP=2 engine vs single-device across cold +
prefix-cache-warm serves with ZERO post-warmup recompiles, the
TP=4 × int8-KV × speculative composition at the batcher level,
export/import round-trips across mesh shapes (snapshots are
host-gathered, mesh-agnostic), and a Router fronting one sharded
replica.
"""
import time

import numpy as np
import pytest
import jax
from jax.sharding import PartitionSpec as P

from paddle_tpu.nlp import llama, paged
from paddle_tpu import serving
from paddle_tpu.serving.router import Router
from paddle_tpu.serving.tp import (
    MeshConfig, param_pspecs, build_shardings, shard_info)

_RNG = np.random.RandomState(7)
PROMPTS = [list(map(int, _RNG.randint(1, 200, n))) for n in (5, 9, 6)]
MAX_NEW = 6


@pytest.fixture(scope="module")
def setup():
    # kv_heads=4 so the pool's head axis splits at TP=4 (tiny()'s
    # default 2 kv heads would fail MeshConfig.validate_for)
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2,
                                 num_key_value_heads=4)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _batcher(setup, **kw):
    cfg, params = setup
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_total_len", 48)
    kw.setdefault("max_new_tokens", MAX_NEW)
    kw.setdefault("chunk", 3)
    kw.setdefault("max_prefill_bucket", 16)
    return paged.ContinuousBatcher(params, cfg, **kw)


E_KW = dict(max_batch=2, block_size=4, max_total_len=48,
            max_new_tokens=MAX_NEW, chunk=3, max_prefill_bucket=16,
            prefix_cache=True)


@pytest.fixture(scope="module")
def baselines(setup):
    """Single-device reference tokens, same engine geometry the TP
    engines use (greedy — device-layout-invariant is the claim)."""
    cfg, params = setup
    eng = serving.ServingEngine(params, cfg, **E_KW)
    out = [eng.generate(p, timeout=300) for p in PROMPTS]
    eng.shutdown()
    return out


class TestMeshConfig:
    def test_key_contents(self):
        assert MeshConfig(tp=2).key() == ("tp", 2, "mp", None)
        assert MeshConfig(tp=4, axis="tpax", devices=(3, 2, 1, 0)).key() \
            == ("tp", 4, "tpax", (3, 2, 1, 0))

    def test_validation(self, setup):
        cfg, _ = setup
        with pytest.raises(ValueError, match="does not divide"):
            MeshConfig(tp=3).validate_for(cfg)
        MeshConfig(tp=4).validate_for(cfg)     # 4 | heads/kv/ffn/vocab
        with pytest.raises(ValueError, match="tp=2"):
            MeshConfig(tp=2, devices=(0,))
        with pytest.raises(ValueError, match="tp degree"):
            MeshConfig(tp=0)

    def test_build_against_device_set(self):
        n = len(jax.devices())
        m = MeshConfig(tp=2, devices=(1, 0)).build()
        assert list(m.devices) == [jax.devices()[1], jax.devices()[0]]
        with pytest.raises(ValueError,
                           match="xla_force_host_platform_device_count"):
            MeshConfig(tp=n + 1).build()
        with pytest.raises(ValueError, match="out of range"):
            MeshConfig(tp=2, devices=(0, n + 7)).build()

    def test_describe(self):
        assert MeshConfig(tp=2).describe() == {
            "tp": 2, "axis": "mp", "devices": [0, 1]}


class TestSpecDerivation:
    def test_weight_specs(self, setup):
        """EVERY projection is output-split — serving never shards a
        contracted dim (Megatron's o/down row split would psum in a
        different bf16 summation order than the unsharded dot, and
        the ulp drift flips near-tie argmaxes — the bit-identity
        invariant forbids it)."""
        cfg, params = setup
        specs = param_pspecs(cfg, params)
        lay = specs["layers"]
        for col in ("q_proj", "k_proj", "v_proj", "gate_proj",
                    "up_proj", "o_proj", "down_proj"):
            assert lay[col] == P(None, None, "mp")
        assert specs["lm_head"] == P(None, "mp")
        assert "mp" not in tuple(specs["embed_tokens"])
        assert "mp" not in tuple(lay["input_layernorm"])

    def test_quantized_scale_specs(self, setup):
        """int8 weight scales follow their weight's split with the
        contracted (size-1) dim replicated — output-split weights
        carry output-split scales."""
        cb = _batcher(setup, weight_dtype="int8")
        specs = param_pspecs(setup[0], cb.params)
        lay = specs["layers"]
        for name in ("q_proj:scale", "o_proj:scale",
                     "down_proj:scale"):
            assert lay[name] == P(None, None, "mp")

    def test_build_shardings(self, setup):
        cfg, params = setup
        mesh, sp, pool, repl = build_shardings(
            MeshConfig(tp=2), cfg, params)
        assert pool.spec == P(None, None, None, "mp", None)
        assert repl.spec == P()
        assert sp["layers"]["o_proj"].spec == P(None, None, "mp")

    def test_shard_info_per_device_bytes(self, setup):
        cb = _batcher(setup, mesh=MeshConfig(tp=2))
        info = shard_info(MeshConfig(tp=2), cb)
        # the mesh stamp carries the resolved fast-path attribution
        # (PR 20): which attention impl runs on the mesh, and which
        # spec backend (None — this batcher isn't speculative)
        assert info["mesh"] == {"tp": 2, "axis": "mp",
                                "devices": [0, 1],
                                "attention_impl": "xla",
                                "spec_backend": None}
        assert info["kv_pool_bytes_per_device"] \
            == cb.kv_pool_bytes() // 2
        assert info["weight_bytes_per_device"] < cb.weight_bytes()


class TestMemoKeys:
    def test_mesh_off_keys_unchanged(self, setup):
        """A mesh-less batcher's memo keys carry NO mesh element —
        byte-identical to the pre-TP key shape (`_mkey` is ())."""
        cb = _batcher(setup)
        assert cb._mkey == ()
        rid = cb.submit(PROMPTS[0])
        cb.run()
        for cache in (cb._prefill_cache, cb._chunk_cache,
                      cb._fused_cache):
            for k in cache:
                assert "tp" not in k

    def test_mesh_key_rides_every_cache(self, setup):
        """Every compiled-shape cache key of a mesh batcher ends with
        MeshConfig.key() — two batchers differing only in mesh layout
        can never collide on an executable."""
        cb = _batcher(setup, mesh=MeshConfig(tp=2))
        mk = cb._mesh_cfg.key()
        assert cb._mkey == mk
        for p in PROMPTS[:2]:
            cb.submit(p)
        cb.run()
        assert cb._prefill_cache and cb._chunk_cache
        for cache in (cb._prefill_cache, cb._chunk_cache,
                      cb._fused_cache, cb._spec_cache):
            for k in cache:
                assert k[-len(mk):] == mk


class TestDonatedShardedPool:
    @pytest.mark.parametrize("tp,kv_dtype", [(2, None), (4, "int8")])
    def test_sharded_pool_is_donated_whole(self, setup, tp, kv_dtype):
        """Under a mesh the returned pool takes the donated pool's
        sharding, so every device's shard is written in place: each step
        program that returns a pool aliases a device's whole share of it
        (`alias_bytes`, a device's bytes), the handle taken before a
        tick is deleted after it, and the pool the batcher holds keeps
        the pinned sharding."""
        from paddle_tpu.core.compile_cache import compile_log
        cb = _batcher(setup, mesh=MeshConfig(tp=tp), kv_dtype=kv_dtype,
                      speculative=True, spec_k=2)
        t0 = compile_log.clock()
        cb.warmup_prefill()
        share = sum(p.addressable_shards[0].data.nbytes
                    for p in cb.cache.pools if p is not None)
        recs = compile_log.records(["^jit_serve_"], since=t0)
        assert {r["alias_bytes"] for r in recs
                if r["name"] != "jit_serve_spec_draft"} == {share}
        pinned = [p.sharding for p in cb.cache.pools if p is not None]
        rids = [cb.submit(p) for p in PROMPTS[:2]]
        cb.step()
        before = [p for p in cb.cache.pools if p is not None]
        out = cb.run()
        assert all(p.is_deleted() for p in before)
        after = [p for p in cb.cache.pools if p is not None]
        assert all(a.sharding.is_equivalent_to(s, a.ndim)
                   for a, s in zip(after, pinned))
        assert all(len(out[r]) == MAX_NEW for r in rids)


class TestTPServing:
    def test_tp2_engine_bit_identity_zero_recompiles(self, setup,
                                                     baselines):
        """The tentpole invariant: a TP=2 engine serves greedy output
        bit-identical to single-device — cold AND prefix-cache-warm —
        with zero recompiles after the AOT warmup ladder, and stamps
        its mesh shape into snapshot()/health()/to_prometheus()."""
        cfg, params = setup
        eng = serving.ServingEngine(params, cfg, mesh=MeshConfig(tp=2),
                                    start=False, **E_KW)
        eng.warmup()
        eng.start()
        warm = eng.batcher.compile_count
        cold = [eng.generate(p, timeout=300) for p in PROMPTS]
        rewarm = [eng.generate(p, timeout=300) for p in PROMPTS]
        assert cold == baselines       # cold serves
        assert rewarm == baselines     # prefix-cache-warm serves
        assert eng.batcher.compile_count == warm   # 0 recompiles
        snap = eng.snapshot()
        assert snap["tp"]["mesh"]["tp"] == 2
        assert snap["tp"]["kv_pool_bytes_per_device"] \
            == eng.batcher.kv_pool_bytes() // 2
        assert eng.health()["mesh"]["tp"] == 2
        assert "mesh_devices 2" in eng.metrics.to_prometheus()
        eng.shutdown()

    def test_tp4_int8kv_speculative_composition(self, setup):
        """TP composes with the quantized-KV and speculative paths:
        TP=4 × int8-KV × speculative decode matches the identical
        single-device batcher token-for-token."""
        ref = _batcher(setup, kv_dtype="int8", speculative=True)
        ref_rids = [ref.submit(p) for p in PROMPTS[:2]]
        want = ref.run()
        cb = _batcher(setup, kv_dtype="int8", speculative=True,
                      mesh=MeshConfig(tp=4))
        rids = [cb.submit(p) for p in PROMPTS[:2]]
        got = cb.run()
        assert [got[r] for r in rids] == [want[r] for r in ref_rids]
        assert cb._spec_cache           # the spec path actually ran

    def test_mesh_off_stamp(self, setup):
        cfg, params = setup
        eng = serving.ServingEngine(params, cfg, start=False, **E_KW)
        assert eng.snapshot()["tp"]["mesh"] is None
        assert eng.health()["mesh"] is None
        eng.shutdown()

    def test_pallas_spec_mesh_composition(self, setup):
        """PR 18's mutual exclusion is gone: attention_impl="pallas"
        under a mesh shard_maps the ragged kernel over the KV-head
        axis (interpret mode on CPU — tests/test_ragged_shard_map.py
        is the kernel-level parity suite). TP=2 × pallas × tree
        speculation serves greedy tokens identical to the mesh-off XLA
        plain batcher, re-serves with ZERO new compiles, and stamps
        the pallas backend into spec_stats()."""
        ref = _batcher(setup)
        ref_rids = [ref.submit(p) for p in PROMPTS[:2]]
        want = ref.run()
        cb = _batcher(setup, attention_impl="pallas", speculative=True,
                      spec_tree=(2, 1), spec_attention_impl="pallas",
                      mesh=MeshConfig(tp=2))
        rids = [cb.submit(p) for p in PROMPTS[:2]]
        got = cb.run()
        assert [got[r] for r in rids] == [want[r] for r in ref_rids]
        warm = cb.compile_count
        rids2 = [cb.submit(list(p)) for p in PROMPTS[:2]]
        got2 = cb.run()
        assert [got2[r] for r in rids2] == [want[r] for r in ref_rids]
        assert cb.compile_count == warm     # warm re-serve: 0 compiles
        st = cb.spec_stats()
        assert st["enabled"] and st["backend"] == "pallas"
        assert st["steps"] >= 1             # speculation ran on the mesh
        # the mesh stamp says what ran: a silent fall-back to the XLA
        # gather under the mesh would keep the tokens and lose the kernel
        stamp = shard_info(MeshConfig(tp=2), cb)["mesh"]
        assert stamp["attention_impl"] == cb.attention_impl == "pallas"
        assert stamp["spec_backend"] == st["backend"]


def _export_mid_decode(cb, rid, min_tokens=2):
    for _ in range(64):
        if len(cb.outputs.get(rid, [])) >= min_tokens:
            break
        cb.step()
    snap = cb.export_kv(rid)
    cb.abort(rid)
    cb.release(rid)
    return snap


class TestShardedKVTransfer:
    def test_export_import_across_mesh_shapes(self, setup):
        """Snapshots are host-gathered FULL arrays (mesh-agnostic):
        export from a TP=2 pool, resume bit-identically on a
        single-device pool AND on a TP=4 pool — zero re-prefill."""
        ref_cb = _batcher(setup)
        r_ref = ref_cb.submit(PROMPTS[0])
        ref = ref_cb.run()[r_ref]

        src = _batcher(setup, mesh=MeshConfig(tp=2))
        rid = src.submit(PROMPTS[0])
        snap = _export_mid_decode(src, rid)
        # gathered, not a shard: full kv-head width on the host
        assert snap.k.shape[3] == setup[0].num_key_value_heads
        for dst in (_batcher(setup),
                    _batcher(setup, mesh=MeshConfig(tp=4))):
            rid2 = dst.import_kv(snap)
            assert dst.run()[rid2] == ref
            assert dst.prefill_chunk_calls == 0


class TestRouterShardedReplica:
    def test_router_fronts_sharded_engine(self, setup, baselines):
        cfg, params = setup
        r = Router(params, cfg, replicas=1,
                   per_replica=[{"mesh": MeshConfig(tp=2)}],
                   start=False, **E_KW)
        r.warmup()
        r.start()
        assert r.generate(PROMPTS[0], timeout=300) == baselines[0]
        assert r.health()["replicas"]["r0"]["mesh"]["tp"] == 2
        r.shutdown()

    def test_sharded_slot_respawns_with_its_mesh(self, setup, baselines):
        """The supervisor replays a slot's per-replica kwargs on
        respawn: the rebuilt engine re-derives its mesh and shardings,
        passes the readiness gate, rejoins and serves bit-identically
        with nothing compiled past that gate."""
        cfg, params = setup
        r = Router(params, cfg, replicas=1, auto_restart=True,
                   per_replica=[{"mesh": MeshConfig(tp=2)}],
                   start=False, **E_KW)
        r.warmup()
        r.start()
        old = r.engines[0]
        assert r._supervisor.restart_slot(0)
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            h = r.health()
            if h["serving_replicas"] == 1 and h["replica_restarts"] >= 1:
                break
            time.sleep(0.05)
        assert h["serving_replicas"] == 1 and h["replica_restarts"] == 1
        fresh = r.engines[0]
        assert fresh is not old
        assert fresh.health()["mesh"]["tp"] == 2
        assert r.generate(PROMPTS[0], timeout=300) == baselines[0]
        assert fresh.batcher.compile_count == \
            r.health()["supervisor"]["r0"]["warm_compile_count"]
        r.shutdown()
