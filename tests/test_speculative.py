"""Self-speculative decoding — the draft-and-verify subsystem.

Deterministic CPU coverage of the PR's acceptance bars: spec==non-spec
greedy tokens BIT-identical (cold, prefix-cache-warm, mid-decode
admission, truncated and full-depth drafts), verify-then-commit pool /
prefix-cache cleanliness (the committed pool is bit-identical to a
plain run's — rejection never writes), acceptance accounting, zero
post-warmup recompiles with spec config in every memo key, the
engine's quarantine plain-decode fallback for victims of a failed spec
tick, spec × int8-KV interplay (shared pool, sequential-commit scale
cleanliness), and trace_report's accepted-per-step column.
"""
import importlib.util
import pathlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.nlp import llama
from paddle_tpu.nlp.paged import ContinuousBatcher
from paddle_tpu import serving
from paddle_tpu.serving.faults import FaultInjector
from paddle_tpu.serving.speculative import SpecConfig, SpecStats

REPO = pathlib.Path(__file__).resolve().parent.parent

_RNG = np.random.RandomState(17)
# mixed lengths incl. past the bucket cap (chunked prefill) and a
# shared-prefix pair (prefix-cache hits under spec)
PROMPTS = [list(map(int, _RNG.randint(1, 200, n)))
           for n in (5, 9, 12, 7)]
SHARED = list(map(int, _RNG.randint(1, 200, 8)))
PROMPTS += [SHARED + [11], SHARED + [13]]
MAX_NEW = 8


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _batcher(params, cfg, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_total_len", 48)
    kw.setdefault("max_new_tokens", MAX_NEW)
    kw.setdefault("chunk", 3)
    kw.setdefault("prefix_cache", True)
    kw.setdefault("max_prefill_bucket", 8)
    return ContinuousBatcher(params, cfg, **kw)


def _run(cb, prompts, budgets=None):
    """Warmup, serve `prompts`, return ({submit order: tokens},
    post-warmup recompiles)."""
    cb.warmup_prefill()
    c0 = cb.compile_count
    rids = [cb.submit(p, max_new_tokens=mn)
            for p, mn in zip(prompts, budgets or [None] * len(prompts))]
    out = cb.run()
    return [list(out[r]) for r in rids], cb.compile_count - c0


class TestSpecConfig:
    def test_validation(self):
        with pytest.raises(ValueError):
            SpecConfig(0)
        with pytest.raises(ValueError):
            SpecConfig(4, draft_layers=0)
        with pytest.raises(ValueError):
            SpecConfig(4, draft_layers=5, num_layers=2)
        c = SpecConfig(3, draft_layers=1, num_layers=2)
        assert c.depth(2) == 1
        assert SpecConfig(3).depth(2) == 2          # None = full depth
        assert c.key(2) == ("spec", 3, 1, "tree", 1, 1, 1)
        assert c.as_dict(2) == {"k": 3, "draft_layers": 1,
                                "draft_depth": 1}

    @pytest.mark.parametrize("k", [1, 3])
    def test_chain_resolves_to_the_tree_of_ones(self, k):
        """`k` is the shorthand for `tree=(1,) * k`: one geometry, one
        memo key, printed as a chain."""
        sc, tc = SpecConfig(k=k), SpecConfig(tree=[1] * k)
        assert sc.tree == tc.tree == (1,) * k and sc.k == tc.k == k
        assert sc.tree_depth() == k and sc.slab_rows() == k + 1
        assert sc.row_parents() == [0] + list(range(k))
        assert sc.key(2) == tc.key(2)
        assert sc.as_dict() == tc.as_dict() \
            == {"k": k, "draft_layers": None}

    def test_stats_math(self):
        s = SpecStats()
        assert s.accept_rate() == 0.0 and s.tokens_per_step() == 0.0
        s.record_step(drafted=6, accepted=3, emitted=4, slots=2)
        s.record_step(drafted=6, accepted=6, emitted=7, slots=2)
        assert s.accept_rate() == pytest.approx(9 / 12)
        # per (sweep, slot): directly comparable to plain decode's 1.0
        assert s.tokens_per_step() == pytest.approx(11 / 4)
        d = s.as_dict()
        assert d["steps"] == 2 and d["emitted"] == 11
        assert d["slot_sweeps"] == 4

    def test_batcher_rejects_bad_config(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError):
            _batcher(params, cfg, speculative=True, spec_k=0)
        with pytest.raises(ValueError):
            _batcher(params, cfg, speculative=True, draft_layers=3)


class TestSpecParity:
    def test_bit_identical_cold_full_and_truncated(self, setup):
        """Greedy spec output == plain greedy output, token for token,
        with zero post-warmup recompiles — full-depth AND truncated
        drafts (a rejected draft changes the schedule, never the
        tokens)."""
        cfg, params = setup
        ref, rec0 = _run(_batcher(params, cfg), PROMPTS)
        assert rec0 == 0
        for dl in (None, 1):
            cb = _batcher(params, cfg, speculative=True, spec_k=3,
                          draft_layers=dl)
            got, rec = _run(cb, PROMPTS)
            assert got == ref, f"draft_layers={dl} diverged"
            assert rec == 0, f"draft_layers={dl} recompiled post-warmup"

    def test_bit_identical_prefix_cache_warm(self, setup):
        """A warm repeat (prefix-cache hits serving the prompts' full
        blocks) decodes the same tokens under spec as plain — and the
        cache actually hit."""
        cfg, params = setup
        cb_ref = _batcher(params, cfg)
        ref1, _ = _run(cb_ref, PROMPTS)
        r2 = [cb_ref.submit(p) for p in PROMPTS]
        out = cb_ref.run()
        ref2 = [list(out[r]) for r in r2]

        cb = _batcher(params, cfg, speculative=True, spec_k=3)
        got1, rec1 = _run(cb, PROMPTS)
        hits0 = cb.prefix_stats()["hit_tokens"]
        r2 = [cb.submit(p) for p in PROMPTS]
        out = cb.run()
        got2 = [list(out[r]) for r in r2]
        assert got1 == ref1 and got2 == ref2
        assert cb.prefix_stats()["hit_tokens"] > hits0   # warm, not vacuous
        assert cb.compile_count and rec1 == 0

    def test_bit_identical_mid_decode_admission(self, setup):
        """n_requests >> max_batch with staggered budgets: admissions
        land while slots decode (the PR 5 fused path carries them) and
        spec ticks interleave with fused ticks — tokens still match
        plain decode exactly, recompiles stay 0."""
        cfg, params = setup
        prompts = PROMPTS + PROMPTS[:2]
        budgets = [1 + (i % MAX_NEW) for i in range(len(prompts))]
        ref, _ = _run(_batcher(params, cfg, chunk=2), prompts, budgets)
        cb = _batcher(params, cfg, chunk=2, speculative=True, spec_k=3)
        got, rec = _run(cb, prompts, budgets)
        assert got == ref
        assert rec == 0
        assert cb.fused_steps > 0        # admissions really piggybacked
        assert cb.spec.steps > 0         # and spec ticks really ran

    def test_budget_exactness(self, setup):
        """Multi-token emission must respect per-request budgets
        exactly — a verify sweep never over-emits past max_new."""
        cfg, params = setup
        budgets = [1, 2, 3, MAX_NEW, 5, 4]
        cb = _batcher(params, cfg, speculative=True, spec_k=4)
        got, _ = _run(cb, PROMPTS, budgets)
        assert [len(t) for t in got] == budgets


class TestVerifyThenCommit:
    def test_rejected_rows_never_write_the_pool(self, setup):
        """THE verify-then-commit invariant, at the write-set level:
        per spec tick, the pool changes at EXACTLY the accepted rows'
        (block, slot) positions — a rejected draft row's K/V never
        lands anywhere. A truncated draft guarantees real rejections
        occur along the way."""
        cfg, params = setup
        cb = _batcher(params, cfg, speculative=True, spec_k=3,
                      draft_layers=1)
        cb.warmup_prefill()
        cb.submit(PROMPTS[0])
        cb._admit()                # standalone prefill, slot 0 active
        assert cb.active[0]
        saw_rejection = False
        while cb.active[0]:
            len0 = int(np.asarray(cb.cache.lengths)[0])
            bud0 = cb.budget[0]
            pre = np.asarray(cb.cache.k.astype(jnp.float32))
            chain = cb.slot_blocks[0]
            out, n_emit = cb._step_spec([0])
            n = int(n_emit[0])
            assert 1 <= n <= cb.spec_k + 1
            if n < min(cb.spec_k + 1, bud0):
                saw_rejection = True     # not a budget truncation
            post = np.asarray(cb.cache.k.astype(jnp.float32))
            changed = {tuple(c) for c in np.argwhere(
                np.any(pre != post, axis=(0, 3, 4)))}
            expect = {(chain[p // cb.bs], p % cb.bs)
                      for p in range(len0, len0 + n)}
            assert changed == expect, \
                "a rejected (or phantom) row wrote the pool"
        assert saw_rejection

    def test_state_matches_plain_run(self, setup):
        """After identical workloads the spec batcher's allocator and
        prefix index are IDENTICAL to the plain batcher's, tokens are
        bit-equal, and committed pool values agree to bf16 noise (the
        score path is a different FP reduction than write-then-gather;
        the write SET is exact — previous test)."""
        cfg, params = setup
        cb0 = _batcher(params, cfg)
        ref, _ = _run(cb0, PROMPTS)
        cb1 = _batcher(params, cfg, speculative=True, spec_k=3,
                       draft_layers=1)
        got, _ = _run(cb1, PROMPTS)
        assert got == ref
        assert 0 < cb1.spec.accepted < cb1.spec.drafted  # real rejections
        assert cb0.alloc.stats() == cb1.alloc.stats()
        for p in PROMPTS:
            assert cb0._match_cached(p)[1] == cb1._match_cached(p)[1]
        assert np.allclose(np.asarray(cb0.cache.k.astype(jnp.float32)),
                           np.asarray(cb1.cache.k.astype(jnp.float32)),
                           atol=0.05)

    def test_acceptance_accounting(self, setup):
        """Full-depth draft (draft == target): every proposal accepted,
        tokens/step multiplies; truncated draft: accepted <= drafted
        with the counters internally consistent."""
        cfg, params = setup
        # two same-bucket short prompts: ONE cold batched prefill,
        # then pure spec decode (no fused ticks to share emission)
        short = [PROMPTS[0], PROMPTS[3]]
        cb = _batcher(params, cfg, speculative=True, spec_k=3)
        got, _ = _run(cb, short)
        s = cb.spec
        assert s.steps > 0
        assert s.accept_rate() == pytest.approx(1.0)
        assert s.tokens_per_step() > 1.0
        # every token after each request's prefill-emitted FIRST one
        # came from a verify sweep
        assert s.emitted == sum(len(t) for t in got) - len(got)
        st = cb.spec_stats()
        assert st["enabled"] and st["k"] == 3 and st["draft_depth"] == 2

        cb2 = _batcher(params, cfg, speculative=True, spec_k=3,
                       draft_layers=1)
        _run(cb2, short)
        assert cb2.spec.accepted <= cb2.spec.drafted
        assert cb2.spec.emitted >= cb2.spec.steps     # >= 1 token/sweep

    def test_memo_keys_carry_spec_config(self, setup):
        """Every compiled-shape memo key carries the spec config
        BEFORE the trailing (weight_dtype, kv_dtype) qkey — and the
        spec cache holds exactly the warmed draft/verify pair."""
        cfg, params = setup
        cb = _batcher(params, cfg, speculative=True, spec_k=3,
                      draft_layers=1, kv_dtype="int8")
        cb.warmup_prefill()
        keys = (list(cb._prefill_cache) + list(cb._fused_cache)
                + list(cb._chunk_cache))
        assert keys
        for k in keys:
            assert k[-2:] == ("fp", "int8")
            assert ("spec", 3, 1, "tree", 1, 1, 1, "xla") \
                == tuple(k[-10:-2])
        assert {k[0] for k in cb._spec_cache} == {"draft", "verify"}
        # a plain batcher's keys are unchanged (no spec element)
        cb0 = _batcher(params, cfg)
        cb0.warmup_prefill()
        assert all(k[-3] in (True, False, "xla", "pallas")
                   for k in cb0._prefill_cache)

    def test_per_request_opt_out(self, setup):
        """submit(speculative=False) decodes THAT request plain inside
        a spec batcher (acceptance forced to 0) with tokens unchanged,
        and the opt-out set drains on retire."""
        cfg, params = setup
        ref, _ = _run(_batcher(params, cfg), PROMPTS[:2])
        cb = _batcher(params, cfg, speculative=True, spec_k=3)
        cb.warmup_prefill()
        r0 = cb.submit(PROMPTS[0], speculative=False)
        r1 = cb.submit(PROMPTS[1])
        out = cb.run()
        assert [list(out[r0]), list(out[r1])] == ref
        # the opted-out slot drafted nothing; the spec slot did
        assert cb.spec.drafted == cb.spec.steps * cb.spec_k
        assert not cb._no_spec


class TestSpecInt8KV:
    def test_int8_kv_parity(self, setup):
        """Spec and plain share one int8 pool discipline (the
        row-sequential commit keeps grow-only scales evolving like
        sequential decode's); the score path reads full-precision
        slab rows, so spec-vs-plain under int8 is a documented
        match-rate floor rather than bitwise (README "Speculative
        decoding") — in practice it is exact or near-exact.

        The shared-prefix pair ends in 12 and 13 here, not PROMPTS' 11
        and 13: SHARED + [11]'s first token is a tie at bf16's
        resolution. Float32 weights and pool give logit[207] 0.406615
        over logit[201] 0.404166, one bf16 step apart; an int8 pool
        whose last prefill chunk runs alone rounds both to 0.404297 and
        argmax takes the lower id, while one whose chunks both ride
        fused steps reads 0.405168 over 0.403780 (XLA's CPU backend
        keeps the head's float32 there) and takes 207 like float32. A
        plain batcher and a speculative one schedule that chunk
        differently, so the fixture would test the tie-break and not
        the pool discipline. With 12 the three schedules (fused,
        fused_prefill=False, speculative) agree on all 48 tokens."""
        cfg, params = setup
        prompts = PROMPTS[:4] + [SHARED + [12], SHARED + [13]]
        cb0 = _batcher(params, cfg, kv_dtype="int8")
        ref, _ = _run(cb0, prompts)
        cb1 = _batcher(params, cfg, kv_dtype="int8", speculative=True,
                       spec_k=3, draft_layers=1)
        got, rec = _run(cb1, prompts)
        n = sum(len(t) for t in ref)
        m = sum(1 for a, b in zip(ref, got)
                for x, y in zip(a, b) if x == y)
        assert m / n >= 0.9, f"int8 spec match {m}/{n}"
        assert rec == 0
        assert cb0.alloc.stats() == cb1.alloc.stats()

    def test_int8_scale_cleanliness_per_tick(self, setup):
        """Grow-only scale hygiene under spec: per spec tick, scale
        entries change ONLY at (layer, block) slots of blocks holding
        accepted rows — a rejected draft's magnitudes can never
        coarsen a block's quantization."""
        cfg, params = setup
        cb = _batcher(params, cfg, speculative=True, spec_k=3,
                      draft_layers=1, kv_dtype="int8")
        cb.warmup_prefill()
        cb.submit(PROMPTS[0])
        cb._admit()
        assert cb.active[0]
        while cb.active[0]:
            len0 = int(np.asarray(cb.cache.lengths)[0])
            # a copy on the host: the tick donates the pool, scales too
            pre = np.array(cb.cache.k_scale)
            chain = cb.slot_blocks[0]
            out, n_emit = cb._step_spec([0])
            n = int(n_emit[0])
            post = np.array(cb.cache.k_scale)
            touched = {chain[p // cb.bs]
                       for p in range(len0, len0 + n)}
            changed = set(np.argwhere(
                np.any(pre != post, axis=0)).ravel().tolist())
            assert changed <= touched, \
                "a rejected draft row grew a block scale"


class TestTreeSpecConfig:
    def test_tree_validation_and_geometry(self):
        with pytest.raises(ValueError):
            SpecConfig(tree=[])
        with pytest.raises(ValueError):
            SpecConfig(tree=[2, 0])
        sc = SpecConfig(tree=[2, 2])
        assert sc.k == 6 and sc.slab_rows() == 7
        assert sc.tree_depth() == 2
        assert sc.level_sizes() == [1, 2, 4]
        assert sc.level_offsets() == [0, 1, 3, 7]
        assert sc.row_levels() == [0, 1, 1, 2, 2, 2, 2]
        assert sc.row_parents() == [0, 0, 0, 1, 1, 2, 2]
        A = sc.ancestor_mask()
        # node 5 (child 0 of slab row 2): sees exactly root -> 2 -> 5
        assert [s for s in range(7) if A[5][s]] == [0, 2, 5]
        # the chain's mask is the causal triangle
        Ac = SpecConfig(k=3).ancestor_mask()
        assert all(Ac[p][s] == (s <= p)
                   for p in range(4) for s in range(4))
        assert SpecConfig(k=3).row_parents() == [0, 0, 1, 2]

    def test_tree_key_and_dict(self):
        """The branching factors and draft_w8 ride the memo-key
        element; a chain's are its (1,) * k."""
        sc = SpecConfig(tree=[2, 1], draft_layers=1, num_layers=2)
        assert sc.key(2) == ("spec", 4, 1, "tree", 2, 1)
        d = sc.as_dict(2)
        assert d["tree"] == [2, 1] and d["k"] == 4
        assert SpecConfig(3).key(2) == ("spec", 3, 2, "tree", 1, 1, 1)
        assert SpecConfig(3, draft_w8=True).key(2) == \
            ("spec", 3, 2, "tree", 1, 1, 1, "w8")

    def test_depth_hist_and_accepted_per_sweep(self):
        s = SpecStats()
        s.record_step(drafted=8, accepted=5, emitted=6, slots=2,
                      depths=[2, 3])
        s.record_step(drafted=8, accepted=3, emitted=4, slots=2,
                      depths=[0, 3])
        assert s.accepted_per_sweep() == pytest.approx(8 / 4)
        assert s.depth_hist == {0: 1, 2: 1, 3: 2}
        # fresh depths drain exactly once (the engine's gauge sync)
        assert s.drain_depths() == [2, 3, 0, 3]
        assert s.drain_depths() == []
        d = s.as_dict()
        assert d["accept_depth_hist"] == {0: 1, 2: 1, 3: 2}
        assert d["accepted_per_sweep"] == pytest.approx(2.0)


class TestTreeSpecParity:
    def test_tree_bit_identical_and_dominates_chain(self, setup):
        """Tree speculation emits plain greedy's exact tokens with 0
        post-warmup recompiles, and at equal accepted-path budget
        (tree depth == chain k) tree acceptance per sweep dominates
        the chain's — child 0 of every node IS the chain's draft."""
        cfg, params = setup
        ref, _ = _run(_batcher(params, cfg), PROMPTS)
        chain = _batcher(params, cfg, speculative=True, spec_k=3)
        gc, _ = _run(chain, PROMPTS)
        tree = _batcher(params, cfg, speculative=True,
                        spec_tree=[2, 1, 1])
        gt, rec = _run(tree, PROMPTS)
        assert gc == ref and gt == ref
        assert rec == 0
        assert tree.spec.steps > 0
        assert tree.spec.accepted_per_sweep() >= \
            chain.spec.accepted_per_sweep()
        assert tree.spec.depth_hist          # histogram populated
        st = tree.spec_stats()
        assert st["tree"] == [2, 1, 1] and st["k"] == 6

    def test_chain_and_tree_of_ones_are_one_batcher(self, setup):
        """spec_k=3 IS spec_tree=[1, 1, 1]: one pair of executables
        (the same memo keys), the same spec_stats() geometry, printed
        as a chain; and its tokens are plain greedy decode's."""
        cfg, params = setup
        short = PROMPTS[:3]
        ref, _ = _run(_batcher(params, cfg), short)
        chain = _batcher(params, cfg, speculative=True, spec_k=3,
                         draft_layers=1)
        gc, rec = _run(chain, short)
        tree = _batcher(params, cfg, speculative=True,
                        spec_tree=[1, 1, 1], draft_layers=1)
        assert tree.spec_k == 3 and chain._spec_cfg.tree == (1, 1, 1)
        tree.warmup_prefill()
        assert set(tree._spec_cache) == set(chain._spec_cache)
        assert gc == ref and rec == 0
        assert chain.spec.steps > 0
        geometry = ("enabled", "backend", "k", "draft_layers",
                    "draft_depth")
        st, tt = chain.spec_stats(), tree.spec_stats()
        assert "tree" not in st and "tree" not in tt
        assert [st[g] for g in geometry] == [tt[g] for g in geometry]

    def test_tree_truncated_draft_bit_identical(self, setup):
        """A truncated tree draft (real rejections at every level)
        still lands plain greedy's exact tokens."""
        cfg, params = setup
        ref, _ = _run(_batcher(params, cfg), PROMPTS)
        cb = _batcher(params, cfg, speculative=True,
                      spec_tree=[2, 2], draft_layers=1)
        got, rec = _run(cb, PROMPTS)
        assert got == ref
        assert rec == 0
        assert cb.spec.accepted < cb.spec.drafted    # real rejections

    def test_tree_write_set(self, setup):
        """Verify-then-commit at the write-set level under TREE drafts:
        per tick the pool changes at exactly the accepted PATH's rows
        — no sibling branch's K/V ever lands."""
        cfg, params = setup
        cb = _batcher(params, cfg, speculative=True,
                      spec_tree=[2, 1], draft_layers=1)
        cb.warmup_prefill()
        cb.submit(PROMPTS[0])
        cb._admit()
        assert cb.active[0]
        while cb.active[0]:
            len0 = int(np.asarray(cb.cache.lengths)[0])
            pre = np.asarray(cb.cache.k.astype(jnp.float32))
            chain = cb.slot_blocks[0]
            out, n_emit = cb._step_spec([0])
            n = int(n_emit[0])
            assert 1 <= n <= cb._spec_cfg.tree_depth() + 1
            post = np.asarray(cb.cache.k.astype(jnp.float32))
            changed = {tuple(c) for c in np.argwhere(
                np.any(pre != post, axis=(0, 3, 4)))}
            expect = {(chain[p // cb.bs], p % cb.bs)
                      for p in range(len0, len0 + n)}
            assert changed == expect, \
                "a sibling/rejected tree row wrote the pool"

    def test_tree_int8_kv_scale_cleanliness(self, setup):
        """Tree spec over an int8 pool: per tick, block scales grow
        only at blocks holding accepted-path rows (grow-only hygiene
        survives the tree commit loop)."""
        cfg, params = setup
        cb = _batcher(params, cfg, speculative=True,
                      spec_tree=[2, 1], draft_layers=1,
                      kv_dtype="int8")
        cb.warmup_prefill()
        cb.submit(PROMPTS[0])
        cb._admit()
        while cb.active[0]:
            len0 = int(np.asarray(cb.cache.lengths)[0])
            # a copy on the host: the tick donates the pool, scales too
            pre = np.array(cb.cache.k_scale)
            chain = cb.slot_blocks[0]
            out, n_emit = cb._step_spec([0])
            n = int(n_emit[0])
            post = np.array(cb.cache.k_scale)
            touched = {chain[p // cb.bs]
                       for p in range(len0, len0 + n)}
            changed = set(np.argwhere(
                np.any(pre != post, axis=0)).ravel().tolist())
            assert changed <= touched

    def test_draft_w8_bit_identical(self, setup):
        """draft-from-w8: the truncated draft reads an int8 weight-only
        quantization of its layer stack (built once at construction on
        an fp target; a no-op on an int8 target) — verification runs
        the target's weights, so emitted tokens stay plain greedy's."""
        cfg, params = setup
        ref, _ = _run(_batcher(params, cfg), PROMPTS)
        cb = _batcher(params, cfg, speculative=True, spec_k=3,
                      draft_layers=1, spec_draft_w8=True)
        assert cb._spec_dlayers is not None      # built on fp target
        got, rec = _run(cb, PROMPTS)
        assert got == ref
        assert rec == 0
        # tree x w8 compose
        cb2 = _batcher(params, cfg, speculative=True,
                       spec_tree=[2, 1, 1], draft_layers=1,
                       spec_draft_w8=True)
        got2, _ = _run(cb2, PROMPTS)
        assert got2 == ref
        # int8 target: the draft already reads quantized weights
        cb3 = _batcher(params, cfg, speculative=True, spec_k=3,
                       weight_dtype="int8", spec_draft_w8=True)
        assert cb3._spec_dlayers is None

    def test_pallas_verify_parity(self, setup):
        """spec_attention_impl="pallas" routes the spec score path
        through the kernel's suffix-slab operand (interpret mode on
        CPU) — tokens bit-identical to the XLA score path and to
        plain decode, chain AND tree."""
        cfg, params = setup
        short = PROMPTS[:2]
        ref, _ = _run(_batcher(params, cfg), short)
        for tree in (None, [2, 1]):
            cb = _batcher(params, cfg, speculative=True, spec_k=2,
                          spec_tree=tree, draft_layers=1,
                          spec_attention_impl="pallas")
            assert cb.spec_attention_impl == "pallas"
            assert cb.attention_impl == "xla"    # decode path unchanged
            got, rec = _run(cb, short)
            assert got == ref, f"tree={tree} diverged under pallas"
            assert rec == 0

    def test_tree_memo_keys(self, setup):
        """Tree + spec-impl configs ride every compiled-shape memo key
        (prefill/fused/chunk caches via _skey; the spec cache via
        _spec_key's phase tuple) — no aliasing across shapes."""
        cfg, params = setup
        cb = _batcher(params, cfg, speculative=True,
                      spec_tree=[2, 1], draft_layers=1,
                      kv_dtype="int8",
                      spec_attention_impl="pallas")
        cb.warmup_prefill()
        keys = (list(cb._prefill_cache) + list(cb._fused_cache)
                + list(cb._chunk_cache))
        assert keys
        for k in keys:
            assert k[-2:] == ("fp", "int8")
            assert tuple(k[-9:-2]) == ("spec", 4, 1, "tree", 2, 1,
                                       "pallas")
        sk = [k for k in cb._spec_cache]
        assert {k[0] for k in sk} == {"draft", "verify"}
        for k in sk:
            assert k[1] == 1                     # draft depth
            assert ("spec", 4, 1, "tree", 2, 1) == k[3:9]
            assert "pallas" in k and "xla" in k  # both resolved impls
            assert "tree" in k


class TestSpecEngine:
    def test_engine_parity_gauges_snapshot(self, setup):
        cfg, params = setup
        def serve(**kw):
            eng = serving.ServingEngine(
                params, cfg, max_batch=2, block_size=4,
                max_total_len=48, max_new_tokens=MAX_NEW, chunk=3,
                max_prefill_bucket=8, start=False, **kw)
            eng.warmup()
            eng.start()
            reqs = [eng.submit(p) for p in PROMPTS]
            outs = [r.result(300) for r in reqs]
            snap = eng.snapshot()
            eng.shutdown()
            return outs, snap
        ref, snap0 = serve()
        got, snap = serve(speculative=True, spec_tree=[2, 1, 1])
        assert got == ref
        sp = snap["speculative"]
        assert sp["enabled"] and sp["tokens_per_step"] > 1.0
        assert sp["tree"] == [2, 1, 1]
        assert snap["gauges"]["spec_accept_rate"] == \
            pytest.approx(sp["accept_rate"])
        assert snap["gauges"]["spec_tokens_per_step"] > 1.0
        # the accept-depth distribution surfaces twice: spec_stats'
        # exact dict and the drained Prometheus histogram — counts
        # must agree (every depth observed exactly once)
        assert sp["accept_depth_hist"]
        h = snap["histograms"]["spec_accept_depth"]
        assert h["count"] == sum(sp["accept_depth_hist"].values())
        assert snap0["speculative"]["enabled"] is False
        assert snap0["gauges"]["spec_steps"] == 0

    def test_quarantine_spec_fallback(self, setup):
        """A failed spec tick quarantines like any step failure — and
        every surviving request re-admits OPTED OUT of speculation
        (plain decode for the victims), with tokens still identical
        to the fault-free run."""
        cfg, params = setup
        def serve(inj=None):
            eng = serving.ServingEngine(
                params, cfg, max_batch=2, block_size=4,
                max_total_len=48, max_new_tokens=MAX_NEW, chunk=3,
                max_prefill_bucket=8, start=False, speculative=True,
                spec_k=3, fault_injector=inj, retry_backoff_s=0.01)
            eng.warmup()
            eng.start()
            # ONE short request: tick 1 is its standalone prefill,
            # ticks 2/3 the first spec draft/verify pair —
            # deterministic tick numbering for the injected fault
            reqs = [eng.submit(PROMPTS[0])]
            outs = [r.result(300) for r in reqs]
            return eng, reqs, outs
        eng0, _, ref = serve()
        eng0.shutdown()
        # fail the FIRST spec verify once, transient
        inj = FaultInjector(seed=0).fail_on_step(3, transient=True)
        eng, reqs, outs = serve(inj)
        assert outs == ref                       # recovery is lossless
        h = eng.health()
        assert h["quarantines"] >= 1
        assert h["requests_retried"] >= 1
        assert all(r.spec_opt_out for r in reqs)
        b = eng.batcher
        # the fallback held: the only attempted sweep FAILED before
        # recording, and with every active request opted out the
        # batcher dropped to the plain chunk path (no vacuous sweeps)
        assert b.spec.steps == 0 and b.spec.accepted == 0
        assert not b._no_spec                    # drained at retire
        eng.shutdown()

    def test_trace_report_accepted_per_step(self, setup):
        """spec_draft/spec_verify events land in the timeline and
        trace_report grows the accepted-per-step column."""
        cfg, params = setup
        eng = serving.ServingEngine(
            params, cfg, max_batch=2, block_size=4, max_total_len=48,
            max_new_tokens=MAX_NEW, chunk=3, max_prefill_bucket=8,
            start=False, speculative=True, spec_k=3)
        eng.warmup()
        eng.start()
        for p in PROMPTS[:2]:
            eng.generate(p, timeout=300)
        chrome = eng.trace.to_chrome_trace()
        eng.shutdown()
        names = {e.get("name") for e in chrome["traceEvents"]}
        assert "spec_draft" in names and "spec_verify" in names
        spec = importlib.util.spec_from_file_location(
            "trace_report", REPO / "tools" / "trace_report.py")
        tr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tr)
        evs = sorted([e for e in chrome["traceEvents"]
                      if e.get("ph") != "M"],
                     key=lambda e: e.get("ts", 0.0))
        summary = tr.summarize(evs)
        t = summary["total"]
        assert t["spec_verify_steps"] > 0
        assert t["spec_accepted_tokens"] > 0
        # accepted drafts/sweep, and total tokens landed/sweep (the
        # latter adds the corrected token: always >= accepted + ~1)
        assert t["accepted_per_step"] > 1.0
        assert t["spec_tokens_per_step"] > t["accepted_per_step"]
        rows = [r for r in summary["requests"]
                if r.get("spec_steps")]
        assert rows and all(r["acc_per_step"] is not None for r in rows)
        assert "acc_per_step" in tr.render(summary)
