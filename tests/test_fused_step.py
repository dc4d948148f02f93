"""Fused prefill+decode steps (PR 5): admission chunks piggyback on the
decode chunk call instead of stalling in-flight slots.

Three layers:

  * scheduling — step() fuses exactly when pending prefill work and
    active decode coexist (fused_steps vs decode_stall_steps), the
    fusion-off flag restores the PR4 standalone path, and a mid-stream
    chunked prefill keeps its slot reserved (free_slots / max_batch
    oversubscription regression);
  * token parity — fused schedules are token-identical to the unfused
    path on mixed admission-during-decode workloads, incl. prefix-cache
    COW admissions and chunked long prompts streaming one fused chunk
    per step;
  * accounting — fused shapes are AOT-warmed with the ladder (zero
    compiles after warmup), and failure/abort paths return every
    pending block.
"""
import importlib.util
import os

import numpy as np
import pytest
import jax

from paddle_tpu.nlp import llama, paged

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "bucket_tuner", os.path.join(_REPO, "tools", "bucket_tuner.py"))
bucket_tuner = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bucket_tuner)


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _batcher(params, cfg, max_new=8, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_total_len", 32)
    kw.setdefault("chunk", 3)
    return paged.ContinuousBatcher(params, cfg, max_new_tokens=max_new,
                                   **kw)


def _prompts(seed, lengths):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(1, 200, n))) for n in lengths]


def _mid_decode_schedule(cb, first, rest):
    """Admit `first`, step until it decodes, then land `rest` one step
    apart — every later admission arrives while a slot is decoding."""
    rids = [cb.submit(first)]
    cb.step()
    for p in rest:
        rids.append(cb.submit(p))
        cb.step()
    out = cb.run()
    return [out[r] for r in rids]


class TestFusedScheduling:
    def test_fuses_only_mid_decode(self, setup):
        """Admissions landing while slots decode piggyback (fused_steps)
        and never stall; the same schedule with fusion off pays one
        standalone stall per admission burst."""
        cfg, params = setup
        a, b, c = _prompts(71, (5, 7, 6))
        for fused in (True, False):
            cb = _batcher(params, cfg, max_batch=3,
                          prefill_buckets=(8,), fused_prefill=fused)
            _mid_decode_schedule(cb, a, [b, c])
            if fused:
                assert cb.fused_steps >= 2       # b and c piggybacked
                assert cb.decode_stall_steps == 0
            else:
                assert cb.fused_steps == 0       # escape hatch: PR4 path
                assert cb.decode_stall_steps >= 2
            assert cb.alloc.stats()["blocks_in_use"] == 0

    def test_standalone_prefill_when_decode_idle(self, setup):
        """An admission with NOTHING decoding runs standalone (no one to
        stall) — neither a fused step nor a stall."""
        cfg, params = setup
        cb = _batcher(params, cfg, fused_prefill=True)
        cb.submit(_prompts(72, (6,))[0])
        cb.run()
        assert cb.fused_steps == 0
        assert cb.decode_stall_steps == 0

    def test_chunked_prefill_reserves_slot_across_steps(self, setup):
        """Oversubscription regression: a long prompt streaming one
        fused chunk per step holds its slot the whole time — free_slots
        counts it taken, admissions never exceed max_batch, and the
        batcher refuses to hand the reserved slot to later traffic."""
        cfg, params = setup
        long_p = _prompts(73, (22,))[0]      # 6 chunks on a (4,) ladder
        a, d = _prompts(74, (6, 7))
        cb = _batcher(params, cfg, max_batch=2, prefill_buckets=(4,),
                      fused_prefill=True)
        ra = cb.submit(a)
        cb.step()                            # a decoding in slot 0
        rl = cb.submit(long_p)               # multi-chunk, mid-decode
        rd = cb.submit(d)                    # must WAIT for a slot
        cb.step()                            # long prefill now mid-stream
        # slot 0 decoding + slot 1 reserved by the pending prefill + d
        # queued: nothing left for new admissions
        assert cb._pending and cb.free_slots() == 0
        seen_active = []
        while cb._pending or cb.queue:
            cb.step()
            seen_active.append(cb.active.count(True))
            assert cb.active.count(True) <= 2
        out = cb.run()
        assert max(seen_active) <= 2
        # everyone completed despite the contention
        assert all(len(out[r]) == 8 for r in (ra, rl, rd))
        assert cb.alloc.stats()["blocks_in_use"] == 0

    def test_abort_pending_midstream_prefill_frees_blocks(self, setup):
        """Aborting a request whose chunked prefill is mid-stream (some
        chunks written, not committed) rolls back its blocks and index
        registrations — nothing else would ever free them."""
        cfg, params = setup
        cb = _batcher(params, cfg, max_batch=2, prefill_buckets=(4,),
                      prefix_cache=True, fused_prefill=True)
        ra = cb.submit(_prompts(75, (6,))[0])
        cb.step()
        rl = cb.submit(_prompts(76, (20,))[0])
        cb.step()                            # first fused chunk ran
        assert cb._pending and cb._pending[0][1] >= 1   # mid-stream
        assert cb.abort(rl) is True
        assert not cb._pending
        cb.run()
        assert cb.alloc.stats()["blocks_in_use"] == 0
        assert ra in cb.outputs and len(cb.outputs[ra]) == 8

    def test_abort_pending_requeues_poisoned_prefix_siblings(self, setup):
        """Aborting a PENDING admission must not strand a co-pending
        sibling that matched the abortee's registered prompt blocks in
        the prefix index: those blocks' KV will now never be written, so
        the sibling is rolled back and re-prepared from the queue — and
        still produces the exact tokens of a clean run (regression:
        silent garbage from a never-computed 'cached' prefix)."""
        cfg, params = setup
        w = _prompts(79, (5,))[0]
        long_p = _prompts(80, (20,))[0]      # multi-chunk pipeline head
        shared = _prompts(81, (8,))[0]       # 2 full blocks on bs=4
        pa, pb = shared + [3, 5], shared + [7, 11, 13]

        clean = _batcher(params, cfg, max_batch=4, prefill_buckets=(4,),
                         prefix_cache=True, fused_prefill=True)
        rb = clean.submit(pb)
        expect = clean.run()[rb]

        cb = _batcher(params, cfg, max_batch=4, prefill_buckets=(4,),
                      prefix_cache=True, fused_prefill=True)
        cb.submit(w)
        cb.step()                            # w decoding in slot 0
        cb.submit(long_p)                    # holds the pending head
        ra, rb = cb.submit(pa), cb.submit(pb)
        cb.step()                            # long_p mid-stream; a + b
        pending = {r.rid for r, _ in cb._pending}
        assert ra in pending and rb in pending
        assert cb.abort(ra) is True          # b's matched chain poisoned
        out = cb.run()
        assert out[rb] == expect             # token-identical to clean
        assert cb.alloc.stats()["blocks_in_use"] == 0

    def test_failed_fused_call_rolls_back_pending(self, setup,
                                                  monkeypatch):
        """A fused-call failure returns every pending record's blocks
        (the slots were never activated) — the engine's step boundary
        relies on it, exactly like the standalone path."""
        cfg, params = setup
        cb = _batcher(params, cfg, prefill_buckets=(8,),
                      fused_prefill=True)
        cb.submit(_prompts(77, (5,))[0])
        cb.step()                            # healthy admission decodes
        in_use = cb.alloc.stats()["blocks_in_use"]
        monkeypatch.setattr(
            cb, "_fused_exe",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
        cb.submit(_prompts(78, (6,))[0])
        with pytest.raises(RuntimeError, match="boom"):
            cb.step()
        # pending rolled back; the in-flight request's blocks untouched
        assert not cb._pending
        assert cb.alloc.stats()["blocks_in_use"] == in_use


class TestFusedParity:
    """Acceptance: fused steps produce bit-identical tokens to the
    unfused PR4 path on mixed admission-during-decode schedules."""

    def _both(self, params, cfg, schedule, **kw):
        outs = []
        for fused in (False, True):
            cb = _batcher(params, cfg, fused_prefill=fused, **kw)
            outs.append(schedule(cb))
            assert cb.alloc.stats()["blocks_in_use"] == 0
        assert cb.fused_steps > 0            # the fused run really fused
        self.fused_cb = cb
        return outs

    @pytest.mark.parametrize("kw", [
        dict(max_batch=2),
        # int8 KV: the fused step quantizes the decode rows' and the
        # prefill rows' writes in two passes over disjoint blocks
        dict(max_batch=2, kv_dtype="int8"),
        # two units in one call: the same program with twice the rows
        dict(max_batch=3, fused_units=2, prefill_buckets=(8,)),
    ], ids=["fp", "kv-int8", "fused-units-2"])
    def test_mid_decode_admissions_match_unfused(self, setup, kw):
        cfg, params = setup
        a, b, c, d = _prompts(81, (5, 9, 13, 3))
        base, fused = self._both(
            params, cfg,
            lambda cb: _mid_decode_schedule(cb, a, [b, c, d]), **kw)
        assert fused == base
        if kw.get("fused_units", 1) > 1:
            cb = self.fused_cb
            assert cb.fused_unit_count > cb.fused_steps

    def test_chunked_long_prompt_mid_decode_matches(self, setup):
        """A prompt past the largest bucket streams one FUSED chunk per
        step while the neighbor keeps decoding — token-identical to the
        stall-the-world unfused chunking."""
        cfg, params = setup
        a, long_p = _prompts(82, (6, 21))
        base, fused = self._both(
            params, cfg,
            lambda cb: _mid_decode_schedule(cb, a, [long_p]),
            max_batch=2, prefill_buckets=(4,))
        assert fused == base

    def test_cow_prefix_admission_mid_decode_matches(self, setup):
        """Prefix-cache interplay: a full-hit COW admission and a
        cached-prefix + long-suffix admission both land mid-decode and
        fuse; outputs match the unfused path token for token."""
        cfg, params = setup
        rng = np.random.RandomState(83)
        head = list(map(int, rng.randint(1, 200, 8)))    # 2 full blocks
        tail = list(map(int, rng.randint(1, 200, 10)))
        filler = list(map(int, rng.randint(1, 200, 5)))

        def schedule(cb):
            r0 = cb.submit(head)             # seeds the cache
            cb.run()
            r1 = cb.submit(filler)
            cb.step()                        # filler decoding
            r2 = cb.submit(head)             # full hit -> COW, mid-decode
            cb.step()
            r3 = cb.submit(head + tail)      # cached prefix + chunked tail
            out = cb.run()
            return [out[r] for r in (r0, r1, r2, r3)]

        base, fused = self._both(params, cfg, schedule, max_batch=2,
                                 prefill_buckets=(4,), prefix_cache=True)
        assert fused == base
        assert base[0] == base[2]            # COW really replayed the hit


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


class TestFusedRunsOnlyItsTokens:
    """The fused step's dense layers run over the packed tokens: B
    decode tokens plus Gp x Pb prefill positions, never a
    [B + Gp, Pb] rectangle (counts on the traced program, CPU)."""

    @pytest.mark.parametrize("B,Gp,Pb", [(4, 1, 32), (4, 2, 32)])
    def test_program_shapes(self, setup, B, Gp, Pb):
        cfg, params = setup
        cb = _batcher(params, cfg, max_batch=B, block_size=4,
                      max_total_len=64, prefill_buckets=(Pb,),
                      fused_units=Gp)
        M, T, i32 = cb.M, B + Gp * Pb, np.int32
        z = lambda shape, dt=i32: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
        jaxpr = jax.make_jaxpr(cb._build_fused())(
            params, cb.cache.pools, z((B, M)),
            z((B,)), z((B,)), z((B,), np.bool_), z((B,)), z((B,)),
            z((Gp, Pb)), z((Gp, Pb)), z((Gp, Pb), np.bool_), z((Gp, M)),
            z((Gp,))).jaxpr
        (call,) = jaxpr.eqns                 # the jitted step itself
        jaxpr = call.params["jaxpr"].jaxpr
        # the first layer scan is the mixed forward; the second scans
        # the rest of the chunk's decode steps
        scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
        assert scans[0].params["length"] == cfg.num_hidden_layers
        assert scans[1].params["length"] == cb.chunk - 1

        def weight_dots(eqns):
            # a projection is a [rows, d] x [d, f] dot (the reference
            # attention's einsums are rank 5)
            return [e for e in eqns if e.primitive.name == "dot_general"
                    and all(len(v.aval.shape) == 2 for v in e.invars)]

        proj = weight_dots(_eqns(scans[0].params["jaxpr"].jaxpr))
        assert len(proj) == 7      # q, k, v, o, gate, up, down: one each
        assert {e.invars[0].aval.shape[0] for e in proj} == {T}
        head = weight_dots(jaxpr.eqns)
        assert [e.outvars[0].aval.shape for e in head] == \
            [(B + Gp, cfg.vocab_size)]
        padded = [v.aval.shape for e in _eqns(jaxpr) for v in e.outvars
                  if tuple(v.aval.shape[:2]) == (B + Gp, Pb)]
        assert padded == []

    @pytest.mark.parametrize("units", [1, 2])
    def test_flight_record_counts_gemm_tokens(self, setup, units):
        cfg, params = setup
        a, b, c = _prompts(87, (5, 19, 6))
        cb = _batcher(params, cfg, max_batch=3, prefill_buckets=(8,),
                      fused_units=units)
        cb.submit(a)
        cb.step()
        cb.submit(b)
        cb.submit(c)
        cb.run()
        fused = [r for r in cb.flight.records() if r["mode"] == "fused"]
        assert len(fused) == cb.fused_steps > 0
        for r in fused:
            assert r["gemm_tokens"] == cb.B + r["rows"] * r["bucket"]
        assert {len(r["units"]) for r in fused} >= {units}
        assert all("gemm_tokens" not in r for r in cb.flight.records()
                   if r["mode"] != "fused")


class TestGroupGrowingAdmission:
    """Group-growing `_units` (the PR 4 follow-on): an admission burst's
    single-chunk records regroup into the EARLIEST open same-(bucket,
    cold) unit with room — interleaved buckets no longer fragment into
    singleton prefill calls — and a record never jumps over a unit
    that registered a block it depends on (matched shared-prefix chain
    or COW source), so greedy tokens are schedule-invariant."""

    @staticmethod
    def _rec(bucket, start=0, matched=(), cow_src=None, inserted=(),
             nchunks=1):
        from types import SimpleNamespace
        chunks = [(start + i * bucket, start + (i + 1) * bucket,
                   bucket) for i in range(nchunks)]
        return SimpleNamespace(chunks=chunks, matched=list(matched),
                               cow_src=cow_src,
                               inserted=list(inserted))

    @pytest.fixture(scope="class")
    def cb(self, setup):
        cfg, params = setup
        return _batcher(params, cfg, max_batch=2,
                        prefill_buckets=(8, 16))

    def test_interleaved_buckets_regroup(self, cb):
        """A-B-A-B regroups to [A,A], [B,B] when independent (the old
        consecutive rule produced four singleton units)."""
        a1 = self._rec(8, inserted=(1,))
        b1 = self._rec(16, inserted=(2,))
        a2 = self._rec(8, inserted=(3,))
        b2 = self._rec(16, inserted=(4,))
        assert cb._units([a1, b1, a2, b2]) == [[a1, a2], [b1, b2]]

    def test_unit_capacity_respected(self, cb):
        """A full unit (max_batch records) stops growing — the third
        same-key record opens a fresh unit."""
        recs = [self._rec(8, inserted=(i,)) for i in range(3)]
        assert cb._units(recs) == [[recs[0], recs[1]], [recs[2]]]

    def test_dependency_blocks_the_jump(self, cb):
        """A record whose chain references a block an INTERMEDIATE
        unit registered must not move past it — even though an
        earlier unit has room and the right key."""
        a = self._rec(8, inserted=(1,))
        b = self._rec(16, inserted=(2,))
        c = self._rec(8, cow_src=2, inserted=(3,))   # depends on b's
        assert cb._units([a, b, c]) == [[a], [b], [c]]
        # matched (non-COW) chains gate the jump identically
        d = self._rec(8, matched=(2,), inserted=(4,))
        assert cb._units([a, b, d]) == [[a], [b], [d]]
        # ... but an independent record still jumps the same gap
        e = self._rec(8, inserted=(5,))
        assert cb._units([a, b, e]) == [[a, e], [b]]

    def test_cow_never_joins_its_source_registrant(self, cb):
        """The COW clone copies the pool OUTSIDE the compiled call, so
        the source's prefill must complete in an EARLIER unit — same
        key, room available, still a new unit."""
        a = self._rec(8, inserted=(5,))
        c = self._rec(8, cow_src=5, inserted=(6,))
        assert cb._units([a, c]) == [[a], [c]]

    def test_chunked_units_stay_closed_but_jumpable(self, cb):
        """A chunked record's unit never grows; an independent later
        record jumps over it into an earlier open unit, while a
        record depending on the chunked record's blocks stays put."""
        a = self._rec(8, inserted=(1,))
        ch = self._rec(8, inserted=(2, 3), nchunks=2)
        free = self._rec(8, inserted=(4,))
        assert cb._units([a, ch, free]) == [[a, free], [ch]]
        dep = self._rec(8, matched=(3,), inserted=(5,))
        assert cb._units([a, ch, dep]) == [[a], [ch], [dep]]

    def test_tokens_schedule_invariant(self, setup):
        """The end-to-end bar: an interleaved-bucket burst landing
        mid-decode decodes token-identically whether units group-grow
        (fused), run standalone (fusion off), or arrive pre-sorted —
        the reorder changes the schedule, never the tokens."""
        cfg, params = setup
        first = _prompts(90, (4,))[0]
        prompts = _prompts(91, (5, 12, 6, 11))   # A B A B buckets

        def serve(order, fused):
            cb = _batcher(params, cfg, max_batch=4, chunk=2,
                          prefill_buckets=(8, 16),
                          fused_prefill=fused, fused_units=2)
            cb.submit(first)
            cb.step()                            # burst lands mid-decode
            rids = {i: cb.submit(prompts[i]) for i in order}
            out = cb.run()
            return [out[rids[i]] for i in range(len(prompts))]

        ref = serve([0, 1, 2, 3], fused=False)
        assert serve([0, 1, 2, 3], fused=True) == ref
        assert serve([0, 2, 1, 3], fused=True) == ref   # pre-sorted

    def test_cow_burst_schedule_invariant(self, setup):
        """Same-prompt pair (the second COW-clones the first's tail)
        split by an alien-bucket record: the clone may not jump its
        source, and tokens still match the standalone schedule."""
        cfg, params = setup
        (p, q) = _prompts(92, (6, 12))

        def serve(fused):
            cb = _batcher(params, cfg, max_batch=4, chunk=2,
                          prefill_buckets=(8, 16), prefix_cache=True,
                          fused_prefill=fused, fused_units=2)
            r = [cb.submit(list(p)), cb.submit(q),
                 cb.submit(list(p))]
            out = cb.run()
            assert cb.prefix_stats()["hits"] >= 1
            return [out[x] for x in r]

        assert serve(True) == serve(False)


class TestBucketTuner:
    """tools/bucket_tuner.py: the pad-minimizing ladder fit over the
    batcher's `prefill_suffix_hist` accounting (pure host DP — no
    model)."""

    def test_pad_cost_matches_bucket_rule(self):
        hist = {3: 2, 5: 1, 9: 4}
        # ladder (4, 16): 3->4 (x2), 5->16, 9->16 (x4)
        assert bucket_tuner.pad_cost(hist, [4, 16]) == \
            2 * 1 + 11 + 4 * 7

    def test_fit_is_optimal_and_covers_max(self):
        hist = {3: 10, 4: 10, 16: 1}
        ladder, pad = bucket_tuner.fit_ladder(hist, 2)
        # one bucket at 4 (pad 10), one at 16 — beats (3,16): pad 130
        assert ladder == [4, 16] and pad == 10
        # k >= distinct lengths: zero pad, buckets ON the lengths
        ladder, pad = bucket_tuner.fit_ladder(hist, 5)
        assert ladder == [3, 4, 16] and pad == 0
        # one bucket: everything pads to the max length
        ladder, pad = bucket_tuner.fit_ladder(hist, 1)
        assert ladder == [16] == [max(hist)]
        assert pad == bucket_tuner.pad_cost(hist, ladder)

    def test_tune_reads_bench_record(self):
        rec = {"prefill_suffix_hist": {"3": 4, "6": 2, "14": 1},
               "prefill_buckets": [8, 16]}
        r = bucket_tuner.tune(rec)          # same 2-bucket budget
        assert r["observed_ladder"] == [8, 16]
        assert len(r["recommended_ladder"]) <= 2
        assert (r["pad_tokens_recommended"]
                <= r["pad_tokens_current_ladder"])
        dense = bucket_tuner.tune(rec, max_buckets=3)
        assert dense["pad_tokens_recommended"] == 0   # one per length

    def test_batcher_records_real_chunk_lengths(self, setup):
        """The histogram feeding the tuner holds PRE-padding lengths:
        a 5-token prompt on an (8,) ladder records 5, not 8; a chunked
        prompt records each chunk."""
        cfg, params = setup
        cb = _batcher(params, cfg, prefill_buckets=(4,))
        cb.submit(_prompts(90, (3,))[0])
        cb.submit(_prompts(90, (9,))[0])    # chunks 4 + 4 + 1
        cb.run()
        assert cb.prefill_suffix_hist == {3: 1, 4: 2, 1: 1}


class TestFusedCompileAccounting:
    def test_no_compiles_after_warmup_with_fusion(self, setup):
        """warmup_prefill covers the fused (group, bucket) ladder too:
        a mixed admission-during-decode run — groups, COW, chunked long
        prompts — never compiles a new shape afterwards."""
        cfg, params = setup
        cb = _batcher(params, cfg, max_batch=2, prefill_buckets=(4, 8),
                      prefix_cache=True, fused_prefill=True)
        warmed = cb.warmup_prefill()
        # standalone ladder x groups {1,2} x {cold,cached} + fused
        # row-counts x ladder + the standalone-decode chunk. Fused
        # rows: only REACHABLE counts warm — at max_batch=2 a fused
        # step needs 1 active slot, leaving 1 for pending records, so
        # only the single-record unit shape (rows=1) can ever run
        assert warmed == 2 * 2 * 2 + 2 * 1 + 1
        c0 = cb.compile_count
        a, b, long_p = _prompts(84, (5, 7, 19))
        _mid_decode_schedule(cb, a, [b, long_p])
        cb.submit(a)                          # warm repeat (cache hit)
        cb.run()
        assert cb.fused_steps > 0
        assert cb.compile_count == c0          # NEVER recompiled

    def test_decode_only_stretch_after_fused_is_warm(self, setup):
        """The warmup bugfix: the plain decode chunk is AOT-warmed with
        the ladder, so a decode-only stretch AFTER a fused stretch (all
        of whose steps ran the fused executable) compiles nothing. The
        flatness gate is `compile_count` — `prefill_compile_count`
        never saw the chunk fn, which is exactly how the lazy compile
        used to slip through."""
        cfg, params = setup
        cb = _batcher(params, cfg, max_batch=2, prefill_buckets=(8,),
                      fused_prefill=True)
        cb.warmup_prefill()
        c0 = cb.compile_count
        assert len(cb._chunk_cache) == 1      # the chunk warmed too
        a, b = _prompts(85, (5, 7))
        # fused stretch: b lands while a decodes -> every device call so
        # far is either a standalone prefill or the FUSED executable
        cb.submit(a)
        cb.step()
        cb.submit(b)
        cb.step()
        assert cb.fused_steps >= 1
        # decode-only stretch: nothing pending, plain chunk steps
        while any(cb.active):
            cb.step()
        assert cb.compile_count == c0

    def test_multi_unit_piggyback_drains_burst(self, setup):
        """fused_units=2: one fused call carries TWO pending units — a
        chunked long prompt's current chunk AND the short admission
        behind it (same bucket; consecutive single-chunk records merge
        into one group unit, so a chunked record is what makes two
        units co-pend) — with fused_unit_count > fused_steps and tokens
        identical to the single-unit schedule."""
        cfg, params = setup
        first, b, c = _prompts(86, (5, 19, 6))

        outs = []
        for units in (1, 2):
            cb = _batcher(params, cfg, max_batch=3, prefill_buckets=(8,),
                          fused_prefill=True, fused_units=units)
            cb.warmup_prefill()
            c0 = cb.compile_count
            rids = [cb.submit(first)]
            cb.step()
            # burst of two admissions while `first` decodes
            rids += [cb.submit(b), cb.submit(c)]
            out = cb.run()
            assert cb.compile_count == c0      # multi-unit shapes warmed
            assert cb.alloc.stats()["blocks_in_use"] == 0
            if units == 2:
                assert cb.fused_unit_count > cb.fused_steps
            else:
                assert cb.fused_unit_count == cb.fused_steps
            outs.append([out[r] for r in rids])
        assert outs[0] == outs[1]

    def test_fused_units_validation(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError):
            _batcher(params, cfg, fused_units=0)
