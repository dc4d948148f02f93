"""Paged KV-cache serving (VERDICT r4 missing 2): block-table cache over
one shared pool, ragged batch admission, decode parity vs the dense path,
and allocator-level pool-reuse evidence.

Reference analog: upstream fused block_multihead_attention + PaddleNLP
serving's block manager (upstream-canonical, unverified — SURVEY.md §0).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.nlp import llama, generation, paged


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class TestPagedGenerate:
    def test_equal_lengths_match_dense_greedy(self, setup):
        cfg, params = setup
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(1, 200, (3, 12)), jnp.int32)
        dense = generation.generate(params, prompt, cfg, max_new_tokens=6,
                                    greedy=True)
        out, alloc, _ = paged.paged_generate(
            params, prompt, np.full((3,), 12), cfg, max_new_tokens=6,
            block_size=4)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(dense))

    def test_mixed_lengths_match_per_request_dense(self, setup):
        """Requests of DIFFERENT lengths decode in ONE paged batch and
        match each request's individual dense run — the dense batch path
        cannot admit this shape without re-padding."""
        cfg, params = setup
        rng = np.random.RandomState(1)
        lens = [5, 9, 12]
        pmax = max(lens)
        rows = np.zeros((3, pmax), np.int64)
        for i, L in enumerate(lens):
            rows[i, :L] = rng.randint(1, 200, L)
        out, alloc, _ = paged.paged_generate(
            params, jnp.asarray(rows, jnp.int32), np.asarray(lens), cfg,
            max_new_tokens=5, block_size=4)
        for i, L in enumerate(lens):
            single = generation.generate(
                params, jnp.asarray(rows[None, i, :L], jnp.int32), cfg,
                max_new_tokens=5, greedy=True)
            np.testing.assert_array_equal(np.asarray(out[i]),
                                          np.asarray(single[0]),
                                          err_msg=f"request {i} (len {L})")

    def test_pool_reuse_and_memory_analysis(self, setup):
        """Completed requests' blocks are reused by later admissions; the
        pool's high-water mark tracks the SUM of ragged lengths, not
        B x T_max (the dense cache's footprint)."""
        cfg, params = setup
        block_size = 4
        max_new = 4
        lens = np.asarray([3, 7])
        pmax, B = 7, 2
        rows = np.zeros((B, pmax), np.int64)
        rng = np.random.RandomState(2)
        for i, L in enumerate(lens):
            rows[i, :L] = rng.randint(1, 200, L)
        # pool sized for exactly one ragged batch
        per_req = -(-(lens.max() + max_new) // block_size)
        alloc = paged.BlockAllocator(B * per_req)
        out1, alloc, owned1 = paged.paged_generate(
            params, jnp.asarray(rows, jnp.int32), lens, cfg,
            max_new_tokens=max_new, block_size=block_size, allocator=alloc)
        assert alloc.stats()["blocks_in_use"] == B * per_req
        with pytest.raises(RuntimeError):   # pool full while batch 1 holds it
            paged.build_table(alloc, lens, int(lens.max()) + max_new,
                              block_size)
        for blocks in owned1:               # batch 1 completes
            alloc.free(blocks)
        out2, alloc, owned2 = paged.paged_generate(
            params, jnp.asarray(rows, jnp.int32), lens, cfg,
            max_new_tokens=max_new, block_size=block_size, allocator=alloc)
        stats = alloc.stats()
        assert stats["reused_blocks"] >= B * per_req    # real pool reuse
        assert stats["high_water_blocks"] == B * per_req
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    def test_predictor_enable_paged_kv(self, setup, tmp_path):
        from paddle_tpu import inference
        from paddle_tpu.inference.llm import save_llm
        cfg, params = setup
        prefix = str(tmp_path / "m")
        save_llm(prefix, params, cfg)
        config = inference.Config(prefix)
        config.enable_llm_generation(max_new_tokens=4, pad_token_id=0)
        config.enable_paged_kv(block_size=4)
        pred = inference.create_predictor(config)
        rows = np.zeros((2, 8), np.int64)
        rows[0, :8] = np.arange(1, 9)
        rows[1, :5] = np.arange(1, 6)
        out = pred.run([rows])[0]
        assert out.shape == (2, 4)
        assert pred._paged_stats["high_water_blocks"] > 0
        # the allocator persists across run() calls: the second request
        # batch reuses the blocks the first released
        out2 = pred.run([rows])[0]
        np.testing.assert_array_equal(out2, out)
        assert pred._paged_stats["reused_blocks"] > 0


class TestBlockAllocatorFree:
    """Regression: free() used to silently accept duplicate and
    out-of-range block ids — a double free splices a block into the
    free list twice, and two later requests then share (and corrupt)
    one KV block."""

    def test_double_free_raises(self):
        alloc = paged.BlockAllocator(4)
        blocks = alloc.allocate(2)
        alloc.free(blocks)
        with pytest.raises(ValueError, match="already free"):
            alloc.free(blocks)                  # freed twice
        assert alloc.free_blocks == 4           # first free stuck

    def test_duplicate_within_one_call_raises(self):
        alloc = paged.BlockAllocator(4)
        b = alloc.allocate(1)
        with pytest.raises(ValueError, match="already free"):
            alloc.free([b[0], b[0]])
        # the failed call must not have half-applied
        assert alloc.free_blocks == 3
        alloc.free(b)
        assert alloc.free_blocks == 4

    def test_out_of_range_raises(self):
        alloc = paged.BlockAllocator(4)
        with pytest.raises(ValueError, match="out of range"):
            alloc.free([4])
        with pytest.raises(ValueError, match="out of range"):
            alloc.free([-1])

    def test_free_list_never_grows_past_capacity(self):
        alloc = paged.BlockAllocator(2)
        blocks = alloc.allocate(2)
        alloc.free(blocks)
        with pytest.raises(ValueError):
            alloc.free([0])
        assert alloc.free_blocks == alloc.num_blocks


class TestContinuousBatching:
    """Continuous batching over the block pool: more requests than batch
    slots, admission into freed slots mid-stream, outputs matching each
    request's individual dense greedy run."""

    def test_three_requests_two_slots(self, setup):
        cfg, params = setup
        rng = np.random.RandomState(7)
        prompts = [list(rng.randint(1, 200, L)) for L in (5, 9, 7)]
        max_new = 6
        # pool sized so the third request can only be admitted by
        # reusing blocks the first two released
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=2, block_size=4, max_total_len=32,
            max_new_tokens=max_new, chunk=3, num_blocks=8)
        rids = [cb.submit(p) for p in prompts]
        out = cb.run()
        assert cb.alloc.stats()["reused_blocks"] > 0  # slot recycled
        for rid, p in zip(rids, prompts):
            dense = generation.generate(
                params, jnp.asarray([p], jnp.int32), cfg,
                max_new_tokens=max_new, greedy=True)
            np.testing.assert_array_equal(
                np.asarray(out[rid]), np.asarray(dense[0]),
                err_msg=f"request {rid}")

    def test_eos_frees_slot_early(self, setup):
        cfg, params = setup
        rng = np.random.RandomState(8)
        p = list(rng.randint(1, 200, 6))
        # discover this prompt's first generated token, then use it as eos
        probe = generation.generate(params, jnp.asarray([p], jnp.int32),
                                    cfg, max_new_tokens=2, greedy=True)
        eos = int(probe[0, 0])
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=1, block_size=4, max_total_len=32,
            max_new_tokens=8, eos_token_id=eos, chunk=4)
        r1 = cb.submit(p)
        r2 = cb.submit(list(rng.randint(1, 200, 4)))
        out = cb.run()
        assert out[r1] == [eos]          # stopped at eos immediately
        assert len(out[r2]) >= 1         # second request got the slot

    def test_chunk_overrun_does_not_corrupt_neighbor(self, setup):
        """A fixed-size chunk much larger than a request's budget must
        deactivate the slot ON DEVICE — continuing to write would spill
        through the table row's padding into block 0 (another request's
        cache). Regression: the first-admitted request's output must
        still match its dense run while sharing the pool."""
        cfg, params = setup
        rng = np.random.RandomState(9)
        p0 = list(rng.randint(1, 200, 4))   # owns block 0
        p1 = list(rng.randint(1, 200, 4))
        max_new = 2
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=2, block_size=4, max_total_len=16,
            max_new_tokens=max_new, chunk=8)   # chunk >> budget
        r0, r1 = cb.submit(p0), cb.submit(p1)
        out = cb.run()
        for rid, p in ((r0, p0), (r1, p1)):
            dense = generation.generate(
                params, jnp.asarray([p], jnp.int32), cfg,
                max_new_tokens=max_new, greedy=True)
            np.testing.assert_array_equal(np.asarray(out[rid]),
                                          np.asarray(dense[0]))

    def test_admission_defers_when_pool_short(self, setup):
        """A free batch slot without enough free blocks DEFERS admission
        until a request retires (instead of aborting the run)."""
        cfg, params = setup
        rng = np.random.RandomState(10)
        p = [list(rng.randint(1, 200, 4)) for _ in range(2)]
        # 3 blocks per request; pool of 4: second must wait for the first
        cb = paged.ContinuousBatcher(
            params, cfg, max_batch=2, block_size=4, max_total_len=16,
            max_new_tokens=4, chunk=2, num_blocks=4)
        rids = [cb.submit(x) for x in p]
        out = cb.run()
        for rid, pr in zip(rids, p):
            dense = generation.generate(
                params, jnp.asarray([pr], jnp.int32), cfg,
                max_new_tokens=4, greedy=True)
            np.testing.assert_array_equal(np.asarray(out[rid]),
                                          np.asarray(dense[0]))
        # a single over-sized request still fails loudly
        big = paged.ContinuousBatcher(
            params, cfg, max_batch=1, block_size=4, max_total_len=64,
            max_new_tokens=40, chunk=2, num_blocks=2)
        big.submit(list(rng.randint(1, 200, 8)))
        with pytest.raises(RuntimeError):
            big.run()


# ---- a layer's blocks are addressed IN the stacked pool ------------------
_L, _N, _BS, _M = 3, 12, 4, 4                      # block 11 is never assigned


def _sliced_forward(params, groups, pools, cfg, is_prefill, mesh=None):
    """The reference of `_forward_groups` for a dense decoder whose GQA
    layers are all alike, as the program ran it before a layer's blocks
    were addressed in place: layer `li`'s K and V pools (and int8
    scales) are sliced out of the stack, `_attention_paged` runs on that
    one layer's pool through the rows' own tables, and the result is
    written back into the stack."""
    from paddle_tpu.kernels.rms_norm import rms_norm_ref
    from paddle_tpu.kernels.rope import rope_freqs
    from paddle_tpu.nlp.generation import _mlp_cached
    x = jnp.take(params["embed_tokens"],
                 paged._pack_rows([g.tokens for g in groups]),
                 axis=0).astype(cfg.dtype)
    cos, sin = rope_freqs(cfg.head_dim, _M * _BS, cfg.rope_theta, jnp.float32)

    def body(carry, lp):
        x, stacks, li = carry
        one = [None if p is None else p[li] for p in stacks]
        h = rms_norm_ref(x, lp["input_layernorm"], cfg.rms_norm_eps)
        a, *one = paged._attention_paged(
            h, lp, cfg, cos, sin, one[0], one[1], groups, is_prefill, "xla",
            one[2], one[3], mesh=mesh)
        stacks = tuple(None if p is None else p.at[li].set(new)
                       for p, new in zip(stacks, one))
        x = x + a
        h = rms_norm_ref(x, lp["post_attention_layernorm"], cfg.rms_norm_eps)
        return (x + _mlp_cached(h, lp, cfg), stacks, li + 1), None

    (x, stacks, _), _ = jax.lax.scan(
        body, (x, tuple(pools), jnp.int32(0)), params["layers"])
    return x, stacks


def _rows(rng, tables, starts, widths, P):
    """A row group of len(tables) rows of P tokens: row r holds `widths[r]`
    valid tokens from position `starts[r]` on (0: an invalid row, whose
    table is unassigned), the rest padding."""
    G = len(tables)
    pos = np.asarray(starts)[:, None] + np.arange(P)[None, :]
    valid = np.arange(P)[None, :] < np.asarray(widths)[:, None]
    table = np.full((G, _M), -1, np.int32)
    for r, blocks in enumerate(tables):
        table[r, :len(blocks)] = blocks
    return paged._RowGroup(
        jnp.asarray(rng.randint(1, 200, (G, P)), jnp.int32),
        jnp.asarray(table), jnp.asarray(np.where(valid, pos, 0), jnp.int32),
        jnp.asarray(valid))


def _in_place_case(mode, rng):
    decode = ([[0, 1], [2, 3, 4], []], [5, 9, 0], [1, 1, 0], 1)
    suffix = ([[5, 6, 7], [8, 9], []], [4, 2, 0], [4, 3, 0], 4)
    if mode == "decode":
        return (_rows(rng, *decode),), False
    if mode == "warm-suffix":
        return (_rows(rng, *suffix),), False
    if mode == "fused":
        return (_rows(rng, *decode), _rows(rng, *suffix)), False
    return (_rows(rng, [[0, 1], [2, 3], []], [0, 0, 0], [8, 5, 0], 8),), True


@pytest.fixture(scope="module")
def in_place_model():
    # 4 KV heads: the pool's head axis splits over the 4-device mesh
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=_L,
                                 num_key_value_heads=4)
    return cfg, llama.init_params(jax.random.PRNGKey(3), cfg)


@pytest.mark.parametrize("tp", [1, 4])
@pytest.mark.parametrize("mode", ["decode", "warm-suffix", "fused", "cold"])
@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_layer_blocks_in_place_match_sliced_reference(in_place_model,
                                                      kv_dtype, mode, tp):
    """`_forward_groups` writes and reads layer li's blocks in the pool
    stacked over layers (block ids offset by li * N): the valid tokens'
    logits and the WHOLE pool it returns equal, bit for bit on the `xla`
    backend, those of the reference that slices the layer out and writes it back; a
    table's unassigned columns (-1, which the offset turns into the last
    block of layer li - 1) and an invalid row touch nothing."""
    from paddle_tpu.nlp.generation import _final_head_cached
    from paddle_tpu.serving.tp import MeshConfig, build_shardings
    cfg, params = in_place_model
    rng = np.random.RandomState(5)
    shape = (_L, _N, _BS, cfg.num_key_value_heads, cfg.head_dim)
    if kv_dtype == "int8":
        pools = tuple(jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
                      for _ in range(2)) + tuple(
            jnp.asarray(rng.uniform(1e-3, 3e-3, (_L, _N)), jnp.float32)
            for _ in range(2))
    else:
        pools = tuple(jnp.asarray(rng.randn(*shape), cfg.dtype)
                      for _ in range(2)) + (None, None)
    groups, is_prefill = _in_place_case(mode, rng)
    mesh = None
    if tp > 1:
        mesh, on_params, on_pool, repl = build_shardings(
            MeshConfig(tp=tp), cfg, params)
        params = jax.device_put(params, on_params)
        pools = tuple(jax.device_put(p, on_pool) for p in pools[:2]) + tuple(
            None if p is None else jax.device_put(p, repl) for p in pools[2:])
        groups = jax.device_put(groups, repl)

    def run(forward):
        def fn(params, groups, pools):
            x, new = forward(params, groups, pools)[:2]
            return _final_head_cached(params, x, cfg), new
        return jax.jit(fn)(params, groups, pools)

    logits, new = run(lambda p, g, s: paged._forward_groups(
        p, g, s, cfg, is_prefill, "xla", mesh=mesh))
    want_logits, want = run(lambda p, g, s: _sliced_forward(
        p, g, s, cfg, is_prefill, mesh=mesh))
    # an invalid token's logits are never read, and part: its attention
    # reads whatever block its unassigned column names
    live = np.asarray(paged._pack_rows([g.valid for g in groups]))
    assert live.any() and not live.all()
    np.testing.assert_array_equal(np.asarray(logits, np.float32)[live],
                                  np.asarray(want_logits, np.float32)[live])
    written = set()
    for g in groups:
        t, pos, val = (np.asarray(a) for a in (g.table, g.positions, g.valid))
        written |= {int(t[r, pos[r, p] // _BS])
                    for r, p in zip(*np.nonzero(val))}
    assert written and -1 not in written and _N - 1 not in written
    rest = sorted(set(range(_N)) - written)
    for was, got, ref in zip(pools, new, want):
        if was is None:
            assert got is None and ref is None
            continue
        assert got.shape == was.shape and got.dtype == was.dtype
        got, was = np.asarray(got, np.float32), np.asarray(was, np.float32)
        np.testing.assert_array_equal(got, np.asarray(ref, np.float32))
        # every layer: nothing but the valid rows' blocks changed, the
        # block before layer li's first (layer li - 1's last) least of all
        np.testing.assert_array_equal(got[:, rest], was[:, rest])
        if got.ndim > 2:                     # K and V: every layer wrote
            assert all((got[li, b] != was[li, b]).any()
                       for li in range(_L) for b in written)
