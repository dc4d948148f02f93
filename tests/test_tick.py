"""The batcher's tick helper (nlp/paged.py `_Tick`): one description of a
tick yields its flight record (written before the call, closed after it),
its `RecordEvent` spans, its profiler sample and its device names. No
profiler runs here: `RecordEvent` is patched, and the names are read from
the lowered program."""
import re
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu import profiler, serving
from paddle_tpu.nlp import llama, paged
from paddle_tpu.serving import FlightRecorder
from paddle_tpu.serving.faults import FaultInjector, InjectedFault

PHASES = ("pack_s", "dispatch_s", "wait_s", "commit_s")
SCOPES = ("embed", "attn_qkv", "attn_kernel", "kv_pool_write", "attn_out",
          "mlp", "lm_head", "sample")
_RNG = np.random.RandomState(11)
SHORT = list(map(int, _RNG.randint(1, 200, 5)))
LONG = list(map(int, _RNG.randint(1, 200, 20)))     # 3 chunks at bucket 8


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _batcher(setup, **kw):
    cfg, params = setup
    kw = {"max_batch": 2, "block_size": 4, "max_total_len": 48,
          "max_new_tokens": 8, "chunk": 2, "max_prefill_bucket": 8, **kw}
    return paged.ContinuousBatcher(params, cfg, **kw)


def _serve_mixed(cb):
    """A short prompt decodes; a long one joins and streams its chunks
    fused; then plain chunks to the end. Returns the two rids."""
    r1 = cb.submit(SHORT)
    cb.step()
    r2 = cb.submit(LONG)
    cb.run()
    return r1, r2


class _Spans:
    """Stands in for `RecordEvent`: keeps every span opened, in order."""

    def __init__(self):
        self.opened = []

    def __call__(self, name, event_type=None, **attrs):
        spans = self

        class Span:
            def begin(self):
                spans.opened.append((name, attrs))

            def end(self):
                pass

            def __enter__(self):
                self.begin()
                return self

            def __exit__(self, *exc):
                return False
        return Span()


# ---- the record: written before the call, closed after it ---------------
def test_flight_recorder_close_adds_to_the_last_record():
    fr = FlightRecorder(cap=4)
    assert fr.record("decode", rids=[1]) == 0
    assert fr.record("fused", rids=[2]) == 1
    fr.close(wait_s=0.5, live_after=1)
    first, last = fr.records()
    assert "closed" not in first and "wait_s" not in first
    assert last["closed"] is True and last["wait_s"] == 0.5
    assert last["mode"] == "fused" and last["seq"] == 1
    FlightRecorder().close(wait_s=1.0)          # an empty ring: no-op


@pytest.mark.parametrize("kind", ["plain", "speculative"])
def test_every_kind_of_tick_closes_its_record(setup, kind):
    cb = _batcher(setup, speculative=kind == "speculative", spec_k=2)
    _serve_mixed(cb)
    recs = cb.flight.records()
    want = {"prefill", "fused", "decode"} if kind == "plain" \
        else {"prefill", "fused", "spec_draft", "spec_verify"}
    assert {r["mode"] for r in recs} >= want
    for r in recs:
        assert r["closed"] is True
        for key in PHASES:
            assert r[key] >= 0.0, (key, r)
        assert r["dispatch_s"] > 0.0 and r["t_dispatch"] >= r["t"]
        assert isinstance(r["live_after"], int)
        # the latent kernel's counters: not a GQA engine's to carry
        assert "attn_work_steps" not in r and "attn_grid_steps" not in r
        if r["synced"]:
            assert r["wait_s"] > 0.0
            assert r["t_synced"] >= r["t_dispatch"] + r["dispatch_s"]
        else:
            # what reads nothing back: a non-final standalone chunk, and
            # the draft (the verify's wait covers it)
            assert r["mode"] == "spec_draft" or r["final"] is False
            assert r["t_synced"] is None and r["wait_s"] == 0.0
    # the slots still decoding when a tick ended are the next tick's
    for a, b in zip(recs, recs[1:]):
        if b["mode"] in ("decode", "spec_draft"):
            assert a["live_after"] == b["active_slots"] > 0
    assert recs[-1]["live_after"] == 0


def test_non_final_standalone_chunk_closes_unsynced(setup):
    cb = _batcher(setup)
    cb.submit(LONG)                 # nothing decodes: chunks run standalone
    cb.run()
    pre = [r for r in cb.flight.records() if r["mode"] == "prefill"]
    assert [r["final"] for r in pre] == [False, False, True]
    assert [r["synced"] for r in pre] == [False, False, True]
    assert [r["prefill_spans"] for r in pre] == \
        [[[0, 8]], [[8, 16]], [[16, 20]]]
    assert [r["cold"] for r in pre] == [True, False, False]


def test_tick_that_raises_leaves_its_record_last_and_unclosed(setup):
    inj = FaultInjector()
    cb = _batcher(setup, fault_injector=inj)
    cb.submit(SHORT)
    cb.step()
    seq = cb.flight.seq
    inj.fail_on_step(inj.calls + 1)
    with pytest.raises(InjectedFault):
        cb.step()
    last = cb.flight.records()[-1]
    assert last["seq"] == seq and last["mode"] == "decode"
    assert "closed" not in last and "wait_s" not in last
    # the span was closed all the same, and the next tick runs
    cb.run()
    assert cb.flight.records()[-1]["closed"] is True


def test_counts_agree_with_the_prompts_served(setup):
    cb = _batcher(setup, max_new_tokens=16)
    r1, r2 = _serve_mixed(cb)
    recs = cb.flight.records()
    fused = [r for r in recs if r["mode"] == "fused"]
    assert [r["prefill_spans"] for r in fused] == \
        [[[0, 8]], [[8, 16]], [[16, 20]]]
    assert all(r["chunk"] == cb.chunk for r in fused)
    # r1 decodes alone in the fused ticks. The keys its first step sees:
    # the prompt, its tokens so far (the prefill's one and a chunk of 2),
    # the last of them being written now; two more every chunk
    assert [r["decode_ctx"] for r in fused] == \
        [[len(SHORT) + 3], [len(SHORT) + 5], [len(SHORT) + 7]]
    plain = next(r for r in recs if r["mode"] == "decode" and r2 in r["rids"])
    ctx = dict(zip(plain["rids"], plain["decode_ctx"]))
    assert ctx == {r1: len(SHORT) + 9, r2: len(LONG) + 1}
    assert recs[0]["mode"] == "prefill" and "decode_ctx" not in recs[0]


# ---- the spans ----------------------------------------------------------
def test_span_names_of_one_fused_and_one_plain_tick(setup, monkeypatch):
    cb = _batcher(setup)
    cb.submit(SHORT)
    cb.step()                       # standalone prefill, a decode chunk
    cb.submit(LONG)
    spans = _Spans()
    monkeypatch.setattr(paged, "RecordEvent", spans)
    cb.step()                       # a fused tick, its program's first
    names = [n for n, _ in spans.opened]
    # the compile shows under its own name, inside the tick that paid it
    assert names.index("serve.tick") < names.index("serve.compile")
    assert dict(spans.opened)["serve.compile"]["program"] == \
        "jit_serve_fused_step"
    names.remove("serve.compile")
    assert names == ["serve.admit", "serve.tick", "serve.pack",
                     "serve.dispatch", "serve.wait", "serve.commit",
                     "serve.admit"]
    tick = dict(spans.opened)["serve.tick"]
    assert tick == {"seq": cb.flight.seq - 1, "mode": "fused"}
    while cb._pending:
        cb.step()
    spans.opened.clear()
    cb.step()                       # a plain tick
    assert [n for n, _ in spans.opened] == names
    assert dict(spans.opened)["serve.tick"] == \
        {"seq": cb.flight.seq - 1, "mode": "decode"}


def test_record_event_hands_attributes_to_the_annotation(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with profiler.RecordEvent("serve.tick", seq=7, mode="fused"):
        pass
    ev = profiler.RecordEvent("plain")
    ev.begin()
    ev.end()
    assert seen == [("serve.tick", {"seq": 7, "mode": "fused"}),
                    ("plain", {})]


def test_engine_loop_opens_deliver_and_housekeeping(setup, monkeypatch):
    from paddle_tpu.serving import engine as engine_mod
    cfg, params = setup
    spans = _Spans()
    monkeypatch.setattr(engine_mod, "RecordEvent", spans)
    eng = serving.ServingEngine(
        params, cfg, max_batch=2, block_size=4, max_total_len=32,
        max_new_tokens=4, chunk=2)

    def asleep():               # the loop's last span is its sleep
        t_end = time.monotonic() + 30
        while time.monotonic() < t_end:
            if spans.opened and spans.opened[-1][0] == "engine.idle":
                return True
            time.sleep(0.005)
        return False

    assert asleep()
    eng.generate(SHORT, timeout=300)
    assert asleep()
    eng.shutdown()
    names = [n for n, _ in spans.opened]
    assert set(names) == {"engine.housekeeping", "engine.deliver",
                          "engine.idle"}
    # every step the loop made was delivered, after its housekeeping
    assert names[0] == "engine.housekeeping"
    assert names.count("engine.deliver") >= 2
    # the loop slept before the request came and after it left, and
    # between its first step and its last delivery not once
    busy = names[names.index("engine.deliver"):
                 len(names) - names[::-1].index("engine.deliver")]
    assert names.index("engine.idle") < names.index("engine.deliver")
    assert "engine.idle" not in busy


# ---- no fence, no compile -----------------------------------------------
def test_no_tick_fences_outside_a_capture_window(setup, monkeypatch):
    """200 ticks and more at the default profile_sample_every: the
    profiler gets every synced tick, and nothing blocks on the device
    but the ticks' own read-backs."""
    fences = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: fences.append(1) or x)
    cb = _batcher(setup, max_total_len=256, max_new_tokens=210, chunk=1)
    assert cb.profiler.sample_every == 64
    for _ in range(2):
        cb.submit(SHORT)
    cb.run()
    assert cb.flight.seq >= 200 and fences == []
    assert cb.profiler.report()["samples"] == cb.flight.seq


def test_zero_compiles_after_warmup_with_the_helper_on(setup):
    cfg, params = setup
    eng = serving.ServingEngine(
        params, cfg, max_batch=2, block_size=4, max_total_len=48,
        max_new_tokens=6, chunk=2, max_prefill_bucket=8, start=False)
    eng.warmup()
    eng.start()
    warm = eng.batcher.compile_count
    hs = [eng.submit(p) for p in (SHORT, LONG, SHORT[:3])]
    for h in hs:
        h.result(timeout=300)
    assert eng.batcher.compile_count == warm
    assert all(r["closed"] for r in eng.batcher.flight.records())
    eng.shutdown()


# ---- the device names ---------------------------------------------------
def _lowered_decode_step(cb):
    cb._chunk_exe()                                 # builds the jit
    sds, i32, B = cb._aval, jnp.int32, cb.B
    return cb._chunk_fn.lower(
        cb._pstruct(), cb._pools_aval(), sds((B, cb._table_width), i32),
        sds((B,), i32), sds((B,), jnp.bool_),
        sds((B,), i32), sds((B,), i32), sds((B,), i32)
    ).as_text(debug_info=True)


def test_lowered_decode_step_names_scopes_and_kernel_once(setup):
    cb = _batcher(setup, attention_impl="pallas")
    text = _lowered_decode_step(cb)
    assert "module @jit_serve_decode_step" in text
    # one kernel function, called from one place: the layer scan's body
    assert len(re.findall(r"func\.func private @ragged_paged_attention\b",
                          text)) == 1
    assert len(re.findall(r"call @ragged_paged_attention\b", text)) == 1
    # name stacks: whole ones at the top level, relative ones inside the
    # scan bodies' functions
    paths = {tuple(p.split("/")) for p in re.findall(r'loc\("([^"]*)"', text)}
    for scope in SCOPES:
        under = [p for p in paths if scope in p]
        assert under, f"no operation under scope {scope}"
        # a scope is entered once on any path: never nested in itself
        assert all(p.count(scope) == 1 for p in under), scope
    calls = [p for p in paths if "jit(ragged_paged_attention)" in p]
    assert calls and all(
        p[p.index("jit(ragged_paged_attention)") - 1] == "attn_kernel"
        for p in calls)
    # a layer's blocks are addressed in place in the stacked pool: no
    # scope reads a layer out, and the write scope is the scatter alone
    assert not any("kv_pool_read" in p for p in paths)
    assert any(p[0] == "kv_pool_write" and "scatter" in p[-1]
               for p in paths)
    assert not any("dynamic_update_slice" in p[-1] or
                   "dynamic_slice" in p[-1] for p in paths
                   if "kv_pool_write" in p)


def test_step_programs_and_kernels_have_their_stable_names(setup):
    from paddle_tpu.nlp import train
    cb = _batcher(setup)
    cb.submit(SHORT)
    cb.step()
    cb.submit(LONG)
    cb.run()
    assert cb._chunk_fn.__name__ == "serve_decode_step"
    assert cb._fused_fn.__name__ == "serve_fused_step"
    assert {f.__name__ for f in cb._prefill_fns.values()} == \
        {"serve_prefill_step"}
    import optax
    cfg, _ = setup
    assert train.make_train_step(cfg, optax.sgd(1e-3)).__name__ == \
        "train_step"
    # a kernel's event is named after the jitted function round its
    # pallas_call, whatever the Python identifier is (kernels/naming.py)
    from paddle_tpu.kernels import rms_norm
    from paddle_tpu.nlp import ragged_attention
    from paddle_tpu.optimizer import quant_state
    assert [fn.__name__ for fn in (
        ragged_attention.ragged_paged_attention, rms_norm._rms_fwd_pallas,
        rms_norm._rms_bwd_pallas, quant_state._fused_leaf_update)] == [
        "ragged_paged_attention", "rms_norm", "rms_norm_bwd",
        "adam8bit_update"]
    x = jnp.ones((8, 128), jnp.float32)
    text = jax.jit(lambda a, w: rms_norm._rms_fwd_pallas(
        a, w, 1e-6, interpret=True)[0]).lower(x, x[0]).as_text()
    assert "call @rms_norm(" in text
