"""The compile log (core/compile_cache.py): one record a compiled XLA
program, fed by `jax.monitoring`'s public listeners once
`enable_compile_cache()` installed them and by the batcher's
ahead-of-time helper (`nlp/paged.py::_aot`), read by
`ServingEngine.snapshot()["compiles"]`; and the two spans that came with
it, `serve.compile` and `engine.idle`. CPU, a persistent cache in a
temporary directory."""
import subprocess
import sys
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu import serving
from paddle_tpu.core import compile_cache
from paddle_tpu.core.compile_cache import (CompileLog, compile_log,
                                           enable_compile_cache,
                                           program_name)
from paddle_tpu.nlp import llama, paged
from paddle_tpu.serving import engine as engine_mod

STAGES = ("trace_s", "lower_s", "executable_s")
STEP_NAMES = {"jit_serve_decode_step", "jit_serve_fused_step",
              "jit_serve_prefill_step"}
PROMPT = list(map(int, np.random.RandomState(5).randint(1, 200, 5)))
ENGINE = dict(max_batch=2, block_size=4, max_total_len=48, max_new_tokens=4,
              chunk=2, max_prefill_bucket=8)


@pytest.fixture(scope="module")
def listening(tmp_path_factory):
    """The entry point's call, with the persistent cache in a directory
    of this module's own that keeps every program, however small."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    where = str(tmp_path_factory.mktemp("compile_cache"))
    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", where)   # placed from outside
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    assert enable_compile_cache() == where
    yield where
    mp.undo()
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _warm(model):
    """One tiny engine's warm-up: (engine, programs warmed, the log's
    records of it, its wall seconds)."""
    cfg, params = model
    eng = serving.ServingEngine(params, cfg, start=False, **ENGINE)
    t0 = compile_log.clock()
    warmed = eng.warmup()
    t1 = compile_log.clock()
    return eng, warmed, compile_log.records(since=t0, until=t1), t1 - t0


@pytest.fixture(scope="module")
def warmups(listening, model):
    """A warm-up into the fresh cache, then one of a second engine after
    `jax.clear_caches()`, the directory the same."""
    before = compile_log.summary(["^jit_serve_"])   # other files' engines
    cold = _warm(model)
    jax.clear_caches()
    warm = _warm(model)
    yield {"cold": cold, "warm": warm, "before": before}
    for eng, *_ in (cold, warm):
        eng.shutdown()


# ---- the name -----------------------------------------------------------
@pytest.mark.parametrize("said, name", [
    ("serve_decode_step", "jit_serve_decode_step"),         # when tracing
    ("jit(serve_decode_step)", "jit_serve_decode_step"),    # after
    ("<lambda>", "jit__lambda"),
    ("jit(<lambda>)", "jit__lambda"),
    ("pmap(step)", "pmap_step"),
])
def test_program_name_is_the_device_traces(said, name):
    assert program_name(said) == name


def test_program_name_is_the_lowered_modules():
    def serve_decode_step(x):
        return x + 1
    text = jax.jit(serve_decode_step).lower(jnp.ones(2)).as_text()
    assert f"module @{program_name('serve_decode_step')} " in text


# ---- a warm-up's records ------------------------------------------------
def test_warmup_leaves_one_record_a_step_program(warmups):
    eng, warmed, recs, _ = warmups["cold"]
    assert warmed == eng.batcher.compile_count == len(recs) > 3
    assert {r["name"] for r in recs} == STEP_NAMES
    # each under its memo's own part of the key, no two alike
    assert len({(r["name"], r["key"]) for r in recs}) == len(recs)
    assert {r["key"] for r in recs
            if r["name"] == "jit_serve_decode_step"} == {"2"}


@pytest.mark.parametrize("stage", STAGES)
def test_every_record_has_each_stage(warmups, stage):
    for which in ("cold", "warm"):
        assert all(r[stage] > 0 for r in warmups[which][2])


def test_stages_add_up_to_no_more_than_the_warmup(warmups):
    _, _, recs, wall = warmups["cold"]
    total = sum(r[s] for r in recs for s in STAGES)
    assert 0.5 * wall < total <= wall
    assert [r["t"] for r in recs] == sorted(r["t"] for r in recs)


def test_nested_jits_add_no_record(listening):
    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2

    def outer(x):
        return inner(x) + jnp.where(x > 0, x, 0.0)

    x = jnp.ones(7)          # made here: its own small programs are not
    t0 = compile_log.clock()  # the ones counted
    jax.jit(outer)(x)
    assert [r["name"] for r in compile_log.records(since=t0)] == ["jit_outer"]


@pytest.mark.parametrize("which, verdict", [("cold", "miss"),
                                            ("warm", "hit")])
def test_cache_verdict_of_every_program(warmups, which, verdict):
    recs = warmups[which][2]
    assert [r["cache"] for r in recs] == [verdict] * len(recs)
    if verdict == "hit":
        assert all(0 < r["cache_read_s"] <= r["executable_s"] for r in recs)
    else:
        assert not any("cache_read_s" in r for r in recs)


def test_second_warmup_matches_the_first_program_for_program(warmups):
    names = [[(r["name"], r["key"]) for r in warmups[w][2]]
             for w in ("cold", "warm")]
    assert names[0] == names[1]


# ---- programs the listeners alone record --------------------------------
def test_plain_jit_first_call_one_record_second_none(listening):
    def plain_step(x):
        return x * 3 + 1
    f, x = jax.jit(plain_step), jnp.ones(6)
    t0 = compile_log.clock()
    f(x)
    recs = compile_log.records(since=t0)
    assert [r["name"] for r in recs] == ["jit_plain_step"]
    assert "key" not in recs[0] and recs[0]["cache"] == "miss"
    assert all(recs[0][s] > 0 for s in STAGES)
    t1 = compile_log.clock()
    f(x)
    assert compile_log.records(since=t1) == []


def test_threads_compiling_at_once_keep_their_records_apart(listening):
    """More threads than cores, each compiling programs of its own name
    while the others do: a record a program, none mixed or lost."""
    import threading
    n_threads, each = 16, 3
    was = sys.getswitchinterval()
    failed = []

    def work(i):
        try:
            for j in range(each):
                def step(x):
                    return jnp.tanh(x * (i + 2)) + j
                step.__name__ = f"thread{i}_step{j}"
                jax.jit(step)(np.ones(3 + i, np.float32))
        except Exception as e:      # noqa: BLE001 — reported below
            failed.append(e)

    t0 = compile_log.clock()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(120)
        assert not failed and not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(was)
    recs = compile_log.records(["^jit_thread"], since=t0)
    assert sorted(r["name"] for r in recs) == sorted(
        f"jit_thread{i}_step{j}" for i in range(n_threads)
        for j in range(each))
    assert all(r[s] > 0 for r in recs for s in STAGES)


def test_enabling_twice_installs_the_listeners_once(monkeypatch):
    calls = []
    for fn in ("register_scalar_listener",
               "register_event_duration_secs_listener",
               "register_event_listener"):
        monkeypatch.setattr(jax.monitoring, fn,
                            lambda cb, fn=fn: calls.append(fn))
    monkeypatch.setattr(compile_cache, "_listening", False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
    assert enable_compile_cache() == enable_compile_cache() \
        == "/placed/outside"
    assert sorted(calls) == ["register_event_duration_secs_listener",
                             "register_event_listener",
                             "register_scalar_listener"]


def test_importing_the_library_installs_no_listener():
    code = ("import jax, paddle_tpu\n"
            "from paddle_tpu import serving\n"
            "from paddle_tpu.core.compile_cache import compile_log\n"
            "assert compile_log.records() == []\n"
            "jax.jit(lambda x: x + 1)(1.0)\n"
            "assert compile_log.records() == [], 'fed without the call'\n"
            "print('unfed')\n")
    out = subprocess.run([sys.executable, "-c", code], text=True,
                         capture_output=True, timeout=300,
                         env={**__import__("os").environ,
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0 and "unfed" in out.stdout, out.stderr[-2000:]


# ---- the log itself -----------------------------------------------------
def _synthetic():
    log = CompileLog(cap=4)
    for i, (name, hit) in enumerate([("jit_a", True), ("jit_b", False),
                                     ("jit_a", False), ("jit_c", True),
                                     ("jit_serve_x", True)]):
        with log.program(name, key=str(i)) as rec:
            rec.update(t=float(i), trace_s=1.0, lower_s=2.0,
                       executable_s=4.0, cache="hit" if hit else "miss")
            if hit:
                rec["cache_read_s"] = 0.5
            if name != "jit_c":         # a program that says nothing of it
                rec["alias_bytes"] = 1024
    return log


def test_the_ring_keeps_the_last_records():
    log = _synthetic()
    assert [r["key"] for r in log.records()] == ["1", "2", "3", "4"]


def test_records_are_copies():
    log = _synthetic()
    log.records()[0]["name"] = "scribbled"
    assert log.records()[0]["name"] == "jit_b"


@pytest.mark.parametrize("kw, count, hits, last_miss", [
    ({}, 4, 2, "jit_a"),
    ({"programs": ["^jit_a$", "^jit_serve_"]}, 2, 1, "jit_a"),
    ({"programs": ["^jit_c$"]}, 1, 1, None),
    ({"since": 2.5}, 2, 2, None),
    ({"until": 1.5}, 1, 0, "jit_b"),
    ({"since": 1.5, "until": 3.5}, 2, 1, "jit_a"),
    ({"programs": ["^nothing$"]}, 0, 0, None),
])
def test_summary_over_names_and_times(kw, count, hits, last_miss):
    s = _synthetic().summary(**kw)
    assert (s["count"], s["hits"], s["misses"]) == (count, hits, count - hits)
    assert (s["trace_s"], s["lower_s"], s["executable_s"]) == \
        (1.0 * count, 2.0 * count, 4.0 * count)
    assert s["cache_read_s"] == 0.5 * hits
    assert s["alias_bytes"] == 1024 * sum(
        r["name"] != "jit_c" for r in _synthetic().records(**kw))
    assert (s["last_miss"] or {}).get("name") == last_miss


def test_a_failed_compile_leaves_no_record():
    log = CompileLog()
    with pytest.raises(ValueError):
        with log.program("jit_broken"):
            raise ValueError("lowering refused")
    assert log.records() == []


# ---- the operator's surface ---------------------------------------------
def test_snapshot_compiles_agrees_with_the_log(warmups):
    eng = warmups["warm"][0]
    got = eng.snapshot()["compiles"]
    assert got == compile_log.summary(["^jit_serve_"])
    # the log is the process's: what the two warm-ups added to it
    was = warmups["before"]
    both = warmups["cold"][2] + warmups["warm"][2]
    assert got["count"] - was["count"] == len(both) \
        == 2 * eng.batcher.compile_count
    assert got["hits"] - was["hits"] == got["misses"] - was["misses"] \
        == len(both) // 2
    assert got["last_miss"]["name"].startswith("jit_serve_")
    assert got["executable_s"] - was["executable_s"] == pytest.approx(
        sum(r["executable_s"] for r in both))


# ---- the spans ----------------------------------------------------------
class _Spans:
    """Stands in for `RecordEvent` (as tests/test_tick.py does): keeps
    every span opened and closed, in order, with what `probe` saw."""

    def __init__(self, probe=lambda: None):
        self.opened, self.closed, self.probe = [], [], probe

    def __call__(self, name, event_type=None, **attrs):
        spans = self

        class Span:
            def __enter__(self):
                spans.opened.append((name, attrs, spans.probe()))
                return self

            def __exit__(self, *exc):
                spans.closed.append(name)
                return False
        return Span()


def test_serve_compile_opens_and_closes_round_each_program(model,
                                                           monkeypatch):
    cfg, params = model
    spans = _Spans()
    monkeypatch.setattr(paged, "RecordEvent", spans)
    cb = paged.ContinuousBatcher(params, cfg, **ENGINE)
    t0 = compile_log.clock()
    warmed = cb.warmup_prefill()
    recs = compile_log.records(since=t0)
    assert [n for n, _, _ in spans.opened] == ["serve.compile"] * warmed
    assert spans.closed == ["serve.compile"] * warmed
    # a span, a record: one name, one key
    assert [(a["program"], a["key"]) for _, a, _ in spans.opened] == \
        [(r["name"], r["key"]) for r in recs]
    # nothing compiles twice: the memos hold, no span opens
    assert cb.warmup_prefill() == 0 and len(spans.opened) == warmed


def test_engine_idle_opens_only_with_nothing_live(model, monkeypatch):
    cfg, params = model
    box = {}
    spans = _Spans(lambda: (len(box["eng"]._running), len(box["eng"].queue)))
    monkeypatch.setattr(engine_mod, "RecordEvent", spans)
    eng = box["eng"] = serving.ServingEngine(params, cfg, start=False,
                                             **ENGINE)
    def asleep():               # the loop's last span is its sleep
        t_end = time.monotonic() + 30
        while time.monotonic() < t_end:
            if spans.opened and spans.opened[-1][0] == "engine.idle":
                return True
            time.sleep(0.005)
        return False

    eng.start()
    assert asleep()
    for _ in range(2):
        hs = [eng.submit(PROMPT), eng.submit(PROMPT[:3])]
        for h in hs:
            h.result(timeout=300)
        assert asleep()
    eng.shutdown()
    idle = [seen for n, _, seen in spans.opened if n == "engine.idle"]
    assert len(idle) >= 3 and set(idle) == {(0, 0)}
    assert spans.closed.count("engine.idle") == len(idle)
    names = [n for n, _, _ in spans.opened]
    assert set(names) == {"engine.housekeeping", "engine.deliver",
                          "engine.idle"}


# ---- the counter that says donation engaged -----------------------------
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_alias_bytes_of_every_program_that_returns_a_pool(model, kv_dtype,
                                                         capsys):
    """`_aot` writes the compiler's `alias_size_in_bytes` into each step
    program's record: a program that returns the pool holds it in the
    donated argument's buffer (at least the pool's bytes, the int8
    pool's scales with it), the speculative draft, which reads the pool
    and returns none, aliases nothing; `summary()` adds them up and
    `tools/setup_phases.py` prints them."""
    cfg, params = model
    cb = paged.ContinuousBatcher(
        params, cfg, max_batch=2, block_size=4, max_total_len=48,
        max_new_tokens=4, chunk=2, max_prefill_bucket=8, speculative=True,
        spec_k=2, kv_dtype=kv_dtype)
    t0 = compile_log.clock()
    cb.warmup_prefill()
    recs = compile_log.records(["^jit_serve_"], since=t0)
    pool = sum(p.nbytes for p in cb.cache.pools if p is not None)
    assert pool >= cb.kv_pool_bytes() > 0
    by_name = {}
    for r in recs:
        by_name.setdefault(r["name"], []).append(r["alias_bytes"])
    assert set(by_name) == STEP_NAMES | {"jit_serve_spec_draft",
                                         "jit_serve_spec_verify"}
    assert by_name.pop("jit_serve_spec_draft") == [0]
    for name, got in by_name.items():
        assert all(b >= pool for b in got), (name, got, pool)
    total = compile_log.summary(["^jit_serve_"], since=t0)["alias_bytes"]
    assert total == sum(r["alias_bytes"] for r in recs) \
        >= (len(recs) - 1) * pool

    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "setup_phases", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "setup_phases.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    log = CompileLog()
    for r in recs:
        log._close(dict(r))
    tool.print_compile_log(log, pool)
    lines = capsys.readouterr().out.splitlines()
    mib = pool / 2 ** 20
    assert f"(the pool: {mib:.1f})" in lines[0]
    assert len(lines) == 1 + len(recs) + 2
    for line in lines[1:-2]:
        aliased = float(line.rsplit(" ", 1)[1])
        assert aliased == 0.0 if "spec_draft" in line \
            else aliased >= round(mib, 1)
    assert f"{total / 2 ** 20:.1f} MiB aliased" in lines[-2]
