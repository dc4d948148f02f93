"""Kinds `serve_open` and `serve_closed`: one ServingEngine of the program,
set up as the configuration's `engine` block says, driven by the load
generator for the window, then checked against the plain reference.

From the program this takes the engine (its normal entry points:
construct, warmup(), start(), submit(), shutdown()), its request handles'
stamps, its flight recorder and its compile counters. Everything else
(traffic, clocks, reduction, reference, comparison) is the benchmark's.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from ..harness import (device, loadgen, manifest, stats, traffic, window,
                       xplane)


class CompileInWindow(RuntimeError):
    """A program compiled inside the measured window: not steady state."""


class LateGenerator(RuntimeError):
    """The load generator could not keep to its schedule."""


def build_engine(config: Dict[str, Any], params, pcfg, trace: bool,
                 overrides: Dict[str, Any]):
    from paddle_tpu import serving
    kw = dict(config["engine"])
    kw.update(overrides)
    if "prefill_buckets" in kw:
        kw["prefill_buckets"] = tuple(kw["prefill_buckets"])
    if config.get("mesh"):
        from paddle_tpu.serving.tp import MeshConfig
        kw["mesh"] = MeshConfig(**config["mesh"])
    if trace:
        # the recorder is a ring: hold every tick of the window
        kw["flight_recorder_cap"] = 1 << 16
    return serving.ServingEngine(params, pcfg, start=False, **kw)


def make_params(fam, config, pcfg, d, seed: int):
    """Weights from the seed on the device(s), in the served type, one
    jitted call; under a mesh, made straight into their shards."""
    dtype = pcfg.param_dtype
    if not config.get("mesh"):
        return fam.make_params(seed, d, dtype)
    from paddle_tpu.serving.tp import MeshConfig, build_shardings
    _, shard, _, _ = build_shardings(MeshConfig(**config["mesh"]), pcfg,
                                     fam.params_shape(d, dtype))
    return fam.make_params(seed, d, dtype, shardings=shard)


def preroll(eng, config, vocab: int, seed: int) -> int:
    """A handful of requests through every kind of step (both buckets, a
    chunked prompt, a group of two, fused with running decodes), so that
    whatever the engine's host code builds on first use is built before
    the window. Part of set-up. Returns the requests sent."""
    kw = config["engine"]
    buckets = sorted(kw.get("prefill_buckets") or [64])
    rng = np.random.default_rng(seed % (1 << 31))
    chunk = int(kw.get("chunk", 8))
    top = int(kw["max_total_len"]) - 3 * chunk - 2
    lens = [max(2, buckets[0] // 2)] * 2 + [
        min(top, buckets[-1] - 7), min(top, buckets[-1] + buckets[0] // 2),
        min(top, 2 * buckets[-1] + 5)]
    handles = []
    for i, n in enumerate(lens):
        p = rng.integers(1, vocab, n).tolist()
        handles.append(eng.submit(p, max_new_tokens=2 * chunk + 1))
        if i == 1:
            handles[0].stream().__next__()      # decodes run: next ones fuse
    for h in handles:
        h.result(timeout=600)
    if not eng.drain(120):
        raise RuntimeError("engine did not drain after the pre-roll")
    return len(handles)


def pick_sample(ctx, recs: List[loadgen.Record]) -> List[loadgen.Record]:
    """A sample, drawn from the seed, of the requests the window finished,
    with the longest among them (a `sample` as large as the window takes
    them all)."""
    done = [r for r in recs if r.done and r.error is None]
    if not done:
        return []
    rng = np.random.default_rng(ctx.seed % (1 << 31))
    longest = max(done, key=lambda r: len(r.req.prompt) + r.req.n_out)
    rest = [r for r in done if r is not longest]
    k = min(int(ctx.cell["correct"]["sample"]) - 1, len(rest))
    return [longest] + [rest[i] for i in rng.permutation(len(rest))[:k]]


def check_correct(ctx, fam, d, recs: List[loadgen.Record]) -> Dict[str, Any]:
    """The comparison that decides `correct`: each sampled prompt with its
    served tokens run once through the plain reference; every served
    token's logit must lie within the limit of the reference's best."""
    ref = manifest.plugin("reference", ctx.config["family"])
    chk = ctx.cell["correct"]
    lines = []
    sample = pick_sample(ctx, recs)
    if not sample:
        lines.append("correct: no request finished in the window")
        return {"ok": False, "lines": lines, "numbers": {}}
    longest = sample[0]
    prompts = [r.req.prompt for r in sample]
    served = [list(r.handle.tokens)[:r.req.n_out] for r in sample]
    t0 = time.perf_counter()
    gaps = ref.served_gaps(ctx.seed, d, prompts, served,
                           weight_dtype=ctx.pcfg.param_dtype)
    numbers = {"served_gap_max": float(gaps.max()),
               "served_gap_mean": float(gaps.mean())}
    ok = True
    for name, value in numbers.items():
        limit = float(chk["limits"][name])
        good = bool(np.isfinite(value) and value <= limit)
        ok = ok and good
        lines.append(f"correct: {name} {value:.6g} (limit {limit:.6g}) "
                     f"{'ok' if good else 'FAILED'}")
    lines.append(f"correct: {len(sample)} requests, {gaps.size} served "
                 f"tokens, longest {len(longest.req.prompt)}+"
                 f"{longest.req.n_out}; reference took "
                 f"{time.perf_counter() - t0:.1f} s (not in setup_s)")
    return {"ok": ok, "lines": lines, "numbers": numbers}


def setup(ctx):
    """Weights, engine, warm-up, start, pre-roll: everything before the
    window. Returns the live engine and what the later steps need."""
    fam = manifest.plugin("models", ctx.config["family"])
    d = fam.dims(ctx.config)
    ctx.pcfg = pcfg = fam.program_config(ctx.config)
    compiles = window.compile_listener()
    params = make_params(fam, ctx.config, pcfg, d, ctx.seed)
    eng = build_engine(ctx.config, params, pcfg, ctx.trace,
                       ctx.overrides.get("engine", {}))
    del params
    try:
        warmed = eng.warmup()
        eng.start()
        n_pre = preroll(eng, ctx.config, d["V"], ctx.seed)
    except BaseException:
        eng.shutdown(drain=False, timeout=60)
        raise
    print(f"set-up: {warmed} programs warmed, {n_pre} pre-roll requests, "
          f"attention {eng.attention_impl}, kv {eng.kv_dtype}, weights "
          f"{eng.weight_dtype}", flush=True)
    return {"eng": eng, "fam": fam, "d": d, "compiles": compiles}


def measure(ctx, sv, seconds: float, seed: int) -> Dict[str, Any]:
    """One window of the cell's traffic on the live engine."""
    eng, d, compiles = sv["eng"], sv["d"], sv["compiles"]
    b, cell = eng.batcher, ctx.cell
    mix = {**ctx.mix, **cell.get("mix", {})}    # the cell's own keys on top
    open_loop = mix["kind"] == "serve_open"
    if open_loop:
        n = int(np.ceil(float(cell["rate_per_s"]) * seconds))
        reqs = traffic.generate(mix, seed, n, d["V"], seconds)
    else:
        # more than the loop can finish, so that it never runs dry
        n = int(np.ceil(float(cell["requests_per_s_max"]) * seconds))
        reqs = traffic.generate(mix, seed, n, d["V"])

    def submit(prompt, n_out, on_token):
        return eng.submit(prompt, max_new_tokens=n_out, on_token=on_token)

    def counts():
        return {"programs": b.compile_count, "xla": compiles["n"],
                "pad": b.prefill_pad_tokens}

    tr: Dict[str, Any] = {}
    before = counts()
    gc.collect()
    th = window.trace_thread(
        seconds * 0.5, float(cell.get("trace_seconds", 8.0)), tr) \
        if ctx.trace else None
    setup_s = time.time() - ctx.t_start     # set-up ends, the window opens
    if open_loop:
        recs, t0, t_end = loadgen.run_open(submit, reqs, seconds)
    else:
        recs, t0, t_end = loadgen.run_closed(
            submit, reqs, int(cell["clients"]), seconds)
    after = counts()
    # refused at submit, or ended by the engine in any state but FINISHED,
    # while the window was open
    failed = [r for r in recs if r.error is not None or (
        r.handle.done and r.handle.state.name != "FINISHED")]
    if th is not None:
        th.join(120)
    flight = [r for r in b.flight.records() if t0 <= r["t"] <= t_end]
    waits = [r.handle.admit_time - r.handle.submit_time for r in recs
             if r.handle is not None and r.handle.admit_time is not None]
    if after["programs"] != before["programs"] \
            and not ctx.overrides.get("allow_compile"):     # control tool
        raise CompileInWindow(
            f"a step program compiled inside the measured window: the "
            f"engine's compile_count went {before['programs']} -> "
            f"{after['programs']} (XLA compilations {before['xla']} -> "
            f"{after['xla']}): not steady state, no result")
    if after["xla"] != before["xla"]:
        # not a step program: the engine's host code runs a few eager
        # operations whose shape follows the admission group (PERF.md 7)
        print(f"note: {after['xla'] - before['xla']} small XLA programs of "
              f"the engine's host code compiled inside the window")

    # --- end-to-end metrics: all requests of the window, all its seconds.
    # Time per output token, over every block of `chunk` tokens of every
    # request: the batcher hands out `chunk` tokens per host read, so most
    # single gaps are zero and a block spans one read. A failed or refused
    # request misses any limit: its blocks enter as infinite.
    blk = int(ctx.config["engine"].get("chunk", 8))
    tpot: List[float] = []
    for r in recs:
        if r in failed:
            tpot += [float("inf")] * (r.req.n_out // blk)
        else:
            tpot += [1e3 * t for t in stats.block_times(r.stamps, blk)]
    done = [r for r in recs if r.done]
    values = {
        "setup_s": setup_s,
        "tpot_p90_ms": stats.percentile(tpot, 90),
        "serve_tok_s": sum(len(r.req.prompt) + r.req.n_out for r in done)
        / (t_end - t0),
    }
    # not a metric (PERF.md section 7): from when a request was DUE to its
    # first token; one still waiting enters with its wait so far
    ttft = [((r.t_first if r.t_first is not None else t_end) - r.due) * 1e3
            for r in recs if r not in failed]
    print(f"set-up took {setup_s:.1f} s\nwindow: {len(recs)} sent, "
          f"{len(done)} finished, {len(failed)} failed, "
          f"{sum(r.n_tok for r in recs)} tokens out, {len(tpot)} blocks of "
          f"{blk}; tpot p25 {stats.percentile(tpot, 25) or 0:.2f} p50 "
          f"{stats.percentile(tpot, 50) or 0:.2f} p90 "
          f"{values['tpot_p90_ms'] or 0:.2f} ms; first token after "
          f"{stats.percentile(ttft, 50) or 0:.0f} (p50) "
          f"{stats.percentile(ttft, 90) or 0:.0f} (p90) ms; "
          f"{values['serve_tok_s']:.0f} tokens/s finished", flush=True)
    if open_loop and recs:
        late = loadgen.lateness(recs)
        gap = seconds / len(recs)
        p90 = stats.percentile(late, 90)
        print(f"generator lateness p90 {p90 * 1e3:.2f} ms, max "
              f"{max(late) * 1e3:.2f} ms (mean gap {gap * 1e3:.1f} ms)")
        if p90 > float(cell.get("max_late_share", 0.1)) * gap:
            raise LateGenerator(
                f"the generator ran late: p90 lateness {p90:.3f} s is over "
                f"{cell.get('max_late_share', 0.1)} of the mean gap "
                f"{gap:.3f} s: the tails are not the server's, no result")
    for r in failed[:5]:
        print(f"failed request {r.req.idx}: "
              f"{r.error or r.handle.state.name}")
    obs = {"records": recs, "flight": flight, "queue_waits_s": waits,
           "counters": {"warm_programs": before["programs"],
                        "prefill_pad_tokens": after["pad"] - before["pad"]},
           "window": (t0, t_end), "values": values, "dims": d,
           "device_kind": ctx.device_kind,
           "trace": None, "trace_dir": tr.get("dir")}
    return {"recs": recs, "failed": failed, "values": values, "obs": obs}


def run(ctx) -> Dict[str, Any]:
    sv = setup(ctx)
    try:
        m = measure(ctx, sv, ctx.seconds, ctx.seed)
        mem = device.memory_peak_bytes(int(ctx.cell["chips"]))
    finally:
        sv["eng"].shutdown(drain=False, timeout=60)
    obs = m["obs"]
    if ctx.trace:
        obs["trace"] = xplane.load(xplane.find_xplane(obs["trace_dir"]))
    # --- the program's state is gone before the reference runs
    fam, d = sv["fam"], sv["d"]
    sv.clear()
    gc.collect()
    verdict = check_correct(ctx, fam, d, m["recs"])
    for line in verdict["lines"]:
        print(line, flush=True)
    return {"correct": verdict["ok"], "attempted": len(m["recs"]),
            "failed": len(m["failed"]), "values": m["values"], "obs": obs,
            "memory_peak_bytes": mem, "numbers": verdict["numbers"]}
