"""Serving benchmark: offline throughput + latency percentiles through
the ServingEngine on the CPU backend.

Prints ONE JSON line (bench.py convention, landed alongside the
BENCH_*.json records): generated tokens/s end-to-end through the full
admission→batcher→channel path, plus TTFT, queue-wait and inter-token
latency percentiles — the serving-layer numbers the device-side decode
benches in bench.py cannot see (queueing, scheduling, host fan-out
overhead).

Workloads:
  * `random` (default) — independent prompts of random lengths, the
    original scheduling/overhead bench;
  * `prefix-share` (`--prefix-share`) — N requests sharing one common
    prompt prefix (the system-prompt / few-shot pattern), exercising the
    `serving.cache` prefix cache: the JSON line gains
    `prefix_cache_hit_rate` and `prefill_tokens_saved`;
  * `mixed` (`--bucketed`) — prompt lengths spread wide enough to span
    every prefill bucket AND chunk past the largest one, exercising the
    bucketed/chunked prefill path. Asserts ZERO prefill recompiles after
    warmup (the TTFT story: admission dispatches to pre-compiled
    shapes), so a recompile regression fails the bench;
  * `fused` (`--fused`) — the mixed admission-during-decode workload run
    TWICE, fusion on then off: admissions land while other slots decode
    (n_requests >> max_batch), so the unfused run pays a standalone
    prefill stall per admission and the fused run piggybacks the same
    chunk on the decode call. Asserts `decode_stall_steps` strictly
    below the unfused baseline AND zero prefill recompiles after warmup
    — both shape/schedule accounting, deterministic on CPU. The JSON
    line carries `decode_stall_steps` / `fused_steps` / `itl_ms_p99`
    for the fused run and the `*_unfused` baselines next to them.

Warmup pre-compiles EVERY prefill shape via `engine.warmup()` (AOT
lowering — no device compute): the standalone ladder AND, with fusion
on, the fused decode+prefill variants; plus one served request for the
decode chunk fn. Before it, the first timed request of each new prompt
length ate a fresh XLA trace+compile and TTFT p99 measured the
compiler, not the server.

Observability: `--trace out.json` writes the run's per-request trace
timelines (serving.trace.TraceSink) as Chrome-trace/Perfetto JSON —
slot lanes show prefill chunks with bucket/pad/cached-token/fused
annotations next to the engine step spans; `tools/trace_report.py`
summarizes the artifact. `--trace-overhead` runs one DISCARDED leg to
burn process-wide warm-up (jax platform init, compilation cache),
then an ABBA sequence — untraced, traced, traced, untraced — so each
side runs once early and once late and first-order warm-state drift
cancels from the pooled tok/s; it HARD-FAILS unless pooled traced
tok/s holds >= 0.97x pooled untraced with zero post-warmup recompiles
across all four legs: the gate that keeps tracing always-on-cheap.

Quantized (`--quantized`): the quantized-serving gate. The mixed
workload runs through FOUR engine configurations — fp, w8 weights,
int8 paged KV, and w8+int8-KV — each a full lifecycle of AOT warmup, a
cold round, and a warm round of the SAME prompts (prefix-cache hits
re-read the quantized pool the cold round committed). HARD-FAILS on
any post-warmup recompile (the (weight_dtype, kv_dtype) memo keys must
stay on the warmed ladder), any warm-vs-cold token mismatch, int8 KV
gather bytes above 0.55x the fp pool's per-token bytes (scale-pool
overhead included), or quantized-vs-fp greedy divergence below the
documented floor. The JSON line carries decode_tok_s_{fp,w8,int8kv,
w8kv8}, kv_pool_bytes, kv_bytes_per_token_{fp,int8}, kv_gather_ratio
and the per-leg token-match rates.

Chaos (`--chaos`): the fault-isolation gate. The staggered-budget
admission-during-decode workload runs TWICE — fault-free (the token
baseline) and with a seeded `serving.faults.FaultInjector` arming a
persistent fail-on-rid fault against one request the moment it streams
its first token (mid-stream poison landing in a fused batch). The leg
HARD-FAILS unless the engine's quarantine isolates the blast radius:
the culprit alone reaches FAILED with its streamed tokens a prefix of
its baseline (nothing re-emitted or lost), every innocent completes
with BIT-identical tokens to the fault-free run, post-warmup
recompiles stay 0 (quarantine probes and victim re-prefills stay on
the warmed ladder), and the allocator drains clean. The JSON line
carries quarantines / requests_requeued / culprit_tokens_streamed and
the engine `health()` snapshot.

Router (`--router`): the multi-replica failover gate, e2e over HTTP.
The mixed workload first runs through ONE engine (the token
reference), then through 2 `ServingEngine` replicas behind
`serving.Router` + `serving.HttpFrontend` as concurrent SSE streams
over a real socket. When the longest-budget request (the victim)
streams its first token, a seeded chaos hang poisons its serving
replica's next device calls: the hung-step watchdog flips that
replica UNHEALTHY and the router must fail its stranded/queued
requests over to the survivor, resuming each from `prompt + tokens`.
HARD-FAILS unless the victim completes on the OTHER replica with its
pre-failover stream a strict prefix of the final one, EVERY request's
streamed tokens are bit-identical to the single-engine reference
(innocents included), post-warmup recompiles stay 0 on both replicas,
and the survivor's pool drains clean. The JSON line carries
router_failovers / router_victim_tokens_kept /
router_recompiles_after_warmup / router_serving_replicas.

Restart (`--restart`): the self-healing gate. Same chaos shape as
`--router` — a seeded hang kills the victim's serving replica
mid-stream and every stranded SSE stream must fail over with the
strict-prefix invariant — but the Router runs `auto_restart=True`:
the leg then HARD-FAILS unless the dead slot is respawned through the
supervisor's readiness gate (teardown → rebuild → AOT warmup →
synthetic probe), rejoins rotation, serves a post-restart request,
and recompiles stay 0 on every engine incarnation with the crash-loop
breaker shut.

TP (`--tp`): the tensor-parallel gate, under 4 forced host devices
(`--xla_force_host_platform_device_count=4`, appended to XLA_FLAGS at
module import when the flag is on argv — before jax binds a backend).
The mixed workload runs through a single-device reference engine,
then through a `mesh=MeshConfig(tp=4)` engine whose weights are
Megatron-sharded and whose paged-KV pool is sharded on the head axis
(serving.tp). HARD-FAILS unless the TP output is bit-identical to
single-device, post-warmup recompiles stay 0 on both engines (the
mesh key rides every compiled-shape memo), and a TP=2-sharded
replica pair survives the `--restart` chaos shape — hang → failover
→ supervisor respawn of the SHARDED slot through its readiness gate
→ rejoin → serve — under the same bit-identity and zero-recompile
bars. The JSON line carries tp_mesh / tp_kv_pool_bytes_per_device /
tp_recompiles_after_warmup plus the restart_* fields.

Load (`--load`): the closed-loop load generator (ROADMAP direction-3
follow-on): Poisson session arrivals, multi-turn sessions (each turn
extends the previous prompt + generated tokens — the prefix-cache
steady state), shared-system-prompt populations. Emits goodput
(tokens of requests completed within `--deadline-s`, per wall second)
and request-latency p50/p99 under load as tracked JSON fields.
`--load --router` runs the same generator through a 2-replica Router
(the "load-leg router mode" follow-on): multi-replica
`goodput_tok_s` / `latency_s_p99_load` plus per-replica routing
counts land in the JSON line.

`--attention-impl {auto,xla,pallas}` selects the paged-attention
backend (nlp/ragged_attention.py); the JSON line records the RESOLVED
impl plus `decode_tok_s` — generated tokens over time spent inside
batcher.step(), the number the attention backend actually moves. On
CPU pallas runs in Pallas interpret mode: a correctness/parity
configuration, not a speed one (the kernel's win is HBM traffic on
TPU). `--fused-units N` lets one fused step carry up to N pending
prefill units (admission bursts drain faster under sustained decode).

Deliberately a tiny model on CPU: this measures the HOST serving layer's
overhead and scheduling behavior deterministically; device-side decode
throughput is bench.py's `decode_tok_s`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--tp" in sys.argv:
    # the tensor-parallel gate needs a 4-device mesh on a CPU host;
    # forcing host devices only works BEFORE jax binds its backend, so
    # this must happen at module import — every jax import in this
    # file is lazy behind it
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4")

import numpy as np


def _make_prompts(rng, n_requests: int, workload: str,
                  prefix_len: int, suffix_len: int):
    if workload in ("prefix-share", "speculative"):
        # the speculative gate runs the shared-prefix population too:
        # the accept-rate story is the steady-state serving shape
        # (system prompt + short user turns), and the prefix cache
        # must stay warm==cold under spec commits
        common = list(map(int, rng.randint(1, 200, prefix_len)))
        return [common + list(map(int, rng.randint(1, 200, suffix_len)))
                for _ in range(n_requests)]
    if workload in ("mixed", "fused", "chaos", "quantized", "router",
                    "restart", "slo", "disagg", "tp"):
        # lengths spanning the whole ladder, incl. past the largest
        # bucket (chunked prefill) — every request a different length
        return [list(map(int, rng.randint(1, 200, int(L))))
                for L in rng.randint(3, 41, n_requests)]
    return [list(map(int, rng.randint(1, 200, int(L))))
            for L in rng.randint(4, 16, n_requests)]


def _serve(params, cfg, prompts, *, max_new: int, max_batch: int,
           block_size: int, chunk: int, prefix_cache: bool,
           max_prefill_bucket: int, fused_prefill: bool,
           attention_impl: str = "auto", fused_units: int = 1,
           budgets=None, trace: bool = True,
           profile_sample_every: int = 0,
           speculative: bool = False, spec_k: int = 4,
           draft_layers=None, spec_tree=None,
           spec_draft_w8: bool = False, spec_attention_impl=None,
           mesh=None) -> dict:
    """One engine lifecycle over `prompts`: warmup (AOT ladder + one
    served request), timed serve, drain. Returns the raw numbers the
    workload-specific JSON assembly picks from. `profile_sample_every`
    defaults OFF here (unlike the engine's 64) so every non-SLO leg's
    numbers stay fence-free; the --slo leg passes it explicitly."""
    from paddle_tpu import serving

    eng = serving.ServingEngine(
        params, cfg, max_batch=max_batch, block_size=block_size,
        max_total_len=64, max_new_tokens=max_new, chunk=chunk,
        max_queue_depth=len(prompts), prefix_cache=prefix_cache,
        max_prefill_bucket=max_prefill_bucket,
        fused_prefill=fused_prefill, fused_units=fused_units,
        attention_impl=attention_impl, trace=trace,
        profile_sample_every=profile_sample_every,
        speculative=speculative, spec_k=spec_k,
        draft_layers=draft_layers, spec_tree=spec_tree,
        spec_draft_w8=spec_draft_w8,
        spec_attention_impl=spec_attention_impl,
        mesh=mesh, start=False)
    # warmup: AOT-compile EVERY prefill shape (group ladder x bucket
    # ladder x cold/cached, + the fused variants) before the loop
    # starts, then serve one request to compile the decode chunk fn
    # (for prefix-share it also PRIMES the cache — the steady-state
    # view a shared system prompt actually serves under)
    t_w = time.perf_counter()
    warmed = eng.warmup()
    eng.start()
    eng.generate(prompts[0], timeout=600)
    warmup_s = time.perf_counter() - t_w
    completed0 = eng.metrics.counter("requests_completed").value
    pc0 = eng.snapshot()["prefix_cache"]
    # compile_count covers EVERY device-step shape (prefill/fused
    # ladder + the plain decode chunk) — the zero-post-warmup gate
    compiles_warm = eng.batcher.compile_count
    itl = eng.metrics.histogram("itl_s")
    # the warmup request's gaps include the decode chunk fn's XLA
    # compile — rank only samples observed inside the timed window
    itl0 = itl.summary().get("count", 0)
    step_h = eng.metrics.histogram("serving.step_s")
    step_s0 = step_h.summary().get("sum", 0.0)

    t0 = time.perf_counter()
    budgets = budgets or [None] * len(prompts)
    reqs = [eng.submit(p, max_new_tokens=mn)
            for p, mn in zip(prompts, budgets)]
    if not eng.drain(timeout=600):
        raise RuntimeError("drain timed out — benchmark invalid")
    wall = time.perf_counter() - t0
    eng.shutdown()

    toks = sum(len(r.result()) for r in reqs)
    b = eng.batcher
    # device-step throughput of the timed window: generated tokens over
    # time spent INSIDE batcher.step() — queueing/host fan-out excluded,
    # so this is the number the attention backend actually moves
    step_s = step_h.summary().get("sum", 0.0) - step_s0
    return {
        "snap": eng.snapshot(),
        "trace": eng.trace,
        "pc0": pc0,
        "reqs": reqs,
        "wall_s": wall,
        "warmup_s": warmup_s,
        "warmed": warmed,
        "completed0": completed0,
        "tok_s": toks / wall,
        "decode_tok_s": toks / step_s if step_s else None,
        "attention_impl": eng.attention_impl,
        "recompiles": b.compile_count - compiles_warm,
        "profile_samples": b.profiler.report()["samples"],
        "compile_count": b.prefill_compile_count,
        "compile_count_total": b.compile_count,
        "fused_unit_count": b.fused_unit_count,
        "pad_tokens": b.prefill_pad_tokens,
        "buckets": list(b.prefill_buckets),
        "suffix_hist": {str(k): v
                        for k, v in sorted(b.prefill_suffix_hist.items())},
        "fused_steps": b.fused_steps,
        "decode_stall_steps": b.decode_stall_steps,
        "itl_ms_p50": _ms(itl.percentile(0.50, since=itl0)),
        "itl_ms_p99": _ms(itl.percentile(0.99, since=itl0)),
    }


def _ms(v):
    return None if v is None else round(v * 1000.0, 3)


# Documented quantized-vs-fp greedy divergence floor on the smoke model
# (README "Quantized serving" has the bound's rationale): across the
# workload, at least this fraction of the fp run's greedy tokens must
# match the quantized run position-for-position up to each request's
# first divergence. Weight/KV int8 error on the tiny random-init model
# flips the argmax on a small minority of steps; a collapse below the
# floor means the quantized math broke, not that rounding moved a
# borderline logit.
QUANT_MATCH_FLOOR = 0.60

# int8 KV must at least HALVE the per-token gather bytes vs the fp
# pool modulo the per-block scale overhead — 0.55x is the gate with
# that overhead priced in (bs >= 8 keeps the scale share under 5%).
KV_GATHER_RATIO_CEIL = 0.55


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


def _quantized_leg(params, cfg, prompts, budgets, *, weight_dtype,
                   kv_dtype, **kw) -> dict:
    """One quantization configuration through a full engine lifecycle:
    AOT warmup, a COLD round over the workload, then a WARM round of
    the SAME prompts (prefix-cache hits re-read the quantized pool the
    cold round committed). HARD-FAILS on any post-warmup recompile
    (the quantized ladder must be as warmable as fp) and on any
    warm-vs-cold token mismatch (cached-prefix reads must reproduce
    the cold prefill exactly — the pool stores what every consumer
    dequantizes)."""
    import time as _t

    from paddle_tpu import serving

    eng = serving.ServingEngine(
        params, cfg, max_batch=kw["max_batch"],
        block_size=kw["block_size"], max_total_len=64,
        max_new_tokens=kw["max_new"], chunk=kw["chunk"],
        max_queue_depth=len(prompts), prefix_cache=kw["prefix_cache"],
        max_prefill_bucket=kw["max_prefill_bucket"],
        attention_impl=kw["attention_impl"],
        fused_units=kw["fused_units"], weight_dtype=weight_dtype,
        kv_dtype=kv_dtype, start=False)
    eng.warmup()
    eng.start()
    warm_compiles = eng.batcher.compile_count
    step_h = eng.metrics.histogram("serving.step_s")

    def _round():
        t0 = _t.perf_counter()
        s0 = step_h.summary().get("sum", 0.0)
        reqs = [eng.submit(p, max_new_tokens=mn)
                for p, mn in zip(prompts, budgets)]
        if not eng.drain(timeout=600):
            raise RuntimeError(
                "quantized drain timed out — benchmark invalid")
        toks = [r.result() for r in reqs]
        wall = _t.perf_counter() - t0
        step_s = step_h.summary().get("sum", 0.0) - s0
        n = sum(len(t) for t in toks)
        return toks, n / wall, (n / step_s if step_s else None)

    cold, tok_s, decode_tok_s = _round()
    warm, _, _ = _round()
    recompiles = eng.batcher.compile_count - warm_compiles
    snap = eng.snapshot()
    eng.shutdown()
    leg = f"{weight_dtype}/{kv_dtype}"
    if recompiles:
        raise RuntimeError(
            f"quantized leg {leg} recompiled {recompiles} shapes after "
            f"warmup — the (weight_dtype, kv_dtype) memo keys fell off "
            f"the warmed ladder")
    if warm != cold:
        raise RuntimeError(
            f"quantized leg {leg} lost warm==cold token parity — "
            f"cached-prefix reads disagree with the cold prefill under "
            f"quantization")
    return {"tokens": cold, "tok_s": tok_s, "decode_tok_s": decode_tok_s,
            "quant": snap["quantization"]}


def _quantized_gates(params, cfg, prompts, budgets, **kw) -> dict:
    """The --quantized matrix: fp / w8 / int8-KV / w8+int8-KV over the
    same workload, each warm==cold and recompile-free, plus the two
    cross-leg gates — int8 KV gather bytes <= 0.55x fp and quantized
    greedy divergence within the documented floor vs the fp leg."""
    legs = {}
    for name, (wd, kd) in (("fp", ("fp", "fp")), ("w8", ("int8", "fp")),
                           ("int8kv", ("fp", "int8")),
                           ("w8kv8", ("int8", "int8"))):
        legs[name] = _quantized_leg(params, cfg, prompts, budgets,
                                    weight_dtype=wd, kv_dtype=kd, **kw)
    fp_bpt = legs["fp"]["quant"]["kv_bytes_per_token"]
    q_bpt = legs["w8kv8"]["quant"]["kv_bytes_per_token"]
    ratio = q_bpt / fp_bpt
    if ratio > KV_GATHER_RATIO_CEIL:
        raise RuntimeError(
            f"quantized gate: int8 KV gather bytes at {ratio:.3f}x fp "
            f"(ceiling {KV_GATHER_RATIO_CEIL}) — the int8 pool no "
            f"longer halves per-token HBM traffic")
    out = {
        "kv_bytes_per_token_fp": fp_bpt,
        "kv_bytes_per_token_int8": q_bpt,
        "kv_gather_ratio": round(ratio, 4),
        "kv_pool_bytes": legs["w8kv8"]["quant"]["kv_pool_bytes"],
        "kv_pool_bytes_fp": legs["fp"]["quant"]["kv_pool_bytes"],
        "weight_bytes_fp": legs["fp"]["quant"]["weight_bytes"],
        "weight_bytes_w8": legs["w8"]["quant"]["weight_bytes"],
        "quantized_recompiles_after_warmup": 0,   # each leg hard-gated
    }
    base = legs["fp"]["tokens"]
    for name in ("w8", "int8kv", "w8kv8"):
        m = _prefix_match(base, legs[name]["tokens"])
        if m < QUANT_MATCH_FLOOR:
            raise RuntimeError(
                f"quantized gate: {name} greedy output matches only "
                f"{m:.3f} of the fp run (documented floor "
                f"{QUANT_MATCH_FLOOR}) — quantization error exceeds "
                f"the accuracy bound")
        out[f"quantized_token_match_{name}"] = round(m, 4)
    for name, leg in legs.items():
        out[f"tok_s_{name}"] = round(leg["tok_s"], 1)
        out[f"decode_tok_s_{name}"] = (round(leg["decode_tok_s"], 1)
                                       if leg["decode_tok_s"] else None)
    return out


def _spec_leg(params, cfg, prompts, *, spec_tree=(2, 1, 1, 1),
              **kw) -> dict:
    """The speculative-decoding gate: the shared-prefix workload runs
    plain (the greedy token reference), then self-speculatively with
    a chain draft, then with a TREE draft (`--spec-tree`, default
    [2,1,1,1]). HARD-FAILS unless BOTH spec runs' outputs are
    BIT-identical to the plain reference (greedy speculation changes
    the schedule, never the tokens), accepted tokens/step exceeds 1
    (speculation actually multiplies decode), the tree leg's accepted
    tokens per sweep >= the chain leg's at equal accepted-path budget
    (the tree's depth equals the chain's k, and child 0 of every tree
    node IS the chain's draft token, so the tree's candidate set
    contains the chain path — acceptance can only dominate), and
    post-warmup recompiles stay 0 on all runs (the spec config —
    branching spec included — rides every memo/warmup key). Drafts
    run at FULL depth here: on the random-init smoke model a
    truncated draft's proposals essentially never match the target's
    greedy choices, so the accept path would be vacuous — truncation
    (`draft_layers=`) is a quality/cost knob for real checkpoints,
    exercised for token parity by tests/test_speculative.py."""
    spec_tree = tuple(int(b) for b in spec_tree)
    ref = _serve(params, cfg, prompts, fused_prefill=True, **kw)
    base_tokens = [q.result() for q in ref["reqs"]]
    # chain leg: k = the tree's depth, so both legs can accept the
    # same number of draft tokens per verify sweep (the fair
    # acceptance comparison; the tree spends more verify WIDTH —
    # that is the trade speculation v2 buys)
    chain_k = len(spec_tree)
    spec = _serve(params, cfg, prompts, fused_prefill=True,
                  speculative=True, spec_k=chain_k,
                  draft_layers=None, **kw)
    spec_tokens = [q.result() for q in spec["reqs"]]
    st = spec["snap"]["speculative"]
    tree = _serve(params, cfg, prompts, fused_prefill=True,
                  speculative=True, spec_tree=list(spec_tree),
                  draft_layers=None, **kw)
    tree_tokens = [q.result() for q in tree["reqs"]]
    tt = tree["snap"]["speculative"]
    for name, toks, stats in (("chain", spec_tokens, st),
                              ("tree", tree_tokens, tt)):
        if toks != base_tokens:
            bad = sum(1 for a, b in zip(base_tokens, toks) if a != b)
            raise RuntimeError(
                f"speculative gate: {name} leg — {bad}/"
                f"{len(base_tokens)} requests diverged from the plain "
                f"greedy reference — greedy speculative decoding must "
                f"be output-identical (accept_rate "
                f"{stats['accept_rate']})")
    if ref["recompiles"] or spec["recompiles"] or tree["recompiles"]:
        raise RuntimeError(
            f"speculative gate: post-warmup recompiles (plain "
            f"{ref['recompiles']}, chain {spec['recompiles']}, tree "
            f"{tree['recompiles']}) — the spec config (branching "
            f"spec included) must ride every memo/warmup key")
    if not st["tokens_per_step"] > 1.0:
        raise RuntimeError(
            f"speculative gate: {st['tokens_per_step']} accepted "
            f"tokens/step over {st['steps']} verify sweeps — "
            f"speculation is not multiplying decode (accept_rate "
            f"{st['accept_rate']})")
    if tt["accepted_per_sweep"] < st["accepted_per_sweep"]:
        raise RuntimeError(
            f"speculative gate: tree accepted/sweep "
            f"{tt['accepted_per_sweep']} < chain's "
            f"{st['accepted_per_sweep']} at equal accepted-path "
            f"budget — the tree's candidate set contains the chain "
            f"path, so tree acceptance must dominate")
    return {
        "_ref": ref,
        "spec_accept_rate": st["accept_rate"],
        "spec_tokens_per_step": st["tokens_per_step"],
        "spec_k": st["k"],
        "spec_draft_layers": st["draft_layers"],
        "spec_verify_steps": st["steps"],
        "spec_token_match": 1.0,
        "spec_recompiles_after_warmup": spec["recompiles"],
        "spec_tree": list(spec_tree),
        "spec_tree_k": tt["k"],
        "spec_tree_accept_rate": tt["accept_rate"],
        "spec_tree_tokens_per_step": tt["tokens_per_step"],
        "spec_tree_accepted_per_sweep": tt["accepted_per_sweep"],
        "spec_chain_accepted_per_sweep": st["accepted_per_sweep"],
        "spec_tree_accept_depth_hist": tt["accept_depth_hist"],
        "spec_tree_token_match": 1.0,
        "spec_tree_recompiles_after_warmup": tree["recompiles"],
        "tok_s_spec": round(spec["tok_s"], 1),
        "decode_tok_s_spec": (round(spec["decode_tok_s"], 1)
                              if spec["decode_tok_s"] else None),
        "tok_s_spec_tree": round(tree["tok_s"], 1),
        "decode_tok_s_spec_tree": (round(tree["decode_tok_s"], 1)
                                   if tree["decode_tok_s"] else None),
    }


def _chaos_leg(params, cfg, prompts, budgets, culprit_idx: int,
               base_tokens, **kw) -> dict:
    """The fault-isolation gate: re-serve the same workload with a
    persistent fail-on-rid fault armed against request `culprit_idx`
    at its first streamed token, and HARD-FAIL unless quarantine
    contains the blast radius (see module docstring)."""
    import threading

    from paddle_tpu import serving
    from paddle_tpu.serving.faults import FaultInjector

    inj = FaultInjector(seed=0)
    eng = serving.ServingEngine(
        params, cfg, max_batch=kw["max_batch"],
        block_size=kw["block_size"], max_total_len=64,
        max_new_tokens=kw["max_new"], chunk=kw["chunk"],
        max_queue_depth=len(prompts), prefix_cache=kw["prefix_cache"],
        max_prefill_bucket=kw["max_prefill_bucket"],
        attention_impl=kw["attention_impl"],
        fused_units=kw["fused_units"], fault_injector=inj, start=False)
    eng.warmup()
    eng.start()
    eng.generate(prompts[0], timeout=600)
    compiles_warm = eng.batcher.compile_count
    armed = threading.Event()

    def arm(tok):
        # first streamed token of the culprit: poison its rid from
        # here on — the fault lands mid-stream, typically inside a
        # fused decode+prefill batch carrying innocents
        if not armed.is_set():
            armed.set()
            inj.fail_on_rid(culprit_req.request_id)

    # the handle is built BEFORE submission so the engine-thread
    # callback never races the submit loop's list bookkeeping
    culprit_req = serving.GenerationRequest(
        prompts[culprit_idx], max_new_tokens=int(budgets[culprit_idx]),
        on_token=arm)
    reqs = []
    for i, (p, mn) in enumerate(zip(prompts, budgets)):
        reqs.append(eng.submit(culprit_req) if i == culprit_idx
                    else eng.submit(p, max_new_tokens=mn))
    if not eng.drain(timeout=600):
        raise RuntimeError("chaos drain timed out — benchmark invalid")
    recompiles = eng.batcher.compile_count - compiles_warm
    health = eng.health()
    blocks_in_use = eng.batcher.alloc.stats()["blocks_in_use"]
    eng.shutdown()

    culprit = reqs[culprit_idx]
    failed = [i for i, r in enumerate(reqs)
              if r.state is serving.RequestState.FAILED]
    if failed != [culprit_idx]:
        raise RuntimeError(
            f"chaos gate: FAILED set {failed} != [{culprit_idx}] — the "
            f"quarantine did not contain the fault to the culprit")
    if not culprit.tokens or \
            culprit.tokens != base_tokens[culprit_idx][:len(culprit.tokens)]:
        raise RuntimeError(
            "chaos gate: the culprit's streamed tokens are not a prefix "
            "of its fault-free run — tokens were re-emitted or lost")
    for i, r in enumerate(reqs):
        if i == culprit_idx:
            continue
        if r.result() != base_tokens[i]:
            raise RuntimeError(
                f"chaos gate: innocent request {i} finished with "
                f"different tokens than the fault-free run — recovery "
                f"lost or corrupted streamed output")
    if recompiles:
        raise RuntimeError(
            f"chaos gate: {recompiles} post-warmup recompiles — "
            f"quarantine re-execution left the warmed ladder")
    if blocks_in_use:
        raise RuntimeError(
            f"chaos gate: {blocks_in_use} KV blocks still in use after "
            f"drain — the recovery path leaked pool blocks")
    if not health["quarantines"]:
        raise RuntimeError(
            "chaos gate: no quarantine ran — the fault never fired "
            "(workload produced no poisoned step)")
    return {
        "chaos_culprit_index": culprit_idx,
        "chaos_culprit_tokens_streamed": len(culprit.tokens),
        "chaos_innocents": len(reqs) - 1,
        "chaos_quarantines": health["quarantines"],
        "chaos_requests_requeued": health["requests_requeued"],
        "chaos_recompiles_after_warmup": recompiles,
        "chaos_injected": inj.stats()["injected"],
        "chaos_health_status": health["status"],
    }


def _sse_stream(host: str, port: int, payload: dict):
    """One SSE round-trip over a real socket (stdlib http.client):
    POST /v1/stream, parse the event stream incrementally. Yields
    ("routed"|"token"|"done"|"error", data) tuples as they arrive, so
    the caller can react mid-stream (the chaos arm)."""
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=600)
    try:
        conn.request("POST", "/v1/stream", json.dumps(payload),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(
                f"/v1/stream answered {resp.status}: {resp.read()!r}")
        event = None
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.decode().rstrip("\n")
            if line.startswith("event: "):
                event = line[len("event: "):].strip()
            elif line.startswith("data: "):
                data = json.loads(line[len("data: "):])
                yield (event or ("token" if "token" in data else "data"),
                       data)
                event = None
    finally:
        conn.close()


def _sse_chaos_run(host, port, prompts, budgets, injs, hang_s):
    """The shared chaos harness of the --router and --restart legs:
    stream every prompt concurrently over SSE through the frontend;
    when the victim (the largest-budget request — it must still be
    DECODING when the poison arms) streams its first token, hang its
    serving replica's next device calls (a spread of step numbers
    absorbs the arm-vs-step race; only the first match fires, the
    rest stay idle). Returns (results, victim_index, wall_s) where
    results[i] = {"tokens", "routed", "final"}."""
    import threading

    victim = max(range(len(prompts)), key=lambda i: budgets[i])
    armed = threading.Event()
    results = [None] * len(prompts)

    def run_one(i):
        toks, routed, final = [], None, None
        for event, data in _sse_stream(
                host, port, {"prompt": prompts[i],
                             "max_new_tokens": int(budgets[i])}):
            if event == "routed":
                routed = data["replica"]
            elif event in ("done", "error"):
                final = data
            elif "token" in data:
                toks.append(data["token"])
                if i == victim and not armed.is_set():
                    armed.set()
                    inj = injs[int(routed[1:])]
                    c = inj.stats()["calls"]
                    for k in range(1, 6):
                        inj.hang_on_step(c + k, hang_s)
        results[i] = {"tokens": toks, "routed": routed, "final": final}

    t0 = time.perf_counter()
    threads = [threading.Thread(target=run_one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    return results, victim, time.perf_counter() - t0


def _check_sse_failover(results, victim, base_tokens, snap, gate):
    """The shared failover gates of the --router and --restart legs:
    the victim finished on ANOTHER replica after >=1 failover, its
    pre-failover stream is a strict prefix of the final one, and
    EVERY stream is bit-identical to the single-engine reference.
    Returns (tokens_kept, dead_replica_id) on success; raises the
    gate's hard failure otherwise."""
    v = results[victim]
    if v is None or v["final"] is None:
        raise RuntimeError(
            f"{gate} gate: the victim's SSE stream never finished — "
            f"failover did not recover it")
    if v["final"]["state"] != "FINISHED":
        raise RuntimeError(
            f"{gate} gate: victim ended {v['final']['state']} "
            f"({v['final'].get('error')}) instead of completing on "
            f"the surviving replica")
    if not v["final"]["failovers"] or v["final"]["replica"] == v["routed"]:
        raise RuntimeError(
            f"{gate} gate: victim finished on {v['final']['replica']} "
            f"with {v['final']['failovers']} failovers — the chaos "
            f"hang never forced a cross-replica failover")
    log = {e["router_rid"]: e for e in snap["failover_log"]}
    kept = log.get(v["final"]["request_id"], {}).get("tokens_kept", 0)
    if not (0 < kept < len(base_tokens[victim])):
        raise RuntimeError(
            f"{gate} gate: victim kept {kept} of "
            f"{len(base_tokens[victim])} tokens across failover — the "
            f"pre-failover stream is not a strict prefix (fault fired "
            f"before the first token, or after the last)")
    for i, r in enumerate(results):
        if r is None or r["tokens"] != base_tokens[i]:
            got = None if r is None else r["tokens"]
            raise RuntimeError(
                f"{gate} gate: request {i} streamed {got} != the "
                f"single-engine reference — failover re-emitted, lost "
                f"or corrupted tokens")
    return kept, v["routed"]


def _router_leg(params, cfg, prompts, budgets, base_tokens, **kw) -> dict:
    """The cross-replica failover gate, e2e over HTTP: 2 replicas
    behind a Router + HttpFrontend serve the mixed workload as
    concurrent SSE streams; when the longest-budget request (the
    victim) streams its first token, a seeded chaos hang poisons its
    serving replica's next device calls — the hung-step watchdog flips
    that replica UNHEALTHY and every stranded/queued request must fail
    over to the survivor. HARD-FAILS unless the victim completes on
    the OTHER replica with its pre-failover stream a strict prefix of
    the final one, every request's tokens are bit-identical to the
    single-engine reference, post-warmup recompiles stay 0 on both
    replicas, and the survivor's pool drains clean."""
    from paddle_tpu import serving
    from paddle_tpu.serving.faults import FaultInjector

    injs = [FaultInjector(seed=0), FaultInjector(seed=1)]
    router = serving.Router(
        params, cfg, replicas=2, max_batch=kw["max_batch"],
        block_size=kw["block_size"], max_total_len=64,
        max_new_tokens=kw["max_new"], chunk=kw["chunk"],
        max_queue_depth=2 * len(prompts),
        prefix_cache=kw["prefix_cache"],
        max_prefill_bucket=kw["max_prefill_bucket"],
        attention_impl=kw["attention_impl"],
        fused_units=kw["fused_units"], watchdog_s=0.5,
        per_replica=[{"fault_injector": injs[0]},
                     {"fault_injector": injs[1]}],
        start=False)
    warmed = router.warmup()
    router.start()
    compiles_warm = [e.batcher.compile_count for e in router.engines]
    fe = serving.HttpFrontend(router, port=0, shutdown_router=False)
    host, port = fe.start()
    results, victim, wall = _sse_chaos_run(
        host, port, prompts, budgets, injs, hang_s=3.0)
    recompiles = sum(e.batcher.compile_count - c0
                     for e, c0 in zip(router.engines, compiles_warm))
    snap = router.snapshot()
    health = router.health()
    fe.shutdown(drain=True)
    router.shutdown(drain=False)

    kept, dead_rid = _check_sse_failover(results, victim, base_tokens,
                                         snap, "router")
    if recompiles:
        raise RuntimeError(
            f"router gate: {recompiles} post-warmup recompiles across "
            f"replicas — failover re-prefills left the warmed ladder")
    survivor = next(e for e in router.engines
                    if e.replica_id != dead_rid)
    leaked = survivor.batcher.alloc.stats()["blocks_in_use"]
    if leaked:
        raise RuntimeError(
            f"router gate: {leaked} KV blocks still in use on the "
            f"survivor after drain — cross-replica recovery leaked")
    ntok = sum(len(r["tokens"]) for r in results)
    return {
        "router_replicas": 2,
        "router_tok_s": round(ntok / wall, 1),
        "router_shapes_warmed": warmed,
        "router_failovers": health["failovers"],
        "router_victim_tokens_kept": kept,
        "router_victim_replicas": [
            dead_rid, results[victim]["final"]["replica"]],
        "router_recompiles_after_warmup": recompiles,
        "router_serving_replicas": health["serving_replicas"],
        "router_watchdog_trips": sum(
            h["watchdog_trips"] for h in health["replicas"].values()),
    }


def _restart_leg(params, cfg, prompts, budgets, base_tokens, *,
                 mesh=None, **kw) -> dict:
    """The self-healing gate (`--restart`), e2e over HTTP: like the
    `--router` leg, a seeded chaos hang kills the victim's replica
    mid-stream and every stranded SSE stream must fail over to the
    survivor with the strict-prefix invariant intact — but here the
    Router runs `auto_restart=True`, so the leg then HARD-FAILS unless
    the dead slot is respawned through the supervisor's readiness gate
    (teardown → rebuild → AOT warmup → synthetic probe), rejoins
    rotation, and serves a post-restart request — with zero
    post-warmup recompiles on EVERY engine incarnation (the originals
    against their warmup baseline, the respawn against the compile
    count its readiness gate recorded) and no circuit-breaker trip."""
    from paddle_tpu import serving
    from paddle_tpu.serving.faults import FaultInjector

    injs = [FaultInjector(seed=0), FaultInjector(seed=1)]
    per_replica = [{"fault_injector": injs[0]},
                   {"fault_injector": injs[1]}]
    if mesh is not None:
        # the --tp leg reruns this chaos shape with BOTH slots sharded:
        # the supervisor replays these per-replica kwargs on respawn,
        # so the rebuilt slot re-derives its mesh + shardings too
        for slot_kw in per_replica:
            slot_kw["mesh"] = mesh
    router = serving.Router(
        params, cfg, replicas=2, max_batch=kw["max_batch"],
        block_size=kw["block_size"], max_total_len=64,
        max_new_tokens=kw["max_new"], chunk=kw["chunk"],
        max_queue_depth=2 * len(prompts),
        prefix_cache=kw["prefix_cache"],
        max_prefill_bucket=kw["max_prefill_bucket"],
        attention_impl=kw["attention_impl"],
        # compile-scale watchdog headroom: a supervisor respawn runs
        # jax tracing + XLA compile CONCURRENTLY with the survivor's
        # serving steps, and a sub-second deadline can trip on that CPU
        # contention alone (the injected hang below is 8s — far past
        # any honest step)
        fused_units=kw["fused_units"], watchdog_s=2.0,
        per_replica=per_replica,
        auto_restart=True,
        # leftover hang rules from the arm spread can poison the first
        # respawn probes (the injector follows the slot) — threshold 5
        # keeps the breaker shut through that worst case; the leg
        # heals the injectors as soon as the streams complete
        restart_opts={"backoff_s": 0.1, "breaker_threshold": 5,
                      "probe_timeout_s": 120.0},
        start=False)
    warmed = router.warmup()
    router.start()
    compiles_warm = {e.replica_id: e.batcher.compile_count
                     for e in router.engines}
    originals = {e.replica_id: e for e in router.engines}
    fe = serving.HttpFrontend(router, port=0, shutdown_router=False)
    host, port = fe.start()
    results, victim, wall = _sse_chaos_run(
        host, port, prompts, budgets, injs, hang_s=8.0)
    # streams done (failover complete): disarm the chaos so the
    # supervisor's respawn probes run against a clean replica
    for inj in injs:
        inj.heal()
    kept, dead_rid = _check_sse_failover(results, victim, base_tokens,
                                         router.snapshot(), "restart")

    # --- the self-healing half: the dead slot must rejoin ---------------
    deadline = time.perf_counter() + 300
    while time.perf_counter() < deadline:
        h = router.health()
        if h["serving_replicas"] == 2 and h["replica_restarts"] >= 1:
            break
        time.sleep(0.05)
    else:
        h = router.health()
        raise RuntimeError(
            f"restart gate: the dead slot never rejoined rotation "
            f"(serving_replicas={h['serving_replicas']}, "
            f"restarts={h['replica_restarts']}, "
            f"supervisor={h.get('supervisor')})")
    if h["circuit_open"]:
        raise RuntimeError(
            "restart gate: the crash-loop breaker opened on what "
            "should have been a recoverable replica")
    # the respawned engine must be a NEW incarnation in the same slot
    respawn = next(e for e in router.engines if e.replica_id == dead_rid)
    if respawn is originals[dead_rid]:
        raise RuntimeError(
            "restart gate: the victim slot still holds the dead "
            "engine — no respawn happened")

    # post-restart: a concurrent burst of FRESH prompts (short enough
    # to carry no affinity blocks, so placement is pure occupancy and
    # spreads) must land traffic on the respawned slot and complete
    post_rng = np.random.RandomState(99)
    post = [router.submit(list(map(int, post_rng.randint(1, 200, 5))),
                          max_new_tokens=kw["max_new"])
            for _ in range(4)]
    outs = [q.result(300) for q in post]
    if not all(outs):
        raise RuntimeError(
            "restart gate: a post-restart request generated nothing")
    served = [q.replica_id for q in post]
    if dead_rid not in served:
        raise RuntimeError(
            f"restart gate: the respawned slot {dead_rid} served none "
            f"of the post-restart burst (placements: {served}) — it "
            f"rejoined health but not rotation")

    # recompile accounting per incarnation: survivors vs their warmup
    # baseline, the respawn vs the compile count its readiness gate
    # recorded (supervisor slot info)
    sup = router.health()["supervisor"]
    recompiles = 0
    for e in router.engines:
        if e is respawn:
            recompiles += e.batcher.compile_count \
                - sup[e.replica_id]["warm_compile_count"]
        else:
            recompiles += e.batcher.compile_count \
                - compiles_warm[e.replica_id]
    if recompiles:
        raise RuntimeError(
            f"restart gate: {recompiles} post-warmup recompiles across "
            f"engine incarnations — the respawn's readiness gate or "
            f"the failover re-prefills left the warmed ladder")
    health = router.health()
    fe.shutdown(drain=True)
    router.shutdown(drain=False)
    ntok = sum(len(r["tokens"]) for r in results)
    return {
        "restart_replicas": 2,
        "restart_tok_s": round(ntok / wall, 1),
        "restart_shapes_warmed": warmed,
        "restart_failovers": health["failovers"],
        "restart_victim_tokens_kept": kept,
        "restart_victim_replica": dead_rid,
        "restart_replica_restarts": health["replica_restarts"],
        "restart_respawn_attempts": health["restart_failures"] + 1,
        "restart_circuit_open": health["circuit_open"],
        "restart_recompiles_after_warmup": recompiles,
        "restart_serving_replicas": health["serving_replicas"],
        "restart_post_burst_replicas": sorted(set(served)),
        "restart_injector_attachments": [
            inj.stats()["attachments"] for inj in injs],
    }


def _tp_leg(params, cfg, prompts, budgets, speculative=False,
            spec_tree=None, **kw) -> dict:
    """The tensor-parallel gate (`--tp`), under 4 forced host devices:
    the mixed workload through a single-device reference engine, then
    the SAME workload through a `mesh=MeshConfig(tp=4)` engine whose
    weights are Megatron-sharded and whose paged-KV pool is sharded on
    the head axis (serving.tp). HARD-FAILS unless the TP output is
    bit-identical to single-device, post-warmup recompiles stay 0 on
    BOTH engines (the mesh key rides every compiled-shape memo, so the
    warmup ladder covers the sharded shapes), and a TP=2-sharded
    replica pair survives the `--restart` chaos shape — hang →
    failover → supervisor respawn of the SHARDED slot through its
    readiness gate → rejoin → serve — under the same bit-identity and
    zero-recompile bars.

    `speculative=True` (`--tp --speculative`) is the fast-path
    COMPOSITION gate: the sharded engine additionally turns on tree
    speculation (with `--attention-impl pallas` the ragged kernel and
    its suffix-slab verify run shard_map-wrapped on the mesh) while
    the reference stays mesh-off PLAIN decode — so the bit-identity
    bar covers mesh x impl x speculation all at once, plus the
    resolved fast-path stamps in snapshot()."""
    import jax

    from paddle_tpu.serving.tp import MeshConfig

    if len(jax.devices()) < 4:
        raise RuntimeError(
            f"tp gate: only {len(jax.devices())} devices visible — "
            f"--tp must be on argv at interpreter start so the module "
            f"top can force 4 host devices via XLA_FLAGS before jax "
            f"binds its backend")

    ref = _serve(params, cfg, prompts, fused_prefill=True,
                 budgets=budgets, **kw)
    base_tokens = [q.result() for q in ref["reqs"]]
    spec_kw = dict(speculative=True, spec_tree=spec_tree) \
        if speculative else {}
    tp = _serve(params, cfg, prompts, fused_prefill=True,
                budgets=budgets, mesh=MeshConfig(tp=4), **spec_kw,
                **kw)
    tp_tokens = [q.result() for q in tp["reqs"]]
    what = "TP=4 mesh engine" if not speculative else \
        "TP=4 mesh+speculative engine"
    if tp_tokens != base_tokens:
        bad = sum(a != b for a, b in zip(tp_tokens, base_tokens))
        raise RuntimeError(
            f"tp gate: {bad}/{len(prompts)} requests diverged between "
            f"the {what} and single-device plain decode — greedy "
            f"sharded decode must be bit-identical (a mismatch means a "
            f"wrong sharding spec, a silently resharded intermediate, "
            f"or a verify/commit divergence)")
    if ref["recompiles"] or tp["recompiles"]:
        raise RuntimeError(
            f"tp gate: post-warmup recompiles (single-device "
            f"{ref['recompiles']}, tp=4 {tp['recompiles']}) — the "
            f"warmup ladder no longer covers the sharded shapes (mesh "
            f"key missing from a memo?)")
    # the fast-path stamps must say what actually ran: a silent
    # fallback to the XLA gather under the mesh would pass bit-identity
    # while forfeiting the kernel — exactly the regression this guards
    mesh_stamp = tp["snap"]["tp"]["mesh"]
    if mesh_stamp["attention_impl"] != tp["attention_impl"]:
        raise RuntimeError(
            f"tp gate: snapshot mesh stamp says attention_impl="
            f"{mesh_stamp['attention_impl']!r} but the engine resolved "
            f"{tp['attention_impl']!r}")
    if speculative:
        spec_snap = tp["snap"]["speculative"]
        if not spec_snap["enabled"] or spec_snap["steps"] < 1:
            raise RuntimeError(
                "tp gate: the mesh+speculative engine reports no spec "
                "verify sweeps — speculation silently off under TP")
        if mesh_stamp["spec_backend"] != spec_snap["backend"]:
            raise RuntimeError(
                f"tp gate: mesh stamp spec_backend="
                f"{mesh_stamp['spec_backend']!r} != batcher backend "
                f"{spec_snap['backend']!r}")

    # the self-healing half at TP=2 × 2 replicas (4 devices, host
    # shards overlap freely): chaos hang, SSE failover, supervisor
    # respawn of a sharded slot, rejoin, post-restart serve
    chaos = _restart_leg(params, cfg, prompts, budgets, base_tokens,
                         mesh=MeshConfig(tp=2), **kw)

    snap_tp = tp["snap"]["tp"]
    result = {
        "metric": "serving_offline_tok_s",
        "value": round(tp["tok_s"], 1),
        "unit": "tokens/s",
        "workload": "tp",
        "attention_impl": tp["attention_impl"],
        "n_requests": len(prompts),
        "tp_mesh": snap_tp["mesh"],
        "tp_kv_pool_bytes_per_device":
            snap_tp["kv_pool_bytes_per_device"],
        "tp_weight_bytes_per_device":
            snap_tp.get("weight_bytes_per_device"),
        "tok_s_single_device": round(ref["tok_s"], 1),
        "tp_bit_identical": True,
        "tp_shapes_warmed": tp["warmed"],
        "tp_recompiles_after_warmup": tp["recompiles"],
        "tp_restart_mesh": MeshConfig(tp=2).describe(),
        "tp_spec_backend": snap_tp["mesh"]["spec_backend"],
    }
    if speculative:
        spec_snap = tp["snap"]["speculative"]
        result["tp_speculative"] = True
        result["tp_spec_tree"] = spec_snap.get("tree")
        result["tp_spec_accept_rate"] = spec_snap["accept_rate"]
        result["tp_spec_tokens_per_step"] = \
            spec_snap["tokens_per_step"]
    result.update(chaos)
    return result


def _disagg_leg(params, cfg, prompts, budgets, *, weight_dtype,
                kv_dtype, **kw) -> dict:
    """One quantization configuration through the disaggregated
    prefill/decode topology: a monolithic single-engine reference
    first, then the SAME workload through `Router(disaggregated=True)`
    with one prefill-role and one decode-role replica. Every request
    prefills on replica 0, surrenders at the first step boundary with
    its KV chain exported as a `KVSnapshot`, and resumes on replica 1
    via `import_kv`. HARD-FAILS unless the disaggregated streams are
    bit-identical to the monolithic reference, the decode replica ran
    ZERO prefill chunks (all of its KV arrived by snapshot import),
    every request migrated exactly once, post-warmup recompiles stay 0
    on BOTH replicas, and both pools drain clean."""
    import time as _t

    from paddle_tpu import serving

    ekw = dict(max_batch=kw["max_batch"], block_size=kw["block_size"],
               max_total_len=64, max_new_tokens=kw["max_new"],
               chunk=kw["chunk"], max_queue_depth=2 * len(prompts),
               prefix_cache=kw["prefix_cache"],
               max_prefill_bucket=kw["max_prefill_bucket"],
               attention_impl=kw["attention_impl"],
               fused_units=kw["fused_units"],
               weight_dtype=weight_dtype, kv_dtype=kv_dtype)
    leg = f"{weight_dtype}/{kv_dtype}"

    # monolithic reference: the same engine config, both roles in one
    # process — its tokens are the bit-identity bar for the hop
    eng = serving.ServingEngine(params, cfg, start=False, **ekw)
    eng.warmup()
    eng.start()
    refs = [eng.submit(p, max_new_tokens=mn)
            for p, mn in zip(prompts, budgets)]
    if not eng.drain(timeout=600):
        raise RuntimeError(
            f"disagg leg {leg}: monolithic reference drain timed out")
    base = [r.result() for r in refs]
    eng.shutdown()

    router = serving.Router(
        params, cfg, replicas=2, disaggregated=True,
        per_replica=[{"role": "prefill"}, {"role": "decode"}],
        start=False, **ekw)
    warmed = router.warmup()
    router.start()
    compiles_warm = [e.batcher.compile_count for e in router.engines]
    t0 = _t.perf_counter()
    reqs = [router.submit(p, max_new_tokens=mn, timeout_s=120.0)
            for p, mn in zip(prompts, budgets)]
    toks = [r.result(timeout=600) for r in reqs]
    wall = _t.perf_counter() - t0
    recompiles = sum(e.batcher.compile_count - c0
                     for e, c0 in zip(router.engines, compiles_warm))
    pre, dec = router.engines
    health = router.health()
    snap = router.snapshot()
    leaked = sum(e.batcher.alloc.stats()["blocks_in_use"]
                 for e in router.engines)
    router.shutdown(drain=False)

    if toks != base:
        bad = [i for i, (a, b) in enumerate(zip(toks, base)) if a != b]
        raise RuntimeError(
            f"disagg leg {leg}: streams {bad} diverged from the "
            f"monolithic reference — the KV hop is not bit-exact")
    if dec.batcher.prefill_chunk_calls:
        raise RuntimeError(
            f"disagg leg {leg}: decode replica ran "
            f"{dec.batcher.prefill_chunk_calls} prefill chunks — KV "
            f"arrived by re-prefill, not by snapshot import")
    # a prefill-role engine surrenders at the first step boundary
    # after the first token, by which point the fused step has already
    # run one decode chunk — so a request holds min(budget, 1 + chunk)
    # tokens at surrender and only budgets past that ever migrate
    # (short requests legitimately finish on the prefill replica)
    expect = sum(1 for b in budgets if b > 1 + kw["chunk"])
    if dec.batcher.imported_kv != expect \
            or health["migrations"] != expect:
        raise RuntimeError(
            f"disagg leg {leg}: {dec.batcher.imported_kv} imports / "
            f"{health['migrations']} migrations, expected {expect} "
            f"(budgets past the surrender boundary) — some hop fell "
            f"back to re-prefill or double-migrated")
    if recompiles:
        raise RuntimeError(
            f"disagg leg {leg}: {recompiles} post-warmup recompiles "
            f"across replicas — imports left the warmed ladder")
    if leaked:
        raise RuntimeError(
            f"disagg leg {leg}: {leaked} KV blocks still in use after "
            f"drain — the export/import hop leaked pool blocks")
    handoffs = [e["handoff_s"] for e in snap["migration_log"]]
    ntok = sum(len(t) for t in toks)
    return {
        "tokens": toks,
        "tok_s": ntok / wall,
        "shapes_warmed": warmed,
        "migrations": health["migrations"],
        "migration_bytes": health["migration_bytes"],
        "handoff_ms_mean": (round(1e3 * sum(handoffs) / len(handoffs), 3)
                            if handoffs else None),
        "handoff_ms_max": (round(1e3 * max(handoffs), 3)
                           if handoffs else None),
        "prefill_chunks_prefill_replica": pre.batcher.prefill_chunk_calls,
        "recompiles": recompiles,
    }


def _disagg_gates(params, cfg, prompts, budgets, **kw) -> dict:
    """The --disagg matrix: the fp leg and the w8+int8-KV leg, each
    individually hard-gated (bit-identity vs its own monolithic
    reference, zero decode-replica prefill chunks, one migration per
    request, zero recompiles), plus the cross-leg accuracy gate — the
    quantized disaggregated output must match the fp reference at
    least as well as the documented quantization floor (the snapshot
    hop must not add divergence on top of int8 rounding)."""
    fp = _disagg_leg(params, cfg, prompts, budgets,
                     weight_dtype="fp", kv_dtype="fp", **kw)
    q = _disagg_leg(params, cfg, prompts, budgets,
                    weight_dtype="int8", kv_dtype="int8", **kw)
    m = _prefix_match(fp["tokens"], q["tokens"])
    if m < QUANT_MATCH_FLOOR:
        raise RuntimeError(
            f"disagg gate: int8 disaggregated output matches only "
            f"{m:.3f} of the fp run (documented floor "
            f"{QUANT_MATCH_FLOOR}) — the snapshot hop amplified "
            f"quantization error")
    return {
        "disagg_replicas": 2,
        "disagg_tok_s": round(fp["tok_s"], 1),
        "disagg_tok_s_int8": round(q["tok_s"], 1),
        "disagg_shapes_warmed": fp["shapes_warmed"],
        "disagg_migrations": fp["migrations"],
        "disagg_migration_bytes": fp["migration_bytes"],
        "disagg_migration_bytes_int8": q["migration_bytes"],
        "disagg_handoff_ms_mean": fp["handoff_ms_mean"],
        "disagg_handoff_ms_max": fp["handoff_ms_max"],
        "disagg_token_match_int8": round(m, 4),
        "disagg_recompiles_after_warmup": 0,      # each leg hard-gated
    }


def _slo_breach_leg(params, cfg, prompts, budgets, **kw) -> dict:
    """The SLO-engine gate, e2e over the whole surface: a 1-replica
    Router + HttpFrontend serve the mixed workload while a seeded
    `FaultInjector` hangs several device steps for 4 s each — SHORT of
    the 30 s watchdog (latency degradation, not a dead replica). The
    leg HARD-FAILS unless the injected latency drives an
    `itl_ms_p99` BREACH that is visible end-to-end — engine
    `health()["slo"]`, the router rollup, the `/health` JSON detail
    (still HTTP 200: SLOs degrade, supervision decides), and
    `slo_breaches_total >= 1` for BOTH the replica and the router
    rollup in the merged `/metrics` exposition — AND the verdict
    clears back to OK after the fault heals, with zero post-warmup
    recompiles. A `POST /debug/profile` capture window during the
    recovery traffic must also complete and land device-wall spans in
    the merged trace (the device-time-attribution half of the PR)."""
    import threading

    from paddle_tpu import serving
    from paddle_tpu.serving.faults import FaultInjector

    inj = FaultInjector(seed=0)
    router = serving.Router(
        params, cfg, replicas=1, max_batch=kw["max_batch"],
        block_size=kw["block_size"], max_total_len=64,
        max_new_tokens=kw["max_new"], chunk=kw["chunk"],
        max_queue_depth=2 * len(prompts),
        prefix_cache=kw["prefix_cache"],
        max_prefill_bucket=kw["max_prefill_bucket"],
        attention_impl=kw["attention_impl"],
        fused_units=kw["fused_units"],
        # the hang must stay SHORT of the watchdog: this is the
        # latency-degradation shape, not the dead-replica one
        watchdog_s=30.0,
        slo_objectives={"itl_ms_p99": 2000.0, "error_rate": 0.5},
        slo_opts={"fast_window_s": 1.0, "slow_window_s": 3.0,
                  "eval_every_s": 0.05},
        per_replica=[{"fault_injector": inj}],
        start=False)
    router.warmup()
    router.start()
    eng = router.engines[0]
    router.generate(prompts[0], timeout=600)
    compiles_warm = eng.batcher.compile_count
    fe = serving.HttpFrontend(router, port=0, shutdown_router=False)
    host, port = fe.start()

    # arm: the next few device calls each stall 4 s — far past the
    # 2000 ms itl objective, far short of the 30 s watchdog
    c = inj.stats()["calls"]
    for k in range(1, 4):
        inj.hang_on_step(c + k, 4.0)
    reqs = [router.submit(p, max_new_tokens=mn)
            for p, mn in zip(prompts, budgets)]
    breach_seen = None
    deadline = time.perf_counter() + 300
    while time.perf_counter() < deadline:
        h = eng.health()
        if h["slo"]["verdict"] == "BREACH":
            breach_seen = h["slo"]
            break
        if all(r.done for r in reqs):
            break
        time.sleep(0.05)
    for r in reqs:
        r.result(600)
    if breach_seen is None:
        raise RuntimeError(
            "slo gate: the injected 4s step hangs never drove an SLO "
            "BREACH — the tracker is not watching the latency the "
            "engine serves")
    if breach_seen["objectives"]["itl_ms_p99"]["verdict"] != "BREACH":
        raise RuntimeError(
            f"slo gate: breach fired on the wrong objective — "
            f"{breach_seen['objectives']}")
    rh = router.health()
    if rh["slo"]["verdict"] not in ("BREACH", "WARN"):
        raise RuntimeError(
            f"slo gate: router rollup says {rh['slo']['verdict']} "
            f"while the replica breached — fleet aggregation is blind")
    if rh["slo"]["breaches_total"] < 1:
        raise RuntimeError("slo gate: rollup lost the breach count")

    # the HTTP surface: /health keeps its 200 (SLOs degrade,
    # supervision decides) while carrying the verdict detail, and the
    # merged /metrics exposition counts the breach for the replica AND
    # the router rollup
    import http.client
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", "/health")
    resp = conn.getresponse()
    health_body = json.loads(resp.read())
    conn.close()
    if resp.status != 200:
        raise RuntimeError(
            f"slo gate: /health flipped to {resp.status} on an SLO "
            f"breach — breaches are detail, not outage")
    if "slo" not in health_body or "objectives" not in health_body["slo"]:
        raise RuntimeError("slo gate: /health carries no slo detail")
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", "/metrics")
    prom = conn.getresponse().read().decode()
    conn.close()
    counts = {}
    for ln in prom.splitlines():
        if ln.startswith("paddle_tpu_slo_breaches_total{"):
            label = ln.split("{")[1].split("}")[0]
            counts[label] = float(ln.split()[-1])
    if counts.get('replica="r0"', 0) < 1 \
            or counts.get('replica="router"', 0) < 1:
        raise RuntimeError(
            f"slo gate: slo_breaches_total missing from the merged "
            f"exposition (saw {counts})")

    # heal → the verdict must CLEAR once the windows forget the spike
    inj.heal()
    clear_deadline = time.perf_counter() + 120
    post_rng = np.random.RandomState(123)
    while time.perf_counter() < clear_deadline:
        router.generate(
            list(map(int, post_rng.randint(1, 200, 6))),
            max_new_tokens=2, timeout=600)
        if eng.health()["slo"]["verdict"] == "OK":
            break
        time.sleep(0.1)
    final = eng.health()["slo"]
    if final["verdict"] != "OK":
        raise RuntimeError(
            f"slo gate: verdict stuck at {final['verdict']} after the "
            f"fault healed — breach→recover hysteresis never released")

    # device-time capture through the frontend while traffic flows
    done = threading.Event()

    def burst():
        for _ in range(4):
            router.generate(
                list(map(int, post_rng.randint(1, 200, 8))),
                max_new_tokens=kw["max_new"], timeout=600)
        done.set()

    t = threading.Thread(target=burst)
    t.start()
    conn = http.client.HTTPConnection(host, port, timeout=120)
    conn.request("POST", "/debug/profile",
                 json.dumps({"steps": 3, "timeout_s": 60}),
                 {"Content-Type": "application/json"})
    profile = json.loads(conn.getresponse().read())
    conn.close()
    t.join(600)
    cap = profile["r0"]["capture"]
    if not cap["complete"] or cap["steps_captured"] < 3:
        raise RuntimeError(
            f"slo gate: the /debug/profile capture window never "
            f"completed under live traffic ({cap})")
    dev_spans = sum(
        1 for e in router.to_chrome_trace()["traceEvents"]
        if str(e.get("name", "")).startswith("device."))
    if dev_spans < 3:
        raise RuntimeError(
            f"slo gate: only {dev_spans} device-wall spans in the "
            f"merged trace — capture fences are not reaching the "
            f"timelines")
    recompiles = eng.batcher.compile_count - compiles_warm
    if recompiles:
        raise RuntimeError(
            f"slo gate: {recompiles} post-warmup recompiles — the SLO "
            f"tracker or the capture fences touched the compiled-shape "
            f"memo")
    breaches_total = final["breaches_total"]
    fe.shutdown(drain=True)
    router.shutdown(drain=False)
    return {
        "slo_breaches_total": breaches_total,
        "slo_breach_objective": "itl_ms_p99",
        "slo_breach_burn_rate_fast":
            breach_seen["objectives"]["itl_ms_p99"]["burn_rate_fast"],
        "slo_verdict_peak": "BREACH",
        "slo_verdict_final": final["verdict"],
        "slo_injected_hangs": inj.stats()["injected"].get("hang", 0),
        "slo_recompiles_after_warmup": recompiles,
        "slo_profile_steps_captured": cap["steps_captured"],
        "slo_device_spans": dev_spans,
    }


def _load_leg(params, cfg, *, sessions: int, turns: int, rate_hz: float,
              deadline_s: float, router_replicas: int = 0, **kw) -> dict:
    """The closed-loop load generator: `sessions` clients arrive as a
    Poisson process (`rate_hz`), each runs `turns` multi-turn rounds
    (turn N+1's prompt is turn N's prompt + generated tokens + fresh
    user tokens — the prefix-cache steady state), and the population
    shares a small set of system prompts. Closed-loop: a session
    blocks on its own previous turn, so offered load self-limits the
    way real clients do. Emits goodput (tokens of requests that
    completed within `deadline_s`, over the wall) and request-latency
    percentiles under load — the tracked direction-3 numbers.

    `router_replicas > 0` (the `--load --router` combination) runs the
    SAME generator through a `serving.Router` over that many replicas
    instead of one engine — the multi-replica goodput-scaling view the
    ROADMAP's "load-leg router mode" follow-on asked for (prefix
    affinity keeps a session's turns on the replica already holding
    its history, so the per-replica caches stay warm)."""
    import threading

    from paddle_tpu import serving

    common = dict(
        max_batch=kw["max_batch"], block_size=kw["block_size"],
        max_total_len=64, max_new_tokens=kw["max_new"],
        chunk=kw["chunk"], max_queue_depth=max(64, sessions * turns),
        prefix_cache=kw["prefix_cache"],
        max_prefill_bucket=kw["max_prefill_bucket"],
        attention_impl=kw["attention_impl"],
        fused_units=kw["fused_units"], start=False)
    if router_replicas:
        eng = serving.Router(params, cfg, replicas=router_replicas,
                             **common)
    else:
        eng = serving.ServingEngine(params, cfg, **common)
    eng.warmup()
    eng.start()

    def pc_stats():
        # aggregated prefix-cache counters (summed across replicas in
        # router mode — hit attribution per replica lives in snapshot)
        snap = eng.snapshot()
        if router_replicas:
            out = {"prompt_tokens": 0, "hit_tokens": 0}
            for s in snap["replicas"].values():
                pc = s["prefix_cache"]
                out["prompt_tokens"] += pc.get("prompt_tokens", 0)
                out["hit_tokens"] += pc.get("hit_tokens", 0)
            return out
        return snap["prefix_cache"]

    rng = np.random.RandomState(7)
    system_prompts = [list(map(int, rng.randint(1, 200, 12)))
                      for _ in range(2)]
    eng.generate(system_prompts[0] + [1, 2, 3], timeout=600)
    pc0 = pc_stats()
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, sessions))
    lock = threading.Lock()
    samples = []          # (latency_s, ntok, within_deadline)

    def session(si):
        srng = np.random.RandomState(100 + si)
        t_arrive = t0 + arrivals[si]
        delay = t_arrive - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        history = list(system_prompts[si % len(system_prompts)])
        for _ in range(turns):
            history = history + list(map(int, srng.randint(1, 200, 4)))
            t_s = time.perf_counter()
            req = eng.submit(history, max_new_tokens=kw["max_new"])
            toks = req.result(timeout=600)
            lat = time.perf_counter() - t_s
            with lock:
                samples.append((lat, len(toks), lat <= deadline_s))
            history = history + toks

    t0 = time.perf_counter()
    threads = [threading.Thread(target=session, args=(i,))
               for i in range(sessions)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    wall = time.perf_counter() - t0
    pc = pc_stats()
    routed_per_replica = None
    if router_replicas:
        h = eng.health()
        routed_per_replica = {
            rid: eng.metrics.counter(f"routed_{rid}").value
            for rid in h["replicas"]}
    eng.shutdown()
    lats = sorted(s[0] for s in samples)
    good_tok = sum(n for _, n, ok in samples if ok)
    total_tok = sum(n for _, n, _ in samples)
    lookups = pc["prompt_tokens"] - pc0["prompt_tokens"]
    saved = pc["hit_tokens"] - pc0["hit_tokens"]
    pct = lambda q: (round(lats[min(len(lats) - 1,
                                    int(round(q * (len(lats) - 1))))], 4)
                     if lats else None)
    out = {
        "metric": "serving_load_goodput_tok_s",
        "value": round(good_tok / wall, 1),
        "unit": "tokens/s",
        "workload": "load",
        "goodput_tok_s": round(good_tok / wall, 1),
        "tok_s_total": round(total_tok / wall, 1),
        "sessions": sessions,
        "turns": turns,
        "arrival_rate_hz": rate_hz,
        "deadline_s": deadline_s,
        "requests_total": len(samples),
        "requests_in_deadline": sum(1 for s in samples if s[2]),
        "latency_s_p50_load": pct(0.50),
        "latency_s_p99_load": pct(0.99),
        "wall_s": round(wall, 3),
        "prefix_cache_hit_rate": (round(saved / lookups, 4)
                                  if lookups else 0.0),
        "max_batch": kw["max_batch"],
        "max_new_tokens": kw["max_new"],
    }
    if router_replicas:
        out["load_router_replicas"] = router_replicas
        out["load_routed_per_replica"] = routed_per_replica
    return out


def main(n_requests: int = 16, max_new: int = 8, max_batch: int = 4,
         block_size: int = 8, chunk: int = 4, workload: str = "random",
         prefix_len: int = 24, suffix_len: int = 6,
         prefix_cache: bool = True,
         max_prefill_bucket: int = 512,
         attention_impl: str = "auto", fused_units: int = 1,
         sessions: int = 6, turns: int = 3, rate_hz: float = 8.0,
         deadline_s: float = 5.0, load_router_replicas: int = 0,
         spec_tree=(2, 1, 1, 1), tp_speculative: bool = False,
         trace_path=None, trace_overhead: bool = False) -> dict:
    import jax
    from paddle_tpu.nlp import llama

    cfg = llama.LlamaConfig.tiny(use_flash=False, num_hidden_layers=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    prompts = _make_prompts(rng, n_requests, workload,
                            prefix_len, suffix_len)
    kw = dict(max_new=max_new, max_batch=max_batch,
              block_size=block_size, chunk=chunk,
              prefix_cache=prefix_cache,
              max_prefill_bucket=max_prefill_bucket,
              attention_impl=attention_impl, fused_units=fused_units)
    if workload == "load":
        # the closed-loop generator builds its own session workload —
        # none of the offline result assembly below applies
        return _load_leg(params, cfg, sessions=sessions, turns=turns,
                         rate_hz=rate_hz, deadline_s=deadline_s,
                         router_replicas=load_router_replicas, **kw)

    base = None
    if workload in ("fused", "prefix-share", "chaos", "quantized",
                    "router", "restart", "slo", "disagg", "tp"):
        # staggered per-request budgets so slots retire at DIFFERENT
        # steps — equal budgets would march the whole batch in lockstep
        # waves and no admission would ever land mid-decode. The fused
        # comparison needs that overlap for stalls to exist at all; the
        # prefix-share trace artifact needs it so cached-prefix
        # requests visibly piggyback (fused prefill_chunk events next
        # to their cached_tokens skip)
        kw["budgets"] = [1 + (i % max_new) for i in range(len(prompts))]
    if workload == "tp":
        # TP=4 splits on the kv-head axis and the bench default model
        # has 2 kv heads — the tp gate gets its own 4-kv-head tiny
        # config (same layers/geometry otherwise) and assembles its
        # own JSON line, gates included
        cfg = llama.LlamaConfig.tiny(use_flash=False,
                                     num_hidden_layers=2,
                                     num_key_value_heads=4)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        return _tp_leg(params, cfg, prompts, kw["budgets"],
                       speculative=tp_speculative,
                       spec_tree=spec_tree if tp_speculative else None,
                       **{k: v for k, v in kw.items()
                          if k != "budgets"})
    if workload == "fused":
        # unfused first: the SAME prompts through the PR4 path give the
        # decode_stall_steps / ITL baseline the fused run must beat
        base = _serve(params, cfg, prompts, fused_prefill=False, **kw)
    spec = None
    if workload == "speculative":
        # plain reference first (its numbers double as this
        # workload's base JSON), then the spec run with the
        # bit-identical / tokens-per-step / zero-recompile gates
        spec = _spec_leg(params, cfg, prompts, spec_tree=spec_tree,
                         **kw)
        r0 = spec.pop("_ref")
    quant = None
    if workload == "quantized":
        # the fp/w8/int8-KV/w8+int8-KV matrix with its warm==cold,
        # recompile, gather-bytes and divergence gates; the plain
        # fp _serve below still provides the base JSON numbers
        quant = _quantized_gates(
            params, cfg, prompts, kw["budgets"],
            **{k: v for k, v in kw.items() if k != "budgets"})
    disagg = None
    if workload == "disagg":
        # the disaggregated prefill/decode matrix (fp + w8/int8-KV)
        # with its bit-identity / zero-decode-prefill / one-migration-
        # per-request / zero-recompile gates; the plain fp _serve
        # below still provides the base JSON numbers
        disagg = _disagg_gates(
            params, cfg, prompts, kw["budgets"],
            **{k: v for k, v in kw.items() if k != "budgets"})
    slo = None
    if workload == "slo":
        # device-time recording must be nearly free (it is fed by the
        # ticks' own stamps; no fence): a discarded leg burns process
        # warm-up, then an ABBA sequence — recording off, on, on, off —
        # so each side runs once early and once late and first-order
        # warm-state drift cancels from the pooled tok/s (the
        # --trace-overhead methodology)
        kw_on = dict(kw, profile_sample_every=4)
        _serve(params, cfg, prompts, fused_prefill=True, **kw)
        u1 = _serve(params, cfg, prompts, fused_prefill=True, **kw)
        s1 = _serve(params, cfg, prompts, fused_prefill=True, **kw_on)
        s2 = _serve(params, cfg, prompts, fused_prefill=True, **kw_on)
        u2 = _serve(params, cfg, prompts, fused_prefill=True, **kw)
        tok_off = (u1["tok_s"] + u2["tok_s"]) / 2
        tok_on = (s1["tok_s"] + s2["tok_s"]) / 2
        ratio = tok_on / tok_off
        samples = s1["profile_samples"] + s2["profile_samples"]
        recompiles = sum(x["recompiles"] for x in (u1, s1, s2, u2))
        if samples < 1:
            raise RuntimeError(
                "slo gate: the recording legs recorded ZERO steps — "
                "the overhead comparison is vacuous")
        if recompiles:
            raise RuntimeError(
                f"slo gate: {recompiles} post-warmup recompiles across "
                f"the recording legs — the profiler touched the "
                f"compiled-shape memo")
        if ratio < 0.97:
            raise RuntimeError(
                f"slo gate: sampled run at {ratio:.3f}x the "
                f"sampling-off tok/s (floor 0.97x) — the device-time "
                f"recording is no longer cheap enough to leave on")
        slo = {
            "slo_tok_s_sampling_off": round(tok_off, 1),
            "slo_tok_s_sampling_on": round(tok_on, 1),
            "slo_sampling_overhead_ratio": round(ratio, 4),
            "slo_profile_samples": samples,
        }
        slo.update(_slo_breach_leg(
            params, cfg, prompts, kw["budgets"],
            **{k: v for k, v in kw.items() if k != "budgets"}))
        r0 = u1           # the first clean leg doubles as the numbers
    routed = None
    if workload in ("router", "restart"):
        # single-engine leg first: its per-request tokens are the
        # parity reference the 2-replica HTTP run must reproduce
        # bit-identically (and it provides this workload's base JSON
        # numbers); then the router+frontend leg with its failover
        # gate — or, for --restart, the self-healing leg that also
        # demands the dead slot respawn, rejoin and serve
        r0 = _serve(params, cfg, prompts, fused_prefill=True, **kw)
        base_tokens = [q.result() for q in r0["reqs"]]
        leg = _restart_leg if workload == "restart" else _router_leg
        routed = leg(
            params, cfg, prompts, kw["budgets"], base_tokens,
            **{k: v for k, v in kw.items() if k != "budgets"})
    chaos = None
    if workload == "chaos":
        # fault-free leg first: its per-request tokens are the parity
        # baseline the chaos engine's survivors must reproduce bit-
        # identically (and it doubles as this workload's JSON numbers)
        r0 = _serve(params, cfg, prompts, fused_prefill=True, **kw)
        base_tokens = [q.result() for q in r0["reqs"]]
        # the culprit must still be DECODING when its first-token
        # poison arms, or the fault can never fire mid-stream — pick
        # the request with the largest decode budget
        culprit = max(range(len(prompts)), key=lambda i: kw["budgets"][i])
        chaos = _chaos_leg(
            params, cfg, prompts, kw["budgets"], culprit, base_tokens,
            **{k: v for k, v in kw.items() if k != "budgets"})
    untraced = None
    if trace_overhead:
        # the tracing-overhead gate needs BIAS-FREE legs: the first
        # engine lifecycle in a process absorbs one-time warm state
        # (jax platform init, compilation cache) and later lifecycles
        # keep getting gradually warmer, so any fixed leg order hands
        # one side a systematic advantage bigger than the 3% floor.
        # Burn the one-time warm-up on a DISCARDED run, then measure
        # an ABBA sequence (untraced, traced, traced, untraced) and
        # compare pooled tok/s — first-order drift cancels because
        # each side runs once early and once late.
        _serve(params, cfg, prompts, fused_prefill=True,
               trace=False, **kw)
        u1 = _serve(params, cfg, prompts, fused_prefill=True,
                    trace=False, **kw)
        t1 = _serve(params, cfg, prompts, fused_prefill=True, **kw)
        t2 = _serve(params, cfg, prompts, fused_prefill=True, **kw)
        u2 = _serve(params, cfg, prompts, fused_prefill=True,
                    trace=False, **kw)
        untraced = u1
        untraced["tok_s"] = (u1["tok_s"] + u2["tok_s"]) / 2
        untraced["recompiles"] = u1["recompiles"] + u2["recompiles"]
        r = t1
        r["tok_s"] = (t1["tok_s"] + t2["tok_s"]) / 2
        r["recompiles"] = t1["recompiles"] + t2["recompiles"]
    elif chaos is not None or routed is not None or slo is not None \
            or spec is not None:
        r = r0            # the reference leg doubles as the numbers
    else:
        r = _serve(params, cfg, prompts, fused_prefill=True, **kw)

    reqs, snap = r["reqs"], r["snap"]
    ttft = np.asarray([q.first_token_time - q.submit_time for q in reqs])
    wait = np.asarray([q.admit_time - q.submit_time for q in reqs])
    pct = lambda a, q: round(float(np.percentile(a, q)), 4)
    result = {
        "metric": "serving_offline_tok_s",
        "value": round(r["tok_s"], 1),
        "unit": "tokens/s",
        "workload": workload,
        "attention_impl": r["attention_impl"],
        "decode_tok_s": (round(r["decode_tok_s"], 1)
                         if r["decode_tok_s"] else None),
        "fused_units": fused_units,
        "fused_unit_count": r["fused_unit_count"],
        "n_requests": n_requests,
        "max_batch": max_batch,
        "max_new_tokens": max_new,
        "wall_s": round(r["wall_s"], 3),
        "warmup_s": round(r["warmup_s"], 3),
        "ttft_s_p50": pct(ttft, 50),
        "ttft_s_p90": pct(ttft, 90),
        "ttft_s_p99": pct(ttft, 99),
        "queue_wait_s_p50": pct(wait, 50),
        "queue_wait_s_p90": pct(wait, 90),
        "queue_wait_s_p99": pct(wait, 99),
        "itl_ms_p50": r["itl_ms_p50"],
        "itl_ms_p99": r["itl_ms_p99"],
        "step_s_p50": snap["histograms"]["serving.step_s"].get("p50"),
        "per_token_s_p50": snap["histograms"]["per_token_s"].get("p50"),
        "requests_completed": snap["counters"]["requests_completed"]
        - r["completed0"],
        "kv_high_water_blocks": snap["allocator"]["high_water_blocks"],
        "kv_reused_blocks": snap["allocator"]["reused_blocks"],
        "prefill_buckets": r["buckets"],
        "prefill_shapes_warmed": r["warmed"],
        "prefill_compile_count": r["compile_count"],
        "compile_count": r["compile_count_total"],
        "prefill_recompiles_after_warmup": r["recompiles"],
        "prefill_pad_tokens": r["pad_tokens"],
        "prefill_suffix_hist": r["suffix_hist"],
        "fused_steps": r["fused_steps"],
        "decode_stall_steps": r["decode_stall_steps"],
        # resolved quantization config + byte accounting (bucket_tuner
        # reads kv_bytes_per_token to price pad tokens in gather bytes)
        "weight_dtype": snap["quantization"]["weight_dtype"],
        "kv_dtype": snap["quantization"]["kv_dtype"],
        "kv_bytes_per_token": snap["quantization"]["kv_bytes_per_token"],
        "kv_pool_bytes": snap["quantization"]["kv_pool_bytes"],
    }
    pc = snap["prefix_cache"]
    if pc.get("enabled"):
        # deltas over the timed window (the warmup request primed the
        # cache but must not count as a hit)
        lookups = pc["prompt_tokens"] - r["pc0"]["prompt_tokens"]
        saved = pc["hit_tokens"] - r["pc0"]["hit_tokens"]
        result.update({
            "prefix_cache_hit_rate": round(saved / lookups, 4)
            if lookups else 0.0,
            "prefill_tokens_saved": saved,
            "prefix_cache_evictions": pc["evicted_blocks"],
            "prefix_cache_cached_blocks": pc["cached_blocks"],
        })
    if base is not None:
        result.update({
            "tok_s_unfused": round(base["tok_s"], 1),
            "decode_stall_steps_unfused": base["decode_stall_steps"],
            "itl_ms_p50_unfused": base["itl_ms_p50"],
            "itl_ms_p99_unfused": base["itl_ms_p99"],
        })
        if base["decode_stall_steps"] == 0:
            raise RuntimeError(
                "unfused baseline recorded ZERO decode stalls — the "
                "workload produced no admission-during-decode overlap "
                "(raise n_requests vs max_batch, or lower chunk), so "
                "the fused-vs-unfused comparison is vacuous")
        if not (r["decode_stall_steps"] < base["decode_stall_steps"]):
            raise RuntimeError(
                f"fused run stalled decode {r['decode_stall_steps']} "
                f"times vs {base['decode_stall_steps']} unfused — "
                f"piggybacked admission is not overlapping prefill "
                f"with in-flight decode")
    if trace_path is not None:
        # the Chrome-trace/Perfetto artifact: per-request timelines on
        # slot lanes + the engine step spans, straight off the sink
        chrome = r["trace"].to_chrome_trace()
        with open(trace_path, "w") as f:
            json.dump(chrome, f)
        result["trace_path"] = trace_path
        result["trace_events"] = len(chrome["traceEvents"])
    if untraced is not None:
        ratio = r["tok_s"] / untraced["tok_s"]
        result["tok_s_untraced"] = round(untraced["tok_s"], 1)
        result["trace_overhead_ratio"] = round(ratio, 4)
        if r["recompiles"] or untraced["recompiles"]:
            raise RuntimeError(
                f"tracing-overhead run recompiled after warmup "
                f"(traced {r['recompiles']}, untraced "
                f"{untraced['recompiles']}) — trace emission must not "
                f"touch compiled-shape memo keys")
        if ratio < 0.97:
            raise RuntimeError(
                f"tracing overhead gate: traced run at {ratio:.3f}x "
                f"the untraced tok/s (floor 0.97x) — trace emission "
                f"is no longer always-on-cheap")
    if chaos is not None:
        result.update(chaos)
    if routed is not None:
        result.update(routed)
    if quant is not None:
        result.update(quant)
    if disagg is not None:
        result.update(disagg)
    if slo is not None:
        result.update(slo)
    if spec is not None:
        result.update(spec)
    if workload in ("mixed", "fused", "chaos", "quantized", "router",
                    "restart", "slo", "speculative", "disagg") \
            and r["recompiles"]:
        raise RuntimeError(
            f"bucketed workload recompiled {r['recompiles']} prefill "
            f"shapes after warmup — the bucket ladder no longer covers "
            f"admission (warmed {r['warmed']}, buckets {r['buckets']})")
    return result


def _cli() -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--prefix-share", action="store_true",
                    help="N requests sharing a common prompt prefix "
                         "(exercises the prefix cache)")
    ap.add_argument("--bucketed", action="store_true",
                    help="mixed-length workload spanning every prefill "
                         "bucket; asserts zero recompiles after warmup")
    ap.add_argument("--fused", action="store_true",
                    help="admission-during-decode workload run fused "
                         "AND unfused; asserts the fused run stalls "
                         "decode less and never recompiles")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-isolation gate: re-serve the workload "
                         "with a seeded mid-stream fail-on-rid poison; "
                         "HARD-FAILS unless the culprit alone FAILS, "
                         "every innocent finishes bit-identical to the "
                         "fault-free run, recompiles stay 0 and the "
                         "pool drains clean")
    ap.add_argument("--router", action="store_true",
                    help="multi-replica failover gate: 2 ServingEngine "
                         "replicas behind Router + HttpFrontend serve "
                         "the mixed workload as concurrent SSE streams "
                         "over a real socket; a seeded chaos hang "
                         "poisons the victim's replica mid-stream; "
                         "HARD-FAILS unless the victim completes on "
                         "the survivor (pre-failover stream a strict "
                         "prefix), every request bit-matches the "
                         "single-engine reference, and recompiles "
                         "stay 0 on both replicas")
    ap.add_argument("--restart", action="store_true",
                    help="self-healing gate: like --router (a chaos "
                         "hang kills the victim's replica mid-stream, "
                         "stranded SSE streams must fail over with "
                         "the strict-prefix invariant) but with "
                         "auto_restart on; HARD-FAILS unless the dead "
                         "slot is respawned through the supervisor's "
                         "readiness gate, rejoins rotation and serves "
                         "a post-restart request with zero recompiles "
                         "on every engine incarnation")
    ap.add_argument("--slo", action="store_true",
                    help="SLO-engine gate: the mixed workload with "
                         "sampled device timing on vs off (HARD-FAILS "
                         "unless sampled tok/s >= 0.97x with zero "
                         "recompiles), then a 1-replica Router + "
                         "frontend leg where injected 4s step hangs "
                         "(short of the watchdog) must drive an "
                         "itl_ms_p99 BREACH visible end-to-end — "
                         "engine health, router rollup, /health "
                         "detail (still 200), slo_breaches_total in "
                         "the merged /metrics — and CLEAR after the "
                         "fault heals; plus a /debug/profile capture "
                         "window landing device-wall spans in the "
                         "merged trace")
    ap.add_argument("--speculative", action="store_true",
                    help="self-speculative decoding gate: the shared-"
                         "prefix workload runs plain, then with a "
                         "chain draft, then with a TREE draft (shape "
                         "from --spec-tree); HARD-FAILS unless both "
                         "spec outputs are bit-identical to the plain "
                         "greedy reference, accepted tokens/step > 1, "
                         "tree accepted/sweep >= chain's, and "
                         "recompiles stay 0; emits spec_accept_rate, "
                         "spec_tree_* and decode_tok_s_spec* fields")
    ap.add_argument("--spec-tree", default="2,1,1,1",
                    help="branching spec for the --speculative tree "
                         "leg, comma-separated per-level factors "
                         "(default 2,1,1,1: two candidates for the "
                         "first token, chains below — depth equals "
                         "the chain leg's k so the acceptance "
                         "comparison is budget-fair)")
    ap.add_argument("--load", action="store_true",
                    help="closed-loop load generator: Poisson session "
                         "arrivals, multi-turn rounds, shared system "
                         "prompts; emits goodput (completed-within-"
                         "deadline tok/s) and latency percentiles "
                         "under load. Combine with --router to run "
                         "the generator through a 2-replica Router "
                         "(multi-replica goodput scaling)")
    ap.add_argument("--sessions", type=int, default=6,
                    help="concurrent client sessions for --load")
    ap.add_argument("--turns", type=int, default=3,
                    help="multi-turn rounds per session for --load")
    ap.add_argument("--arrival-rate", type=float, default=8.0,
                    help="Poisson session arrival rate (1/s) for --load")
    ap.add_argument("--deadline-s", type=float, default=5.0,
                    help="per-request goodput deadline for --load")
    ap.add_argument("--quantized", action="store_true",
                    help="quantized-serving gate: the same workload "
                         "through fp, w8, int8-KV and w8+int8-KV "
                         "engines; HARD-FAILS on any post-warmup "
                         "recompile, any warm-vs-cold token mismatch, "
                         "int8 KV gather bytes > 0.55x fp, or "
                         "quantized-vs-fp greedy divergence below the "
                         "documented floor")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode gate: the "
                         "mixed workload through a monolithic "
                         "reference engine, then through "
                         "Router(disaggregated=True) with one "
                         "prefill-role and one decode-role replica "
                         "(KVSnapshot export/import per request), fp "
                         "AND w8+int8-KV; HARD-FAILS unless the "
                         "disaggregated streams are bit-identical to "
                         "the monolithic run, the decode replica ran "
                         "zero prefill chunks, every request migrated "
                         "exactly once, the int8 leg holds the "
                         "documented fp-match floor and recompiles "
                         "stay 0 on both replicas; emits migration "
                         "count/bytes and handoff latency")
    ap.add_argument("--tp", action="store_true",
                    help="tensor-parallel gate (forces 4 host devices "
                         "at module import): the mixed workload "
                         "single-device, then through a TP=4 mesh "
                         "engine with Megatron-sharded weights and a "
                         "head-sharded paged-KV pool; HARD-FAILS "
                         "unless TP output is bit-identical to "
                         "single-device, post-warmup recompiles stay "
                         "0 on both engines, and a TP=2-sharded "
                         "replica pair survives the --restart chaos "
                         "shape (failover + supervisor respawn of a "
                         "sharded slot). Composes with --speculative "
                         "(tree spec on the sharded engine) and "
                         "--attention-impl pallas (the ragged kernel "
                         "shard_map-wrapped on the mesh)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="serve with the prefix cache disabled")
    ap.add_argument("--attention-impl", default="auto",
                    choices=("auto", "xla", "pallas"),
                    help="paged-attention backend: xla reference "
                         "gather, pallas ragged kernel (interpret mode "
                         "off-TPU — parity, not speed), or auto "
                         "(pallas on TPU, xla elsewhere); the JSON "
                         "line records the RESOLVED impl")
    ap.add_argument("--fused-units", type=int, default=1,
                    help="max pending prefill units one fused step "
                         "carries (PR 5 follow-on: >1 drains "
                         "admission bursts faster under decode load)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write the run's per-request trace timelines "
                         "as Chrome-trace/Perfetto JSON to PATH "
                         "(load in ui.perfetto.dev; summarize with "
                         "tools/trace_report.py)")
    ap.add_argument("--trace-overhead", action="store_true",
                    help="run a discarded warm-up leg, then an ABBA "
                         "untraced/traced sequence (order bias "
                         "cancels); HARD-FAIL unless pooled traced "
                         "tok/s >= 0.97x pooled untraced with zero "
                         "post-warmup recompiles (the always-on-"
                         "cheap gate)")
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=None,
                    help="decode chunk length (default 4; 2 for "
                         "--fused so staggered budgets desync the "
                         "batch and admissions land mid-decode)")
    ap.add_argument("--prefix-len", type=int, default=24,
                    help="shared prefix length for --prefix-share")
    ap.add_argument("--suffix-len", type=int, default=6,
                    help="per-request suffix length for --prefix-share")
    ap.add_argument("--max-prefill-bucket", type=int, default=None,
                    help="cap the prefill bucket ladder (default 512; "
                         "16 for --bucketed/--fused so the workload "
                         "chunks)")
    a = ap.parse_args()
    # two legal combinations: --load --router (the load generator
    # through the Router) and --tp --speculative (the fast-path
    # composition gate: tree speculation on the TP=4 mesh engine —
    # add --attention-impl pallas for the full mesh x kernel x spec
    # composition); every other pairing stays exclusive
    load_router = a.load and a.router
    if load_router:
        a.router = False
    tp_spec = a.tp and a.speculative
    if tp_spec:
        a.speculative = False
    if sum((a.prefix_share, a.bucketed, a.fused, a.chaos,
            a.quantized, a.router, a.restart, a.slo, a.speculative,
            a.disagg, a.load, a.tp)) > 1:
        ap.error("--prefix-share, --bucketed, --fused, --chaos, "
                 "--quantized, --router, --restart, --slo, "
                 "--speculative, --disagg, --load and --tp are "
                 "mutually exclusive (except --load --router and "
                 "--tp --speculative)")
    workload = ("prefix-share" if a.prefix_share
                else "mixed" if a.bucketed
                else "fused" if a.fused
                else "chaos" if a.chaos
                else "quantized" if a.quantized
                else "router" if a.router
                else "restart" if a.restart
                else "slo" if a.slo
                else "speculative" if a.speculative
                else "disagg" if a.disagg
                else "tp" if a.tp
                else "load" if a.load else "random")
    bucket_cap = a.max_prefill_bucket
    if bucket_cap is None:
        # the mixed/fused/chaos/quantized/router/restart/slo workloads
        # should also exercise CHUNKED prefill, so cap the ladder below
        # their longest prompts (load's multi-turn histories chunk too)
        bucket_cap = (16 if workload in ("mixed", "fused", "chaos",
                                         "quantized", "router",
                                         "restart", "slo", "load",
                                         "speculative", "disagg",
                                         "tp")
                      else 512)
    chunk = (a.chunk if a.chunk is not None
             else 2 if workload in ("fused", "prefix-share", "chaos",
                                    "quantized", "router", "restart",
                                    "slo", "speculative", "disagg",
                                    "tp")
             else 4)
    return main(n_requests=a.n_requests, max_new=a.max_new,
                max_batch=a.max_batch, block_size=a.block_size,
                chunk=chunk, workload=workload,
                prefix_len=a.prefix_len, suffix_len=a.suffix_len,
                prefix_cache=not a.no_prefix_cache,
                max_prefill_bucket=bucket_cap,
                attention_impl=a.attention_impl,
                fused_units=a.fused_units,
                sessions=a.sessions, turns=a.turns,
                rate_hz=a.arrival_rate, deadline_s=a.deadline_s,
                load_router_replicas=2 if load_router else 0,
                spec_tree=tuple(int(b) for b in
                                a.spec_tree.split(",") if b.strip()),
                tp_speculative=tp_spec,
                trace_path=a.trace, trace_overhead=a.trace_overhead)


if __name__ == "__main__":
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    print(json.dumps(_cli()))
