"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Default run (one TPU chip), four phases at the full width of
`Sizes.flagship()` (d 4096, ffn 9472, 32/8 heads, hd 128,
11 layers, vocab 32000, bf16; weights random, made from --seed):

  device   the platform must be "tpu"; prints what JAX and the chip report
  kernels  every Pallas kernel of the two main paths, COMPILED, against
           its in-tree reference on the chip
  serve    serving.ServingEngine with the default attention backend:
           warmup(), start(), overlapping generate()/stream() requests,
           tokens compared with an engine pinned to attention_impl="xla"
  train    train.init_state / make_train_step, 8-bit Adam state, three
           steps on one fixed batch

`--chips 4` runs ONLY the cross-chip paths and what each is compared
with, one process driving all four chips: TP=4 serving against a
mesh-off engine on chip 0, and sharding=2 x mp=2 training against the
same seed and batch on one chip.

There is no CPU branch: without a TPU the script exits non-zero before
any phase and prints no result line. The LAST line of stdout is one
JSON object, {"ok": ..., "device": {"platform", "kind", "count"}};
the exit code is 0 only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Sequence, Tuple

# Stated tolerances. Kernel outputs are bf16 and the XLA references round
# their softmax weights to bf16 for the MXU, so kernels agree within
# KERNEL_TOL of the reference's scale: max|got - ref| <= tol * max(1, max|ref|).
KERNEL_TOL = 2e-2
# 8-bit Adam moments are float8 (3 mantissa bits), so the two update
# paths may land one code apart: 15 % elementwise for m, 32 % for v
# (stored as its square root, so one code squares), plus 1 % of the
# tensor's scale for elements in a block's subnormal range.
F8_RTOL_M, F8_RTOL_V, F8_ATOL_SCALE = 0.15, 0.32, 1e-2
# what a compiled Pallas kernel looks like in a TPU program's text
KERNEL_MARKER = "tpu_custom_call"
# Where two token streams part, next-token logits are recomputed through
# both attention backends AND in float32: the Pallas path may sit at most
# LOGIT_RATIO times as far from float32 as the XLA path does, plus
# LOGIT_SLACK (two bf16 steps of a logit in [2, 4) — the head emits bf16).
LOGIT_RATIO, LOGIT_SLACK = 1.5, 2.0 ** -5
# sharded vs one-chip training loss (reduction order + bf16 collectives;
# later steps also the fused vs chunked 8-bit update)
LOSS_TOL = 5e-2
# per-device bytes_in_use under a mesh: largest / smallest
MEM_BALANCE = 1.25


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size the phases use. `flagship()` is what the script runs;
    the CPU rehearsal (tests/test_chip_smoke.py) passes a tiny one."""
    cfg: Any
    max_batch: int = 8
    block_size: int = 16
    max_total_len: int = 2048
    max_new: int = 32
    # two buckets keep the cold warm-up ladder at 25 programs; prompts
    # of 100..1000 tokens exercise both, chunked and fused
    prefill_buckets: Tuple[int, ...] = (128, 512)
    prompt_lens: Tuple[int, ...] = (100, 333, 512, 700, 1000, 257)
    train_batch: int = 8
    train_seq: int = 2048
    norm_rows: int = 8192

    @staticmethod
    def flagship() -> "Sizes":
        """The ~2.1B bf16 Llama the serve and train phases share."""
        import jax.numpy as jnp
        from paddle_tpu.nlp import llama
        return Sizes(cfg=llama.LlamaConfig(
            vocab_size=32000, hidden_size=4096, intermediate_size=9472,
            num_hidden_layers=11, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=2048,
            param_dtype=jnp.bfloat16))


# ---------------------------------------------------------------------------
# small helpers
# ---------------------------------------------------------------------------

def _close(name: str, got, ref, tol: float = KERNEL_TOL) -> str:
    """Scale-aware comparison; returns a report line, raises on a miss."""
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {ref.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite values")
    scale = max(1.0, float(np.max(np.abs(ref))))
    err = float(np.max(np.abs(got - ref)))
    line = f"{name}: max|err| {err:.3g} (limit {tol * scale:.3g})"
    if err > tol * scale:
        raise AssertionError(line)
    return line


def _hbm(device=None) -> Dict[str, int]:
    import jax
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return {k: int(stats.get(k, 0))
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def _gib(n: int) -> str:
    return f"{n / 2**30:.2f} GiB"


def _prompts(sizes: Sizes, seed: int) -> List[List[int]]:
    import numpy as np
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, sizes.cfg.vocab_size, n)))
            for n in sizes.prompt_lens]


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------

def require_tpu(chips: int) -> Dict[str, Any]:
    """Fail (non-zero exit, no result line) unless JAX found the chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; jax.devices()[0].platform is "
                 f"{devs[0].platform!r} ({len(devs)} device(s)) — no CPU "
                 f"branch, no result")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"jax sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def phase_device(device: Dict[str, Any]) -> None:
    from importlib import metadata
    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    print(f"  device_kind {device['kind']!r}, count {device['count']}, "
          f"bytes_limit {_gib(_hbm()['bytes_limit'])}")
    print(f"  jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
          f"libtpu {libtpu}")


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------

def _ragged_inputs(sizes: Sizes, seed: int, rows: Sequence[Tuple[int, str]],
                   P: int):
    """Pools + one batch for the ragged kernel. `rows` is (live length,
    kind) per row: "suffix" rows carry P queries ending at length-1
    (shorter rows left-pad as invalid — the bucketed/chunked prefill
    shape); "decode" rows carry one valid query in column 0 at position
    length-1 (the fused step's decode rows, or plain decode at P=1)."""
    import jax.numpy as jnp
    import numpy as np
    cfg = sizes.cfg
    H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    bs = sizes.block_size
    M = -(-sizes.max_total_len // bs)
    R = len(rows)
    N = R * M + 1
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.standard_normal((N, bs, KV, hd), np.float32),
                     cfg.dtype)
    vp = jnp.asarray(rng.standard_normal((N, bs, KV, hd), np.float32),
                     cfg.dtype)
    q = jnp.asarray(rng.standard_normal((R, P, H, hd), np.float32),
                    cfg.dtype)
    free = list(rng.permutation(np.arange(1, N)))
    table = np.zeros((R, M), np.int32)
    pos = np.zeros((R, P), np.int32)
    val = np.zeros((R, P), np.bool_)
    maxpos = M * bs - 1
    for r, (length, kind) in enumerate(rows):
        for j in range(-(-length // bs)):
            table[r, j] = free.pop()
        for p in range(P):
            if kind == "suffix":
                j = length - P + p
                pos[r, p], val[r, p] = min(max(j, 0), maxpos), j >= 0
            else:
                pos[r, p] = min(length - 1 + p, maxpos)
                val[r, p] = p == 0
    return q, kp, vp, jnp.asarray(table), jnp.asarray(pos), jnp.asarray(val)


def _ragged_check(name: str, q, kp, vp, table, pos, val, **scales) -> str:
    import jax
    import numpy as np
    from paddle_tpu.nlp import paged
    from paddle_tpu.nlp.ragged_attention import ragged_paged_attention
    got = jax.jit(lambda *a: ragged_paged_attention(*a, **scales))(
        q, kp, vp, table, pos, val)
    ref = jax.jit(lambda *a: paged._paged_gqa_attention(
        *a, impl="xla", **scales))(q, kp, vp, table, pos, val)
    # the XLA path leaves never-read garbage in invalid rows; the kernel
    # writes zeros there
    ref = np.where(np.asarray(val)[:, :, None, None],
                   np.asarray(ref, np.float32), 0.0)
    return _close(f"ragged {name} q{tuple(q.shape)}", got, ref)


def phase_kernels(sizes: Sizes, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels import rms_norm as rn
    from paddle_tpu.optimizer.quant_state import _dequantize, adamw_q_fused
    from paddle_tpu.quantization import kv as kvq

    cfg = sizes.cfg
    T, B, Pb = sizes.max_total_len, sizes.max_batch, sizes.prefill_buckets
    rng = np.random.default_rng(seed)
    lens = [int(x) for x in rng.integers(1, T, B)]

    # --- ragged paged attention: decode, a prefill bucket, the fused
    # mixed batch, int8 KV with scales
    cases = {
        "decode": ([(n, "decode") for n in lens], 1),
        "prefill bucket": ([(Pb[-1] + T // 8, "suffix"),
                            (Pb[-1] // 2, "suffix")], Pb[-1]),
        "fused mixed": ([(n, "decode") for n in lens]
                        + [(Pb[0] + T // 4, "suffix")], Pb[0]),
    }
    for name, (rows, P) in cases.items():
        args = _ragged_inputs(sizes, seed, rows, P)
        print("  " + _ragged_check(name, *args))
        if name == "fused mixed":
            continue
        q, kp, vp, table, pos, val = args
        ks = jnp.max(jnp.abs(kp.astype(jnp.float32)), (1, 2, 3)) / kvq.BOUND
        vs = jnp.max(jnp.abs(vp.astype(jnp.float32)), (1, 2, 3)) / kvq.BOUND
        kq = kvq.quantize(kp, ks[:, None, None, None])
        vq = kvq.quantize(vp, vs[:, None, None, None])
        print("  " + _ragged_check(f"int8 {name}", q, kq, vq, table, pos,
                                   val, k_scale=ks, v_scale=vs))
        del q, kp, vp, kq, vq, args
    gc.collect()

    # --- flash attention forward + backward at the training sequence
    H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    S = sizes.train_seq
    q, k, v, w = (jnp.asarray(rng.standard_normal(s, np.float32), cfg.dtype)
                  for s in ((1, S, H, hd), (1, S, KV, hd), (1, S, KV, hd),
                            (1, S, H, hd)))

    def _loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    flash = lambda q, k, v: fa.flash_attention_fwd(q, k, v, True)  # noqa: E731
    exact = lambda q, k, v: fa.mha_ref(q, k, v, causal=True)       # noqa: E731
    if not fa._pallas_ok(q, k, True):
        raise AssertionError("flash: the Pallas gate refused the "
                             "training shape")
    print("  " + _close(f"flash fwd S={S}", jax.jit(flash)(q, k, v),
                        jax.jit(exact)(q, k, v)))
    got = jax.jit(jax.grad(_loss(flash), (0, 1, 2)))(q, k, v)
    ref = jax.jit(jax.grad(_loss(exact), (0, 1, 2)))(q, k, v)
    for n, g, r in zip(("dq", "dk", "dv"), got, ref):
        print("  " + _close(f"flash bwd {n}", g, r))
    del q, k, v, w, got, ref
    gc.collect()

    # --- RMSNorm forward + backward at the model width
    D = cfg.hidden_size
    x = jnp.asarray(rng.standard_normal((sizes.norm_rows, D), np.float32),
                    cfg.dtype)
    wt = jnp.asarray(1.0 + 0.1 * rng.standard_normal(D, np.float32),
                     cfg.dtype)
    dy = jnp.asarray(rng.standard_normal((sizes.norm_rows, D), np.float32),
                     cfg.dtype)
    eps = cfg.rms_norm_eps
    if not rn._use_pallas_norm(x):
        raise AssertionError("rms_norm: the Pallas gate refused d="
                             f"{D}")
    fused = lambda x, w: rn.rms_norm_train(x, w, eps, True)        # noqa: E731
    plain = lambda x, w: rn.rms_norm_ref(x, w, eps)                # noqa: E731
    nloss = lambda f: (lambda x, w: jnp.sum(                       # noqa: E731
        f(x, w).astype(jnp.float32) * dy.astype(jnp.float32)))
    print("  " + _close(f"rms_norm fwd d={D}", jax.jit(fused)(x, wt),
                        jax.jit(plain)(x, wt)))
    got = jax.jit(jax.grad(nloss(fused), (0, 1)))(x, wt)
    ref = jax.jit(jax.grad(nloss(plain), (0, 1)))(x, wt)
    for n, g, r in zip(("dx", "dw"), got, ref):
        print("  " + _close(f"rms_norm bwd {n}", g, r))
    del x, wt, dy, got, ref
    gc.collect()

    # --- the fused 8-bit Adam update against the chunked jnp stream
    params = {"w": jnp.asarray(
        0.02 * rng.standard_normal((D, D), np.float32), cfg.dtype)}
    grads = {"w": jnp.asarray(
        0.01 * rng.standard_normal((D, D), np.float32), cfg.dtype)}
    tx = adamw_q_fused(1e-3, weight_decay=0.1, clip_norm=1.0)
    state = tx.init(params)

    @jax.jit
    def chained(g, s, p):
        upd, s2 = tx.update(g, s, p)
        return optax.apply_updates(p, upd), s2

    for step in range(2):       # two steps: count / bias correction move
        p_ref, s_ref = chained(grads, state, params)
        p_got, s_got = jax.jit(tx.apply_fused)(grads, state, params)
        print("  " + _close(f"adam8 step {step} params", p_got["w"],
                            p_ref["w"]))
        for n, tg, tr, sq in (("m", s_got.m, s_ref.m, False),
                              ("v", s_got.v, s_ref.v, True)):
            g_ = np.asarray(_dequantize(tg["w"], (D, D), sq))
            r_ = np.asarray(_dequantize(tr["w"], (D, D), sq))
            np.testing.assert_allclose(
                g_, r_, rtol=F8_RTOL_V if sq else F8_RTOL_M,
                atol=F8_ATOL_SCALE * float(np.max(np.abs(r_))),
                err_msg=f"adam8 step {step} moment {n}")
        params, state = p_got, s_got
    print(f"  adam8 moments: m within {F8_RTOL_M:.0%}, v within "
          f"{F8_RTOL_V:.0%} elementwise (+{F8_ATOL_SCALE:.0%} of scale)")
    del params, grads, state, p_ref, s_ref, p_got, s_got
    gc.collect()


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def _engine(params, sizes: Sizes, **kw):
    from paddle_tpu import serving
    return serving.ServingEngine(
        params, sizes.cfg, max_batch=sizes.max_batch,
        block_size=sizes.block_size, max_total_len=sizes.max_total_len,
        max_new_tokens=sizes.max_new,
        prefill_buckets=sizes.prefill_buckets, start=False, **kw)


def _serve_once(eng, prompts: List[List[int]], sizes: Sizes,
                want_impl: str) -> Dict[str, Any]:
    """warmup(), start(), overlapping generate()/stream() requests,
    drain, and the engine-side assertions. Returns tokens + facts; the
    caller shuts the engine down."""
    t0 = time.perf_counter()
    warmed = eng.warmup()
    warm_s = time.perf_counter() - t0
    if eng.attention_impl != want_impl:
        raise AssertionError(f"attention_impl resolved to "
                             f"{eng.attention_impl!r}, want {want_impl!r}")
    compiles = eng.batcher.compile_count
    chunk_text = eng.batcher._chunk_exe().as_text()
    eng.start()
    outs: List[Any] = [None] * len(prompts)

    def one(i: int) -> None:
        try:
            if i % 2:
                outs[i] = list(eng.stream(prompts[i]))
            else:
                outs[i] = eng.generate(prompts[i], timeout=600)
        except Exception as e:          # noqa: BLE001 — reported below
            outs[i] = e

    t0 = time.perf_counter()
    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(900)
    if any(t.is_alive() for t in threads):
        raise AssertionError("a request did not finish within 900 s")
    serve_s = time.perf_counter() - t0
    if not eng.drain(60):
        raise AssertionError("engine did not drain")
    for i, o in enumerate(outs):
        if isinstance(o, Exception):
            raise AssertionError(f"request {i} failed: {o!r}") from o
        if len(o) != sizes.max_new:
            raise AssertionError(f"request {i} returned {len(o)} tokens, "
                                 f"budget {sizes.max_new}")
    if eng.batcher.compile_count != compiles:
        raise AssertionError(
            f"compile_count moved {compiles} -> "
            f"{eng.batcher.compile_count} after warmup()")
    snap = eng.snapshot()
    if snap["gauges"]["kv_blocks_in_use"] != 0:
        raise AssertionError(f"kv_blocks_in_use "
                             f"{snap['gauges']['kv_blocks_in_use']} after "
                             f"drain")
    return {"tokens": outs, "warm_s": warm_s, "warmed": warmed,
            "serve_s": serve_s, "snap": snap, "chunk_text": chunk_text}


def _next_logits_fn(cfg, sizes: Sizes, impl: str, f32: bool = False):
    """(params, context tokens) -> next-token logits through the paged
    forward with attention backend `impl`, on a private single-request
    pool (one cached-prefix-style pass: every token written, then
    attended per-query-causally through the block table). Compiles once
    for any context length. `f32` computes in float32 at the highest
    matmul precision — the plain reference both backends are held
    against."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.nlp import paged
    if f32:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    bs = sizes.block_size
    M = -(-sizes.max_total_len // bs)
    P = M * bs
    table = jnp.arange(1, M + 1, dtype=jnp.int32)[None]

    @jax.jit
    def fwd(params, toks, n):
        kp, vp, ks, vs = paged.init_pool(cfg, M + 1, bs)
        cache = paged.PagedKVCache(kp, vp, table,
                                   jnp.zeros((1,), jnp.int32), ks, vs)
        col = jnp.arange(P)[None]
        logits, _ = paged.forward_paged(
            params, toks, cache, jnp.minimum(col, n - 1), col < n, cfg,
            is_prefill=False, attention_impl=impl)
        return jnp.take(logits[0], n - 1, axis=0)

    def next_logits(params, context: List[int]):
        toks = np.zeros((1, P), np.int32)
        toks[0, :len(context)] = context
        with jax.default_matmul_precision("highest" if f32 else "default"):
            out = fwd(params, jnp.asarray(toks), jnp.int32(len(context)))
        return np.asarray(out, np.float32)

    return next_logits


def compare_tokens(params, sizes: Sizes, prompts, got, ref,
                   names=("pallas", "xla")) -> List[str]:
    """Tokens identical — or, where a request's two streams first
    differ, CHECK that rounding explains it: recompute the next-token
    logits after the common context through the Pallas path, the XLA
    path and in float32. With d_p, d_x the two paths' largest distance
    from float32: the Pallas path is held to
    d_p <= LOGIT_RATIO * d_x + LOGIT_SLACK (a wrong kernel would be far
    off), and both chosen tokens must sit within 2 * max(d_p, d_x) of
    the float32 maximum — a near-tie that either rounding could tip.
    After a divergence the rest of that request is not comparable."""
    import numpy as np
    lines = []
    pallas, xla, exact = (
        _next_logits_fn(sizes.cfg, sizes, impl, f32) for impl, f32 in
        (("pallas", False), ("xla", False), ("xla", True)))
    for i, (a, b) in enumerate(zip(got, ref)):
        if a == b:
            lines.append(f"request {i} (prompt {len(prompts[i])}): "
                         f"{len(a)} tokens identical")
            continue
        j = next(j for j in range(len(a)) if a[j] != b[j])
        ctx = list(prompts[i]) + list(a[:j])
        lp, lx, lr = (f(params, ctx) for f in (pallas, xla, exact))
        d_p = float(np.max(np.abs(lp - lr)))
        d_x = float(np.max(np.abs(lx - lr)))
        ta, tb = a[j], b[j]
        below = [float(lr.max() - lr[t]) for t in (ta, tb)]
        line = (f"request {i} (prompt {len(prompts[i])}): first differ at "
                f"token {j} ({names[0]} {ta} vs {names[1]} {tb}); "
                f"recomputed logits vs float32 (range "
                f"{lr.min():.2f}..{lr.max():.2f}): pallas {d_p:.3g}, xla "
                f"{d_x:.3g}; the two tokens sit {below[0]:.3g} and "
                f"{below[1]:.3g} below the float32 maximum")
        if d_p > LOGIT_RATIO * d_x + LOGIT_SLACK:
            raise AssertionError("pallas logits too far from float32 — "
                                 + line)
        if max(below) > 2 * max(d_p, d_x):
            raise AssertionError("not a near-tie — " + line)
        lines.append(line + " — near-tie within rounding")
    return lines


def phase_serve(sizes: Sizes, seed: int) -> None:
    import jax
    from paddle_tpu.nlp import llama
    params = llama.init_params(jax.random.key(seed), sizes.cfg)
    prompts = _prompts(sizes, seed)
    print(f"  params {llama.num_params(sizes.cfg) / 1e9:.2f} B, "
          f"{len(prompts)} prompts of {list(sizes.prompt_lens)} tokens, "
          f"{sizes.max_new} new each, buckets {sizes.prefill_buckets}")

    eng = _engine(params, sizes)            # default attention_impl
    try:
        run = _serve_once(eng, prompts, sizes, "pallas")
    finally:
        eng.shutdown(drain=False, timeout=30)
    if KERNEL_MARKER not in run.pop("chunk_text"):
        raise AssertionError(f"the served decode step's compiled text has "
                             f"no {KERNEL_MARKER} — the kernel is not in "
                             f"the program that runs")
    print(f"  pallas engine: warm-up {run['warm_s']:.1f} s "
          f"({run['warmed']} programs, cold unless a compile cache was "
          f"already warm), requests {run['serve_s']:.1f} s, "
          f"{KERNEL_MARKER} in the decode step, 0 recompiles, "
          f"fused_steps {run['snap']['gauges']['fused_steps']:.0f}, "
          f"peak HBM {_gib(_hbm()['peak_bytes_in_use'])}")
    del eng
    gc.collect()

    ref_eng = _engine(params, sizes, attention_impl="xla")
    try:
        ref = _serve_once(ref_eng, prompts, sizes, "xla")
    finally:
        ref_eng.shutdown(drain=False, timeout=30)
    print(f"  xla engine: warm-up {ref['warm_s']:.1f} s, requests "
          f"{ref['serve_s']:.1f} s")
    del ref_eng
    gc.collect()
    for line in compare_tokens(params, sizes, prompts, run["tokens"],
                               ref["tokens"]):
        print("  " + line)

    # the compile cache, shown working: the same ladder again
    again = _engine(params, sizes)
    t0 = time.perf_counter()
    n = again.warmup()
    again_s = time.perf_counter() - t0
    again.shutdown(drain=False, timeout=30)
    print(f"  warm-up again, same {n} programs through the persistent "
          f"compile cache: {again_s:.1f} s (first {run['warm_s']:.1f} s)")
    del again, params
    gc.collect()


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------

def _train(sizes: Sizes, seed: int, mesh, steps: int = 3):
    """init_state + make_train_step, AOT-compiled once, `steps` steps on
    one fixed batch. Returns (losses, step seconds, compiled text,
    final state)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.nlp import train
    cfg = sizes.cfg
    tx = train.make_optimizer(1e-4, state_quant="8bit", grad_clip=1.0)
    state = train.init_state(jax.random.key(seed), cfg, tx, mesh=mesh)
    step = train.make_train_step(cfg, tx, mesh=mesh)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (sizes.train_batch, sizes.train_seq)), jnp.int32)
    t0 = time.perf_counter()
    exe = step.lower(state, tokens).compile()
    compile_s = time.perf_counter() - t0
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, m = exe(state, tokens)
        jax.block_until_ready((state, m))
        secs.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
    return losses, secs, compile_s, exe.as_text(), state


def _check_losses(losses: List[float]) -> None:
    import math
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")


def phase_train(sizes: Sizes, seed: int) -> None:
    print(f"  HBM in use before: {_gib(_hbm()['bytes_in_use'])}; fixed "
          f"batch {sizes.train_batch} x {sizes.train_seq} made from the "
          f"seed (no DataLoader, so no shm transport is built)")
    losses, secs, compile_s, text, state = _train(sizes, seed, None)
    del state
    gc.collect()
    _check_losses(losses)
    if KERNEL_MARKER not in text:
        raise AssertionError(f"the train step's compiled text has no "
                             f"{KERNEL_MARKER}")
    print(f"  compile {compile_s:.1f} s, {text.count(KERNEL_MARKER)} "
          f"{KERNEL_MARKER} sites; loss "
          + " -> ".join(f"{x:.4f}" for x in losses)
          + "; step seconds (smoke timings, not metrics) "
          + ", ".join(f"{s:.2f}" for s in secs)
          + f"; peak HBM {_gib(_hbm()['peak_bytes_in_use'])}")


# ---------------------------------------------------------------------------
# --chips 4: the cross-chip paths
# ---------------------------------------------------------------------------

def _spans(tree, n: int, what: str) -> None:
    import jax
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if len(leaf.sharding.device_set) != n:
            raise AssertionError(
                f"{what}{jax.tree_util.keystr(path)} lives on "
                f"{len(leaf.sharding.device_set)} device(s), want {n}")


def _balanced(what: str, n: int = 4) -> str:
    import jax
    used = [_hbm(d)["bytes_in_use"] for d in jax.devices()[:n]]
    line = f"{what} bytes_in_use per device: " + ", ".join(map(_gib, used))
    if min(used) <= 0 or max(used) / min(used) > MEM_BALANCE:
        raise AssertionError(f"unbalanced (limit {MEM_BALANCE}x) — " + line)
    return line


def _has_collectives(text: str, what: str) -> str:
    found = [op for op in ("all-reduce", "all-gather", "reduce-scatter",
                           "all-to-all", "collective-permute")
             if op in text]
    if not found:
        raise AssertionError(f"{what}: no collective in the compiled text")
    return f"{what} collectives: {', '.join(found)}"


def phase_tp_serve(sizes: Sizes, seed: int, tp: int = 4) -> None:
    import jax
    import numpy as np
    from paddle_tpu.nlp import llama
    from paddle_tpu.serving.tp import MeshConfig
    # weights leave the device between engines so chip 0 never holds a
    # full copy next to its shard (the balance check would read that)
    host = jax.tree.map(np.asarray,
                        llama.init_params(jax.random.key(seed), sizes.cfg))
    gc.collect()
    prompts = _prompts(sizes, seed)

    eng = _engine(host, sizes, mesh=MeshConfig(tp=tp))
    try:
        run = _serve_once(eng, prompts, sizes, "pallas")
        _spans(eng.batcher.params, tp, "param ")
        _spans((eng.batcher.cache.k, eng.batcher.cache.v), tp, "kv pool ")
        print("  " + _balanced(f"TP={tp} engine"))
        per_dev = run["snap"]["tp"]["kv_pool_bytes_per_device"]
        shard = max(s.data.nbytes for s in
                    eng.batcher.cache.k.addressable_shards) * 2
    finally:
        eng.shutdown(drain=False, timeout=30)
    text = run.pop("chunk_text")
    if KERNEL_MARKER not in text:
        raise AssertionError(f"TP decode step has no {KERNEL_MARKER}")
    print("  " + _has_collectives(text, f"TP={tp} decode step"))
    print(f"  TP={tp} engine: warm-up {run['warm_s']:.1f} s "
          f"({run['warmed']} programs), requests {run['serve_s']:.1f} s, "
          f"0 recompiles")
    del eng
    gc.collect()

    params0 = jax.device_put(host, jax.devices()[0])
    ref_eng = _engine(params0, sizes)
    try:
        ref = _serve_once(ref_eng, prompts, sizes, "pallas")
        full = ref["snap"]["tp"]["kv_pool_bytes_per_device"]
    finally:
        ref_eng.shutdown(drain=False, timeout=30)
    del ref_eng
    gc.collect()
    print(f"  kv pool bytes per device: snapshot {per_dev}, largest real "
          f"shard {shard}, mesh-off pool {full}")
    if not (per_dev == shard and abs(per_dev * tp - full) <= full // 100):
        raise AssertionError("the KV pool is not split in four")
    print(f"  mesh-off engine on chip 0: warm-up {ref['warm_s']:.1f} s, "
          f"requests {ref['serve_s']:.1f} s")
    for line in compare_tokens(params0, sizes, prompts, run["tokens"],
                               ref["tokens"], names=(f"tp{tp}", "tp1")):
        print("  " + line)


def phase_sharded_train(sizes: Sizes, seed: int) -> None:
    import jax
    from paddle_tpu.parallel.topology import build_mesh
    mesh = build_mesh(sharding=2, mp=2, devices=jax.devices()[:4])
    losses, secs, compile_s, text, state = _train(sizes, seed, mesh)
    _spans(state.params, 4, "param ")
    print("  " + _balanced("sharding=2 x mp=2 train state"))
    del state
    gc.collect()
    _check_losses(losses)
    print("  " + _has_collectives(text, "sharded train step"))
    print(f"  sharded: compile {compile_s:.1f} s, loss "
          + " -> ".join(f"{x:.4f}" for x in losses)
          + "; step seconds (smoke timings) "
          + ", ".join(f"{s:.2f}" for s in secs))
    one, secs1, compile1, _, state = _train(sizes, seed, None)
    del state
    gc.collect()
    print(f"  one chip: compile {compile1:.1f} s, loss "
          + " -> ".join(f"{x:.4f}" for x in one)
          + "; step seconds (smoke timings) "
          + ", ".join(f"{s:.2f}" for s in secs1))
    worst = max(abs(a - b) for a, b in zip(losses, one))
    print(f"  max |sharded - one chip| loss {worst:.4g} "
          f"(limit {LOSS_TOL})")
    if worst > LOSS_TOL:
        raise AssertionError("sharded and one-chip losses disagree")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_phases(phases: Sequence[Tuple[str, Callable[[], None]]]) -> bool:
    """Run every phase (a failure does not stop the later ones — one
    chip call should show everything that is broken); True iff all
    passed."""
    from paddle_tpu.core.compile_cache import compile_log
    failed = []
    for name, fn in phases:
        print(f"[{name}]", flush=True)
        t0 = time.perf_counter()
        try:
            fn()
            verdict = "ok"
        except Exception:       # noqa: BLE001 — phase boundary: report, go on
            traceback.print_exc(file=sys.stdout)
            failed.append(name)
            verdict = "FAILED"
        gc.collect()
        # the programs of the phase, by the compile log (its clock is t0's)
        cache = compile_log.summary(since=t0)
        print(f"[{name}] {verdict} in {time.perf_counter() - t0:.1f} s "
              f"(compile cache: {cache['hits']} hits, "
              f"{cache['misses']} misses)", flush=True)
    if failed:
        print("failed phases: " + ", ".join(failed))
    return not failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: device, kernels, serve, train on one chip; "
                         "4: only the cross-chip paths and their "
                         "comparisons")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and batch")
    args = ap.parse_args(argv)

    # JAX is first touched here, after the arguments are known
    from paddle_tpu.core.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    device = require_tpu(args.chips)
    print(f"compile cache: {cache_dir}")
    sizes = Sizes.flagship()
    if args.chips == 1:
        phases = [("device", lambda: phase_device(device)),
                  ("kernels", lambda: phase_kernels(sizes, args.seed)),
                  ("serve", lambda: phase_serve(sizes, args.seed)),
                  ("train", lambda: phase_train(sizes, args.seed))]
    else:
        # half the batch slots: a third fewer warm-up programs per
        # engine, at four chips' price per second
        small = dataclasses.replace(sizes, max_batch=4,
                                    prompt_lens=sizes.prompt_lens[:4])
        phases = [("tp-serve", lambda: phase_tp_serve(small, args.seed)),
                  ("sharded-train",
                   lambda: phase_sharded_train(sizes, args.seed))]
    ok = run_phases(phases)
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
