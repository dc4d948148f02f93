"""The main paths' Pallas kernels, compiled for a TPU v5e that is
described, not attached (on-chip-measurement guide, section 2,
rehearsal 3): the chip's own compiler is installed here, and it refuses
here what it would refuse there — block shapes the tiling cannot hold,
reshapes Mosaic cannot lay out, kernels GSPMD cannot partition, programs
that do not fit VMEM. Interpret mode sees none of that.

Every case is one kernel at the flagship widths of
`chip_smoke.py::Sizes.flagship` (H 32, KV 8, hd 128, d 4096, block 16,
table width 128, bf16). A compile that passes is not a chip run; it only
keeps what `chip_smoke.py` needs from breaking between chip runs.
"""
import contextlib
import os
import re
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.core import flags

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, KV, HD, D, BS, M = 32, 8, 128, 4096, 16, 128
N = 8 * M                                     # pool blocks: 8 slots
BF = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(autouse=True)
def _real_kernels_no_cache():
    """Take every kernel gate's TPU branch from this CPU host, and keep
    the persistent compile cache out of it: an entry compiled for a
    described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    flags.set_flags({"FLAGS_pallas_force": True})
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    flags.set_flags({"FLAGS_pallas_force": False})


def _compile(fn, shardings, *shapes):
    """Compile fn for the described chip(s); returns the program text."""
    avals = [jax.ShapeDtypeStruct(s, d, sharding=sh)
             for (s, d), sh in zip(shapes, shardings)]
    return jax.jit(fn).lower(*avals).compile().as_text()


@contextlib.contextmanager
def _tpu_backend():
    """A kernel's gate that asks the backend hears "tpu" from this CPU
    host: steered from the test (the program has no option for it)."""
    default_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        yield
    finally:
        jax.default_backend = default_backend


def _assert_pool_aliased(exe, pools):
    """A step program compiled with its pool donated, as the batcher
    compiles every one that returns a pool (`ContinuousBatcher._step_jit`):
    each of the pool's buffers comes back in its argument's own
    (`input_output_alias`, `alias_size_in_bytes`), and no `copy`,
    `copy-start` or `copy-done` of a pool's shape is left ANYWHERE, the
    entry computation included, where the undonated program copies K and
    V whole before it does anything."""
    txt = exe.as_text()
    shapes = [",".join(map(str, p.shape)) for p in pools]
    made = re.findall(
        r"^\s*(?:ROOT )?%\S+ = \(*\w+\[([\d,]+)\]\S* (copy\S*)\(", txt,
        re.M)
    assert not [m for m in made if m[0] in shapes], made
    header = txt.split("\n", 1)[0]
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    entry = txt[txt.index("\nENTRY "):]
    of = {int(n): shape for shape, n in re.findall(
        r"= \w+\[([\d,]+)\]\S* parameter\((\d+)\)", entry)}
    assert sorted(of[n] for n in aliased) == sorted(shapes), (header[:400])
    assert exe.memory_analysis().alias_size_in_bytes >= sum(
        int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)


def _list_grid(txt, R, Pq, width, pool, windowed=False):
    """The kernel's call in a compiled program, its grid a work list: the
    bound is an operand (the scalar that leads them), then the table,
    the tiles' live blocks, the list's three arrays at the full grid's
    length and, for a window layer, the tiles' first blocks."""
    from paddle_tpu.nlp.ragged_attention import (attn_grid_steps,
                                                 gqa_tiling_args)
    call = next(line for line in txt.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    n = attn_grid_steps(R, Pq, width, **gqa_tiling_args(*pool))
    T = -(-Pq // 128)
    tiles = f"s32[{R},{T}]{{1,0}}, "
    assert ("operand_layout_constraints={s32[], "
            f"s32[{R},{width}]{{1,0}}, " + tiles + f"s32[{n}]{{0}}, " * 3
            + (tiles if windowed else "")) in call, call[:600]
    return call


def _ragged(topo, R, Pq, kv_dtype=BF, slab=0, mesh=False):
    from paddle_tpu.nlp.ragged_attention import ragged_paged_attention
    if mesh:
        m = Mesh(np.array(topo.devices), ("mp",))
        rep, head = NamedSharding(m, P()), \
            NamedSharding(m, P(None, None, "mp", None))
    else:
        m, rep = None, SingleDeviceSharding(topo.devices[0])
        head = rep
    shapes = [((R, Pq, H, HD), BF), ((N, BS, KV, HD), kv_dtype),
              ((N, BS, KV, HD), kv_dtype), ((R, M), jnp.int32),
              ((R, Pq), jnp.int32), ((R, Pq), jnp.bool_)]
    sh = [head, head, head, rep, rep, rep]
    names = []
    if kv_dtype == jnp.int8:
        names += ["k_scale", "v_scale"]
        shapes += [((N,), jnp.float32)] * 2
        sh += [rep, rep]
    if slab:
        names += ["suffix_k", "suffix_v", "suffix_vis"]
        shapes += [((R, slab, KV, HD), BF)] * 2 + [((R, Pq, slab), jnp.bool_)]
        sh += [head, head, rep]

    def fn(q, kp, vp, tab, pos, val, *rest):
        return ragged_paged_attention(q, kp, vp, tab, pos, val,
                                      interpret=False, mesh=m,
                                      **dict(zip(names, rest)))

    txt = _compile(fn, sh, *shapes)
    # under a mesh every shard walks the list of the GLOBAL pool's call
    _list_grid(txt, R, Pq, M, ((N, BS, KV, HD), kv_dtype))
    return txt


def _ragged_window(topo, R, Pq, width, ring):
    """The window form of the ragged kernel at the widths of the window +
    full GQA decoder in the benchmark (H 32, KV 4 of 128: a group of 8):
    a window of 1024 over a ring table 97 wide, and, window None, the
    full layers' call over their table of 800. The walk's first block
    rides the scalar prefetch after the work list."""
    from paddle_tpu.nlp.ragged_attention import ragged_paged_attention
    one = SingleDeviceSharding(topo.devices[0])
    KVw, Nw = 4, 4 * width
    window = 1024 if ring else None

    def fn(q, kp, vp, tab, pos, val):
        return ragged_paged_attention(q, kp, vp, tab, pos, val,
                                      interpret=False, window=window,
                                      ring=ring)

    txt = _compile(fn, [one] * 6, ((R, Pq, H, HD), BF),
                   ((Nw, BS, KVw, HD), BF), ((Nw, BS, KVw, HD), BF),
                   ((R, width), jnp.int32), ((R, Pq), jnp.int32),
                   ((R, Pq), jnp.bool_))
    call = _list_grid(txt, R, Pq, width, ((Nw, BS, KVw, HD), BF), ring)
    # the window form's instruction carries its own name: a trace's
    # readers tell the two kinds of layer apart by it
    assert ("%ragged_window_attention" in call) == ring, call[:200]
    return txt


def _ragged_gated(topo, R, Pq, kind):
    """The ragged kernel at the widths of the gated window + full decoder
    in the benchmark, whose kinds differ in query heads over 8 KV heads of
    128: a window layer's 72 (a group of 9: 9 rows a KV head at decode,
    no multiple of the 8 sublanes) over a ring table 65 wide, a full
    layer's 48 (a group of 6) over its table of 304; 64 queries a
    prefill tile in both (`_TILE_HEAD_ROWS`)."""
    from paddle_tpu.nlp.ragged_attention import (attn_grid_steps,
                                                 gqa_tiling_args,
                                                 ragged_paged_attention)
    one = SingleDeviceSharding(topo.devices[0])
    Hk, width, window = (72, 65, 512) if kind == "window" else (48, 304, None)
    Nk = 4 * width
    pool = ((Nk, BS, KV, HD), BF)

    def fn(q, kp, vp, tab, pos, val):
        return ragged_paged_attention(q, kp, vp, tab, pos, val,
                                      interpret=False, window=window,
                                      ring=window is not None)

    txt = _compile(fn, [one] * 6, ((R, Pq, Hk, HD), BF), pool, pool,
                   ((R, width), jnp.int32), ((R, Pq), jnp.int32),
                   ((R, Pq), jnp.bool_))
    call = next(line for line in txt.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    n = attn_grid_steps(R, Pq, width, **gqa_tiling_args(*pool, heads=Hk))
    Pt = min(Pq, 64)
    assert f"s32[{R},{width}]{{1,0}}, s32[{R},{Pq // Pt}]{{1,0}}, " \
        + f"s32[{n}]{{0}}, " * 3 in call, call[:600]
    assert f"bf16[{R},{Pq // Pt},{KV},{Pt * Hk // KV},{HD}]" in call
    return txt


def _gated_prefill_step(topo, donated=False):
    """The warm one-row prefill forward of the 128 bucket at
    `laguna-s-ep4`'s widths (`paged.forward_paged` over the kinded pool,
    both kernels, the gate, the leading dense group and the period's
    scan): inside a step program the compiler also gives the kernel's
    small operands scoped VMEM, and a tile of 128 queries at 48 heads,
    which compiles alone, took 17.71 MiB of 16.75 there (PR 43's first
    chip call). `donated`: the pool donated as the batcher donates it,
    `_assert_pool_aliased`."""
    from benchmark.harness import manifest
    from benchmark.models import gated_window_moe_decoder as fam
    from paddle_tpu.nlp import paged
    one = SingleDeviceSharding(topo.devices[0])
    spec = manifest.config(manifest.ROOT, "laguna-s-ep4")
    d, cfg, eng = fam.dims(spec), fam.program_config(spec), spec["engine"]
    width = eng["max_total_len"] // BS
    ring = paged.ring_blocks(cfg.sliding_window, 512, BS)
    lay = paged.KVLayout(full_layers=2, window_layers=3,
                         full_blocks=eng["max_batch"] * width,
                         window_blocks=eng["max_batch"] * ring, width=width,
                         ring=ring)
    pool = jax.eval_shape(lambda: paged.init_pool(cfg, 0, BS, layout=lay))
    R, Pq = 1, 128

    def fn(params, pool, table, tokens, positions, valid):
        cache = paged.PagedKVCache(pool[0], pool[1], table,
                                   jnp.zeros((R,), jnp.int32))
        logits, cache = paged.forward_paged(
            params, tokens, cache, positions, valid, cfg, False, "pallas",
            layout=lay)
        return logits, cache.k, cache.v

    on = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)  # noqa: E731
    i32 = jax.ShapeDtypeStruct((R, Pq), jnp.int32, sharding=one)
    with _tpu_backend():
        exe = jax.jit(fn, donate_argnums=(1,) if donated else ()).lower(
            jax.tree.map(on, fam.params_shape(d, BF)),
            jax.tree.map(on, pool[:2]),
            jax.ShapeDtypeStruct((R, width + ring), jnp.int32, sharding=one),
            i32, i32, jax.ShapeDtypeStruct((R, Pq), jnp.bool_, sharding=one)
        ).compile()
    txt = exe.as_text()
    if donated:
        _assert_pool_aliased(exe, pool[:2])
    for name in ("%ragged_paged_attention", "%ragged_window_attention",
                 "%grouped_gemm"):
        assert name in txt, name
    assert txt.count(" while(") >= 2        # the lead group, the periods
    return txt


def _flash(topo, mesh=False):
    from paddle_tpu.kernels import flash_attention as fa
    S = 2048
    if mesh:
        m = Mesh(np.array(topo.devices).reshape(2, 2), ("sharding", "mp"))
        spec = P("sharding", None, "mp", None)
        attn = lambda q, k, v: fa.flash_attention_sharded(  # noqa: E731
            q, k, v, m, spec)
        sh, B = NamedSharding(m, spec), 2
    else:
        attn = lambda q, k, v: fa.flash_attention_fwd(      # noqa: E731
            q, k, v, True)
        sh, B = SingleDeviceSharding(topo.devices[0]), 1
    loss = lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32))  # noqa: E731
    return _compile(jax.value_and_grad(loss, (0, 1, 2)), [sh] * 3,
                    ((B, S, H, HD), BF), ((B, S, KV, HD), BF),
                    ((B, S, KV, HD), BF))


def _flash_mla(topo):
    """Flash forward and backward at the latent attention's expanded
    widths, as `mla.attend_expanded` calls them: q and k 192 wide, v
    zero-padded from 128 to 192, 4 x 4096 tokens of 32 heads (the streamed
    backward: 4096 is past the resident kernels' 2048)."""
    from paddle_tpu.nlp import mla
    one = SingleDeviceSharding(topo.devices[0])
    cfg = mla.MlaMoeConfig(
        hidden_size=3584, num_attention_heads=32, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, dtype=BF, param_dtype=BF)

    def loss(q, row, w):
        o = mla.attend_expanded(q, row, {"kv_b_proj": w}, cfg)
        return jnp.sum(o.astype(jnp.float32))

    txt = _compile(jax.value_and_grad(loss, (0, 1, 2)), [one] * 3,
                   ((4, 4096, 32, 192), BF), ((4, 4096, 576), BF),
                   ((512, 32 * 256), BF))
    calls = [line for line in txt.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert any("bf16[128,4096,192]" in c for c in calls), calls[:1]
    assert len(calls) >= 2                          # forward and backward
    return txt


def _mhc_train_step(topo):
    """The whole training step of the benchmark's `xing4-ep4-train`
    configuration at its own sizes (4 x 4096 tokens, bf16 parameters, 8-bit
    Adam, per-layer remat), for ONE described v5e: the chip's compiler
    refuses a program that does not fit its 15.75 GiB."""
    from benchmark.harness import manifest
    from paddle_tpu.nlp import train
    one = SingleDeviceSharding(topo.devices[0])
    config = manifest.config(manifest.ROOT, "xing4-ep4-train")
    fam = manifest.plugin("models", config["family"])
    d, pcfg = fam.dims(config), fam.program_config(config)
    t = config["trainer"]
    tx = train.make_optimizer(
        t["learning_rate"], weight_decay=t["weight_decay"], b1=t["b1"],
        b2=t["b2"], grad_clip=t["grad_clip"], state_quant=t["state_quant"])
    params = fam.params_shape(d, pcfg.param_dtype)
    state = train.TrainState(jax.ShapeDtypeStruct((), jnp.int32), params,
                             jax.eval_shape(tx.init, params))
    on = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)  # noqa: E731
    exe = train.make_train_step(pcfg, tx, mesh=None).lower(
        jax.tree.map(on, state),
        on(jax.ShapeDtypeStruct((4, 4096), jnp.int32))).compile()
    mem = exe.memory_analysis()
    # the state is donated: all of it but the batch is written in place
    assert mem.argument_size_in_bytes - mem.alias_size_in_bytes <= 4 * 16384
    assert 4.5 * 2**30 < mem.argument_size_in_bytes < 6 * 2**30
    txt = exe.as_text()
    # the experts' grouped GEMMs are the chip's own, over ONE sorted
    # buffer of all 16384 x 4 pairs, in no loop of passes
    assert re.search(r"ragged-dot[-\w.]* = bf16\[65536,1024\]", txt)
    return txt


def _rms_norm(topo):
    from paddle_tpu.kernels.rms_norm import rms_norm_train
    one = SingleDeviceSharding(topo.devices[0])
    loss = lambda x, w: jnp.sum(                             # noqa: E731
        rms_norm_train(x, w, 1e-5, True).astype(jnp.float32))
    return _compile(jax.value_and_grad(loss, (0, 1)), [one, one],
                    ((8, 2048, D), BF), ((D,), BF))


def _adam8(topo):
    from paddle_tpu.optimizer.quant_state import adamw_q_fused
    one = SingleDeviceSharding(topo.devices[0])
    tx = adamw_q_fused(1e-4, weight_decay=0.1, clip_norm=1.0)
    leaf = jax.ShapeDtypeStruct((D, D), BF)
    state = jax.eval_shape(tx.init, {"w": leaf})
    avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        ({"w": leaf}, state, {"w": leaf}))
    return jax.jit(tx.apply_fused).lower(*avals).compile().as_text()


def _mla(topo, R, Pq):
    """The latent (MLA) kernel at A.X-K1's widths: 64 query heads over one
    cached row of 512 + 64 columns, values the row's first 512. Its grid
    is its work list: the bound is an operand of the call (the scalar
    that leads them), then the table, the tiles' live blocks and the
    list's three arrays at the full grid's length."""
    from paddle_tpu.nlp.ragged_attention import (attn_grid_steps,
                                                 mla_paged_attention)
    one = SingleDeviceSharding(topo.devices[0])

    def fn(q, pool, tab, pos, val):
        return mla_paged_attention(q, pool, tab, pos, val, scale=0.13086,
                                   v_width=512, interpret=False)

    txt = _compile(fn, [one] * 5, ((R, Pq, 64, 576), BF),
                   ((N, BS, 576), BF), ((R, M), jnp.int32),
                   ((R, Pq), jnp.int32), ((R, Pq), jnp.bool_))
    call = next(line for line in txt.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    n = attn_grid_steps(R, Pq, M)
    T = Pq // min(Pq, 16)
    assert (f"operand_layout_constraints={{s32[], s32[{R},{M}]{{1,0}}, "
            f"s32[{R},{T}]{{1,0}}, s32[{n}]{{0}}, s32[{n}]{{0}}, "
            f"s32[{n}]{{0}}, ") in call, call[:600]
    return txt


def _gqa_uniform_decode(topo, kv_dtype="fp", donated=False):
    """The decode forward of a uniform GQA decoder at Mistral-7B's widths
    (`paged.forward_paged`, 16 rows of one token, the Pallas kernel) over
    a pool stacked by layer: a layer's blocks are written and read IN the
    stack (`_forward_groups`, `mix_gqa`). Nothing in the module has one
    layer's shape, and outside the entry (the scan's body and what it
    calls) the stack, viewed [L, N, ...] or flat, is made by parameters,
    bitcasts, tuple plumbing and the scatters alone: no copy, no slice
    out, no write back. The stack is 128 MiB a pool (4 layers of bf16, 8
    of int8): one of 64 MiB the compiler keeps in its fast memory space
    and copies whole a layer, which no served pool is small enough for.
    `donated`: the pool donated as the batcher donates it, and then no
    copy of the stack in the entry either (`_assert_pool_aliased`)."""
    from paddle_tpu.nlp import llama, paged
    one = SingleDeviceSharding(topo.devices[0])
    L, R = (8 if kv_dtype == "int8" else 4), 16
    cfg = llama.LlamaConfig(
        vocab_size=32768, hidden_size=D, intermediate_size=14336,
        num_hidden_layers=L, num_attention_heads=H, num_key_value_heads=KV,
        dtype=BF, param_dtype=BF, use_flash=False)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: paged.init_pool(cfg, N, BS, kv_dtype))

    def fn(params, pool, table, tokens, positions, valid):
        cache = paged.PagedKVCache(pool[0], pool[1], table,
                                   jnp.zeros((R,), jnp.int32), *pool[2:])
        logits, cache = paged.forward_paged(
            params, tokens, cache, positions, valid, cfg, False, "pallas")
        return logits, cache.k, cache.v, cache.k_scale, cache.v_scale

    on = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)  # noqa: E731
    i32 = jax.ShapeDtypeStruct((R, 1), jnp.int32, sharding=one)
    with _tpu_backend():
        exe = jax.jit(fn, donate_argnums=(1,) if donated else ()).lower(
            jax.tree.map(on, params), jax.tree.map(on, pool),
            jax.ShapeDtypeStruct((R, M), jnp.int32, sharding=one), i32, i32,
            jax.ShapeDtypeStruct((R, 1), jnp.bool_, sharding=one)
        ).compile()
    txt = exe.as_text()
    if donated:
        _assert_pool_aliased(exe, [p for p in pool if p is not None])
    assert " while(" in txt
    row = f"{BS},{KV},{HD}"
    layer = (f"{N},{row}", f"1,{N},{row}", f"{N * BS},{KV},{HD}")
    stack = (f"{L},{N},{row}", f"{L * N},{row}", f"{L * N * BS},{KV},{HD}")
    entry = False
    for line in txt.splitlines():
        entry = entry or line.startswith("ENTRY ")
        made = re.match(r"\s*(?:ROOT )?%\S+ = \(*\w+\[([\d,]+)\]\S* (\S+?)\(",
                        line)
        if not made:
            continue
        assert made[1] not in layer, line[:300]
        if made[1] in stack and not entry:
            assert made[2] in ("parameter", "bitcast", "get-tuple-element",
                               "tuple", "conditional", "scatter",
                               "fusion"), line[:300]
            # a fusion of the stack's shape is a scatter in place: the new
            # rows' or, in a branch, the rescaled blocks' of an int8 pool
            assert made[2] != "fusion" or re.search(
                r'op_name="[^"]*kv_pool_write/[^"]*scatter', line), line[:600]
    return txt


# held experts n of E routed, Lm expert layers in the stack, D x F
EXPERT_SHAPES = {"axk1-ep16": (12, 192, 2, 7168, 2048),
                 "mellum2-l8": (64, 64, 8, 2304, 896),
                 "laguna-s-ep4": (64, 256, 4, 3072, 1024)}


def _expert_share(topo, T, short, shape="axk1-ep16", k=8):
    """The dropless expert layer at a served configuration's widths
    (`axk1-ep16`: 12 held experts under a 192-wide router; `mellum2-l8`:
    all 64 of 64, 8 layers in the stack; `laguna-s-ep4`: 64 of 256, top
    10, 4 layers): the three grouped GEMMs must be
    the repo's own kernel, each handed the WHOLE stack of every layer's
    experts (a bitcast of the argument: no slice or copy of it anywhere
    in the module) and its list of items, over the `short` sorted buffer
    alone (a loop of as many passes as the local pairs need), never over
    all T x 8 pairs; `lax.ragged_dot` is not in the served form."""
    from paddle_tpu.kernels.grouped_gemm import gemm_items
    from paddle_tpu.nlp import moe
    one = SingleDeviceSharding(topo.devices[0])
    n, E, Lm, Dm, Fm = EXPERT_SHAPES[shape]

    def fn(h, router, g, u, d):
        lp = {"router": router, "experts_gate": g, "experts_up": u,
              "experts_down": d}
        return moe.expert_share_ffn(h, lp, k=k, first=0, scale=2.5,
                                    layer=1)[0]

    with _tpu_backend():
        txt = _compile(fn, [one] * 5, ((T, Dm), BF), ((Dm, E), BF),
                       ((Lm, n, Dm, Fm), BF), ((Lm, n, Dm, Fm), BF),
                       ((Lm, n, Fm, Dm), BF))
    calls = re.findall(
        r"%grouped_gemm[.\d]* = bf16\[(\d+),(\d+)\]\S* custom-call\(.*"
        r'custom_call_target="tpu_custom_call", '
        r"operand_layout_constraints=\{(.*?)\}, frontend_attributes", txt)
    items = f"s32[], s32[4,{gemm_items(short, n)}]{{1,0}}, "
    assert sorted((int(r), int(w), ops) for r, w, ops in calls) == sorted([
        (short, Fm, items + f"bf16[{short},{Dm}]{{1,0}}, "
         f"bf16[{Lm * n},{Dm},{Fm}]{{2,1,0}}")] * 2 + [
        (short, Dm, items + f"bf16[{short},{Fm}]{{1,0}}, "
         f"bf16[{Lm * n},{Fm},{Dm}]{{2,1,0}}")]), calls  # gate, up, down
    assert "ragged-dot" not in txt and " while(" in txt
    # the stacks reach the kernel as they came in: nothing but the entry's
    # parameters, bitcasts and tuple plumbing has a stack's (or one
    # layer's) shape
    for line in txt.splitlines():
        made = re.match(r"\s*(?:ROOT )?%\S+ = bf16\[([\d,]+)\]\S* (\S+?)\(",
                        line)
        if made and made[1] in (
                f"{Lm},{n},{Dm},{Fm}", f"{Lm},{n},{Fm},{Dm}",
                f"{Lm * n},{Dm},{Fm}", f"{Lm * n},{Fm},{Dm}",
                f"{n},{Dm},{Fm}", f"{n},{Fm},{Dm}",
                f"1,{n},{Dm},{Fm}", f"1,{n},{Fm},{Dm}"):
            assert made[2] in ("parameter", "bitcast",
                               "get-tuple-element"), line[:300]
    return txt


CASES = {
    "mla-decode-64-rows": lambda t: _mla(t, 64, 1),
    "mla-prefill-bucket-512": lambda t: _mla(t, 2, 512),
    # the fused step's prefill rows at `max_prefill_group` 4
    "mla-fused-prefill-rows": lambda t: _mla(t, 4, 512),
    # the sorted buffers of a decode step's 64 slots and of the fused
    # steps on the 512 and 128 buckets (64 + 512 and 64 + 128 tokens)
    "expert-share-decode-64-tokens": lambda t: _expert_share(t, 64, 128),
    "expert-share-fused-576-tokens": lambda t: _expert_share(t, 576, 640),
    "expert-share-fused-192-tokens": lambda t: _expert_share(t, 192, 384),
    # every expert held (the sorted buffer is all T x 8 pairs): a decode
    # step's 32 slots, and a fused step's 32 + 512 tokens
    "expert-share-mellum2-decode-32-tokens": lambda t: _expert_share(
        t, 32, 256, "mellum2-l8"),
    "expert-share-mellum2-fused-544-tokens": lambda t: _expert_share(
        t, 544, 4352, "mellum2-l8"),
    "ragged-decode": lambda t: _ragged(t, 8, 1),
    "ragged-prefill-bucket-512": lambda t: _ragged(t, 1, 512),
    "ragged-prefill-bucket-8": lambda t: _ragged(t, 8, 8),
    # the fused step calls the kernel once per row group: the decode
    # rows as [B, 1], then the prefill rows as [Gp, Pb]
    "ragged-fused-decode-rows": lambda t: _ragged(t, 16, 1),
    "ragged-fused-prefill-rows": lambda t: _ragged(t, 2, 128),
    "ragged-fused-prefill-rows-4x128": lambda t: _ragged(t, 4, 128),
    "ragged-int8-decode": lambda t: _ragged(t, 8, 1, jnp.int8),
    "ragged-int8-prefill": lambda t: _ragged(t, 2, 512, jnp.int8),
    "ragged-suffix-slab": lambda t: _ragged(t, 8, 5, slab=5),
    "ragged-suffix-slab-int8": lambda t: _ragged(t, 8, 7, jnp.int8, slab=7),
    "ragged-shard_map-4dev": lambda t: _ragged(t, 8, 1, mesh=True),
    "ragged-shard_map-4dev-prefill": lambda t: _ragged(t, 1, 512,
                                                       mesh=True),
    # window + full GQA layers (H 32, KV 4): the window layers' ring of
    # 97 blocks and the full layers' table of 800, a decode step's 32
    # rows and a prefill row of the 512 bucket
    "ragged-window-ring-97-decode": lambda t: _ragged_window(
        t, 32, 1, 97, True),
    "ragged-window-ring-97-prefill-512": lambda t: _ragged_window(
        t, 1, 512, 97, True),
    "ragged-full-table-800-decode": lambda t: _ragged_window(
        t, 32, 1, 800, False),
    "ragged-full-table-800-prefill-512": lambda t: _ragged_window(
        t, 1, 512, 800, False),
    "ragged-window-ring-97-prefill-4x128": lambda t: _ragged_window(
        t, 4, 128, 97, True),
    "ragged-full-table-800-prefill-4x128": lambda t: _ragged_window(
        t, 4, 128, 800, False),
    # gated window + full GQA layers whose kinds differ in query heads (72
    # and 48 over 8 KV heads): a decode step's 32 rows and a fused step's
    # prefill rows, each kind; the expert layer at 64 of 256 held, top 10
    "ragged-gated-window-72-heads-decode": lambda t: _ragged_gated(
        t, 32, 1, "window"),
    "ragged-gated-window-72-heads-prefill-4x512": lambda t: _ragged_gated(
        t, 4, 512, "window"),
    "ragged-gated-full-48-heads-decode": lambda t: _ragged_gated(
        t, 32, 1, "full"),
    "ragged-gated-full-48-heads-prefill-4x512": lambda t: _ragged_gated(
        t, 4, 512, "full"),
    "gated-window-moe-prefill-step-1x128": _gated_prefill_step,
    "expert-share-laguna-decode-32-tokens": lambda t: _expert_share(
        t, 32, 320, "laguna-s-ep4", k=10),
    "expert-share-laguna-fused-544-tokens": lambda t: _expert_share(
        t, 544, 2944, "laguna-s-ep4", k=10),
    # a uniform GQA decoder's decode forward: a layer's blocks addressed
    # in place in the pool stacked by layer, bf16 and int8
    "gqa-uniform-decode-step-in-place": _gqa_uniform_decode,
    "gqa-uniform-decode-step-in-place-int8": lambda t: _gqa_uniform_decode(
        t, "int8"),
    # the same whole steps with the pool DONATED, as the batcher compiles
    # every program that returns one: no whole-pool copy at the entry
    "gqa-uniform-decode-step-donated": lambda t: _gqa_uniform_decode(
        t, donated=True),
    "gqa-uniform-decode-step-donated-int8": lambda t: _gqa_uniform_decode(
        t, "int8", donated=True),
    "gated-window-moe-prefill-step-1x128-donated": lambda t:
        _gated_prefill_step(t, donated=True),
    "flash-fwd-bwd-2048": _flash,
    "flash-fwd-bwd-4096-q192-v128": _flash_mla,
    "mhc-mla-moe-train-step-4x4096": _mhc_train_step,
    "flash-shard_map-4dev": lambda t: _flash(t, mesh=True),
    "rms_norm-fwd-bwd-4096": _rms_norm,
    "adam8-fused-update": _adam8,
}


@pytest.mark.parametrize("case", list(CASES))
def test_compiles_for_v5e(topo, case):
    assert "tpu_custom_call" in CASES[case](topo)


def test_importing_the_package_initialises_no_backend():
    """A chip belongs to one process: a launcher parent that imports the
    package must not take it from the child it is about to start."""
    code = ("import paddle_tpu, paddle_tpu.serving, "
            "paddle_tpu.distributed.launch.main\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
