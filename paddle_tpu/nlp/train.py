"""Sharded training step for the flagship models.

Reference analog: the fleet hybrid-parallel train loop —
`fleet.distributed_model` + `distributed_optimizer` + per-strategy wrappers
(SURVEY.md §3.2, upstream-canonical, unverified §0). TPU-native: ONE jitted
train step whose in/out shardings carry the whole strategy; XLA inserts every
collective (grad psum over dp, FSDP all-gathers over 'sharding', TP
collectives over 'mp') — the reference's reducer/GroupSharded/mp_ops code
has no runtime equivalent here by design.
"""
from __future__ import annotations

import functools
import sys
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import llama


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


def make_optimizer(learning_rate=3e-4, weight_decay=0.1, b1=0.9, b2=0.95,
                   grad_clip=1.0, warmup_steps=0, total_steps=10000,
                   state_quant: Optional[str] = None):
    """AdamW + cosine schedule + global-norm clip — the reference's Llama
    recipe optimizer (paddle.optimizer.AdamW + LinearWarmup/Cosine).

    state_quant="8bit" stores the Adam moments 8-bit blockwise — float8
    codes + per-block scales (optimizer.quant_state; NOT linear int8,
    which underflows) — ~2 bytes/param of state instead of 8, the
    single-chip flagship-bench mode; None keeps f32 moments (multi-chip
    shards those over 'sharding' instead). "int8" is accepted as an
    alias for the storage-width reading of the name."""
    if warmup_steps:
        sched = optax.warmup_cosine_decay_schedule(
            0.0, learning_rate, warmup_steps, total_steps)
    else:
        sched = learning_rate
    if state_quant is None:
        adam = optax.adamw(sched, b1=b1, b2=b2, weight_decay=weight_decay)
    elif state_quant in ("8bit", "int8"):
        # the clip streams through the chunked 8-bit update (no second
        # grad tree — the single-chip 2B config OOMs with the optax clip);
        # on TPU the train step takes the fused one-pass Pallas apply
        # (decode+adam+requant+param update in ~10 bytes/param of HBM
        # traffic instead of the chain's ~5 full-tree passes)
        from ..optimizer.quant_state import adamw_q_fused
        return adamw_q_fused(sched, b1=b1, b2=b2,
                             weight_decay=weight_decay,
                             clip_norm=grad_clip or None)
    else:
        raise ValueError(f"unknown state_quant {state_quant!r}")
    tx = optax.chain(
        optax.clip_by_global_norm(grad_clip) if grad_clip else optax.identity(),
        adam,
    )
    return tx


def model_of(cfg):
    """The model module of a configuration object: the module that defines
    its class (`llama.LlamaConfig` -> llama, `moe.MoeConfig` -> moe,
    `mla_train.MlaTrainConfig` -> mla_train). Each exposes `init_params`,
    `param_specs` and `loss_fn` with the same signatures, and optionally
    `loss_and_metrics` (a loss with counters beside it)."""
    return sys.modules[type(cfg).__module__]


def state_specs(cfg, tx, pp: bool = False, model=None) -> TrainState:
    """PartitionSpec tree for the full TrainState: optimizer moments inherit
    each param's spec (= ZeRO: opt state sharded exactly like params).
    `model` is the model module; it follows from `cfg` (`model_of`)."""
    model = model or model_of(cfg)
    pspecs = model.param_specs(cfg, pp=pp)
    params_shape = jax.eval_shape(
        functools.partial(model.init_params, cfg=cfg), jax.random.key(0))
    opt_state_shape = jax.eval_shape(tx.init, params_shape)
    opt_specs = _opt_specs_like(opt_state_shape, params_shape, pspecs)
    return TrainState(step=P(), params=pspecs, opt_state=opt_specs)


def _opt_specs_like(opt_state_shape, params_shape, pspecs):
    """Map an optax state pytree to specs: any subtree that is structurally
    identical to the param tree gets the param specs; other leaves P()."""
    params_treedef = jax.tree.structure(params_shape)

    def rec(node):
        try:
            if jax.tree.structure(node) == params_treedef:
                return pspecs
        # ptlint: disable=EXC001 — structure() on arbitrary optax state
        # leaves raises type-dependent errors; "not param-shaped" is the
        # answer, recursion below handles the node
        except Exception:
            pass
        if isinstance(node, tuple) and hasattr(node, "_fields"):  # NamedTuple
            return type(node)(*[rec(c) for c in node])
        if isinstance(node, tuple):
            return tuple(rec(c) for c in node)
        if isinstance(node, list):
            return [rec(c) for c in node]
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        return P()

    return rec(opt_state_shape)


def _use_pp(mesh: Optional[Mesh]) -> bool:
    return (mesh is not None and "pp" in mesh.axis_names
            and mesh.shape["pp"] > 1)


def init_state(key, cfg, tx, mesh: Optional[Mesh] = None, model=None):
    """Initialize params + opt state, jitted with out_shardings so big models
    materialize directly sharded (never replicated on one chip)."""
    model = model or model_of(cfg)

    def init():
        params = model.init_params(key, cfg)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=tx.init(params))

    if mesh is None:
        return init()
    pp = _use_pp(mesh) and hasattr(model, "forward_pp")
    specs = state_specs(cfg, tx, pp=pp, model=model)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(init, out_shardings=shardings)()


def make_train_step(cfg, tx, mesh: Optional[Mesh] = None,
                    donate: bool = True,
                    num_microbatches: Optional[int] = None,
                    grad_accum_steps: int = 1,
                    pp_schedule: str = "1f1b",
                    virtual_pp_degree: int = 2,
                    model=None) -> Callable:
    """Build the jitted train step for `cfg`'s model (`model_of`: the
    module follows from the configuration object). With a mesh: full GSPMD
    shardings on state and batch; without: plain jit (single device). A mesh with pp > 1
    runs the decoder through a compiled pipeline schedule —
    `num_microbatches` (default 2·pp) microbatches per step (llama AND moe
    both pipeline via their forward_pp). pp_schedule picks the compiled
    schedule (reference: PipelineParallel's 1F1B / interleaved modes,
    SURVEY.md §3.3): "1f1b" (default) runs the fused one_f_one_b
    forward+backward with O(pp) activation residency; "gpipe" runs
    forward_pp under jax.grad (scan transpose, O(num_microbatches)
    residency) and is the automatic fallback for models without a
    loss_and_grad_pp; "interleaved" runs the interleaved/virtual-pp 1F1B
    (virtual_pp_degree chunks per device — bubble shrinks by that factor,
    O(v·pp) residency) when the model has loss_and_grad_pp, else the
    circular virtual-pp GPipe under jax.grad.

    grad_accum_steps > 1 splits the batch axis into that many chunks and
    accumulates grads through one lax.scan before the optimizer update —
    the reference's gradient-merge / accumulate_steps (fleet
    DistributedStrategy), compiled instead of host-looped. Activation
    memory drops by the accumulation factor; numerics match the full batch
    up to bf16 forward rounding (chunked reductions associate differently).
    Chunks interleave rows (strided) so each chunk stays spread across the
    dp/sharding batch shards.

    A model whose module has `loss_and_metrics` (a loss with its counters
    beside it) gets them into the step's `metrics`."""
    model = model or model_of(cfg)
    with_aux = hasattr(model, "loss_and_metrics")
    if with_aux and (_use_pp(mesh) or grad_accum_steps > 1):
        raise ValueError("loss_and_metrics: one whole batch a step, no pp")
    pp = _use_pp(mesh) and hasattr(model, "forward_pp")
    mb = (num_microbatches or 2 * mesh.shape["pp"]) if pp else None
    if pp_schedule not in ("1f1b", "gpipe", "interleaved"):
        raise ValueError(f"unknown pp_schedule {pp_schedule!r}")
    use_1f1b = (pp and pp_schedule in ("1f1b", "interleaved")
                and hasattr(model, "loss_and_grad_pp"))
    pp_virtual = virtual_pp_degree if (
        pp and pp_schedule == "interleaved") else 1
    if grad_accum_steps < 1:
        raise ValueError(
            f"grad_accum_steps must be >= 1, got {grad_accum_steps}")
    if grad_accum_steps > 1 and pp:
        raise ValueError(
            "grad_accum_steps composes with num_microbatches inside the pp "
            "schedule — use num_microbatches when pp > 1")

    def train_step(state: TrainState, tokens):
        if pp:
            if pp_virtual > 1:
                lfn = lambda p, t: model.loss_fn(  # noqa: E731
                    p, t, cfg, mesh, mb, pp_virtual)
            else:
                lfn = lambda p, t: model.loss_fn(p, t, cfg, mesh, mb)  # noqa: E731
        elif with_aux:
            lfn = lambda p, t: model.loss_and_metrics(p, t, cfg, mesh)  # noqa: E731
        else:
            lfn = lambda p, t: model.loss_fn(p, t, cfg, mesh)  # noqa: E731
        aux = {}
        if grad_accum_steps > 1:
            b = tokens.shape[0]
            if b % grad_accum_steps:
                raise ValueError(
                    f"batch {b} not divisible by grad_accum_steps "
                    f"{grad_accum_steps}")
            # strided (row-interleaved) chunks: contiguous blocks would
            # concentrate each chunk onto one dp/sharding shard and force a
            # reshard per scan iteration
            chunks = jnp.swapaxes(
                tokens.reshape((b // grad_accum_steps, grad_accum_steps)
                               + tokens.shape[1:]), 0, 1)

            def micro(carry, mtoks):
                gsum, lsum = carry
                l, g = jax.value_and_grad(lfn)(state.params, mtoks)
                return (jax.tree.map(jnp.add, gsum, g), lsum + l), None

            init = (jax.tree.map(jnp.zeros_like, state.params),
                    jnp.zeros((), jnp.float32))
            (gsum, lsum), _ = jax.lax.scan(micro, init, chunks)
            grads = jax.tree.map(lambda g: g / grad_accum_steps, gsum)
            loss = lsum / grad_accum_steps
        elif use_1f1b:
            loss, grads = model.loss_and_grad_pp(
                state.params, tokens, cfg, mesh, mb, pp_virtual)
        elif with_aux:
            (loss, aux), grads = jax.value_and_grad(lfn, has_aux=True)(
                state.params, tokens)
        else:
            loss, grads = jax.value_and_grad(lfn)(state.params, tokens)
        if mesh is None and hasattr(tx, "apply_fused"):
            # single chip: one-pass Pallas update (params+moments in one
            # pipelined stream); under a mesh the pure-jnp update tree
            # stays so GSPMD can shard it
            new_params, new_opt = tx.apply_fused(
                grads, state.opt_state, state.params)
        else:
            # ptlint: disable=TRACE001 — optax GradientTransformation.
            # update is pure: it RETURNS (updates, new_state), mutating
            # nothing (the name collides with dict.update)
            updates, new_opt = tx.update(grads, state.opt_state,
                                         state.params)
            new_params = optax.apply_updates(state.params, updates)
        metrics = {**aux, "loss": loss,
                   "grad_norm": optax.global_norm(grads),
                   "step": state.step}
        return TrainState(state.step + 1, new_params, new_opt), metrics

    if mesh is None:
        return jax.jit(train_step, donate_argnums=(0,) if donate else ())

    specs = state_specs(cfg, tx, pp=pp, model=model)
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))
    batch_sh = NamedSharding(
        mesh, getattr(model, "batch_spec", llama.batch_spec)())
    # every metric a replicated scalar, whatever the model adds to them
    return jax.jit(train_step,
                   in_shardings=(state_sh, batch_sh),
                   out_shardings=(state_sh, NamedSharding(mesh, P())),
                   donate_argnums=(0,) if donate else ())
