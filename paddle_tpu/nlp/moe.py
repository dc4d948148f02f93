"""Mixture-of-Experts: gating, capacity dispatch, expert parallelism, and
the flagship MoE transformer (routed experts + an always-on shared expert —
BASELINE config 4).

Two routers live here and they are NOT the same function. The TRAINED
path (`top_k_routing`, `moe_block`, `MoeConfig`) is GShard's: softmax
scores, a fixed capacity per expert, tokens over capacity DROPPED (they
fall through the residual). The SERVED path (`sigmoid_top_k`,
`expert_share_ffn`, used by nlp/paged.py for `mla.MlaMoeConfig`; under
`jax.grad` `expert_share_train`, used by nlp/mla_train.py) is what
published DeepSeek-V3-style routers do: sigmoid scores in float32, plain
top-k, normalised and scaled gates, and NO capacity: no token is ever
dropped, whatever the routing.

Reference analog: python/paddle/incubate/distributed/models/moe/
(moe_layer.py with gshard/switch/naive gates, capacity + all_to_all dispatch
over the moe_group, fused dispatch CUDA kernels) and the PaddleNLP
DeepSeekMoE recipes — upstream-canonical, unverified, SURVEY.md §0, §2.3 EP
row.

TPU-native design (SURVEY.md §7 M7): GShard-style STATIC-SHAPE dispatch —
top-k gating builds [T, E, C] one-hot dispatch/combine tensors (cumsum
position assignment, capacity-dropped tokens fall through the residual);
dispatch and combine are einsums, so under GSPMD with experts sharded
P('ep', ...) XLA inserts the all_to_all the reference hand-codes. The whole
MoE block stays differentiable jnp — no host-side routing, no ragged shapes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..kernels.grouped_gemm import gemm_work_list, grouped_gemm
from ..kernels.rms_norm import rms_norm_ref, rms_norm_train
from ..kernels.rope import rope_freqs
from . import llama as _llama


def gshard_capacity(tokens: int, k: int, num_experts: int,
                    factor: float) -> int:
    """GShard expert capacity: ceil-ish share of k·T routed slots per
    expert, scaled by the capacity factor (single source of the rounding
    rule for MoeConfig and the incubate MoELayer facade)."""
    per = tokens * k / num_experts
    return max(int(per * factor + 0.5), 1)


def top_k_routing(gate_logits: jax.Array, k: int, capacity: int,
                  renormalize: bool = True):
    """GShard top-k gating with capacity, INDEX form.

    gate_logits: [T, E] (f32). Returns (eidx [T,k] i32, slot [T,k] i32,
    probs [T,k] f32, valid [T,k] bool, inv [E,C] i32, aux): token t's j-th
    choice goes to expert eidx[t,j] at capacity slot slot[t,j] with gate
    weight probs[t,j], dropped when not valid; inv is the inverse map
    (which token fills slot [e,c]; -1 = empty). Everything downstream is
    gathers over these indices — nothing materializes [T,E,C] (the round-1
    einsum dispatch; VERDICT item 4: memory scaled with E*C).
    """
    T, E = gate_logits.shape
    probs_full = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)

    # iterative top-k: mask out chosen experts each round
    masked = probs_full
    sel_idx = []            # k × [T] chosen expert
    sel_masks = []          # k × [T, E] one-hot
    sel_probs = []          # k × [T]
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        sel_idx.append(idx.astype(jnp.int32))
        sel_masks.append(onehot)
        sel_probs.append(jnp.sum(probs_full * onehot, axis=-1))
        masked = masked * (1.0 - onehot)

    if renormalize:
        denom = sum(sel_probs)
        sel_probs = [p / jnp.maximum(denom, 1e-9) for p in sel_probs]

    # capacity slots: cumulative position of each token within its expert,
    # later-k choices stack after earlier-k occupancy (GShard ordering)
    slots, valids = [], []
    prior_count = jnp.zeros((E,), jnp.float32)
    for mask in sel_masks:
        pos = jnp.cumsum(mask, axis=0) - 1.0 + prior_count[None, :]
        prior_count = prior_count + jnp.sum(mask, axis=0)
        in_cap = (pos < capacity) & (mask > 0)
        slots.append(jnp.sum(pos * mask, axis=-1).astype(jnp.int32))
        valids.append(jnp.any(in_cap, axis=-1))

    eidx = jnp.stack(sel_idx, axis=1)                    # [T, k]
    slot = jnp.stack(slots, axis=1)                      # [T, k]
    probs = jnp.stack(sel_probs, axis=1)                 # [T, k]
    valid = jnp.stack(valids, axis=1)                    # [T, k]

    # inverse map: token filling each (e, c) slot — scatter token ids into
    # a flat [E*C] table (+1 dump slot for dropped/invalid entries)
    flat = eidx * capacity + slot                        # [T, k]
    flat = jnp.where(valid, flat, E * capacity)
    inv = jnp.full((E * capacity + 1,), -1, jnp.int32)
    tok = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None], flat.shape)
    inv = inv.at[flat.reshape(-1)].set(tok.reshape(-1), mode="drop")
    inv = inv[:-1].reshape(E, capacity)

    # Switch load-balance loss: E * Σ_e fraction_tokens_e · mean_prob_e
    # (fraction from the FIRST choice, the standard formulation)
    frac = jnp.mean(sel_masks[0], axis=0)
    mean_p = jnp.mean(probs_full, axis=0)
    aux = {
        "load_balance_loss": E * jnp.sum(frac * mean_p),
        "router_z_loss": jnp.mean(
            jax.scipy.special.logsumexp(gate_logits, axis=-1) ** 2),
    }
    return eidx, slot, probs, valid, inv, aux


def top_k_gating(gate_logits: jax.Array, k: int, capacity: int,
                 renormalize: bool = True
                 ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """GShard top-k gating, ONE-HOT form (the incubate MoELayer facade and
    tests): [T,E,C] dispatch/combine built from top_k_routing's indices —
    single-sourcing the assignment rule. Prefer the index form for anything
    large; this materializes the O(T*E*C) tensors."""
    T, E = gate_logits.shape
    eidx, slot, probs, valid, _, aux = top_k_routing(
        gate_logits, k, capacity, renormalize)
    # accumulate per choice j: peak memory stays one [T,E,C] (the eager
    # incubate facade runs this op-by-op — a [T,k,E,C] intermediate would
    # k-fold the old peak)
    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    for j in range(k):
        oh = (jax.nn.one_hot(eidx[:, j], E, dtype=jnp.float32)[..., None]
              * jax.nn.one_hot(slot[:, j], capacity, dtype=jnp.float32)[:, None]
              * valid[:, j, None, None].astype(jnp.float32))
        dispatch = dispatch + oh
        combine = combine + oh * probs[:, j, None, None]
    return dispatch, combine, aux


def sigmoid_top_k(h: jax.Array, router_w: jax.Array, k: int,
                  scale: float = 1.0, normalize: bool = True):
    """The served router, float32 throughout: `s = sigmoid(h W_g)` over
    ALL routed experts, `I = top-k(s)`, gates `scale * s_i / sum_{j in I}
    s_j` (the sum over all k chosen, wherever they live). h [T, D],
    router_w [D, E] -> (idx [T, k] int32, gates [T, k] float32). No
    group limit, no correction bias, no capacity."""
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    top, idx = jax.lax.top_k(jax.nn.sigmoid(logits), k)
    if normalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), top * scale


def softmax_top_k(h: jax.Array, router_w: jax.Array, k: int,
                  scale: float = 1.0, normalize: bool = True):
    """`sigmoid_top_k` with softmax scores (the Mixtral / Qwen-MoE
    router), float32 throughout: `p = softmax(h W_g)` over ALL routed
    experts, `I = top-k(p)`, gates `scale * p_i / sum_{j in I} p_j` when
    `normalize`, else `scale * p_i`."""
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if normalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), top * scale


def sigmoid_bias_top_k(h: jax.Array, router_w: jax.Array, k: int,
                       scale: float, normalize: bool, bias: jax.Array):
    """`sigmoid_top_k` with a per-expert SELECTION bias (DeepSeek-V3's
    auxiliary-loss-free balancing, `topk_method: "noaux_tc"`; with
    `n_group` 1 and `topk_group` 1 there is no group limit): `I =
    top-k(s + b)`, gates from `s` alone, `scale * s_i / sum_{j in I}
    s_j`. The bias [E] moves which experts are chosen and nothing else:
    the gates do not see it, and no gradient reaches it (the selection
    carries none; the loss does not train the bias)."""
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    if normalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), top * scale


ROUTERS = {"sigmoid": sigmoid_top_k, "softmax": softmax_top_k,
           "sigmoid_bias": sigmoid_bias_top_k}


def _route(h, lp, k, scale, normalize, score):
    """(idx [T, k], gates [T, k]) by the router `score` names; the one
    that selects by a bias reads the layer's `e_bias` [E]."""
    bias = (lp["e_bias"],) if score == "sigmoid_bias" else ()
    return ROUTERS[score](h, lp["router"], k, scale, normalize, *bias)


def expert_share_ffn(h: jax.Array, lp: Dict[str, jax.Array], *, k: int,
                     first: int, scale: float = 1.0, normalize: bool = True,
                     valid: Optional[jax.Array] = None,
                     layer=0, token_block: int = 1024,
                     score: str = "sigmoid"):
    """One chip's share of a routed-expert layer, DROPLESS: route every
    token of h [T, D] over all `lp["router"].shape[1]` experts, and
    compute `sum_{i in top-k, i held here} g_i E_i(h)` for the experts
    held here: `lp["experts_gate" | "experts_up"]` [Lm, n, D, F] and
    `lp["experts_down"]` [Lm, n, F, D] hold routed experts `first ..
    first + n - 1` of ALL Lm expert layers, each a gated SiLU MLP, and
    this is layer `layer` of them (an int32 scalar, traced in a scan).
    What absent experts would add is left out; no code stands in for
    their chips. The shared expert is the caller's
    (`generation._mlp_cached`).

    `score` names the router (`ROUTERS`: "sigmoid" | "softmax" |
    "sigmoid_bias", which reads the layer's selection bias
    `lp["e_bias"]` [E]), float32 either way.

    This is the SERVED form: a `while_loop` of passes has no reverse
    mode. Under `jax.grad` the same layer is `expert_share_train`.

    The grouped GEMMs are the repo's own kernel
    (`kernels/grouped_gemm.py`): its grid is a list, built on the device
    from the rows on each held expert, of the live (row tile, hit expert)
    items of a pass, and an item's weight block is read IN the stack,
    `stack[layer * n + expert]`, so a layer-step reads the matrices of
    the experts it hit, once each, and nothing of the rest. The stack
    goes in whole (reshaped to Lm*n groups: a bitcast): a scan that
    sliced the layer's experts out instead would copy them (1 GB a layer
    at A.X-K1's widths) before every GEMM, a custom call taking no fused
    slice (my chip runs, PR 26: 19 of a decode step's 43 ms). Until PR
    37 the GEMMs were `lax.ragged_dot` over all Lm*n groups: the groups
    cost it nothing, but every HIT expert a tile of its own choosing,
    0.025 ms for a Mellum2 matrix of 0.005 ms of bytes (PERF.md section
    6, PR 37).

    Static shapes, no capacity and no recompile per routing: the T*k
    (token, choice) pairs are sorted by held expert (pairs of absent
    experts, and of tokens `valid` [T] masks, sort last and belong to no
    expert) and the experts run as grouped GEMMs over the sorted rows, so
    the work follows the pairs routed here, not held experts x tokens.
    More than `token_block` tokens are processed in blocks of that size,
    which bounds the sorted buffer at token_block * k rows (every pair
    local is the worst case, and it must fit).

    The sorted buffer is SHORT (`_short_rows`): the local pairs lie
    first after the sort, n / E of the T*k on average (n held of the
    router's E), so the gathers, the GEMMs' list and the gated SiLU run
    over S sorted rows at a time, in a loop of as many passes as the
    local pairs need: one in all but freak routings, none where no pair
    is local, T*k / S where every pair is. Every local pair is computed
    in exactly one pass, in the same precision (a token's gated sum is
    kept in float32 across passes): dropless and exact, whatever the
    routing.

    Returns (y [T, D] in h's dtype, stats): stats holds int32 scalars
    `moe_pairs` (pairs computed here), `moe_experts_hit` (held experts
    that got a token), `moe_load_max` (most pairs on one expert),
    `moe_full_passes` (token blocks whose local pairs overflowed one
    sorted buffer, so that it ran again) and `moe_gemm_items` (the items
    one grouped GEMM's list held, summed over passes and blocks: over
    `moe_experts_hit` it is 1.0 where every hit expert's rows lie in one
    row tile, and says how many masked tiles a prefill pass pays)."""
    T, D = h.shape
    n = lp["experts_gate"].shape[-3]
    if valid is None:
        valid = jnp.ones((T,), bool)
    # what one trace of `_share_block` may not decide for the next caller
    # at its shapes is its static argument, read here: the buffer's rows
    # and the kernel's mode
    block = functools.partial(
        _share_block, k=k, first=first, n=n, scale=scale,
        normalize=normalize, score=score,
        interpret=jax.default_backend() != "tpu")

    def buffer_rows(tokens):
        return min(_short_rows(tokens * k, n, lp["router"].shape[1]),
                   tokens * k)

    if T > token_block:
        nb = -(-T // token_block)
        pad = nb * token_block - T
        hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, token_block, D)
        vb = jnp.pad(valid, (0, pad)).reshape(nb, token_block)
        yb, sizes, full, items = jax.lax.map(
            lambda a: block(a[0], lp, a[1], layer,
                            buffer_rows=buffer_rows(token_block)),
            (hb, vb))
        y = yb.reshape(nb * token_block, D)[:T]
        sizes = jnp.sum(sizes, 0, dtype=jnp.int32)
    else:
        y, sizes, full, items = block(h, lp, valid, layer,
                                      buffer_rows=buffer_rows(T))
    return y, {**_share_stats(sizes, full),
               "moe_gemm_items": jnp.sum(items, dtype=jnp.int32)}


def _share_stats(sizes, full):
    """The expert layer's counters from the pairs on each held expert [n]
    and the blocks that overflowed; int32 whatever jax_enable_x64 says:
    they ride a scan's carry."""
    return {"moe_pairs": jnp.sum(sizes, dtype=jnp.int32),
            "moe_experts_hit": jnp.sum(sizes > 0, dtype=jnp.int32),
            "moe_load_max": jnp.max(sizes).astype(jnp.int32),
            "moe_full_passes": jnp.sum(full, dtype=jnp.int32)}


def _sorted_pairs(h, lp, valid, k, first, n, scale, normalize, score):
    """What both forms of the share start from, for the tokens h [T, D]:
    (`local` [T, k]: the pairs computed here; `gates` [T, k]: every
    pair's gate; `order` [T k]: the pairs sorted by held expert, those of
    absent experts and masked tokens last; `sizes` [n]: pairs on each
    held expert)."""
    T = h.shape[0]
    with jax.named_scope("moe_router"):
        idx, gates = _route(h, lp, k, scale, normalize, score)
    with jax.named_scope("moe_dispatch"):
        local = (idx >= first) & (idx < first + n) & valid[:, None]
        # held expert of each pair, n for a pair that is not computed here
        e = jnp.where(local, idx - first, n).reshape(T * k)
        order = jnp.argsort(e, stable=True)
        sizes = jnp.zeros((n + 1,), jnp.int32).at[e].add(1)[:n]
    return local, gates, order, sizes


def _short_rows(pairs: int, held: int, routed: int) -> int:
    """Rows of the sorted buffer a pass of the grouped GEMMs runs over,
    for `pairs` = T*k (token, choice) pairs on a chip that holds `held`
    of `routed` experts: the smallest ODD multiple of 128 that is at
    least twice the pairs expected here, 2 * pairs * held / routed (the
    caller caps it at `pairs`: a chip that holds half its experts, or a
    handful of tokens, makes one pass over them all).

    What the rule is for since PR 37: the buffer bounds the rows the
    gathers, the gated SiLU and the combine touch a pass (the layer at
    A.X-K1's widths, a fused step's 576 tokens: 2.03 ms over 640 rows,
    2.32 over all 4608 pairs); the grouped GEMMs no longer care, their
    kernel walks the row tiles that hold a hit expert's rows and no
    other (0.0405-0.0408 ms a hit expert a GEMM in buffers of 128 to 640
    rows, `tools/micro_moe.py share`, PERF.md section 6, PR 37). Twice
    the expectation, because a second pass costs as much as the first
    and routing is not uniform: `moe_full_passes` counts how often one
    is needed. A multiple of 128, the kernel's row tile.

    Why ODD multiples: history. `lax.ragged_dot` took its row tile from
    the buffer's ROW COUNT on the v5e, 512 rows where that is a multiple
    of 512, 256 of 256, and 128 where it is an odd multiple of 128, and
    every hit expert paid a whole tile of masked rows (0.094 ms a hit
    expert a GEMM in 512 rows, 0.060 in 256, 0.049 in 128, 384 or 640).
    The kernel's tile is its own, so an even multiple would do as well;
    the sizes are kept (the compiled programs and the tests know them)
    and cost at most one tile of rows that no item visits."""
    tiles = max(-(-2 * pairs * held // (routed * 128)), 1)
    return (tiles + 1 - tiles % 2) * 128


# one jitted object: the layers of a scan step's period, the forwards of
# a fused step and every step program that runs a block of the same
# shapes share one trace, and a program lowers the block once however
# many of its layers call it. A `mellum2-l8` forward has four call
# sites; traced and lowered apart they took 155 k + 150 k Python calls a
# forward, 21 k + 89 k as one (19 programs' warm set-up: PERF.md section
# 6, PR 37). XLA inlines the call before it fuses.
@functools.partial(jax.jit, static_argnames=(
    "k", "first", "n", "scale", "normalize", "score", "buffer_rows",
    "interpret"))
def _share_block(h, lp, valid, layer, *, k, first, n, scale, normalize,
                 score, buffer_rows, interpret):
    """(y [T, D], pairs on each held expert [n], 1 if the local pairs
    overflowed one sorted buffer else 0, the items a grouped GEMM's list
    held summed over the passes) of one block of tokens, over a sorted
    buffer of `buffer_rows` rows a pass (`_short_rows`, the caller's to
    read); `interpret`: the grouped GEMM's Pallas mode."""
    T, D = h.shape
    cd = h.dtype
    S = buffer_rows
    local, gates, order, sizes = _sorted_pairs(h, lp, valid, k, first, n,
                                               scale, normalize, score)
    with jax.named_scope("moe_dispatch"):
        n_local = jnp.sum(sizes)
        ends = jnp.cumsum(sizes)        # a group's end among the sorted
    with jax.named_scope("moe_experts"):
        Lm = lp["experts_gate"].shape[0]
        # the stack's Lm*n experts as the kernel's groups: a bitcast
        w = {m: lp["experts_" + m].astype(cd).reshape(
            Lm * n, *lp["experts_" + m].shape[2:])
            for m in ("gate", "up", "down")}
        base = jnp.asarray(layer, jnp.int32) * n
    with jax.named_scope("moe_combine"):
        inv = jnp.argsort(order)        # a pair's place when sorted
        gw = jnp.where(local, gates, 0.0)                   # [T, k]
    # whole passes: the last one may reach past the T*k pairs
    padded = jnp.pad(order, (0, -(T * k) % S))

    def one_pass(carry):
        """Sorted rows lo .. lo + S: their part of every token's sum."""
        lo, y, items = carry
        with jax.named_scope("moe_dispatch"):
            pairs = jax.lax.dynamic_slice(padded, (lo,), (S,))
            rows = jnp.take(h, pairs // k, axis=0)              # [S, D]
        with jax.named_scope("moe_experts"):
            # what of each expert's rows lies in this pass, and the list
            # of (row tile, hit expert) items its three GEMMs walk
            part = (jnp.clip(ends - lo, 0, S)
                    - jnp.clip(ends - sizes - lo, 0, S))
            work = gemm_work_list(part, base, rows=S)
            out = _served_mlp(rows, w, part, base, work, interpret)
        with jax.named_scope("moe_combine"):
            # back to (token, choice) order; a pair outside this pass, or
            # outside every group, contributes nothing whatever the row
            # it reads holds
            at = inv - lo
            here = local & ((at >= 0) & (at < S)).reshape(T, k)
            out = jnp.take(out, jnp.clip(at, 0, S - 1), axis=0)
            out = jnp.where(here[..., None], out.reshape(T, k, D),
                            jnp.zeros((), cd))
            y = y + jnp.einsum("tkd,tk->td", out, gw,
                               preferred_element_type=jnp.float32)
        return lo + S, y, items + work.count

    z = jnp.zeros((), jnp.int32)
    _, y, items = jax.lax.while_loop(
        lambda c: c[0] < n_local, one_pass,
        (z, jnp.zeros((T, D), jnp.float32), z))
    return y.astype(cd), sizes, (n_local > S).astype(jnp.int32), items


def _served_mlp(rows, w, sizes, base, work, interpret=None):
    """Gated SiLU MLPs of ONE layer's experts inside the stack, as three
    grouped GEMMs of the repo's own kernel (`kernels/grouped_gemm.py`):
    rows [S, D] sorted by expert, `sizes` [n] rows on each, w["gate" |
    "up"] [Lm n, D, F], w["down"] [Lm n, F, D] the whole stacks, `base`
    the layer's first expert in them, `work` the items all three walk. A
    row past the experts' sum belongs to none and its output means
    nothing. The served form's alone: `_grouped_mlp` is the other."""
    gemm = functools.partial(grouped_gemm, sizes=sizes, base=base, work=work,
                             interpret=interpret)
    g, u = gemm(rows, w["gate"]), gemm(rows, w["up"])
    return gemm((jax.nn.silu(g) * u).astype(rows.dtype), w["down"])


@jax.custom_vjp
def _gather_pairs(h, tok, at, here):
    """rows[s] = h[tok[s]]: the sorted buffer's rows. `at` [T, k] is each
    pair's place in it and `here` whether it is computed here. The
    transpose is written as a gather too (each token sums the rows of its
    own pairs): XLA's own would be a scatter-add of S rows into T, and it
    would read what the grouped GEMM leaves in rows that belong to no
    expert."""
    return jnp.take(h, tok, axis=0)


def _gather_pairs_fwd(h, tok, at, here):
    return jnp.take(h, tok, axis=0), (tok, at, here)


def _gather_pairs_bwd(res, d_rows):
    tok, at, here = res
    dh = sum(g.astype(jnp.float32) for g in _pairs_out(d_rows, at, here))
    return (dh.astype(d_rows.dtype), _float0(tok), _float0(at),
            _float0(here))


_gather_pairs.defvjp(_gather_pairs_fwd, _gather_pairs_bwd)


@jax.custom_vjp
def _sum_pairs(out, gw, g_row, tok, at, here):
    """y[t] = sum_j gw[t, j] out[at[t, j]] over the pairs `here`, float32:
    the gated sum, back in token order. Its transpose gathers too: a
    sorted row takes its token's cotangent times its pair's gate (`g_row`
    [S]: `gw` in sorted order, 0 for a row of no expert)."""
    return _sum_pairs_fwd(out, gw, g_row, tok, at, here)[0]


def _pairs_out(out, at, here):
    """Per choice j, the sorted rows of every token's j-th pair [T, D]
    (zeros where that pair is not computed here): k gathers of T rows,
    each consumed by the elementwise pass that follows it, where one
    gather of T k rows would stand whole in memory, k on the chip's 8-row
    tile."""
    return [jnp.where(here[:, j, None], jnp.take(out, at[:, j], axis=0),
                      jnp.zeros((), out.dtype)) for j in range(at.shape[1])]


def _sum_pairs_fwd(out, gw, g_row, tok, at, here):
    y = sum(gw[:, j, None] * g.astype(jnp.float32)
            for j, g in enumerate(_pairs_out(out, at, here)))
    return y, (out, g_row, tok, at, here)


def _sum_pairs_bwd(res, dy):
    out, g_row, tok, at, here = res
    # dy rounded to the rows' type BEFORE the gather: S float32 rows of D
    # (0.45 GB in a 16k-token step) would stand whole in memory otherwise
    d_out = (jnp.take(dy.astype(out.dtype), tok, axis=0)
             * g_row[:, None].astype(out.dtype))
    d_gw = jnp.stack([jnp.sum(g.astype(jnp.float32) * dy, -1)
                      for g in _pairs_out(out, at, here)], axis=1)
    return (d_out, d_gw, jnp.zeros_like(g_row), _float0(tok), _float0(at),
            _float0(here))


_sum_pairs.defvjp(_sum_pairs_fwd, _sum_pairs_bwd)


def _float0(x):
    return np.zeros(x.shape, jax.dtypes.float0)


def expert_share_train(h: jax.Array, lp: Dict[str, jax.Array], *, k: int,
                       first: int, scale: float = 1.0,
                       normalize: bool = True,
                       valid: Optional[jax.Array] = None,
                       score: str = "sigmoid"):
    """`expert_share_ffn` under `jax.grad`, for ONE layer's experts
    (`lp["experts_*"]` [n, D, F], what a scan over layers hands its body:
    a gradient with respect to a whole stack would be Lm times the
    layer's): the same routing, sort by held expert, grouped GEMMs and
    float32 gated sum (`_sorted_pairs`, `_grouped_mlp`), the same value
    and `stats`. What differs follows from the reverse mode. All T tokens
    go at once, so that a held expert sees its T k / E rows in one
    grouped GEMM and reads its weights once, forward and in each backward
    form. ONE pass over a sorted buffer of all T k pairs (a `while_loop`
    of passes has no reverse mode), so no pair is ever dropped, whatever
    the routing: the buffer bounds the worst case, and the grouped GEMMs
    walk only the row tiles of the local pairs that lead it (n / E of
    them on average), so the step does not pay for the rows behind them.
    A short buffer with further passes under `lax.cond` was tried first
    and takes MORE memory: the chip's compiler holds every pass's buffers
    apart (one layer's forward and backward at 16k tokens, D 3584: 4.57
    GiB of temporaries in this form, 6.0 with two passes of half the
    rows, 7.7 with four of 5/16; compiled for a described v5e, PR 36).
    The gates carry gradient; the selection (and a selection bias) none.
    `moe_full_passes` is 0 by construction."""
    T, D = h.shape
    cd = h.dtype
    n = lp["experts_gate"].shape[0]
    if valid is None:
        valid = jnp.ones((T,), bool)
    local, gates, order, sizes = _sorted_pairs(h, lp, valid, k, first, n,
                                               scale, normalize, score)
    with jax.named_scope("moe_dispatch"):
        order = order.astype(jnp.int32)
        at = jnp.argsort(order).reshape(T, k).astype(jnp.int32)
        tok = order // k
        gw = jnp.where(local, gates, 0.0)                   # [T, k]
        rows = _gather_pairs(h, tok, at, local)                 # [S, D]
        # each sorted row's gate, for the combine's transpose: 0 behind
        # the local pairs, as gw is for a pair that is not computed here
        g_row = jax.lax.stop_gradient(jnp.take(gw.reshape(T * k), order))
    with jax.named_scope("moe_experts"):
        w = {m: lp["experts_" + m].astype(cd)
             for m in ("gate", "up", "down")}
        out = _grouped_mlp(rows, w, sizes)
    with jax.named_scope("moe_combine"):
        y = _sum_pairs(out, gw, g_row, tok, at, local)
    return y.astype(cd), _share_stats(sizes, jnp.zeros((), jnp.int32))


def _grouped_mlp(rows, w, group_sizes):
    """Gated SiLU MLPs as three grouped GEMMs: rows [R, D] sorted by
    group, w["gate" | "up"] [G, D, F], w["down"] [G, F, D]; a row past
    the groups' sum belongs to none and its output means nothing."""
    g = jax.lax.ragged_dot(rows, w["gate"], group_sizes)
    u = jax.lax.ragged_dot(rows, w["up"], group_sizes)
    return jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(rows.dtype),
                              w["down"], group_sizes)


@dataclasses.dataclass
class MoeConfig:
    """Flagship TRAINED MoE transformer (routed experts + optional
    always-on shared expert). Its router is GShard's capacity router: it
    DROPS the tokens an expert has no capacity slot for. The served,
    dropless sigmoid router is the other function (`expert_share_ffn`)."""
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632       # dense (shared) FFN width
    moe_intermediate_size: int = 1408   # per-expert FFN width
    num_experts: int = 8
    num_experts_per_tok: int = 2
    num_shared_experts: int = 1         # 0 disables the shared expert
    capacity_factor: float = 1.25
    num_hidden_layers: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 8
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    router_aux_loss_coef: float = 0.01
    router_z_loss_coef: float = 0.001
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    attn_impl: str = "flash"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def capacity(self, tokens: int) -> int:
        return gshard_capacity(tokens, self.num_experts_per_tok,
                               self.num_experts, self.capacity_factor)

    @staticmethod
    def tiny(**over) -> "MoeConfig":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    moe_intermediate_size=32, num_experts=4,
                    num_experts_per_tok=2, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=2,
                    max_position_embeddings=128)
        base.update(over)
        return MoeConfig(**base)

    @staticmethod
    def qwen2_moe_a14b(**over) -> "MoeConfig":
        """Qwen2-57B-A14B-shaped config (public card numbers)."""
        base = dict(vocab_size=151936, hidden_size=3584,
                    intermediate_size=18944, moe_intermediate_size=2560,
                    num_experts=64, num_experts_per_tok=8,
                    num_shared_experts=1, num_hidden_layers=28,
                    num_attention_heads=28, num_key_value_heads=4,
                    max_position_embeddings=32768, rope_theta=1000000.0)
        base.update(over)
        return MoeConfig(**base)

    @staticmethod
    def deepseek_moe_16b(**over) -> "MoeConfig":
        """DeepSeekMoE-16B-shaped config (public card numbers)."""
        base = dict(vocab_size=102400, hidden_size=2048,
                    intermediate_size=10944, moe_intermediate_size=1408,
                    num_experts=64, num_experts_per_tok=6,
                    num_shared_experts=2, num_hidden_layers=28,
                    num_attention_heads=16, num_key_value_heads=16,
                    max_position_embeddings=4096)
        base.update(over)
        return MoeConfig(**base)


def _llama_cfg(cfg: MoeConfig) -> _llama.LlamaConfig:
    """Attention reuses the llama block implementation."""
    return _llama.LlamaConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        max_position_embeddings=cfg.max_position_embeddings,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype, remat=cfg.remat,
        attn_impl=cfg.attn_impl, use_flash=True)


def init_params(key: jax.Array, cfg: MoeConfig) -> Dict[str, Any]:
    """Parameter pytree; layers stacked [L], experts stacked [E]."""
    D, V, L = cfg.hidden_size, cfg.vocab_size, cfg.num_hidden_layers
    E, Fm = cfg.num_experts, cfg.moe_intermediate_size
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    pd = cfg.param_dtype
    ks = jax.random.split(key, 12)

    def norm(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pd)

    layers = {
        "input_layernorm": jnp.ones((L, D), pd),
        "q_proj": norm(ks[1], (L, D, H * hd)),
        "k_proj": norm(ks[2], (L, D, KV * hd)),
        "v_proj": norm(ks[3], (L, D, KV * hd)),
        "o_proj": norm(ks[4], (L, H * hd, D)),
        "post_attention_layernorm": jnp.ones((L, D), pd),
        "gate": norm(ks[5], (L, D, E)),
        "expert_gate_proj": norm(ks[6], (L, E, D, Fm)),
        "expert_up_proj": norm(ks[7], (L, E, D, Fm)),
        "expert_down_proj": norm(ks[8], (L, E, Fm, D)),
    }
    if cfg.num_shared_experts:
        Fs = cfg.moe_intermediate_size * cfg.num_shared_experts
        layers.update({
            "shared_gate_proj": norm(ks[9], (L, D, Fs)),
            "shared_up_proj": norm(ks[10], (L, D, Fs)),
            "shared_down_proj": norm(ks[11], (L, Fs, D)),
        })
    return {
        "embed_tokens": norm(ks[0], (V, D)),
        "layers": layers,
        "norm": jnp.ones((D,), pd),
        "lm_head": norm(jax.random.fold_in(key, 99), (D, V)),
    }


def param_specs(cfg: MoeConfig, pp: bool = False) -> Dict[str, Any]:
    """Sharding table: experts over 'ep' (expert parallelism — the
    reference's moe_group), expert matrices 2D-sharded over
    (sharding, mp) like dense weights; attention same as llama."""
    lspec = "pp" if pp else None
    layers = {
        "input_layernorm": P(lspec, None),
        "q_proj": P(lspec, "sharding", "mp"),
        "k_proj": P(lspec, "sharding", "mp"),
        "v_proj": P(lspec, "sharding", "mp"),
        "o_proj": P(lspec, "mp", "sharding"),
        "post_attention_layernorm": P(lspec, None),
        "gate": P(lspec, None, None),
        "expert_gate_proj": P(lspec, "ep", "sharding", "mp"),
        "expert_up_proj": P(lspec, "ep", "sharding", "mp"),
        "expert_down_proj": P(lspec, "ep", "mp", "sharding"),
    }
    if cfg.num_shared_experts:
        layers.update({
            "shared_gate_proj": P(lspec, "sharding", "mp"),
            "shared_up_proj": P(lspec, "sharding", "mp"),
            "shared_down_proj": P(lspec, "mp", "sharding"),
        })
    return {
        "embed_tokens": P("mp", "sharding"),
        "layers": layers,
        "norm": P(None),
        "lm_head": P("sharding", "mp"),
    }


def moe_block(x: jax.Array, lp: Dict[str, jax.Array], cfg: MoeConfig,
              mesh=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: [B, S, D] → (y, aux). Routed experts + optional shared expert.

    GShard GROUPED dispatch: capacity is per group (group = batch row), so
    routing state is [B, S, k] indices + an inverse map [B, E, C(S)] —
    linear in total tokens. Dispatch gathers token rows into [B, E, C, D]
    (combine gathers back), so nothing materializes the round-1 [B,S,E,C]
    one-hot tensors whose memory scaled with E*C (VERDICT item 4). Groups
    align with the dp/sharding batch axes, so each data shard routes
    independently and the gathers stay shard-local under GSPMD — the same
    locality the reference gets from per-rank all_to_all over the
    moe_group; the expert einsums sharded P('ep') still make GSPMD insert
    the EP all_to_all. On a single TPU chip (mesh=None) the two gathers run
    the Pallas ragged dispatch kernel (kernels.moe_dispatch, SURVEY.md §7
    M7) — under a mesh they stay jnp gathers, which GSPMD can partition."""
    B, S, D = x.shape
    cd = cfg.dtype
    k = cfg.num_experts_per_tok
    E = cfg.num_experts
    C = cfg.capacity(S)

    logits = x.astype(jnp.float32) @ lp["gate"].astype(jnp.float32)  # [B,S,E]
    # routing's token-inverse map is NOT consumed here: dispatch/combine
    # need the POSITION-inverse map (inv_pos below) too, and deriving the
    # token map from it (inv_pos // k) keeps the pair consistent by
    # construction instead of by parallel scatters
    eidx, slot, probs, valid, _, aux = jax.vmap(
        lambda lg: top_k_routing(lg, k, C))(logits)
    aux = jax.tree.map(jnp.mean, aux)

    from jax.ad_checkpoint import checkpoint_name
    from ..kernels.moe_dispatch import (combine_gather, combine_wsum,
                                        dispatch_gather)
    # both directions of dispatch AND their gradients are masked row
    # gathers over a pair of inverse index maps (slot assignment is
    # injective — kernels.moe_dispatch): flat maps (token, choice) → slot,
    # inv_pos maps slot → token position. Nothing in the MoE path scatters.
    flat = jnp.where(valid, eidx * C + slot, -1).reshape(B, S * k)
    if mesh is None:
        # single chip: EXPERT-LEADING global layout [E, B*C, D]. The
        # (b, e)-batched einsums made XLA shuffle every expert tensor
        # between {b-major} and {e-major} layouts in fwd AND bwd (~170
        # ms/step of pure transposes on the config-4 bench); with e
        # leading and one flat row index space, dispatch/GEMMs/combine
        # all agree on the layout. Rows: slot (e, b, c) at e*B*C + b*C + c,
        # token position (b, s, j) at b*S*k + s*k + j.
        boff = (jnp.arange(B, dtype=jnp.int32) * C)[:, None]
        flat_g = jnp.where(flat >= 0, (eidx * (B * C)).reshape(B, S * k)
                           + boff + slot.reshape(B, S * k), -1)
        flat_g = flat_g.reshape(1, B * S * k)
        safe = jnp.where(flat_g >= 0, flat_g, E * B * C)
        inv_pos = jnp.full((E * B * C + 1,), -1, jnp.int32).at[safe[0]].set(
            jnp.arange(B * S * k, dtype=jnp.int32), mode="drop")[None, :-1]
        inv_tok = jnp.where(inv_pos >= 0, inv_pos // k, -1)
        flat_g, inv_pos, inv_tok, probs = (
            checkpoint_name(t, "moe_routing")
            for t in (flat_g, inv_pos, inv_tok, probs))
        # gather, then XLA einsums: fusing the row gather into the
        # gate/up GEMMs was tried and lost, the row DMA does not hide
        # under the MXU work at the block VMEM can hold
        expert_in = dispatch_gather(
            x.reshape(1, B * S, D).astype(cd), inv_tok, flat_g, k,
            True).reshape(E, B * C, D)
        g = jnp.einsum("emd,edf->emf", expert_in,
                       lp["expert_gate_proj"].astype(cd))
        u = jnp.einsum("emd,edf->emf", expert_in,
                       lp["expert_up_proj"].astype(cd))
        expert_out = jnp.einsum("emf,efd->emd", jax.nn.silu(g) * u,
                                lp["expert_down_proj"].astype(cd))
        # FUSED weighted combine: y[t] = sum_j probs[t,j]·eout[slot(t,j)]
        # in one kernel — the unfused gather-to-[B,S,k,D] + einsum path
        # cost ~100 ms/step of T(2,128)-tiled reshape/reduce traffic
        # (round-4 profile); its backward gathers dy rows once for BOTH
        # d_eout and d_probs (kernels.moe_dispatch.combine_wsum)
        idx_tk = jnp.clip(flat_g, 0).reshape(1, B * S, k)
        w_tk = jnp.where(flat_g >= 0,
                         probs.reshape(1, B * S * k).astype(jnp.float32),
                         0.0).reshape(1, B * S, k)
        y = combine_wsum(expert_out.reshape(1, E * B * C, D), idx_tk,
                         w_tk, inv_pos, True).reshape(B, S, D).astype(cd)
    else:
        # under GSPMD: per-batch-row index space — groups align with the
        # dp/sharding batch shards so the gathers stay shard-local
        safe = jnp.where(flat >= 0, flat, E * C)
        pos_ids = jnp.broadcast_to(
            jnp.arange(S * k, dtype=jnp.int32)[None], (B, S * k))
        inv_pos = jax.vmap(
            lambda ip, s, p: ip.at[s].set(p, mode="drop"))(
                jnp.full((B, E * C + 1), -1, jnp.int32), safe,
                pos_ids)[:, :-1]
        inv_tok = jnp.where(inv_pos >= 0, inv_pos // k, -1)
        flat, inv_pos, inv_tok, probs = (
            checkpoint_name(t, "moe_routing")
            for t in (flat, inv_pos, inv_tok, probs))
        # r5 (VERDICT r4 next-3): on TPU the batch-local gathers run the
        # SAME fused Pallas kernels as the single-chip bench, shard_mapped
        # over the batch shards (a bare pallas_call is opaque to GSPMD —
        # wrapping it manual over the batch axes is exactly the shard-
        # local computation the jnp path relied on GSPMD to discover).
        # jnp stays the fallback off-TPU and inside pipeline stages
        # (manual-over-pp shard_map cannot nest another shard_map).
        from ..kernels.flash_attention import _use_pallas
        fused = _use_pallas(x) and not _llama.in_manual_axis("pp")
        if fused:
            from jax import shard_map
            bax = ("dp", "sharding")
            expert_in = shard_map(
                lambda xs, it, fl: dispatch_gather(xs, it, fl, k, True),
                mesh=mesh,
                in_specs=(P(bax, None, None), P(bax, None), P(bax, None)),
                out_specs=P(bax, None, None), check_vma=False,
            )(x.astype(cd), inv_tok, flat)
            expert_in = expert_in.reshape(B, E, C, D)
        else:
            expert_in = dispatch_gather(x.astype(cd), inv_tok, flat, k,
                                        False).reshape(B, E, C, D)
        g = jnp.einsum("becd,edf->becf", expert_in,
                       lp["expert_gate_proj"].astype(cd))
        u = jnp.einsum("becd,edf->becf", expert_in,
                       lp["expert_up_proj"].astype(cd))
        expert_out = jnp.einsum("becf,efd->becd", jax.nn.silu(g) * u,
                                lp["expert_down_proj"].astype(cd))
        if fused:
            # FUSED weighted combine per batch shard (same contract as
            # the single-chip branch: idx pre-clipped, w pre-zeroed)
            idx_tk = jnp.clip(flat, 0).reshape(B, S, k)
            w_tk = jnp.where(flat >= 0, probs.reshape(B, S * k)
                             .astype(jnp.float32), 0.0).reshape(B, S, k)
            y = shard_map(
                lambda eo, it, wt, ip: combine_wsum(eo, it, wt, ip, True),
                mesh=mesh,
                in_specs=(P(bax, None, None), P(bax, None, None),
                          P(bax, None, None), P(bax, None)),
                out_specs=P(bax, None, None), check_vma=False,
            )(expert_out.reshape(B, E * C, D), idx_tk, w_tk,
              inv_pos).astype(cd)
        else:
            got = combine_gather(expert_out.reshape(B, E * C, D), flat,
                                 inv_pos, False).reshape(B, S, k, D)
            # combine: y[b,s] = Σ_j probs[b,s,j] · expert_out[slot(b,s,j)]
            y = jnp.einsum("bskd,bsk->bsd", got, probs.astype(cd))

    if cfg.num_shared_experts:
        sg = x @ lp["shared_gate_proj"].astype(cd)
        su = x @ lp["shared_up_proj"].astype(cd)
        y = y + (jax.nn.silu(sg) * su) @ lp["shared_down_proj"].astype(cd)
    return y, aux


def _decoder_body(carry, lp, cfg: MoeConfig, lcfg, cos, sin, mesh,
                  constrain=None):
    """One MoE decoder layer on the (x, lb, zl) carry — the SINGLE source
    for both the plain scan (forward) and the pipeline stage (forward_pp);
    `constrain` optionally re-annotates activation sharding."""
    h, lb, zl = carry
    norm = _llama._make_norm(cfg, mesh)  # fused kernel, shard_mapped
    # under a mesh (r5; jnp inside pipeline stages — llama.in_manual_axis)
    a = norm(h, lp["input_layernorm"])
    h = h + _llama._attention(a, lp, lcfg, cos, sin, mesh)
    a = norm(h, lp["post_attention_layernorm"])
    y, aux = moe_block(a, lp, cfg, mesh)
    h = h + y
    if constrain is not None:
        h = constrain(h)
    return (h, lb + aux["load_balance_loss"], zl + aux["router_z_loss"])


def _backbone(params, tokens, cfg: MoeConfig, mesh=None):
    """Embed + MoE decoder stack → (pre-norm x [B,S,D], aux losses)."""
    lcfg = _llama_cfg(cfg)
    cd = cfg.dtype
    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(cd)
    cos, sin = rope_freqs(cfg.head_dim, tokens.shape[1], cfg.rope_theta,
                          jnp.float32)

    def maybe_constrain(h):
        if mesh is not None:
            from jax.sharding import NamedSharding
            h = jax.lax.with_sharding_constraint(
                h, NamedSharding(mesh, _llama.act_spec()))
        return h

    x = maybe_constrain(x)

    def body(carry, lp):
        return _decoder_body(carry, lp, cfg, lcfg, cos, sin, mesh,
                             constrain=maybe_constrain), None

    if cfg.remat:
        # save the (tiny) routing index maps so the backward refwd skips
        # the router; everything big is still rematerialized
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "moe_routing"))
    (x, lb, zl), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        params["layers"])
    L = cfg.num_hidden_layers
    return x, {"load_balance_loss": lb / L, "router_z_loss": zl / L}


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: MoeConfig,
            mesh=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens [B,S] → (logits [B,S,V] f32, aux losses)."""
    cd = cfg.dtype
    x, aux = _backbone(params, tokens, cfg, mesh)
    x = rms_norm_ref(x, params["norm"], cfg.rms_norm_eps)
    logits = (x.astype(cd) @ params["lm_head"].astype(cd)).astype(jnp.float32)
    return logits, aux


def forward_pp(params: Dict[str, Any], tokens: jax.Array, cfg: MoeConfig,
               mesh, num_microbatches: int) -> Tuple[jax.Array,
                                                     Dict[str, jax.Array]]:
    """Pipeline-parallel MoE forward: decoder stages run the compiled GPipe
    schedule over the mesh's `pp` axis, composing with ep/sharding/mp
    (reference: DeepSeek-class recipes run pp x ep). The router aux losses
    ride the pipe as extra pytree-buffer channels — each stage adds its
    layers' load-balance and z losses to the per-microbatch accumulators
    (parallel.pipeline.gpipe_apply carries arbitrary pytrees)."""
    from ..parallel.pipeline import pipelined, stack_stages

    n = mesh.shape["pp"]
    B, S = tokens.shape
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    L = cfg.num_hidden_layers
    lcfg = _llama_cfg(cfg)
    cd = cfg.dtype
    cos, sin = rope_freqs(cfg.head_dim, S, cfg.rope_theta, jnp.float32)
    stage_params = stack_stages(params["layers"], n)

    def stage_fn(local_layers, buf):
        def body(carry, lp):
            return _decoder_body(carry, lp, cfg, lcfg, cos, sin, mesh), None
        (x, lb, zl), _ = jax.lax.scan(
            body, (buf["x"], buf["lb"], buf["zl"]), local_layers)
        return {"x": x, "lb": lb, "zl": zl}

    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(cd)
    mb = {
        "x": x.reshape((M, B // M) + x.shape[1:]),
        "lb": jnp.zeros((M,), jnp.float32),
        "zl": jnp.zeros((M,), jnp.float32),
    }
    outs = pipelined(stage_fn, mesh, remat=cfg.remat)(stage_params, mb)
    x = outs["x"].reshape(B, S, -1)
    x = rms_norm_ref(x, params["norm"], cfg.rms_norm_eps)
    logits = (x.astype(cd) @ params["lm_head"].astype(cd)).astype(jnp.float32)
    aux = {"load_balance_loss": jnp.mean(outs["lb"]) / L,
           "router_z_loss": jnp.mean(outs["zl"]) / L}
    return logits, aux


def loss_and_grad_pp(params: Dict[str, Any], tokens: jax.Array,
                     cfg: MoeConfig, mesh, num_microbatches: int,
                     virtual_pp: int = 1):
    """Fused loss + grads for MoE through the compiled 1F1B schedule.

    Reference analog: DeepSeek-class MoE under fleet's 1F1B scheduler
    (SURVEY.md §2.3 EP row; VERDICT r2 missing 5 — MoE+pp previously fell
    back to GPipe because 1F1B's activation contract was a single array).
    The router aux-loss accumulators ride the pipe as extra PYTREE buffer
    channels — pipeline.one_f_one_b carries arbitrary pytrees now — and
    their cotangents flow back up the same ring, so load-balance/z-loss
    gradients reach every stage's routers. virtual_pp > 1 uses the
    interleaved 1F1B (O(v·pp) residency) with the same pytree buffers.
    Returns (loss, grads) with grads matching the params tree."""
    from ..parallel.pipeline import run_1f1b

    n = mesh.shape["pp"]
    B, S = tokens.shape
    M = num_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    L = cfg.num_hidden_layers
    lcfg = _llama_cfg(cfg)
    cd = cfg.dtype
    cos, sin = rope_freqs(cfg.head_dim, S, cfg.rope_theta, jnp.float32)
    f32 = jnp.float32

    def stage_fn(local_layers, buf):
        def body(carry, lp):
            return _decoder_body(carry, lp, cfg, lcfg, cos, sin, mesh), None
        (x, lb, zl), _ = jax.lax.scan(
            body, (buf["x"], buf["lb"], buf["zl"]), local_layers)
        return {"x": x, "lb": lb, "zl": zl}

    def first_fn(embed, tok_mb):
        return {"x": jnp.take(embed, tok_mb, axis=0).astype(cd),
                "lb": jnp.zeros((), f32), "zl": jnp.zeros((), f32)}

    def last_fn(lp, buf, tok_mb):
        x = rms_norm_ref(buf["x"], lp["norm"], cfg.rms_norm_eps)
        logits = (x.astype(cd) @ lp["lm_head"].astype(cd)).astype(f32)
        ce = _llama._mb_loss(logits, tok_mb)
        return (ce + cfg.router_aux_loss_coef * buf["lb"] / L
                + cfg.router_z_loss_coef * buf["zl"] / L)

    first_params = params["embed_tokens"]
    last_params = {"norm": params["norm"], "lm_head": params["lm_head"]}
    toks_mb = tokens.reshape((M, B // M) + tokens.shape[1:])
    loss, g_layers, g_f, g_l = run_1f1b(
        stage_fn, first_fn, last_fn, mesh, params["layers"], first_params,
        last_params, toks_mb, n_stages=n, virtual_pp=virtual_pp)
    grads = {"embed_tokens": g_f, "layers": g_layers,
             "norm": g_l["norm"], "lm_head": g_l["lm_head"]}
    grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
    return loss, grads


def loss_fn(params, tokens, cfg: MoeConfig, mesh=None,
            pp_microbatches=None, pp_virtual: int = 1):
    """Next-token CE + router aux losses (full-shape roll+mask, same
    rationale as llama.loss_fn). pp_microbatches: with a mesh whose pp
    axis > 1, run the decoder through the compiled GPipe schedule.
    pp_virtual > 1 under the GPipe forward is not implemented for MoE —
    use schedule='1f1b' (loss_and_grad_pp handles virtual_pp with the
    pytree aux channels)."""
    if pp_virtual > 1:
        raise NotImplementedError(
            "interleaved virtual-pp under the MoE GPipe forward is not "
            "implemented (paddle_tpu/nlp/moe.py) — use pp_schedule='1f1b', "
            "whose interleaved_one_f_one_b carries the aux-loss pytree")
    if (pp_microbatches and mesh is not None
            and "pp" in mesh.axis_names and mesh.shape["pp"] > 1):
        logits, aux = forward_pp(params, tokens, cfg, mesh, pp_microbatches)
        ce = _llama._mb_loss(logits, tokens)
    else:
        x, aux = _backbone(params, tokens, cfg, mesh)
        x = rms_norm_ref(x, params["norm"], cfg.rms_norm_eps)
        # fused head+CE: no [B, S, V] f32 logits materialization
        ce = _llama.fused_head_ce(
            x.astype(cfg.dtype),
            params["lm_head"].astype(cfg.dtype), tokens)
    return (ce + cfg.router_aux_loss_coef * aux["load_balance_loss"]
            + cfg.router_z_loss_coef * aux["router_z_loss"])


def num_params(cfg: MoeConfig) -> int:
    D, V, L = cfg.hidden_size, cfg.vocab_size, cfg.num_hidden_layers
    E, Fm = cfg.num_experts, cfg.moe_intermediate_size
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    per = (2 * D + D * (H + 2 * KV) * hd + H * hd * D
           + D * E + 3 * E * D * Fm)
    if cfg.num_shared_experts:
        per += 3 * D * Fm * cfg.num_shared_experts
    return V * D + L * per + D + D * V


def active_params(cfg: MoeConfig) -> int:
    """Parameters touched per token (the 'A14B' in Qwen2-57B-A14B)."""
    D, V, L = cfg.hidden_size, cfg.vocab_size, cfg.num_hidden_layers
    Fm = cfg.moe_intermediate_size
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    per = (2 * D + D * (H + 2 * KV) * hd + H * hd * D + D * cfg.num_experts
           + 3 * D * Fm * cfg.num_experts_per_tok)
    if cfg.num_shared_experts:
        per += 3 * D * Fm * cfg.num_shared_experts
    return V * D + L * per + D + D * V
