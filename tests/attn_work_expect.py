"""What the ragged kernel's work list must hold, reckoned by hand: a call's
live (row, tile, chunk) items from its positions and valid, and what a
decode or fused tick's kernels walk from the tick's flight record alone
(the schedule: `decode_ctx`, `chunk`, `prefill_spans`). For
tests/test_ragged_attention.py and tests/test_window_moe_serving.py."""
import numpy as np


def enumerate_work(pos, val, bs, M, Pt, nb, window=None):
    """(a call's live (row, tile, chunk) items in order, each (row,
    tile)'s first block [R, T]): for every (row, tile) with a valid
    query, the chunks of `nb` blocks from the block of its first visible
    key to the block of its last, at most `M`."""
    pos, val = np.asarray(pos), np.asarray(val)
    R, T = pos.shape[0], pos.shape[1] // Pt
    items, firsts = [], np.zeros((R, T), int)
    for r in range(R):
        for t in range(T):
            sl = slice(t * Pt, (t + 1) * Pt)
            if val[r, sl].any():
                p = pos[r, sl][val[r, sl]]
                n = _items(p.min(), p.max(), bs, nb, M, window)
                firsts[r, t] = max(p.min() - window + 1, 0) // bs \
                    if window else 0
                items += [(r, t, c) for c in range(n)]
    return items, firsts


def work_items(work):
    """The (row, tile, chunk) items of an `AttnWork`, its first `count`."""
    n = int(work.count)
    return list(zip(*(np.asarray(a)[:n].tolist()
                      for a in (work.row, work.tile, work.chunk))))


def _items(first_pos, last_pos, bs, nb, width, window):
    """The work items of one (row, tile) whose valid queries lie at
    `first_pos..last_pos`: chunks of `nb` blocks from the block of its
    first visible key to the block of its last, at most `width`."""
    first = max(first_pos - window + 1, 0) // bs if window else 0
    return -(-min(last_pos // bs + 1 - first, width) // nb)


def work_steps(rec, bs, slots, kinds):
    """(items ONE layer of each of `kinds` walks in the tick if no row
    retires inside it, the full grid of the same calls, rows x chunks)
    of a batcher of `slots` slots and blocks of `bs` tokens.
    `kinds`: (table width, window or None, blocks a decode step, blocks a
    prefill tile's step) each; a prefill row is one tile here (buckets up
    to the query tile)."""
    work = grid = 0
    for width, window, nb_dec, nb_pre in kinds:
        for s in range(rec["chunk"]):
            work += sum(_items(c + s - 1, c + s - 1, bs, nb_dec, width,
                               window) for c in rec["decode_ctx"])
        grid += rec["chunk"] * slots * -(-width // nb_dec)
        if rec["mode"] == "fused":
            work += sum(_items(start, end - 1, bs, nb_pre, width, window)
                        for start, end in rec["prefill_spans"])
            grid += rec["rows"] * -(-width // nb_pre)
    return work, grid
