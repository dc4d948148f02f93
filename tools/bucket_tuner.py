"""Pad-aware prefill bucket-ladder tuner.

A served batcher keeps the accounting a workload-specific ladder is
fitted from: `batcher.prefill_suffix_hist` (real pre-padding chunk
length -> count) and `batcher.prefill_buckets` (the ladder that served
the run). The default
power-of-two ladder is workload-agnostic — chat-like traffic whose
prompts cluster under 64 tokens pays pad tokens a denser sub-64 ladder
would not — so this tool fits the ladder that MINIMIZES total pad
tokens over the observed length distribution, subject to a bucket-count
budget (every extra bucket is another compiled shape per group size and
phase, i.e. warmup time and executable cache).

Exact fit, not a heuristic: with lengths sorted, an optimal ladder's
buckets sit ON observed lengths (any bucket between two observed
lengths can be lowered to the smaller one without adding pad), so a
classic O(n^2 * k) interval DP over the (length, count) histogram finds
the minimum-pad ladder with at most k buckets.

Usage: after serving the workload, dump one JSON record from the
engine's batcher (`b = engine.batcher`):
    {"prefill_suffix_hist": {str(k): v for k, v in
                             b.prefill_suffix_hist.items()},
     "prefill_buckets": list(b.prefill_buckets),
     "kv_bytes_per_token": b.kv_bytes_per_token(),   # optional
     "kv_dtype": b.kv_dtype}                         # optional
then
    python tools/bucket_tuner.py record.json [--max-buckets 4]
    python tools/bucket_tuner.py record.json --json   # machine-readable

Prints the recommended ladder as a `prefill_buckets=(...)` /
`--prefill-buckets` setting plus the projected pad-token saving vs the
ladder the run actually served (re-costed over the same histogram).
Standalone stdlib tool — no jax import, safe anywhere ptlint runs.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Tuple


def pad_cost(hist: Dict[int, int], ladder: List[int]) -> int:
    """Total pad tokens when every observed chunk length pads up to the
    smallest ladder bucket that fits it (the batcher's `_bucket_for`
    rule; a length above the top bucket would have been chunked, so the
    histogram never contains one)."""
    total = 0
    ladder = sorted(ladder)
    for length, count in hist.items():
        bucket = next((b for b in ladder if b >= length), length)
        total += (bucket - length) * count
    return total


def fit_ladder(hist: Dict[int, int], k: int) -> Tuple[List[int], int]:
    """Minimum-pad ladder with at most `k` buckets over the observed
    (length -> count) histogram: interval DP where cost(i, j) is the pad
    paid when lengths[i..j] all share bucket lengths[j]."""
    lengths = sorted(hist)
    n = len(lengths)
    if n == 0:
        return [], 0
    k = max(1, min(k, n))
    counts = [hist[L] for L in lengths]
    # prefix sums for O(1) interval cost:
    #   cost(i, j) = L[j] * sum(c[i..j]) - sum(c*L)[i..j]
    pc = [0] * (n + 1)
    pcl = [0] * (n + 1)
    for t, (L, c) in enumerate(zip(lengths, counts)):
        pc[t + 1] = pc[t] + c
        pcl[t + 1] = pcl[t] + c * L

    def cost(i: int, j: int) -> int:
        return lengths[j] * (pc[j + 1] - pc[i]) - (pcl[j + 1] - pcl[i])

    INF = float("inf")
    # f[j][m]: min pad covering lengths[0..j] with exactly m buckets,
    # the m-th bucket at lengths[j]; arg for reconstruction
    f = [[INF] * (k + 1) for _ in range(n)]
    arg = [[-1] * (k + 1) for _ in range(n)]
    for j in range(n):
        f[j][1] = cost(0, j)
        for m in range(2, k + 1):
            for i in range(1, j + 1):
                if f[i - 1][m - 1] is INF:
                    continue
                c = f[i - 1][m - 1] + cost(i, j)
                if c < f[j][m]:
                    f[j][m] = c
                    arg[j][m] = i - 1
    best_m = min(range(1, k + 1), key=lambda m: f[n - 1][m])
    ladder, j, m = [], n - 1, best_m
    while j >= 0 and m >= 1:
        ladder.append(lengths[j])
        j, m = arg[j][m], m - 1
    return sorted(ladder), int(f[n - 1][best_m])


def tune(bench: Dict, max_buckets: int = 0) -> Dict:
    """Fit a ladder from one JSON record. max_buckets 0 keeps the
    observed ladder's bucket count (same compile budget, less pad)."""
    raw = bench.get("prefill_suffix_hist") or {}
    hist = {int(k): int(v) for k, v in raw.items()}
    observed = [int(b) for b in bench.get("prefill_buckets", [])]
    if not hist:
        raise SystemExit(
            "record has no prefill_suffix_hist — dump "
            "batcher.prefill_suffix_hist after serving the workload")
    k = max_buckets or (len(observed) or 4)
    ladder, best = fit_ladder(hist, k)
    current = pad_cost(hist, observed) if observed else None
    out = {
        "observed_ladder": observed,
        "recommended_ladder": ladder,
        "max_buckets": k,
        "chunk_lengths_seen": len(hist),
        "chunks_observed": sum(hist.values()),
        "pad_tokens_current_ladder": current,
        "pad_tokens_recommended": best,
    }
    if current:
        out["pad_reduction"] = round(1.0 - best / current, 4)
    # price the padding in KV-gather bytes under the run's kv_dtype:
    # kv_bytes_per_token (batcher.kv_bytes_per_token(), from
    # quantization.kv.kv_block_bytes) already includes the int8
    # scale-pool overhead, so an int8-KV run's pad bytes are ~half an
    # fp run's for the same ladder — the tuner's recommendation stays
    # token-driven (the DP is dtype-invariant), but the byte stakes it
    # reports reflect what the attention gather actually moves.
    bpt = bench.get("kv_bytes_per_token")
    if bpt:
        out["kv_dtype"] = bench.get("kv_dtype", "fp")
        out["kv_bytes_per_token"] = bpt
        if current is not None:
            out["pad_kv_bytes_current_ladder"] = int(current * bpt)
        out["pad_kv_bytes_recommended"] = int(best * bpt)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("bench", nargs="?", default="-",
                    help="JSON record of a served batcher's "
                         "prefill_suffix_hist / prefill_buckets "
                         "(file or '-')")
    ap.add_argument("--max-buckets", type=int, default=0,
                    help="bucket-count budget (0 = match the observed "
                         "ladder: same compile cost, less pad)")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line instead of the report")
    a = ap.parse_args(argv)
    text = (sys.stdin.read() if a.bench == "-"
            else open(a.bench).read())
    # tolerate a log with one JSON object per line: last record wins
    rec = None
    for line in text.strip().splitlines():
        line = line.strip()
        if line.startswith("{"):
            rec = json.loads(line)
    if rec is None:
        raise SystemExit(f"no JSON record found in {a.bench!r}")
    r = tune(rec, a.max_buckets)
    if a.json:
        print(json.dumps(r))
        return 0
    print(f"observed ladder : {tuple(r['observed_ladder'])} "
          f"-> {r['pad_tokens_current_ladder']} pad tokens over "
          f"{r['chunks_observed']} prefill chunks")
    print(f"recommended     : {tuple(r['recommended_ladder'])} "
          f"-> {r['pad_tokens_recommended']} pad tokens "
          f"({r.get('pad_reduction', 0) * 100:.1f}% less padding, "
          f"same <= {r['max_buckets']}-bucket compile budget)")
    if "kv_bytes_per_token" in r:
        cur = r.get("pad_kv_bytes_current_ladder")
        print(f"pad gather cost : {cur if cur is not None else '-'} -> "
              f"{r['pad_kv_bytes_recommended']} KV bytes at "
              f"{r['kv_bytes_per_token']:.0f} B/token "
              f"(kv_dtype={r['kv_dtype']}, scale overhead included)")
    print("apply with      : ContinuousBatcher(..., prefill_buckets="
          f"{tuple(r['recommended_ladder'])}) or the ServingEngine "
          "kwarg of the same name")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
