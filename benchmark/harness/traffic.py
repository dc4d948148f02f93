"""One general traffic generator. A mix is a data file of parameters
(benchmark/traffic/<mix>.json); this module turns a mix, a seed and a
count into requests. It never sees the program.

Everything about a request follows from `--seed`: when it falls due, how
long its prompt and its answer are, and every token. What the seed cannot
change is the SET of sizes and gaps a window meets: each distribution of
the mix is cut into n strata of equal probability and one value is taken
from each (its mid-quantile), so every seed offers the same prompt
lengths, the same answer lengths and the same arrival gaps, each in an
order of its own. Draws that are free of each other moved a window of 41
requests as much by which sizes it happened to get as by anything the
server did (PERF.md section 6).
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass
class Request:
    idx: int
    prompt: List[int]
    n_out: int
    due_s: float = 0.0          # open loop: offset from the window's start


def _strata(dist: Dict[str, Any], n: int) -> np.ndarray:
    """The n mid-quantiles of one distribution block of a mix, as whole
    numbers inside its limits, ascending."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] + 1 - dist["min"])
    elif kind == "constant":
        x = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown distribution {kind!r} in the mix")
    lo = dist.get("min", 1)
    hi = dist.get("max", np.inf)
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def _seeded(seed: int) -> np.random.Generator:
    # seeds run past 2**31; SeedSequence takes any non-negative integer
    return np.random.default_rng(np.random.SeedSequence(abs(int(seed))))


def _order(rng: np.random.Generator, values: np.ndarray,
           block: int) -> np.ndarray:
    """The seed's order of n ascending strata. Without `block` any order:
    one permutation. With it (the mix's `order_block`) the window is
    n // block hands of consecutive requests, and every hand is itself an
    even sample of the distribution: of each run of as many consecutive
    strata as there are hands, every hand gets one (which: drawn), and
    each hand is shuffled. So no seed puts the long prompts, or the short
    gaps, all into one part of the window; inside a hand's dozen or two of
    requests any order can fall."""
    n = len(values)
    hands = n // block if block else 0
    if hands < 2:
        return rng.permutation(values)
    to = np.concatenate([rng.permutation(hands)
                         for _ in range(-(-n // hands))])[:n]
    return np.concatenate([rng.permutation(values[to == h])
                           for h in range(hands)])


def generate(mix: Dict[str, Any], seed: int, n: int, vocab: int,
             span_s: float = 0.0) -> List[Request]:
    """n requests of the mix, in the seed's order. With span_s > 0 (open
    loop) they carry due times inside (0, span_s): a Poisson process's
    gaps (the exponential distribution's n strata, scaled to the span),
    the first request half its gap in. A closed loop ignores due times."""
    rng = _seeded(seed)
    block = int(mix.get("order_block", 0))
    plen = _order(rng, _strata(mix["prompt"], n), block)
    olen = _order(rng, _strata(mix["output"], n), block)
    if span_s:
        gaps = _order(rng, -np.log1p(-(np.arange(n) + 0.5) / n), block)
        gaps *= span_s / gaps.sum()
        due = np.cumsum(gaps) - 0.5 * gaps[0]
    else:
        due = np.zeros(n)
    return [Request(i, rng.integers(1, vocab, int(plen[i])).tolist(),
                    int(olen[i]), float(due[i])) for i in range(n)]
