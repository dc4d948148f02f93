"""Order statistics the metrics and the spread readings share."""
from __future__ import annotations

import math
import statistics
from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in 0..100) of all `values`; None when
    there are none. No interpolation: the result is a measured sample."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def block_times(stamps: Sequence[float], block: int) -> List[float]:
    """Time per token over each whole block of `block` consecutive tokens
    after the first: (t[k] - t[k - block]) / block for k = block, 2 block,
    ... A server that hands tokens out some at a time makes most single
    gaps zero; a block spans its host reads as the stream's reader sees
    them."""
    return [(stamps[k] - stamps[k - block]) / block
            for k in range(block, len(stamps), block)]


def median(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the builder's contract defines it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
