"""Window arithmetic: schedules, latencies from the due time, tails, rates.

Pure functions of numbers, so the CPU tests check them exactly.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank q-th percentile (0 < q <= 100) over every value."""
    if not len(values):
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


def latencies_from_due(due: Sequence[float], done: Sequence[Optional[float]],
                       limit: float) -> List[float]:
    """Seconds from when each request was due to when it resolved.

    Timing from the due time, not from the send, counts the wait a stall
    imposes on every request behind it.  A request that never resolved, or
    resolved with a failure (``done`` None), counts as at least ``limit``:
    it missed any latency limit."""
    out = []
    for t_due, t_done in zip(due, done):
        out.append(limit if t_done is None else max(t_done - t_due, 0.0))
    return out


def rate(count: float, seconds: float) -> float:
    """Work over the whole window's time."""
    if seconds <= 0:
        raise ValueError("window of no length")
    return count / seconds


def strata(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` points of (0, 1), one in each of n equal strata, shuffled.

    Every seed gets the same set of quantiles in another order, so the work
    a window holds does not change with the seed."""
    u = (np.arange(n) + 0.5) / n
    return u[rng.permutation(n)]


def log_uniform_sizes(u: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Integers log-uniform over [lo, hi] at the quantiles ``u``."""
    lo_l, hi_l = math.log(lo), math.log(hi + 1)
    return np.clip(np.floor(np.exp(lo_l + u * (hi_l - lo_l))), lo,
                   hi).astype(np.int64)


def arrival_offsets(arrivals: Dict, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Due times (seconds from the window's start) of an open-loop mix:
    Poisson arrivals at ``rate`` per second.  The gaps are exponential
    quantiles taken one per stratum and shuffled, so every seed offers the
    same load over the window, in another order."""
    kind = arrivals["kind"]
    if kind == "poisson":
        r = float(arrivals["rate"])
        n = max(1, int(round(r * seconds)))
        gaps = -np.log1p(-strata(n, rng)) / r
        # the first request is due at 0 and the gaps fill the window
        return (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())
    raise ValueError(f"unknown arrival kind {kind!r}")
