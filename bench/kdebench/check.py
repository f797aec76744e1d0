"""The comparison that decides ``correct``.

The timed path's densities are compared with the plain reference
(``bench/reference.py``) row by row.  Each compared number has its own
limit, stated in the configuration file beside the tier it holds:

  max_rel_err  max over rows of |got - want| / max(|want|, floor), with
               floor = FLOOR_FRAC * the largest reference density, so a
               deep-tail row is held to an absolute error at the floor;
  med_rel_err  the median of the same ratio over rows.

A non-finite density or a missing answer reads as infinitely wrong.
"""

from __future__ import annotations

import math
import sys
from typing import Dict, Iterable, Tuple

import numpy as np

#: Absolute floor of the relative error, as a share of the peak density:
#: the bar ``chip_smoke.py`` and ``serve_kde --verify`` apply at f32.
FLOOR_FRAC = 1e-6


def numbers(pairs: Iterable[Tuple[np.ndarray, np.ndarray]],
            floor_from=None) -> Dict[str, float]:
    """The compared numbers over several (got, want) answers, all held to
    one floor: that of ``floor_from`` (the whole reference) when given."""
    pairs = list(pairs)
    if not pairs:
        return {"max_rel_err": math.inf, "med_rel_err": math.inf}
    want_all = np.concatenate([np.asarray(w, np.float64).reshape(-1)
                               for _, w in pairs])
    peak = float(np.max(np.abs(
        want_all if floor_from is None else np.asarray(floor_from))))
    floor = max(FLOOR_FRAC * peak, 1e-300)
    rs = []
    for got, want in pairs:
        got = np.asarray(got, np.float64).reshape(-1)
        want = np.asarray(want, np.float64).reshape(-1)
        if got.shape != want.shape:
            rs.append(np.full(max(want.size, 1), math.inf))
            continue
        r = np.abs(got - want) / np.maximum(np.abs(want), floor)
        rs.append(np.where(np.isfinite(got), r, math.inf))
    r = np.concatenate(rs)
    return {"max_rel_err": float(r.max()),
            "med_rel_err": float(np.median(r))}


def judge(nums: Dict[str, float], limits: Dict[str, float], *,
          extra: Dict[str, Tuple[float, float]] = None):
    """(correct, report): every number within its limit.  ``extra`` adds
    (value, limit) pairs held as value <= limit too."""
    report = {k: {"value": float(v), "limit": float(limits[k])}
              for k, v in nums.items() if k in limits}
    for k, (v, lim) in (extra or {}).items():
        report[k] = {"value": float(v), "limit": float(lim)}
    ok = all(r["value"] <= r["limit"] for r in report.values())
    return ok, report


def print_report(report: dict, stream=None) -> None:
    """The compared numbers beside their limits, as the last lines of
    standard error."""
    stream = stream or sys.stderr
    for k, r in report.items():
        print(f"check {k} {r['value']!r} limit {r['limit']!r}", file=stream)
    stream.flush()
