"""The host steps of the program's pruned passes, read from the profile.

The program opens a ``kernels.prune.pass`` span around each pruned pass, a
``kernels.prune.*`` span around each host step inside it and a
``kernels.pruned_*`` span around each kernel launch; with tracing on each
span is also a profiler host event, on the device trace's clock.  It
counts every device-to-host fetch of a pruned pass in the histogram
``kernels.prune.host_sync_bytes``.  Each reader here gives its number per
job (per ``bench.job`` event in the window), and None where the program
records none of these, as a program older than them does.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from kdebench import xtrace

PASS = "kernels.prune.pass"
STEP = "kernels.prune."          # the pass and every step inside it
LAUNCHES = ("kernels.pruned_score", "kernels.pruned_eval")
KERNELS = "kernels."             # the program's spans of the kernels layer
JOB = "bench.job"
SYNC_BYTES = "kernels.prune.host_sync_bytes"


def _in_window(tr: xtrace.Trace, s: float, e: float) -> bool:
    return tr.t0 <= (s + e) / 2.0 < tr.t1


def jobs(tr: xtrace.Trace) -> int:
    """``bench.job`` events whose midpoint lies in the window."""
    return sum(1 for _, name, s, e in tr.host
               if name == JOB and _in_window(tr, s, e))


def idle_gaps(tr: xtrace.Trace) -> List[Tuple[float, float]]:
    """The window's intervals in which no operation ran on chip 0."""
    busy = xtrace.merged([(max(s, tr.t0), min(e, tr.t1))
                          for _, _, s, e in tr.devices[0]
                          if min(e, tr.t1) > max(s, tr.t0)])
    gaps, cur = [], tr.t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < tr.t1:
        gaps.append((cur, tr.t1))
    return gaps


def _innermost(events, t: float) -> Optional[str]:
    best = None
    for name, s, e in events:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else None


def idle_ms(ctx) -> Optional[float]:
    """Device-idle ms per job in the gaps whose midpoint lies, innermost
    among the kernels layer's spans, under a pruned pass or one of its
    host steps (not under a launch)."""
    tr = ctx.trace
    n = jobs(tr)
    spans = [(name, s, e) for _, name, s, e in tr.host
             if name.startswith(KERNELS)]
    if not tr.devices or not n \
            or not any(name.startswith(STEP) for name, _, _ in spans):
        return None
    idle = sum(e - s for s, e in idle_gaps(tr)
               if (_innermost(spans, (s + e) / 2.0) or "").startswith(STEP))
    return idle / 1e6 / n


def host_ms(ctx) -> Optional[float]:
    """Host ms per job inside the pruned passes, less the launches inside
    them."""
    tr = ctx.trace
    n = jobs(tr)
    passes = [(th, s, e) for th, name, s, e in tr.host
              if name == PASS and _in_window(tr, s, e)]
    if not n or not passes:
        return None
    launches = [(th, s, e) for th, name, s, e in tr.host if name in LAUNCHES]
    total = 0.0
    for th, s, e in passes:
        inside = [(max(ls, s), min(le, e)) for lth, ls, le in launches
                  if lth == th and ls < e and le > s]
        total += (e - s) - xtrace.union_length(inside)
    return total / 1e6 / n


def host_syncs(ctx):
    """(device-to-host fetches per job, {"bytes": bytes per job}) of the
    pruned passes in the window."""
    got = ctx.hist_delta(SYNC_BYTES)
    n = jobs(ctx.trace)
    if got is None or not n:
        return None
    count, nbytes = got
    return count / n, {"bytes": nbytes / n}
