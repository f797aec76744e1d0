"""The sharding layer of a multi-chip cell, read from the profile.

The program opens a ``distributed.shard.pass`` span around each pass whose
rows it shards over the chips, a ``distributed.shard.dispatch`` span around
each chip's host steps and launch inside it, and a
``distributed.shard.gather`` span around each exchange between chips; with
tracing on each span is also a profiler host event, on the device trace's
clock.  Each reader returns None where the program records no such span,
as a program older than them does, or where the trace holds one chip.
"""

from __future__ import annotations

from typing import Optional

from kdebench import spans, xtrace

PASS = "distributed.shard.pass"
SHARD = "distributed.shard."
KERNELS = "kernels."
#: The pruned kernels, as the device trace names them.
PRUNED = ("flash_score_pallas_pruned", "flash_kde_pallas_pruned")


def _idle_gaps(tr: xtrace.Trace, chip: int):
    busy = xtrace.merged(tr._clipped(chip))
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


def imbalance(ctx) -> Optional[float]:
    """For each sharded pass in the window, the slowest chip's pruned
    kernel device time over the chips' mean, less one, averaged over the
    passes (%).  A kernel belongs to the pass whose span holds its start."""
    tr = ctx.trace
    if len(tr.devices) < 2:
        return None
    passes = [(s, e) for _, name, s, e in tr.host
              if name == PASS and tr.t0 <= (s + e) / 2.0 < tr.t1]
    shares = []
    for s, e in passes:
        per_chip = [sum(oe - os for op, _, os, oe in ops
                        if op.startswith(PRUNED) and s <= os < e)
                    for ops in tr.devices]
        mean = sum(per_chip) / len(per_chip)
        if mean > 0:
            shares.append(max(per_chip) / mean - 1.0)
    return 100.0 * sum(shares) / len(shares) if shares else None


def idle_ms(ctx) -> Optional[float]:
    """Device-idle ms per job, averaged over the chips, in the gaps whose
    midpoint lies, innermost among the program's sharding and kernels
    spans, under a ``distributed.shard.*`` span."""
    tr = ctx.trace
    n = spans.jobs(tr)
    events = [(name, s, e) for _, name, s, e in tr.host
              if name.startswith((SHARD, KERNELS))]
    if len(tr.devices) < 2 or not n \
            or not any(name.startswith(SHARD) for name, _, _ in events):
        return None
    idle = sum(e - s for c in range(len(tr.devices))
               for s, e in _idle_gaps(tr, c)
               if (_innermost(events, (s + e) / 2.0) or "").startswith(SHARD))
    return idle / 1e6 / n / len(tr.devices)
