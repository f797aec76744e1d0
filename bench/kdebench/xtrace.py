"""Reduction of a profiler trace to device busy time, kernel time and gaps.

A traced run records one profile (``jax.profiler``) around the window.  The
reduction reads the ``.xplane.pb`` with nothing but JAX:

  * device operations are the events of each TPU plane's ``XLA Ops`` line,
    each named by its HLO instruction (``%flash_score_pallas_pruned.1 =
    ...`` reads as ``flash_score_pallas_pruned``) and its module on the
    ``XLA Modules`` line;
  * the window is the host annotation ``bench.window``; host and device
    events share one clock in the profile;
  * busy time is the union of the device operations' intervals inside the
    window, averaged over the chips the cell uses; the idle share is one
    minus busy over the window;
  * each idle gap is named by what the host was doing: the innermost
    ``bench.*`` annotation that covers its midpoint, and the innermost
    other annotation (the program's ``obs.annotate`` names) if one does.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

_OP_RE = re.compile(r"^%?([A-Za-z0-9_\-]+?)(?:\.\d+)?(?:\s|=|$)")
_MOD_RE = re.compile(r"^([^()]+)")


def op_name(event_name: str) -> str:
    """``%flash_kde_pallas_pruned.1 = f32[...] custom-call(...)`` ->
    ``flash_kde_pallas_pruned``."""
    head = event_name.split(" = ", 1)[0].strip()
    m = _OP_RE.match(head)
    return m.group(1) if m else head


def module_name(event_name: str) -> str:
    m = _MOD_RE.match(event_name)
    return (m.group(1) if m else event_name).strip()


def union_length(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by possibly overlapping [start, end) pairs."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Trace:
    """Device operations, host annotations and the window, in ns.

    Built from plain lists so the tests can check the arithmetic on
    hand-made events; :func:`load` builds one from a profile."""

    def __init__(self, devices: List[List[Tuple[str, str, float, float]]],
                 host: List[Tuple[str, str, float, float]],
                 window: Optional[Tuple[float, float]] = None):
        # devices: per chip, (op, module, start, end); host: (thread,
        # name, start, end)
        self.devices = devices
        self.host = host
        if window is None:
            w = [(s, e) for _, n, s, e in host if n == "bench.window"]
            window = w[0] if w else None
        if window is None:
            raise ValueError("no bench.window annotation in the trace")
        self.t0, self.t1 = window

    # -- window -----------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _clipped(self, chip: int, pred=None):
        out = []
        for op, mod, s, e in self.devices[chip]:
            if pred is not None and not pred(op, mod):
                continue
            s, e = max(s, self.t0), min(e, self.t1)
            if e > s:
                out.append((s, e))
        return out

    @property
    def busy_s(self) -> float:
        """Seconds some operation ran on the device, averaged over chips."""
        if not self.devices:
            return 0.0
        return sum(union_length(self._clipped(c))
                   for c in range(len(self.devices))) / len(self.devices) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, prefixes) -> float:
        """Device seconds of the operations whose name starts with one of
        ``prefixes``, summed over chips, inside the window."""
        prefixes = tuple(prefixes)
        return sum(
            union_length(self._clipped(
                c, lambda op, mod: op.startswith(prefixes)))
            for c in range(len(self.devices))) / 1e9

    def launches(self, op_prefix: str):
        """Durations (s) of every launch of one operation, on any chip,
        anywhere in the profile."""
        return [(e - s) / 1e9 for chip in self.devices
                for op, _, s, e in chip if op.startswith(op_prefix)]

    # -- breakdown --------------------------------------------------------

    def device_ops(self, top: int = 10) -> List[List]:
        tot: Dict[str, float] = {}
        for op, mod, s, e in self.devices[0] if self.devices else []:
            s, e = max(s, self.t0), min(e, self.t1)
            if e > s:
                key = f"{mod}/{op}"
                tot[key] = tot.get(key, 0.0) + (e - s) / 1e9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:top]]

    def _covering(self, t: float) -> str:
        best_bench = best_other = None
        for _, name, s, e in self.host:
            if not (s <= t < e):
                continue
            if name.startswith("bench."):
                if best_bench is None or e - s < best_bench[1]:
                    best_bench = (name, e - s)
            elif best_other is None or e - s < best_other[1]:
                best_other = (name, e - s)
        label = best_bench[0] if best_bench else "no bench annotation"
        return label + (f"/{best_other[0]}" if best_other else "")

    def idle_gaps(self, top: int = 10) -> List[List]:
        """Idle seconds of chip 0 in the window, summed by what the host
        was doing, largest first."""
        busy = merged(self._clipped(0)) if self.devices else []
        gaps, cur = [], self.t0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.t1:
            gaps.append((cur, self.t1))
        tot: Dict[str, float] = {}
        for s, e in gaps:
            key = self._covering((s + e) / 2.0)
            tot[key] = tot.get(key, 0.0) + (e - s) / 1e9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:top]]

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(), "idle_gaps": self.idle_gaps()}


#: Host events kept for naming gaps: the harness's ``bench.*`` annotations
#: and the program's own, which are snake_case (``flash_kde_pruned``); the
#: runtime's internal events (``AllocateRawBuffer``, ``tpu::System...``)
#: and the Python tracer's (``$file.py:12 f``) are left out.
_ANNOTATION_RE = re.compile(r"^(bench\.[a-z_.]+|[a-z_][a-z0-9_.]*)$")


def _keep_host(name: str) -> bool:
    return bool(_ANNOTATION_RE.match(name))


def load(trace_dir, chips: int = 1) -> Trace:
    """The :class:`Trace` of the one profile under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one profile under {trace_dir}, "
                                f"found {len(files)}")
    pd = ProfileData.from_file(files[0])
    devices, host = [], []
    tpu = sorted((p for p in pd.planes
                  if p.name.startswith("/device:TPU:")),
                 key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for plane in tpu[:chips]:
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       module_name(e.name))
                      for e in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        starts = [m[0] for m in mods]
        ops = []
        for e in lines["XLA Ops"].events if "XLA Ops" in lines else []:
            s, t = e.start_ns, e.start_ns + e.duration_ns
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            ops.append((op_name(e.name), mod, s, t))
        devices.append(ops)
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if _keep_host(e.name):
                    host.append((line.name, e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
    return Trace(devices, host)
