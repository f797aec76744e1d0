"""One run of one cell: set-up, the measured window, the check, one line.

Order matters: the window closes, the device's memory peak is read, the
program's state is freed, and only then does the reference run, so the
reference neither sets the peak nor shares the chip with the program.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from typing import Optional

from kdebench import check, device, drive, spec, xtrace
from kdebench.spec import ROOT

#: Where a traced run writes its profile, under the checkout's root;
#: emptied before and after.
TRACE_DIR = ("bench", ".out", "trace")


def _histograms() -> dict:
    from repro import obs

    snap = obs.metrics_snapshot()
    return {k: v for k, v in snap.items()
            if isinstance(v, dict) and v.get("type") == "histogram"}


class Context:
    """What a per-layer metric reader may read about the traced window."""

    def __init__(self, cell, trace, spans, hist0, hist1, compiles, peak):
        self.cell, self.trace, self.spans = cell, trace, spans
        self._h0, self._h1 = hist0, hist1
        self.compiles_in_window = compiles
        self.peak = peak

    def hist_delta(self, name: str):
        """(count, sum) a histogram gained over the window; None if the
        program never fed it there."""
        h1 = next((v for k, v in self._h1.items()
                   if k == name or k.startswith(name + "{")), None)
        if h1 is None:
            return None
        h0 = next((v for k, v in self._h0.items()
                   if k == name or k.startswith(name + "{")),
                  {"count": 0, "sum": 0.0})
        count = h1["count"] - h0["count"]
        return None if count <= 0 else (count, h1["sum"] - h0["sum"])

    def spans_named(self, name: str):
        return [e for e in self.spans if e["name"] == name]


def _options():
    """Host and device tracing on, the Python tracer off: it slows every
    Python call and would inflate the host's share of the window."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: Optional[float] = None, root=ROOT, require_chip=True,
        tier: Optional[str] = None, out=None,
        compile_cache: bool = True) -> int:
    """Run one cell once; prints its result as the last line of ``out``
    (stdout).  Returns the exit code: 2 without a chip, else 0.

    ``require_chip=False`` and ``compile_cache=False`` let a rehearsal off
    the chip drive the same path; ``tier`` substitutes a lower precision
    for the configuration's (the control)."""
    t_start = time.perf_counter() if t_start is None else t_start
    out = out or sys.stdout
    bench = spec.load_benchmark(root)
    cell = spec.resolve_cell(bench, workload, root)

    import jax

    if require_chip:
        try:
            devices = device.require_tpu(cell.chips)
        except device.NoChip as e:
            print(f"bench: {e}; nothing run", file=sys.stderr)
            return 2
    else:
        devices = jax.devices()[:cell.chips]
    cache = device.enable_compile_cache() if compile_cache else "off"
    dev = device.describe(devices)
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} jax={jax.__version__} cache={cache}",
          flush=True)
    compiles = device.CompileCounter()

    from repro import obs
    from repro.obs.trace import DEFAULT_CAPACITY

    trace_dir = root.joinpath(*TRACE_DIR)
    if trace:
        obs.configure(trace=True)
        obs.set_trace_capacity(1 << 17)
    try:
        return _run(cell, devices, dev, compiles, seed, seconds, trace,
                    trace_dir, t_start, root, tier, out)
    finally:
        if trace:
            obs.configure(trace=False)
            obs.set_trace_capacity(DEFAULT_CAPACITY)


def _run(cell, devices, dev, compiles, seed, seconds, trace, trace_dir,
         t_start, root, tier, out) -> int:
    import jax

    from repro import obs

    drv = drive.driver(cell.config, cell.traffic, seed, seconds, tier)
    with drive.annotate("bench.setup"):
        drv.setup()
    setup_s = time.perf_counter() - t_start
    print(f"setup: {setup_s:.3f}s ({compiles.n} programs compiled or "
          f"loaded)", flush=True)

    hist0 = _histograms()
    c0 = compiles.n
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs.clear_trace()
        jax.profiler.start_trace(str(trace_dir), profiler_options=_options())
    t_w = time.perf_counter()
    drv.window(seconds)
    window_s = time.perf_counter() - t_w
    in_window = compiles.n - c0
    if trace:
        from kdebench import probe

        with drive.annotate("bench.exp_probe"):
            probe.run()
        jax.profiler.stop_trace()
    hist1 = _histograms()
    spans = obs.trace_events() if trace else []
    mem = device.memory_peak_bytes(devices)
    print(f"window: {window_s:.3f}s, {drv.attempted} attempted, "
          f"{drv.failed} failed, {in_window} programs compiled or loaded "
          f"in the window", flush=True)
    if hasattr(drv, "generator_lateness"):
        print("generator lateness: " + json.dumps(drv.generator_lateness()),
              flush=True)
        print("failed by cause: " + json.dumps(drv.failures()), flush=True)

    metrics = {}
    units = {m.name: m.unit for m in cell.end_to_end + cell.per_layer}
    dev_out = dict(dev, memory_peak_bytes=mem)
    breakdown = None
    if not trace:
        vals = dict(drv.end_to_end(), setup_s=setup_s)
        # every number the driver measured, reported by the cell or not
        print("end to end: " + json.dumps(vals), flush=True)
        for m in cell.end_to_end:
            if m.name in vals:
                metrics[m.name] = {"value": vals[m.name], "unit": m.unit}
    else:
        from kdebench import roofline

        tr = xtrace.load(trace_dir, chips=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        try:
            peak = roofline.peaks(dev["kind"])
        except KeyError:
            if dev["platform"] == "tpu":
                raise
            peak = None                 # a rehearsal off the chip
        ctx = Context(cell, tr, spans, hist0, hist1, in_window, peak)
        for name, read in spec.readers(cell, root / "bench").items():
            got = read(ctx)
            if got is None:
                continue
            value, extra = (got if isinstance(got, tuple) else (got, {}))
            metrics[name] = dict({"value": value, "unit": units[name]},
                                 **extra)
        dev_out["busy_s"] = tr.busy_s
        dev_out["window_s"] = tr.window_s
        breakdown = tr.breakdown()

    drv.free()
    t_c = time.perf_counter()
    pairs, info = drv.check()
    floor_from = info.pop("floor_from", None)
    nums = check.numbers(pairs, floor_from=floor_from)
    extra = {"failed_jobs": (drv.failed, 0)} if cell.traffic["kind"] \
        == "jobs" else {"unresolved": (info.get("unresolved", 0), 0)}
    # the limits belong to the configuration's stated tier: a control run
    # at a lower tier is held to them too, and has to fail them
    correct, report = check.judge(nums, cell.config["limits"], extra=extra)
    print(f"check: {json.dumps(info)} in {time.perf_counter() - t_c:.1f}s",
          flush=True)

    result = {"correct": bool(correct), "attempted": drv.attempted,
              "failed": drv.failed, "metrics": metrics, "device": dev_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = report
    check.print_report(report)
    print(json.dumps(result), file=out, flush=True)
    return 0
