"""Shared arithmetic of the per-layer metric readers.

Each reader file under ``bench/layer_metrics/`` is a few lines that call
one of these with the names of what it reads.  A reader returns None when
it finds nothing to read, and the harness then leaves its metric out.
"""

from __future__ import annotations

from typing import Optional

from kdebench import probe, roofline

#: (span the program records per pruned launch, kernel operation in the
#: device trace, score kernel or not)
SCORE = ("kernels.pruned_score", "flash_score_pallas_pruned", True)
EVAL = ("kernels.pruned_eval", "flash_kde_pallas_pruned", False)


def _tiles(cfg: dict):
    sys_cfg = cfg["system"]
    knobs = sys_cfg.get("estimator") or sys_cfg.get("serve") or {}
    return int(knobs.get("block_m", 128)), int(knobs.get("block_n", 512))


def launch_work(ctx, which) -> Optional[roofline.LaunchWork]:
    """Pairs a kernel evaluated in the window, from the program's spans.

    A pruned launch evaluates rows x columns x occupancy pairs, counted
    from the real (unpadded) rows and columns, so sentinel padding inside
    a visited tile is not counted as work."""
    span, _, score = which
    cfg = ctx.cell.config
    n, d = int(cfg["data"]["n"]), int(cfg["data"]["d"])
    block_m, block_n = _tiles(cfg)
    passes = roofline.MXU_PASSES[cfg["precision"]]
    works = []
    for ev in ctx.spans_named(span):
        a = ev["attrs"]
        if a.get("kind", "kde") != "kde" and not score:
            continue
        rows = float(a["rows"])
        works.append(roofline.LaunchWork(
            pairs=rows * n * float(a["occupancy"]), rows=rows, d=d,
            block_m=block_m, block_n=block_n, score=score, passes=passes))
    return roofline.add(works)


def exp_per_s(ctx) -> Optional[float]:
    """The fastest exp rate the probe reached in this run's trace."""
    times = [t for t in ctx.trace.launches(probe.NAME) if t > 0]
    return probe.EXPS / min(times) if times else None


def kernel_roofline(ctx, which):
    """(share %, {"bound": term}) of one kernel over the window."""
    if ctx.peak is None:
        return None
    work = launch_work(ctx, which)
    dev_s = ctx.trace.kernel_s([which[1]])
    got = roofline.share(work, dev_s, ctx.peak, exp_per_s(ctx))
    if got is None:
        return None
    pct, bound = got
    return pct, {"bound": bound}


def step_mfu(ctx):
    """Flops the window's kernels did, each GEMM counted once whatever its
    tier's passes, over the window and the chips' bf16 peak: the whole
    step's share of the chip."""
    if ctx.peak is None or not ctx.trace.devices:
        return None
    flops = 0.0
    for which in (SCORE, EVAL):
        work = launch_work(ctx, which)
        if work is not None:
            flops += work.flops
    if not flops:
        return None
    chips = len(ctx.trace.devices)
    return 100.0 * flops / (ctx.trace.window_s * chips
                            * ctx.peak["mxu_flops_per_s"])


def visit_fraction(ctx):
    """Column tiles visited over total, weighted by rows, of every pruned
    launch in the window (%)."""
    num = den = 0.0
    for span in (SCORE[0], EVAL[0]):
        for ev in ctx.spans_named(span):
            rows = float(ev["attrs"]["rows"])
            num += rows * float(ev["attrs"]["occupancy"])
            den += rows
    return 100.0 * num / den if den else None


def hist_mean(ctx, name: str, scale: float = 1.0):
    got = ctx.hist_delta(name)
    return None if got is None else scale * got[1] / got[0]


def idle_share(ctx):
    return 100.0 * ctx.trace.idle_share if ctx.trace.devices else None


def compiles(ctx):
    return float(ctx.compiles_in_window)
