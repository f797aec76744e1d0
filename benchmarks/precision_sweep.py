"""Precision-tier sweep: accuracy vs runtime for f32 / bf16 / bf16x2.

Two kinds of cells:

  * ``precision`` — the full flash_sdkde pipeline per tier at CPU-scaled
    sizes: wall time, max relative error against the f32 pipeline, and the
    autotuned launch tile the dispatch actually used (on CPU the kernels
    run in interpret mode, so wall times are validation-only; on TPU they
    are the real thing).
  * ``precision_model`` — the acceptance cell: the paper-scale 32k-sample
    16-d problem (n_test = n/8), comparing the *modeled* step time of the
    fixed f32 128×512 launch against the autotuned bf16 configuration
    (kernels/autotune.py).  This is the number the issue gates on; on TPU
    hardware the ``precision`` cells above provide the measured
    counterpart.

    PYTHONPATH=src python -m benchmarks.precision_sweep
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from benchmarks.common import emit, timeit
from repro.core.mixtures import benchmark_mixture_16d
from repro.kernels import autotune, interpret_mode, ops

TIERS = ("f32", "bf16", "bf16x2")


def pipeline_cells(ns=(1024, 2048), d: int = 16, seed: int = 0):
    """flash_sdkde per tier: wall ms + max rel err vs the f32 pipeline."""
    interpret = interpret_mode()
    mix = benchmark_mixture_16d()
    key = jax.random.PRNGKey(seed)
    h = 0.5
    for n in ns:
        x = mix.sample(jax.random.fold_in(key, n), n)
        y = mix.sample(jax.random.fold_in(key, n + 1), max(n // 8, 1))
        base = None
        for tier in TIERS:
            fn = lambda a, b: ops.flash_sdkde(  # noqa: E731
                a, b, h, precision=tier)
            t = timeit(fn, x, y)
            dens = np.asarray(fn(x, y))
            if tier == "f32":
                base, err = dens, 0.0
            else:
                err = float(np.max(np.abs(dens - base)
                                   / (np.abs(base) + 1e-30)))
            bm, bn = autotune.resolve_blocks(
                "auto", "auto", rows=y.shape[0], cols=n, d=d,
                precision=tier, measure=False,
            )
            emit("precision", n=n, d=d, tier=tier,
                 wall_ms=round(t * 1e3, 2),
                 max_rel_err_vs_f32=f"{err:.2e}",
                 block_m=bm, block_n=bn,
                 interpret=interpret)


def model_cell(n: int = 32768, d: int = 16):
    """The §6.2 acceptance cell: autotuned bf16 vs the fixed f32 128×512."""
    m = n // 8
    fixed = autotune.modeled_cost(m, n, d, block_m=128, block_n=512,
                                  precision="f32")
    tuned_blocks = autotune.autotune_blocks(m, n, d, precision="bf16",
                                            measure=False)
    tuned = autotune.modeled_cost(m, n, d, block_m=tuned_blocks[0],
                                  block_n=tuned_blocks[1], precision="bf16")
    emit("precision_model", n=n, d=d,
         f32_fixed_us=round(fixed.step_time * 1e6, 2),
         f32_fixed_bound=fixed.bound,
         bf16_auto_us=round(tuned.step_time * 1e6, 2),
         bf16_auto_bound=tuned.bound,
         bf16_block_m=tuned.block_m, bf16_block_n=tuned.block_n,
         modeled_speedup=round(fixed.step_time / tuned.step_time, 2))


def main(ns=(1024, 2048), d: int = 16, seed: int = 0):
    pipeline_cells(ns=ns, d=d, seed=seed)
    model_cell()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=1)
    a = ap.parse_args()
    main(ns=tuple(1024 * a.scale * 2**i for i in range(2)))
