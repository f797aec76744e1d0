"""Fig. 5 / Fig. 7 reproduction: utilization from the paper's flop model.

CPU-measured utilization = FLOPs_model(k) / (runtime × peak).  The peak is
a rough single-socket CPU estimate — the point is the TREND (utilization
rising with n, the compute-bound signature), matching the paper's Fig. 5
shape.
"""

from __future__ import annotations

import argparse

import jax

from benchmarks.common import emit, timeit
from repro.analysis.flops import sdkde_flops, sdkde_flops_1d
from repro.core import kde
from repro.core.mixtures import benchmark_mixture_16d

CPU_PEAK_FLOPS = 100e9   # rough: a few cores × AVX2 f32 — trend, not truth


def main(ns=(1024, 2048, 4096, 8192)):
    mix = benchmark_mixture_16d()
    key = jax.random.PRNGKey(0)
    h = 0.5
    for n in ns:
        x = mix.sample(jax.random.fold_in(key, n), n)
        y = mix.sample(jax.random.fold_in(key, n + 1), n // 8)
        t = timeit(jax.jit(
            lambda a, b: kde.kde_eval(kde.sdkde_shift(a, h, block=2048),
                                      b, h, block=2048)), x, y)
        model_flops = sdkde_flops(n, 16, n_test=n // 8)
        emit("fig5_cpu", n=n, runtime_ms=round(t * 1e3, 2),
             model_flops=f"{model_flops:.3e}",
             util_pct=round(100 * model_flops / (t * CPU_PEAK_FLOPS), 2))


if __name__ == "__main__":
    argparse.ArgumentParser().parse_args()
    main()
