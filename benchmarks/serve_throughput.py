"""Serve-path throughput: queries/sec and p50/p99 latency per backend.

Exercises the ``repro.serve`` engine the way an online deployment would:
one expensive ``register`` (the SD-KDE debias pass) per backend, then a
stream of fixed-size query requests per batch size, timed individually so
tail latency is visible.  Also cross-checks the served densities against the
pure-jnp reference path (rtol 1e-5 at the default 4k-sample, 8-d problem).

    PYTHONPATH=src python -m benchmarks.serve_throughput
    PYTHONPATH=src python -m benchmarks.serve_throughput --backends jnp pallas ring
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.core import kde as ref
from repro.core.mixtures import mixture_for_dim
from repro.serve import QueryRequest, ServeConfig, ServeEngine


def main(
    n: int = 4096,
    d: int = 8,
    backends=("jnp", "pallas"),
    batch_sizes=(8, 64, 256),
    n_requests: int = 24,
    method: str = "sdkde",
    seed: int = 0,
    verify: bool = True,
    rtol: float = 1e-5,
    precision: str = "f32",
) -> None:
    mix = mixture_for_dim(d)
    key = jax.random.PRNGKey(seed)
    x = mix.sample(key, n)
    y_all = mix.sample(jax.random.fold_in(key, 1), max(batch_sizes) * 2)
    h = 0.5  # fixed so every backend serves the identical estimator

    for backend in backends:
        cfg = ServeConfig(
            backend=backend, method=method,
            block_m=min(128, max(8, min(batch_sizes))),
            block_n=min(512, n),
            precision=precision,
            min_batch=min(batch_sizes), max_batch=max(batch_sizes),
        )
        eng = ServeEngine(cfg)
        t0 = time.perf_counter()
        prep = eng.register("bench", x, h=h)
        emit("serve_fit", backend=backend, method=method, n=n, d=d,
             precision=precision,
             block_m=prep.block_m, block_n=prep.block_n,
             ms=f"{1e3 * (time.perf_counter() - t0):.1f}")

        if verify:
            yv = y_all[: max(batch_sizes)]
            got = np.asarray(
                eng.query(QueryRequest(key="bench", points=yv)).value)
            ref_fn = {"kde": ref.kde_eval, "sdkde": ref.sdkde_eval,
                      "laplace": ref.laplace_kde_eval}[method]
            want = np.asarray(ref_fn(x, yv, h, block=1024))
            # atol floor: deep-tail densities (≥1e6× below peak) accumulate
            # f32 ordering noise through the flash debias pass.  Reduced
            # precision tiers get their documented tolerance floors
            # (rtol + peak-relative atol, as in tests/test_precision_autotune).
            tier_rtol = max(rtol, {"f32": 0.0, "bf16": 5e-2,
                                   "bf16x2": 5e-4}[precision])
            atol_frac = {"f32": 1e-6, "bf16": 5e-3,
                         "bf16x2": 1e-5}[precision]
            np.testing.assert_allclose(
                got, want, rtol=tier_rtol, atol=atol_frac * float(want.max())
            )
            emit("serve_verify", backend=backend, n=n, d=d,
                 precision=precision, rtol=tier_rtol, status="ok")

        rng = np.random.default_rng(seed)
        for b in batch_sizes:
            for _ in range(2):  # warm the shape bucket (compile outside timing)
                eng.query(QueryRequest(key="bench", points=y_all[:b]))
            eng.latency.reset()
            for _ in range(n_requests):
                off = int(rng.integers(0, y_all.shape[0] - b + 1))
                eng.query(QueryRequest(key="bench",
                                       points=y_all[off:off + b]))
            s = eng.latency.summary()
            emit("serve", backend=backend, method=method, n=n, d=d, batch=b,
                 precision=precision,
                 qps=f"{s.qps:.1f}", p50_ms=f"{s.p50_ms:.2f}",
                 p99_ms=f"{s.p99_ms:.2f}")
        emit("serve_cache", backend=backend, hits=eng.cache.hits,
             misses=eng.cache.misses, evictions=eng.cache.evictions)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--d", type=int, default=8)
    ap.add_argument("--backends", nargs="+", default=["jnp", "pallas"])
    ap.add_argument("--batch-sizes", nargs="+", type=int,
                    default=[8, 64, 256])
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--method", default="sdkde",
                    choices=["kde", "sdkde", "laplace"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument("--precision", default="f32",
                    choices=["f32", "bf16", "bf16x2"])
    args = ap.parse_args()
    main(n=args.n, d=args.d, backends=tuple(args.backends),
         batch_sizes=tuple(args.batch_sizes), n_requests=args.requests,
         method=args.method, seed=args.seed, verify=not args.no_verify,
         precision=args.precision)
