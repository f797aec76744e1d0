"""Streaming SD-KDE: append throughput, staleness, amortized cost vs refit.

Two modes, mirroring ``pruning_sweep``:

  * **smoke** (CI): a small streaming estimator served through the real
    engine — sliding-window updates interleaved with query traffic, with
    appends/sec, served-staleness percentiles, and an allclose cross-check
    of the post-update densities against a from-scratch jnp refit.
  * **acceptance**: the paper-scale 256k×16-d cell.  The amortized cost of
    one append update is *measured* (the O(n·b·d) delta score pass at full
    scale + the layout/column maintenance flush of a real 256k stream);
    the full-refit cost it replaces is *modeled* — the O(n²·d) score pass
    measured at a feasible size and scaled by (n/n₀)², plus the measured
    re-prepare — because actually running a 256k² score pass on the CI CPU
    is exactly what streaming exists to avoid.  The gate: amortized
    per-append-batch cost ≥ 10× below the full refit.

    PYTHONPATH=src python -m benchmarks.streaming_throughput
    PYTHONPATH=src python -m benchmarks.streaming_throughput --acceptance
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
from repro.stream import StreamConfig, StreamingSDKDE, delta


def smoke(
    n: int = 2048,
    d: int = 8,
    batch: int = 64,
    updates: int = 6,
    staleness_budget: int = 2,
    seed: int = 0,
    verify: bool = True,
) -> None:
    """Serve-level streaming smoke: real engine, real updates, verified."""
    mix = mixture_for_dim(d)
    key = jax.random.PRNGKey(seed)
    x = np.asarray(mix.sample(key, n), np.float32)
    y = np.asarray(mix.sample(jax.random.fold_in(key, 1), 256), np.float32)
    h = 0.5

    cfg = ServeConfig(
        backend="pallas", method="sdkde",
        block_m=8, block_n=min(512, n), min_batch=64, max_batch=256,
        stream=True, staleness_budget=staleness_budget,
    )
    eng = ServeEngine(cfg)
    t0 = time.perf_counter()
    eng.register("stream", x, h=h)
    fit_s = time.perf_counter() - t0
    eng.query(QueryRequest(key="stream", points=y))   # warm the bucket

    append_s, appended = 0.0, 0
    for i in range(updates):
        fresh = np.asarray(
            mix.sample(jax.random.fold_in(key, 100 + i), batch), np.float32
        )
        t0 = time.perf_counter()
        eng.registry.slide("stream", fresh)     # append batch + evict oldest
        append_s += time.perf_counter() - t0
        appended += batch
        eng.query(QueryRequest(key="stream", points=y))
    st = eng.registry.get("stream").stream
    stale = eng.staleness_summary()
    emit("streaming_smoke", n=n, d=d, batch=batch, updates=updates,
         fit_s=round(fit_s, 3),
         appends_per_s=round(appended / append_s, 1),
         amortized_append_ms=round(1e3 * append_s / appended, 3),
         staleness_p50=stale.get("p50", 0), staleness_p99=stale.get("p99", 0),
         staleness_budget=staleness_budget, rebuilds=st.rebuilds)

    if verify:
        # flush before comparing: the engine may legally serve up to
        # staleness_budget generations behind the live reference set
        st.ensure(0)
        got = np.asarray(
            eng.query(QueryRequest(key="stream", points=y)).value)
        want = np.asarray(ref.sdkde_eval(st.x, y, h, block=1024))
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-6 * float(want.max()))
        emit("streaming_verify", n=n, d=d, live=st.n_live,
             rel_err=f"{float(np.abs(got - want).max() / want.max()):.2e}",
             status="ok")


def acceptance(
    n: int = 262144,
    d: int = 16,
    batch: int = 256,
    refit_n: int = 8192,
    target_ratio: float = 10.0,
    seed: int = 0,
) -> None:
    """The 256k×16-d amortized-append-vs-refit cell (CI gate ≥ 10×)."""
    mix = mixture_for_dim(d)
    key = jax.random.PRNGKey(seed)
    x = np.asarray(mix.sample(key, n), np.float32)
    fresh = np.asarray(mix.sample(jax.random.fold_in(key, 1), batch),
                       np.float32)
    h = 0.2

    # measured: the O(n·b·d) delta score pass at FULL scale (the sdkde
    # streaming append's dominant cost) — warm the jit on a small slice
    delta.append_delta(x[:4096], fresh, h)
    t0 = time.perf_counter()
    delta.append_delta(x, fresh, h)
    delta_s = time.perf_counter() - t0

    # measured: layout/column maintenance at full scale via a real 256k
    # stream (kde mode: same layout machinery, no O(n²) constructor)
    t0 = time.perf_counter()
    stream = StreamingSDKDE(x, h, method="kde", backend="pallas",
                            block_n=512, config=StreamConfig(slack=0.25))
    prep_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    stream.append(fresh)
    stream.flush()
    flush_s = time.perf_counter() - t0

    # modeled: the full refit this append replaces = the O(n²·d) score
    # pass (measured at refit_n, scaled quadratically) + the measured
    # re-cluster/re-prepare at full scale
    x0 = x[:refit_n]
    delta.initial_stats(x0[:2048], h)           # warm
    t0 = time.perf_counter()
    delta.initial_stats(x0, h)
    score_small_s = time.perf_counter() - t0
    refit_s = score_small_s * (n / refit_n) ** 2 + prep_s

    append_batch_s = delta_s + flush_s
    ratio = refit_s / append_batch_s
    emit("streaming_acceptance", n=n, d=d, batch=batch,
         delta_pass_ms=round(1e3 * delta_s, 1),
         flush_ms=round(1e3 * flush_s, 1),
         amortized_append_ms=round(1e3 * append_batch_s / batch, 3),
         refit_model_ms=round(1e3 * refit_s, 1),
         refit_measured_at=refit_n,
         prep_measured_ms=round(1e3 * prep_s, 1),
         modeled_speedup=round(ratio, 1),
         target_speedup=target_ratio,
         meets_target=bool(ratio >= target_ratio))
    if ratio < target_ratio:
        raise RuntimeError(
            f"streaming amortized append only {ratio:.1f}x below the "
            f"modeled full refit (target {target_ratio}x)"
        )


def main(
    smoke_n: int = 2048,
    smoke_d: int = 8,
    run_acceptance: bool = False,
    seed: int = 0,
) -> None:
    smoke(n=smoke_n, d=smoke_d, seed=seed)
    if run_acceptance:
        acceptance(seed=seed)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--d", type=int, default=8)
    ap.add_argument("--acceptance", action="store_true",
                    help="run the 256k×16-d amortized-vs-refit cell")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    main(smoke_n=args.n, smoke_d=args.d, run_acceptance=args.acceptance,
         seed=args.seed)
