#!/usr/bin/env python3
"""Bring-up smoke of the SD-KDE serving path on a TPU.

Drives the system the way a user does (``ServeEngine`` + ``QueryRequest``,
and the ``SDKDE`` estimator) at the paper's sizes, with compiled Pallas
kernels, and checks every answer against the plain ``core/kde.py``
reference at ``jax.default_matmul_precision("highest")``:

  python chip_smoke.py               # one chip: f32 GEMM precision probe,
                                     # Table 1 serving, streaming, RFF cascade
  python chip_smoke.py --paper-1m    # + the paper's 1M x 16-d fit and
                                     # 131k-query evaluation
  python chip_smoke.py --chips 4     # only the ring backend across four
                                     # chips: SDKDE at 64k x 16-d, then
                                     # ServeEngine at 1M x 16-d, each vs
                                     # the one-device reference

Exits nonzero when JAX finds no TPU, or when any phase fails.  On success
the last line of stdout is one JSON object naming the device.  Everything
runs in this one process (a chip belongs to one process at a time).
Times printed here are bring-up observations, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: Accuracy bars per serving tier against the f32 reference: relative
#: tolerance, and an absolute floor as a fraction of the peak density
#: (deep-tail densities) — the bars ``serve_kde --verify`` applies.
TIER_RTOL = {"f32": 1e-5, "bf16": 5e-2, "bf16x2": 5e-4}
TIER_ATOL_FRAC = {"f32": 1e-6, "bf16": 5e-3, "bf16x2": 1e-5}

#: (train points, queries, dimension) of the paper's two workloads: the
#: Figure 1 / Table 1 scale (32k train points, n/8 queries), and the
#: 1M-sample 16-d task evaluated on 131k queries (sections 1 and 7).
TABLE1 = (32768, 4096, 16)
PAPER_1M = (1048576, 131072, 16)


class SmokeFailure(RuntimeError):
    """A phase's check failed; uncaught, it ends the run nonzero."""


def require(ok: bool, msg: str) -> None:
    """A phase check that ``python -O`` cannot strip."""
    if not ok:
        raise SmokeFailure(msg)


class Compiles:
    """Counts XLA programs compiled (or loaded from the persistent cache)
    in this process, through JAX's monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def pallas_traces(mode: str) -> float:
    from repro import obs

    return obs.counter("kernels.pallas_traces",
                       labels={"mode": mode}).value


def sample(d: int, n: int, m: int, seed: int):
    """Train set and query pool from the paper's mixture for dimension d."""
    import jax

    from repro.core.mixtures import mixture_for_dim

    mix = mixture_for_dim(d)
    key = jax.random.PRNGKey(seed)
    return mix.sample(key, n), mix.sample(jax.random.fold_in(key, 1), m)


def reference_sdkde(x, y, h, *, row_chunk: int = 32768):
    """SD-KDE densities at ``y`` from ``core/kde.py`` at highest matmul
    precision.  The score pass runs in row chunks so the reference fits
    one device at any n; each chunk is ``kde.empirical_score`` itself."""
    import jax
    import jax.numpy as jnp

    from repro.core import kde as ref

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(x, jnp.float32)
        score = jnp.concatenate([
            ref.empirical_score(x[i:i + row_chunk], x, h)
            for i in range(0, x.shape[0], row_chunk)])
        x_sd = x + 0.5 * h * h * score
        return np.asarray(ref.kde_eval(x_sd, jnp.asarray(y), h), np.float64)


def check_close(what: str, got, want, tier: str) -> float:
    """Assert ``got`` matches ``want`` at the tier's bar; returns the max
    relative error over rows above the absolute floor."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    require(got.shape == want.shape,
            f"{what}: shape {got.shape} vs reference {want.shape}")
    require(bool(np.isfinite(got).all()), f"{what}: non-finite densities")
    atol = TIER_ATOL_FRAC[tier] * float(np.max(np.abs(want)))
    np.testing.assert_allclose(got, want, rtol=TIER_RTOL[tier], atol=atol,
                               err_msg=what)
    big = np.abs(want) > atol
    rel = np.abs(got - want)[big] / np.abs(want)[big]
    return float(rel.max()) if rel.size else 0.0


def phase_precision(seed: int, *, m=512, k=1024, bar=1e-6):
    """What an f32 GEMM gets on this device: XLA and Mosaic at their
    default precision, and at the precision the repo asks for
    (``Precision.HIGHEST``; ``precision.dot_f32`` inside a kernel), each
    against an f64 product on the host.  Errors are max abs error over
    max |ab|; the repo's two must stay under ``bar``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    from repro.kernels import compiler_params, launch_interpret
    from repro.kernels.precision import dot_f32

    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (m, k), jnp.float32)
    b = jax.random.normal(kb, (k, m), jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    scale = float(np.max(np.abs(exact)))

    def in_kernel(dot):
        def body(a_ref, b_ref, o_ref):
            o_ref[...] = dot(a_ref[...], b_ref[...])
        return pl.pallas_call(
            body, out_shape=jax.ShapeDtypeStruct((m, m), jnp.float32),
            interpret=launch_interpret(None),
            compiler_params=compiler_params())(a, b)

    def err(c):
        return float(np.max(np.abs(np.asarray(c, np.float64) - exact))) / scale

    errs = {
        "xla default": err(jnp.dot(a, b)),
        "xla HIGHEST": err(jnp.dot(a, b, precision=lax.Precision.HIGHEST)),
        "mosaic default": err(in_kernel(
            lambda x, y: jnp.dot(x, y, preferred_element_type=jnp.float32))),
        "mosaic dot_f32": err(in_kernel(dot_f32)),
    }
    print(f"precision: f32 ({m}x{k})@({k}x{m}), max abs err / max |ab| "
          + "; ".join(f"{n} {e:.3e}" for n, e in errs.items()))
    for name in ("xla HIGHEST", "mosaic dot_f32"):
        require(errs[name] <= bar,
                f"precision: {name} error {errs[name]:.3e} over {bar:g}")


def phase_table1(seed: int, compiles: Compiles, *, n=None, m=None, d=None,
                 sizes=None):
    """Paper Table 1 deployment through ServeEngine: auto tiles, auto
    pruning, ragged requests at the f32 default and bf16/bf16x2 pins."""
    import jax

    from repro import obs
    from repro.serve import QueryRequest, ServeConfig, ServeEngine

    n, m, d = n or TABLE1[0], m or TABLE1[1], d or TABLE1[2]
    x, pool = sample(d, n, m, seed)
    sizes = sizes or (1, 37, 300, 1000, m)
    eng = ServeEngine(ServeConfig(backend="pallas", method="sdkde",
                                  block_m="auto", block_n="auto",
                                  prune="auto", max_batch=m))
    t0 = time.perf_counter()
    prep = eng.register("table1", x)
    fit_s = time.perf_counter() - t0
    print(f"table1: n={n} d={d} queries={m} h={prep.h:.5f} "
          f"fit={fit_s:.2f}s tiles=({prep.block_m}, {prep.block_n}) "
          f"prune={prep.config.prune} buckets="
          f"{prep.config.bucket_sizes(1, prep.block_m)}")
    want = reference_sdkde(x, pool, prep.h)
    for pin in (None, "bf16", "bf16x2"):
        tier = pin or "f32"
        reqs = [QueryRequest(key="table1", points=pool[:k], precision=pin)
                for k in sizes]
        c0 = compiles.n
        for r in reqs:                       # warm every bucket + tier
            jax.block_until_ready(eng.query(r).value)
        warm = compiles.n - c0
        eng.latency.reset()
        c1 = compiles.n
        worst = 0.0
        for r, k in zip(reqs, sizes):
            ans = eng.query(r)
            require(ans.tier == tier,
                    f"table1: answered at {ans.tier}, asked for {tier}")
            worst = max(worst, check_close(
                f"table1 {tier} m={k}", ans.value, want[:k], tier))
        s = eng.latency.summary()
        print(f"table1 {tier}: verified {len(sizes)} requests "
              f"(max rel err {worst:.3e}; bar rtol {TIER_RTOL[tier]:g} + "
              f"atol {TIER_ATOL_FRAC[tier]:g} x peak); "
              f"p50={s.p50_ms:.3f}ms p99={s.p99_ms:.3f}ms; compiles "
              f"warm={warm} timed={compiles.n - c1}")
    launches = {k: obs.counter("kernels.prune.launches",
                               labels={"kind": k}).value
                for k in ("score", "kde")}
    print(f"table1: pruned launches {launches}")


def phase_stream(seed: int, *, n=None, d=None, slide=512, m=256):
    """Streaming estimator at the Table 1 size: one slide() of 512 points,
    then a verified query against the live set."""
    import jax

    from repro.core.mixtures import mixture_for_dim
    from repro.serve import QueryRequest, ServeConfig, ServeEngine

    n, d = n or TABLE1[0], d or TABLE1[2]
    x, pool = sample(d, n, m, seed)
    eng = ServeEngine(ServeConfig(backend="pallas", method="sdkde",
                                  block_m="auto", block_n="auto",
                                  stream=True))
    t0 = time.perf_counter()
    prep = eng.register("stream", x)
    fit_s = time.perf_counter() - t0
    fresh = mixture_for_dim(d).sample(
        jax.random.fold_in(jax.random.PRNGKey(seed), 100), slide)
    t0 = time.perf_counter()
    eng.registry.slide("stream", fresh)
    st = prep.stream
    st.ensure(0)                             # verify the live generation
    slide_s = time.perf_counter() - t0
    ans = eng.query(QueryRequest(key="stream", points=pool))
    want = reference_sdkde(st.x, pool, prep.h)
    worst = check_close("stream f32", ans.value, want, "f32")
    print(f"stream: n={n} d={d} fit={fit_s:.2f}s slide({slide})+flush="
          f"{slide_s:.2f}s live={st.x.shape[0]} rebuilds={st.rebuilds}; "
          f"verified {m} rows vs live-set reference (max rel err "
          f"{worst:.3e})")


def phase_cascade(seed: int, *, n=262144, d=2, sizes=(64, 500, 2048)):
    """RFF cascade at a 1e-2 target: every answered row's certified band
    dominates its realized error, and some rows resolve at the RFF tier."""
    from repro.kernels import flash_rff
    from repro.serve import QueryRequest, ServeConfig, ServeEngine

    x, pool = sample(d, n, max(sizes), seed)
    eng = ServeEngine(ServeConfig(backend="pallas", method="sdkde",
                                  block_m="auto", block_n="auto",
                                  rff="on", accuracy_target=1e-2))
    t0 = time.perf_counter()
    prep = eng.register("cascade", x)
    fit_s = time.perf_counter() - t0
    want = reference_sdkde(x, pool, prep.h)
    p_scale = prep.rff.state.p_scale
    hits = total = 0
    worst = -np.inf
    for k in sizes:
        ans = eng.query(QueryRequest(key="cascade", points=pool[:k]))
        got = np.asarray(ans.value, np.float64)
        require(bool(np.isfinite(got).all()),
                "cascade: non-finite densities")
        realized = flash_rff.realized_error(got, want[:k], p_scale)
        bounds = np.asarray(ans.rel_err_bounds, np.float64)
        worst = max(worst, float((realized - bounds).max()))
        hits += ans.rff_hits
        total += ans.rff_hits + ans.escalated
    require(worst <= 1e-6, f"cascade: realized error exceeds the "
            f"certified band by {worst:.2e}")
    require(hits > 0, "cascade: no row resolved at the RFF tier")
    print(f"cascade: n={n} d={d} fit={fit_s:.2f}s; {hits}/{total} rows at "
          f"the RFF tier; certified bands dominate realized error (worst "
          f"slack {-worst:.3e})")


def phase_paper_1m(seed: int, *, n=None, m=None, d=None, verify=1024):
    """Paper §7: a 1M x 16-d SDKDE fit and evaluation on 131k queries
    through the estimator API; the verified rows are spread evenly over
    all queries, so every launch the evaluation splits into is checked."""
    from repro.core.estimator import SDKDE, EstimatorConfig

    n, m, d = n or PAPER_1M[0], m or PAPER_1M[1], d or PAPER_1M[2]
    x, y = sample(d, n, m, seed)
    est = SDKDE(config=EstimatorConfig(backend="pallas"))
    t0 = time.perf_counter()
    est.fit(x).x_sd.block_until_ready()
    fit_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    dens = np.asarray(est.evaluate(y))
    eval_s = time.perf_counter() - t0
    require(dens.shape == (m,) and bool(np.isfinite(dens).all()),
            f"paper_1m: shape {dens.shape} or non-finite densities")
    rows = np.arange(verify) * (m // verify)
    t0 = time.perf_counter()
    want = reference_sdkde(x, np.asarray(y)[rows], est.h)
    ref_s = time.perf_counter() - t0
    worst = check_close("paper_1m f32", dens[rows], want, "f32")
    print(f"paper_1m: n={n} d={d} queries={m} fit={fit_s:.2f}s "
          f"evaluate={eval_s:.2f}s reference({verify} rows, stride "
          f"{m // verify})={ref_s:.2f}s; "
          f"max rel err {worst:.3e}")


def phase_ring_estimator(seed: int, *, n=65536, d=16, m=4096):
    """SDKDE(backend="ring"): the Flash kernels on every device, rows
    sharded (pruned at this n), every answer verified."""
    import jax

    from repro.core.estimator import SDKDE, EstimatorConfig

    x, y = sample(d, n, m, seed)
    est = SDKDE(config=EstimatorConfig(backend="ring"))
    t0 = time.perf_counter()
    est.fit(x).x_sd.block_until_ready()
    fit_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    dens = np.asarray(est.evaluate(y))
    eval_s = time.perf_counter() - t0
    want = reference_sdkde(x, y, est.h)
    worst = check_close("ring estimator f32", dens, want, "f32")
    print(f"ring estimator: n={n} d={d} queries={m} devices="
          f"{len(jax.devices())} fit={fit_s:.2f}s evaluate={eval_s:.2f}s "
          f"(first calls, compile included); max rel err {worst:.3e}")


def phase_ring(seed: int, *, n=1048576, d=16, m=1024):
    """The multi-chip SD-KDE path: ServeEngine(backend="ring") over every
    device, verified against the one-device reference."""
    from repro.serve import QueryRequest, ServeConfig, ServeEngine

    x, pool = sample(d, n, m, seed)
    eng = ServeEngine(ServeConfig(backend="ring", method="sdkde",
                                  max_batch=m))
    t0 = time.perf_counter()
    prep = eng.register("ring", x)
    prep.x_sharded.block_until_ready()
    fit_s = time.perf_counter() - t0
    shards = [tuple(s.data.shape) for s in prep.x_sharded.addressable_shards]
    print(f"ring: n={n} d={d} devices={prep.ring_size} shard shapes "
          f"{shards} fit={fit_s:.2f}s")
    t0 = time.perf_counter()
    ans = eng.query(QueryRequest(key="ring", points=pool))
    got = np.asarray(ans.value)
    query_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = reference_sdkde(x, pool, prep.h)
    ref_s = time.perf_counter() - t0
    worst = check_close("ring f32", got, want, "f32")
    print(f"ring: {m} query rows in {query_s:.2f}s (first call, compile "
          f"included); reference {ref_s:.2f}s; verified vs one-device "
          f"reference (max rel err {worst:.3e})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run only the ring backend across four chips")
    ap.add_argument("--paper-1m", action="store_true",
                    help="add the paper's 1M x 16-d fit + 131k queries")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.launch import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"nothing run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} cache={cache}")
    compiles = Compiles()

    t_all = time.perf_counter()
    if args.chips == 4:
        phases = [("ring_estimator", lambda: phase_ring_estimator(args.seed)),
                  ("ring", lambda: phase_ring(args.seed))]
    else:
        phases = [("precision", lambda: phase_precision(args.seed)),
                  ("table1", lambda: phase_table1(args.seed, compiles)),
                  ("stream", lambda: phase_stream(args.seed)),
                  ("cascade", lambda: phase_cascade(args.seed))]
        if args.paper_1m:
            phases.append(("paper_1m", lambda: phase_paper_1m(args.seed)))
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        print(f"phase {name}: ok in {time.perf_counter() - t0:.1f}s")
    interpreted = pallas_traces("interpret")
    compiled = pallas_traces("compiled")
    print(f"pallas kernel programs: compiled={compiled:g} "
          f"interpreted={interpreted:g}; XLA programs={compiles.n}; "
          f"total {time.perf_counter() - t_all:.1f}s")
    require(interpreted == 0, "a Pallas kernel ran in interpret mode")
    if args.chips == 1:
        require(compiled > 0, "no compiled Pallas kernel was launched")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
