"""KDE query-serving driver: fit once, answer ragged query traffic.

The density analogue of ``repro.launch.serve`` (the LM serving driver):
registers a dataset with the ``repro.serve`` engine (the one-time quadratic
debias pass — "prefill"), then serves a stream of variable-size query
batches (cheap GEMMs — "decode") and reports throughput, tail latency, and
shape-bucket cache efficiency.

  PYTHONPATH=src python -m repro.launch.serve_kde \\
      --backend pallas --method sdkde --n 8192 --d 8 --requests 64
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import obs
from repro.core import kde as ref
from repro.core.mixtures import mixture_for_dim
from repro.launch import enable_compile_cache
from repro.serve import QueryRequest, ServeConfig, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    # Plannable knobs default to None = "not supplied": under --plan auto
    # they stay unset so the planner fills them (a supplied flag always
    # wins — override precedence); under --plan off they fall back to the
    # historical CLI defaults below.
    ap.add_argument("--backend", default=None,
                    choices=["jnp", "pallas", "ring"])
    ap.add_argument("--method", default="sdkde",
                    choices=["kde", "sdkde", "laplace"])
    ap.add_argument("--n", type=int, default=8192, help="train samples")
    ap.add_argument("--d", type=int, default=8, help="dimension")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=512,
                    help="largest query batch in the traffic mix")
    ap.add_argument("--min-batch", type=int, default=32,
                    help="smallest shape bucket")
    block_arg = lambda s: s if s == "auto" else int(s)  # noqa: E731
    ap.add_argument("--block-m", type=block_arg, default=None,
                    help="Pallas row tile (int or 'auto' = autotuned)")
    ap.add_argument("--block-n", type=block_arg, default=None,
                    help="Pallas column tile (int or 'auto')")
    ap.add_argument("--precision", default=None,
                    choices=["f32", "bf16", "bf16x2", "rff"],
                    help="Pallas GEMM-operand tier (kernels/precision.py) "
                         "or 'rff' to pin the random-feature fast tier")
    ap.add_argument("--rff", default=None, choices=["auto", "on", "off"],
                    help="random-feature fast tier policy "
                         "(kernels/flash_rff.py; 'auto' fits lazily on "
                         "first cascade-eligible query)")
    ap.add_argument("--rff-features", type=int, default=None,
                    help="random Fourier features D (cos+sin pairs; "
                         "default 8192)")
    prune_arg = lambda s: s if s in ("auto", "off") else float(s)  # noqa: E731
    ap.add_argument("--prune", type=prune_arg, default=None,
                    help="cluster pruning: 'auto' (exact, epsilon=0, on for "
                         "large sets), 'off' (dense), or a per-point "
                         "contribution epsilon like 1e-9 "
                         "(kernels/spatial.py)")
    ap.add_argument("--plan", default="off", choices=["off", "auto"],
                    help="'auto' resolves unset knobs through the "
                         "repro.plan cost-model planner at fit time")
    ap.add_argument("--accuracy-target", type=float, default=None,
                    help="certified relative-error budget: the planner's "
                         "accuracy request AND the per-query accuracy-"
                         "cascade gate (queries whose RFF band fits are "
                         "answered at the fast tier, the rest escalate)")
    ap.add_argument("--plan-json", metavar="PATH", default=None,
                    help="write the resolved execution plan (request, "
                         "decision, resolved knobs) to PATH")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify", action="store_true",
                    help="cross-check a batch against the jnp reference")
    ap.add_argument("--stream", action="store_true",
                    help="register a streaming estimator (repro.stream) and "
                         "interleave appends/evictions with the query "
                         "traffic — the O(n·b·d) delta pass instead of a "
                         "refit per update")
    ap.add_argument("--staleness-budget", type=int, default=None,
                    help="generations a streamed query may lag live "
                         "(stream mode; 0 = always fresh)")
    ap.add_argument("--append-batch", type=int, default=64,
                    help="points per streaming append (stream mode)")
    ap.add_argument("--updates", type=int, default=16,
                    help="append/evict updates interleaved with the "
                         "traffic (stream mode)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through the resilient dispatch layer with "
                         "this many replica engines per shard (>1 enables "
                         "repro.serve.ResilientEngine)")
    ap.add_argument("--shards", type=int, default=2,
                    help="cluster-partitioned shards (resilient mode)")
    ap.add_argument("--chaos", default=None, metavar="MODES",
                    help="comma-separated fault modes to inject "
                         "(shard_kill,slow_shard,compile_fail,nan_poison,"
                         "staleness_blowout,client_burst,admit_stall); "
                         "shard_kill also schedules a sustained kill + "
                         "recovery window")
    ap.add_argument("--deadline-ms", type=float, default=5000.0,
                    help="per-request deadline (resilient and open-loop "
                         "modes)")
    ap.add_argument("--open-loop", action="store_true",
                    help="drive traffic open-loop through the admission "
                         "front end (repro.serve.AsyncFrontend): arrivals "
                         "are paced by --qps, not by answers, so overload "
                         "actually overloads; closed-loop stays the "
                         "default")
    ap.add_argument("--qps", type=float, default=0.0,
                    help="open-loop steady arrival rate in requests/s "
                         "(0 = auto: half the probed capacity)")
    ap.add_argument("--burst", type=float, default=4.0,
                    help="mid-run burst arrival rate, as a multiple of "
                         "the steady --qps (open-loop mode)")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="admission queue bound (open-loop mode)")
    ap.add_argument("--expect-shed", action="store_true",
                    help="exit nonzero unless the run shed at least one "
                         "request with a typed Overloaded AND every "
                         "request resolved (the CI overload smoke "
                         "contract)")
    ap.add_argument("--metrics-json", metavar="PATH", default=None,
                    help="write a telemetry document (metrics snapshot, "
                         "Prometheus exposition, trace events if --trace) "
                         "to PATH on exit")
    ap.add_argument("--trace", action="store_true",
                    help="record structured spans for every request "
                         "(repro.obs; also enables jax.profiler "
                         "annotations on real devices)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.trace:
        obs.configure(trace=True)

    mix = mixture_for_dim(args.d)
    key = jax.random.PRNGKey(args.seed)
    x = mix.sample(key, args.n)
    pool = mix.sample(jax.random.fold_in(key, 1), 4 * args.max_batch)

    # Historical CLI defaults, applied only when the planner is off; under
    # --plan auto an unsupplied knob stays at its ServeConfig default,
    # which the planner reads as "mine to fill".
    cli_defaults = dict(backend="jnp", block_m=32, block_n=512,
                        precision="f32", prune="auto", staleness_budget=2)
    knobs = {}
    for name in cli_defaults:
        v = getattr(args, name)
        if v is None and args.plan == "off":
            v = cli_defaults[name]
        if v is not None:
            knobs[name] = v
    if isinstance(knobs.get("block_n"), int):
        knobs["block_n"] = min(knobs["block_n"], args.n)
    for name in ("rff", "rff_features"):
        v = getattr(args, name)
        if v is not None:
            knobs[name] = v
    cfg = ServeConfig(
        method=args.method,
        min_batch=args.min_batch, max_batch=args.max_batch,
        stream=args.stream, plan=args.plan,
        accuracy_target=args.accuracy_target, **knobs,
    )

    if args.open_loop:
        if args.stream:
            ap.error("--open-loop and --stream are mutually exclusive "
                     "(drive streaming updates closed-loop)")
        _run_open_loop(args, cfg, x, pool)
        return

    if args.replicas > 1 or args.chaos:
        if args.stream:
            ap.error("--replicas/--chaos and --stream are mutually "
                     "exclusive (the resilient layer replicates static "
                     "engines)")
        _run_resilient(args, cfg, x, pool)
        return

    eng = ServeEngine(cfg)

    t0 = time.perf_counter()
    prep = eng.register("traffic", x)
    fit_ms = 1e3 * (time.perf_counter() - t0)
    rcfg = prep.config          # plan-resolved (== cfg when --plan off)
    print(f"registered: backend={rcfg.backend} method={args.method} "
          f"n={args.n} d={args.d} h={prep.h:.4f} precision={rcfg.precision} "
          f"prune={rcfg.prune} "
          f"fit={fit_ms:.0f}ms (debias amortized; never re-run per query)")
    if prep.plan is not None:
        print(f"plan: {prep.plan.plan_id} "
              f"(accuracy target {prep.plan.request.accuracy:g}, modeled "
              f"{prep.plan.modeled_cost_s * 1e6:.0f}us/pass, "
              f"bound {prep.plan.bound})")
    if prep.block_m is not None:
        print(f"launch tiles: block_m={prep.block_m} block_n={prep.block_n}"
              + (" (autotuned)" if "auto" in (args.block_m, args.block_n)
                 else ""))
    print(f"shape buckets: "
          f"{rcfg.bucket_sizes(prep.ring_size, prep.block_m)}")

    if args.plan_json:
        import json

        doc = {
            "request": (prep.plan.request.as_dict()
                        if prep.plan is not None else None),
            "plan": (prep.plan.as_dict()
                     if prep.plan is not None else None),
            "plan_id": (prep.plan.plan_id
                        if prep.plan is not None else None),
            "resolved": {
                "backend": rcfg.backend, "precision": rcfg.precision,
                "prune": rcfg.prune, "block_m": prep.block_m,
                "block_n": prep.block_n,
                "staleness_budget": rcfg.staleness_budget,
                "stream_background": rcfg.stream_background,
            },
        }
        with open(args.plan_json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"plan json -> {args.plan_json}")

    # Ragged traffic: log-uniform batch sizes, like real query fan-in.
    rng = np.random.default_rng(args.seed)
    sizes = np.exp(rng.uniform(np.log(1), np.log(args.max_batch),
                               args.requests)).astype(int).clip(1)
    update_every = (max(1, args.requests // max(args.updates, 1))
                    if args.stream else 0)
    # warm the largest bucket
    eng.query(QueryRequest(key="traffic", points=pool[: args.max_batch]))
    eng.latency.reset()
    append_s, n_updates = 0.0, 0
    rff_hits = escalated = 0
    t0 = time.perf_counter()
    for i, m in enumerate(sizes):
        if update_every and i % update_every == 0:
            # sliding-window update: append a fresh batch, evict the
            # oldest as many — the O(n·b·d) delta pass, never a refit
            fresh = mix.sample(jax.random.fold_in(key, 100 + i),
                               args.append_batch)
            ta = time.perf_counter()
            eng.registry.slide("traffic", fresh)
            append_s += time.perf_counter() - ta
            n_updates += 1
        off = int(rng.integers(0, pool.shape[0] - m))
        ans = eng.query(QueryRequest(key="traffic",
                                     points=pool[off:off + m]))
        rff_hits += ans.rff_hits
        escalated += ans.escalated
    wall = time.perf_counter() - t0

    s = eng.latency.summary()
    print(f"served {s.count} requests / {s.queries} queries in {wall:.2f}s: "
          f"{s.queries / wall:.0f} q/s  p50={s.p50_ms:.2f}ms "
          f"p99={s.p99_ms:.2f}ms")
    print(f"bucket cache: {eng.cache.hits} hits / {eng.cache.misses} misses "
          f"/ {eng.cache.evictions} evictions "
          f"({len(eng.cache)} resident executables)")
    if rff_hits or escalated:
        total = rff_hits + escalated
        print(f"cascade: {rff_hits}/{total} query rows answered at the "
              f"RFF tier ({rff_hits / total:.0%}), {escalated} escalated "
              f"to {rcfg.exact_precision}")
    if args.stream and n_updates:
        st = eng.registry.get("traffic").stream
        stale = eng.staleness_summary()
        appends = n_updates * args.append_batch
        print(f"streamed {n_updates} sliding-window updates "
              f"({appends} appends + {appends} evictions) in "
              f"{append_s:.2f}s: {appends / append_s:.0f} appends/s  "
              f"staleness p50={stale.get('p50', 0)} "
              f"p99={stale.get('p99', 0)} (budget "
              f"{rcfg.staleness_budget})  rebuilds={st.rebuilds}"
              + (f" (last: {st.last_rebuild_reason})"
                 if st.rebuilds else ""))

    if args.verify:
        import sys

        yv = pool[:256]
        if args.stream:
            # the engine may legally serve up to staleness_budget
            # generations behind live; force a flush so the verify query
            # and the live-set reference see the same generation
            eng.registry.get("traffic").stream.ensure(0)
        vans = eng.query(QueryRequest(key="traffic", points=yv))
        got = np.asarray(vans.value)
        ref_fn = {"kde": ref.kde_eval, "sdkde": ref.sdkde_eval,
                  "laplace": ref.laplace_kde_eval}[args.method]
        # stream mode: the reference is the *current* live set, not the
        # registered one — the whole point of the updates
        x_ref = (eng.registry.get("traffic").stream.x
                 if args.stream else x)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref_fn(x_ref, yv, prep.h, block=1024))
        cascaded = vans.rff_hits or rff_hits
        if cascaded:
            # cascade verification: the certified per-row bound must
            # dominate the realized error (flash_rff's tail-floored
            # relative metric), and the fast tier must actually answer
            from repro.kernels import flash_rff

            state = eng.registry.get("traffic").rff.state
            realized = flash_rff.realized_error(got, want, state.p_scale)
            bounds = np.asarray(vans.rel_err_bounds, np.float64)
            worst = float((realized - bounds).max())
            if worst > 1e-6:
                print(f"FAIL: realized error exceeds the certified band "
                      f"by {worst:.2e}", file=sys.stderr)
                sys.exit(1)
            hits = rff_hits + vans.rff_hits
            total = (rff_hits + escalated + vans.rff_hits
                     + vans.escalated)
            if hits == 0:
                print("FAIL: accuracy cascade engaged but zero rows "
                      "resolved at the RFF tier (loosen "
                      "--accuracy-target or raise --rff-features)",
                      file=sys.stderr)
                sys.exit(1)
            print(f"verify: certified bands dominate realized error "
                  f"(worst slack {-worst:.1e}); {hits}/{total} rows "
                  f"({hits / total:.0%}) answered at the RFF tier")
        else:
            # the f32 reference path; reduced tiers verify at their
            # documented accuracy bars (rtol + peak-relative atol for
            # deep-tail densities, see kernels/precision.py)
            tier = rcfg.exact_precision
            rtol = {"f32": 1e-5, "bf16": 5e-2, "bf16x2": 5e-4}[tier]
            atol_frac = {"f32": 1e-6, "bf16": 5e-3, "bf16x2": 1e-5}[tier]
            np.testing.assert_allclose(
                got, want, rtol=rtol,
                atol=atol_frac * float(np.max(np.abs(want))))
            print(f"verify: serve path matches jnp reference "
                  f"(rtol {rtol:g})")

    if args.metrics_json:
        import json

        events = eng.trace_events() if args.trace else []
        doc = {
            "args": {k: v for k, v in vars(args).items()
                     if isinstance(v, (int, float, str, bool, type(None)))},
            "metrics": eng.metrics(),
            "prometheus": obs.prometheus_text(),
            "trace_events": events,
        }
        with open(args.metrics_json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        n_metrics = len(doc["metrics"]["registry"])
        print(f"telemetry: {n_metrics} registry metrics"
              + (f", {len(events)} trace events" if args.trace else "")
              + f" -> {args.metrics_json}")


def _run_open_loop(args, cfg, x, pool) -> None:
    """Open-loop traffic through the admission front end.

    Arrivals follow a steady → burst → steady schedule paced by the
    wall clock, NOT by answers — the regime where the admission queue,
    backpressure, and shedding actually engage.  Reports the frontend's
    full shed/brownout ledger; with ``--expect-shed`` (the CI smoke
    contract) exits nonzero unless at least one request was shed with a
    typed ``Overloaded`` and every submitted request resolved.
    """
    import json
    import sys

    from repro.fault_injection import ChaosConfig, FaultInjector
    from repro import fault_injection
    from repro.serve import (AsyncFrontend, FrontendConfig, Overloaded,
                             ResilienceConfig, ResilientEngine, ServeError)

    resilient = args.replicas > 1
    if resilient:
        eng = ResilientEngine(cfg, ResilienceConfig(
            shards=args.shards, replicas=args.replicas,
            deadline_ms=args.deadline_ms, seed=args.seed, backoff_ms=1.0))
    else:
        eng = ServeEngine(cfg)
    t0 = time.perf_counter()
    prep = eng.register("traffic", x)
    h = getattr(prep, "h", None)
    print(f"registered: backend={cfg.backend} method={args.method} "
          f"n={args.n} d={args.d} h={h:.4f} "
          f"fit={1e3 * (time.perf_counter() - t0):.0f}ms"
          + (f" ({args.shards} shards x {args.replicas} replicas)"
             if resilient else ""))
    if args.chaos:
        print(f"chaos: {args.chaos} seed={args.seed}")

    rng = np.random.default_rng(args.seed)
    # warm the buckets the traffic will hit, then probe capacity with a
    # saturated all-at-once window if --qps was not pinned
    for b in cfg.bucket_sizes():
        eng.query(QueryRequest(key="traffic", points=pool[:b]))
    qps = args.qps
    if qps <= 0:
        probe = AsyncFrontend(eng, FrontendConfig(
            workers=1, max_queue=72, default_deadline_ms=60_000.0))
        t0 = time.perf_counter()
        fs = []
        for _ in range(64):
            m = int(rng.integers(1, max(2, args.max_batch // 8)))
            off = int(rng.integers(0, pool.shape[0] - m))
            fs.append(probe.submit(
                QueryRequest(key="traffic", points=pool[off:off + m])))
        probe.drain(timeout=60.0)
        probe.close()
        qps = 0.5 * 64 / (time.perf_counter() - t0)
        print(f"probed capacity: steady qps auto-set to {qps:.0f}")

    injector = None
    if args.chaos and not resilient:
        # installed AFTER the probe so chaos hits the measured run, not
        # the capacity measurement; the resilient engine installs its own
        injector = fault_injection.install(FaultInjector(
            ChaosConfig.from_modes(args.chaos, requests=args.requests,
                                   seed=args.seed)))
    fe = AsyncFrontend(eng, FrontendConfig(
        workers=1, max_queue=args.max_queue,
        default_deadline_ms=args.deadline_ms,
        rate=max(qps, 8.0), p99_slo_ms=args.deadline_ms))
    # steady for the first/last third, --burst x in the middle
    third = max(args.requests // 3, 1)
    futs, shed, answered, expired, degraded, browned = [], 0, 0, 0, 0, 0
    start = time.perf_counter()
    t_next = 0.0
    t0 = time.perf_counter()
    for i in range(args.requests):
        rate = qps * (args.burst if third <= i < 2 * third else 1.0)
        while (now := time.perf_counter() - start) < t_next:
            time.sleep(min(2e-3, t_next - now))
        t_next += 1.0 / rate
        m = int(rng.integers(1, max(2, args.max_batch // 8)))
        off = int(rng.integers(0, pool.shape[0] - m))
        try:
            futs.append(fe.submit(
                QueryRequest(key="traffic", points=pool[off:off + m])))
        except Overloaded:
            shed += 1
    fe.drain(timeout=60.0)
    wall = time.perf_counter() - t0
    unresolved = 0
    for f in futs:
        if not f.done():
            unresolved += 1
        elif f.exception() is None:
            answered += 1
            degraded += int(f.result().degraded)
            browned += int(f.result().browned)
        elif isinstance(f.exception(), Overloaded):
            shed += 1
        elif isinstance(f.exception(), ServeError):
            expired += 1
        else:
            raise f.exception()

    rep = fe.report()
    silent = fe.unaccounted() + unresolved
    print(f"open-loop: {args.requests} arrivals in {wall:.2f}s "
          f"(steady {qps:.0f} rps, burst x{args.burst:g}): "
          f"answered={answered} shed={shed} expired={expired} "
          f"degraded={degraded} browned={browned} silent={silent}")
    print(f"admission: state={rep['state']} "
          f"rejected_by={rep['rejected_by']} "
          f"admit_rate={rep['admit_rate']:.0f} rps "
          f"queue_wait p50={rep['queue_wait_ms']['p50']}ms "
          f"p99={rep['queue_wait_ms']['p99']}ms "
          f"transitions={rep['transitions']}")
    if injector is not None:
        print(f"faults injected: {injector.snapshot()}")

    if args.metrics_json:
        doc = {
            "args": {k: v for k, v in vars(args).items()
                     if isinstance(v, (int, float, str, bool, type(None)))},
            "frontend": rep,
            "outcomes": {"answered": answered, "shed": shed,
                         "expired": expired, "degraded": degraded,
                         "browned": browned, "silent": silent},
            "metrics": obs.metrics_snapshot(),
            "prometheus": obs.prometheus_text(),
            "trace_events": obs.trace_events() if args.trace else [],
        }
        with open(args.metrics_json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"telemetry: {len(doc['metrics'])} registry metrics "
              f"-> {args.metrics_json}")

    fe.close()
    if resilient:
        eng.close()
    if injector is not None:
        fault_injection.uninstall()
    if silent:
        print(f"FAIL: {silent} requests without a typed outcome",
              file=sys.stderr)
        sys.exit(1)
    if args.expect_shed and not shed:
        print("FAIL: --expect-shed but the run shed nothing (raise "
              "--burst or lower --max-queue)", file=sys.stderr)
        sys.exit(1)


def _run_resilient(args, cfg, x, pool) -> None:
    """Traffic loop through the resilient dispatch layer (optionally
    under chaos), reporting the full fault-tolerance story: retries,
    hedges, breaker states, fenced/readmitted hosts, degraded answers —
    and a nonzero exit if any query was dropped under chaos."""
    import json
    import sys

    from repro.fault_injection import ChaosConfig
    from repro.serve import (ResilienceConfig, ResilientEngine, ServeError)

    replicas = max(args.replicas, 2)   # chaos without a sibling = drops
    chaos = (ChaosConfig.from_modes(args.chaos, requests=args.requests,
                                    seed=args.seed)
             if args.chaos else None)
    rcfg = ResilienceConfig(
        shards=args.shards, replicas=replicas,
        deadline_ms=args.deadline_ms, seed=args.seed, backoff_ms=1.0,
    )
    eng = ResilientEngine(cfg, rcfg, chaos=chaos)
    t0 = time.perf_counter()
    table = eng.register("traffic", x)
    fit_ms = 1e3 * (time.perf_counter() - t0)
    print(f"registered: backend={cfg.backend} method={args.method} "
          f"n={args.n} d={args.d} h={table.h:.4f} -> "
          f"{table.n_shards} shards x {replicas} replicas "
          f"(shard sizes {table.shard_n}) fit={fit_ms:.0f}ms")
    if chaos is not None:
        active = [m for m in ("shard_kill", "slow_shard", "compile_fail",
                              "nan_poison", "staleness_blowout")
                  if getattr(chaos, m) > 0 or any(
                      e.kind == m for e in chaos.events)]
        windows = [f"{e.kind}@s{e.shard}r{e.replica}[{e.start},{e.stop})"
                   for e in chaos.events]
        print(f"chaos: {','.join(active)} seed={chaos.seed} "
              f"events={windows}")

    rng = np.random.default_rng(args.seed)
    sizes = np.exp(rng.uniform(np.log(1), np.log(args.max_batch),
                               args.requests)).astype(int).clip(1)
    degraded = errors = rff_hits = 0
    t0 = time.perf_counter()
    for m in sizes:
        off = int(rng.integers(0, pool.shape[0] - m))
        try:
            ans = eng.query(QueryRequest(key="traffic",
                                         points=pool[off:off + m]))
            degraded += int(ans.degraded)
            rff_hits += ans.rff_hits
        except ServeError as e:
            errors += 1
            print(f"  shed: {type(e).__name__}: {e}")
    wall = time.perf_counter() - t0

    s = eng.latency.summary()
    st = eng.stats
    print(f"served {s.count} requests / {s.queries} queries in {wall:.2f}s: "
          f"{s.queries / wall:.0f} q/s  p50={s.p50_ms:.2f}ms "
          f"p99={s.p99_ms:.2f}ms")
    print(f"resilience: retries={st['retries']} hedges={st['hedges']} "
          f"(won {st['hedge_wins']}) fenced={st['fenced']} "
          f"probes={st['probes']} readmits={st['readmits']} "
          f"degraded={degraded} shed={st['shed']} "
          f"dropped={st['dropped']}"
          + (f" rff_rows={rff_hits}" if rff_hits else ""))
    open_brk = [k for k, v in eng.breaker_states().items() if v != "closed"]
    if open_brk:
        print(f"breakers not closed: {open_brk}")
    if eng.injector is not None:
        print(f"faults injected: {eng.injector.snapshot()}")

    if args.verify:
        # post-traffic (outside any scheduled chaos window): the resilient
        # answer must match the full-data reference exactly — and must NOT
        # be degraded, so disallow uncertified fallbacks here
        yv = pool[:256]
        ans = eng.query(QueryRequest(key="traffic", points=yv,
                                     allow_degraded=False,
                                     deadline_s=60.0))
        ref_fn = {"kde": ref.kde_eval, "sdkde": ref.sdkde_eval,
                  "laplace": ref.laplace_kde_eval}[args.method]
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref_fn(x, yv, table.h, block=1024))
        rtol = {"f32": 1e-5, "bf16": 5e-2,
                "bf16x2": 5e-4}[cfg.exact_precision]
        np.testing.assert_allclose(
            np.asarray(ans.value), want, rtol=rtol,
            atol=1e-6 * float(np.max(np.abs(want))))
        print(f"verify: resilient path matches full-data jnp reference "
              f"(rtol {rtol:g})")

    if args.metrics_json:
        doc = {
            "args": {k: v for k, v in vars(args).items()
                     if isinstance(v, (int, float, str, bool, type(None)))},
            "metrics": eng.metrics(),
            "prometheus": obs.prometheus_text(),
            "trace_events": obs.trace_events() if args.trace else [],
        }
        with open(args.metrics_json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"telemetry: {len(doc['metrics']['registry'])} registry "
              f"metrics -> {args.metrics_json}")

    eng.close()
    if st["dropped"]:
        print(f"FAIL: {st['dropped']} dropped queries under "
              f"{'chaos' if chaos else 'steady state'}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
