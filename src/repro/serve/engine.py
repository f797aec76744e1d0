"""The serving engine: registry + micro-batcher + backend dispatch.

Request lifecycle (see docs/architecture.md):

  register(key, x)         — one-time: debias (sdkde), precompute layouts,
                             cache, optionally fit the RFF fast tier
  query(QueryRequest)      — resolve the tier (request pin > explicit config
                             > planner), route through the accuracy cascade
                             when a target gates it, pad to a shape bucket,
                             run the bucket executable, return an Answer
                             with per-row certified bounds
  query_many([requests…])  — coalesce several ragged requests into ONE padded
                             dispatch, then split the fused Answer back out

Legacy ``query(key, y)`` / ``query_many(key, [y…])`` signatures still work
behind ``DeprecationWarning`` shims and return bare density arrays.

All three backends dispatch through the same bucket executables, built
lazily per (estimator, bucket) and kept in a small LRU:

  * ``jnp``    — streaming-GEMM reference (repro.core.kde), any hardware
  * ``pallas`` — prepared fast path (repro.kernels.ops.flash_kde_prepared):
                 train tensors transposed/normed once at fit, queries arrive
                 pre-padded so the per-call wrapper work disappears
  * ``ring``   — mesh-sharded evaluation (repro.distributed.ring) against
                 the fit-time sharded train placement
"""

from __future__ import annotations

import time
from collections import deque
from typing import Deque, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import fault_injection, obs
from repro.serve import cascade
from repro.serve.api import (RFF_TIER, Answer, QueryRequest, resolve_tier,
                             warn_legacy)
from repro.serve.batching import ShapeBucketCache, coalesce, pad_queries, split
from repro.serve.config import ServeConfig
from repro.serve.errors import BadRequest, DeadlineExceeded
from repro.serve.registry import EstimatorRegistry, PreparedEstimator
from repro.serve.stats import LatencyRecorder


class ServeEngine:
    def __init__(
        self,
        config: ServeConfig | None = None,
        registry: EstimatorRegistry | None = None,
    ):
        if config is None:
            config = registry.config if registry is not None else ServeConfig()
        self.config = config
        self.registry = registry or EstimatorRegistry(config)
        self.cache = ShapeBucketCache(config.cache_buckets)
        self.latency = LatencyRecorder()
        # generations-behind-live of recent streaming dispatches (staleness
        # telemetry; a budget of 0 pins this to all-zeros).  Bounded so a
        # long-lived server doesn't grow it with request count.
        self.staleness_log: Deque[int] = deque(maxlen=8192)

    # -- fit path --------------------------------------------------------

    def register(
        self,
        key: str,
        x: jnp.ndarray,
        h: Optional[float] = None,
        config: ServeConfig | None = None,
        refit: bool = False,
        prewarm: Optional[bool] = None,
    ) -> PreparedEstimator:
        """Fit (or fetch) an estimator.  ``prewarm=None`` follows the
        resolved execution plan: plan-routed estimators build their
        chosen bucket executable at register time so the first real
        request never pays the compile; explicitly pass False to defer."""
        prep = self.registry.fit(key, x, h, config=config, refit=refit)
        if refit:
            self.cache.invalidate(lambda k: k[0] == key)
        if prewarm is None:
            prewarm = prep.plan is not None and getattr(
                prep.plan, "prewarm", False)
        if prewarm:
            self.prewarm(key)
        return prep

    def prewarm(self, key: str, all_buckets: bool = False) -> int:
        """Build bucket executables ahead of traffic through the normal
        LRU path (so prewarmed programs are the very ones requests hit).

        Default warms the largest bucket — the one every oversize batch
        chunks at; ``all_buckets`` walks the whole ladder.  Returns the
        number of buckets warmed.  Prewarm dispatches are not recorded as
        served latency."""
        prep = self.registry.get(key)
        cfg = prep.config
        tier = cfg.exact_precision
        sizes = cfg.bucket_sizes(prep.ring_size, prep.block_m)
        targets = sizes if all_buckets else sizes[-1:]
        with obs.span("plan.prewarm", key=key, buckets=len(targets),
                      plan=getattr(prep.plan, "plan_id", "")):
            for bucket in targets:
                snap = (prep.stream.ensure(cfg.staleness_budget)
                        if prep.stream is not None else None)
                y = jnp.zeros((bucket, prep.d), jnp.float32)
                jax.block_until_ready(
                    self._run_bucket(prep, y, tier, snap))
        obs.counter("plan.prewarms",
                    "bucket executables built ahead of traffic",
                    ).inc(len(targets))
        return len(targets)

    # -- query path ------------------------------------------------------

    def query(self, request: Union[QueryRequest, str],
              y: Optional[jnp.ndarray] = None,
              precision: Optional[str] = None,
              deadline_s: Optional[float] = None,
              ) -> Union[Answer, jnp.ndarray]:
        """Serve one request.

        Typed API: pass a :class:`~repro.serve.api.QueryRequest`, receive
        an :class:`~repro.serve.api.Answer`.  This is the only path that
        routes through the accuracy cascade — a request carrying an
        ``accuracy_target`` (or a config-level default) first runs the
        RFF fast tier and escalates only the rows whose certified band
        misses the target; ``request.precision`` pins a tier outright
        (precedence: request pin > explicit config > planner).  The
        request's ``deadline_s`` is *relative* seconds from admission.

        Legacy API (deprecated): ``query(key, y, precision=, deadline_s=)``
        returns the bare densities array; its ``deadline_s`` is an
        absolute ``time.monotonic()`` instant, and it never engages the
        cascade (unless the config's default tier itself is ``"rff"``),
        exactly as before the typed API existed.

        Either way, a request past its deadline raises
        ``DeadlineExceeded`` before any compute, and an answer that
        completes past it raises too — a late density is not an answer.
        """
        if isinstance(request, QueryRequest):
            if y is not None or precision is not None \
                    or deadline_s is not None:
                raise BadRequest(
                    "pass either a QueryRequest or the legacy "
                    "(key, y, ...) arguments, not both")
            return self._query_request(request)
        warn_legacy("ServeEngine.query(key, y, ...)",
                    "ServeEngine.query(QueryRequest(...)) -> Answer")
        req = QueryRequest(key=request, points=y, precision=precision)
        ans = self._query_request(req, deadline_abs=deadline_s, legacy=True)
        return ans.value

    def query_many(
        self,
        requests: Union[Sequence[QueryRequest], str],
        batches: Optional[Sequence[jnp.ndarray]] = None,
        precision: Optional[str] = None,
        deadline_s: Optional[float] = None,
    ) -> Union[List[Answer], List[jnp.ndarray]]:
        """Coalesce several ragged requests into one padded dispatch.

        Typed API: a non-empty sequence of :class:`QueryRequest` sharing
        one key and one precision pin (coalesce upstream per
        ``(key, precision)`` — the async front end already does) returns
        one :class:`Answer` per request, each carrying its own slice of
        the fused per-row certified bounds and cascade counters.
        Members' ``accuracy_target`` may differ: the cascade gates row
        ranges independently, and members without a target (and no
        config default) always resolve at the exact tier.  The fused
        dispatch runs under the *latest* member deadline — per-member
        lateness is the upstream batcher's call.

        Legacy API (deprecated): ``query_many(key, batches, ...)`` with
        an absolute monotonic ``deadline_s`` returns bare density arrays.
        """
        if batches is None and not isinstance(requests, str):
            reqs = list(requests)
            if not reqs or not all(isinstance(r, QueryRequest)
                                   for r in reqs):
                raise BadRequest(
                    "query_many takes a non-empty sequence of QueryRequest "
                    "(or the legacy key + batches arguments)")
            key, pin = reqs[0].key, reqs[0].precision
            for r in reqs[1:]:
                if r.key != key or r.precision != pin:
                    raise BadRequest(
                        "fused query_many requests must share one key and "
                        "one precision pin — coalesce upstream per "
                        "(key, precision)")
            prep = self.registry.get(key)
            fused, sizes = coalesce([
                jnp.atleast_2d(jnp.asarray(r.points, jnp.float32))
                for r in reqs])
            self._check_query(prep, fused)
            now = time.monotonic()
            member_dl = [now + r.deadline_s for r in reqs
                         if r.deadline_s is not None]
            fused_dl = max(member_dl) if member_dl else None
            self._check_deadline(key, fused_dl, phase="dispatch")
            with obs.span("serve.request", key=key,
                          rows=int(fused.shape[0]), requests=len(sizes)):
                t0 = time.perf_counter()
                ans, esc_rows = self._serve(prep, fused, reqs, sizes)
                ans.value = jax.block_until_ready(fault_injection.poison(
                    "serve.result", ans.value))
                dt = time.perf_counter() - t0
            self._check_deadline(key, fused_dl, phase="answer")
            self._note_served(dt, fused.shape[0], len(sizes))
            return self._split_answer(ans, reqs, sizes, esc_rows, dt)
        warn_legacy(
            "ServeEngine.query_many(key, batches, ...)",
            "ServeEngine.query_many([QueryRequest, ...]) -> [Answer, ...]")
        key = requests
        reqs = [QueryRequest(key=key, points=b, precision=precision)
                for b in batches]
        prep = self.registry.get(key)
        fused, sizes = coalesce([
            jnp.atleast_2d(jnp.asarray(b, jnp.float32)) for b in batches])
        self._check_query(prep, fused)
        self._check_deadline(key, deadline_s, phase="dispatch")
        with obs.span("serve.request", key=key, rows=int(fused.shape[0]),
                      requests=len(sizes)):
            t0 = time.perf_counter()
            ans, _ = self._serve(prep, fused, reqs, sizes, legacy=True)
            dens = jax.block_until_ready(fault_injection.poison(
                "serve.result", ans.value))
            dt = time.perf_counter() - t0
        self._check_deadline(key, deadline_s, phase="answer")
        self._note_served(dt, fused.shape[0], len(sizes))
        return split(dens, sizes)

    def _query_request(self, req: QueryRequest, *,
                       deadline_abs: Optional[float] = None,
                       legacy: bool = False) -> Answer:
        prep = self.registry.get(req.key)
        y = jnp.atleast_2d(jnp.asarray(req.points, jnp.float32))
        self._check_query(prep, y)
        if deadline_abs is None and req.deadline_s is not None:
            deadline_abs = time.monotonic() + req.deadline_s
        self._check_deadline(req.key, deadline_abs, phase="dispatch")
        with obs.span("serve.request", key=req.key, rows=int(y.shape[0]),
                      requests=1):
            t0 = time.perf_counter()
            ans, _ = self._serve(prep, y, [req], [int(y.shape[0])],
                                 legacy=legacy)
            ans.value = jax.block_until_ready(fault_injection.poison(
                "serve.result", ans.value))
            dt = time.perf_counter() - t0
        self._check_deadline(req.key, deadline_abs, phase="answer")
        self._note_served(dt, y.shape[0], 1)
        ans.latency_s = dt
        return ans

    def _serve(self, prep: PreparedEstimator, y: jnp.ndarray,
               reqs: Sequence[QueryRequest], sizes: Sequence[int], *,
               legacy: bool = False):
        """Resolve the tier, route through the cascade when engaged, and
        assemble one fused :class:`Answer` for ``y`` (per-request slicing
        is the caller's job).  Returns ``(answer, esc_rows)`` where
        ``esc_rows`` marks the fused rows that escalated."""
        cfg = prep.config
        tier, overrode = resolve_tier(reqs[0].precision, cfg.precision,
                                      prep.plan)
        if overrode:
            obs.counter(
                "serve.pin_overrides_plan",
                "requests whose precision pin overrode the planner tier",
            ).inc()
        m = int(y.shape[0])
        target = None if legacy else self._targets(cfg, reqs, sizes)
        snap = (prep.stream.ensure(cfg.staleness_budget)
                if prep.stream is not None else None)
        res = None
        # an explicit exact-tier pin skips the fast tier entirely — the
        # pin IS the routing decision; only unpinned requests (or an
        # "rff" pin) consult the cascade gate
        if tier == RFF_TIER or (not legacy and reqs[0].precision is None
                                and cascade.engaged(cfg, prep, tier, target)):
            res = cascade.run(self, prep, y, tier, target, snap=snap)
            if res is None and tier == RFF_TIER:
                raise BadRequest(
                    f"precision='rff' pinned but the RFF tier is "
                    f"unavailable for method={cfg.method!r} "
                    f"backend={cfg.backend!r} (rff={cfg.rff!r})")
        if res is not None:
            value, bounds = res.value, res.bounds
            hits, esc, path = res.hits, res.escalated, res.path
            esc_rows = res.esc_rows
        else:
            exact = cfg.exact_precision if tier == RFF_TIER else tier
            value = self._dispatch(prep, y, exact)
            bounds = np.full(m, cascade.exact_bound(exact, cfg.prune))
            hits, esc, path = 0, 0, (exact,)
            esc_rows = np.zeros(m, bool)
        staleness = (prep.stream.gen - snap.gen) if snap is not None else 0
        ans = Answer(
            value=value, key=prep.key, tier=path[-1], path=path,
            rel_err_bound=float(bounds.max()) if m else 0.0,
            rel_err_bounds=bounds, rff_hits=hits, escalated=esc,
            staleness=staleness,
            plan_id=getattr(prep.plan, "plan_id", "") or "",
        )
        return ans, esc_rows

    @staticmethod
    def _targets(cfg: ServeConfig, reqs: Sequence[QueryRequest],
                 sizes: Sequence[int]):
        """Per-row accuracy-target vector for a fused batch, or None when
        no member carries one.  A request target beats the config
        default; a member with neither gets ``-inf`` so its rows always
        escalate — an untargeted request expects an exact-grade answer
        even when fused with cascade-routed neighbors."""
        per = [r.accuracy_target if r.accuracy_target is not None
               else cfg.accuracy_target for r in reqs]
        if all(t is None for t in per):
            return None
        out = np.empty(int(sum(sizes)))
        off = 0
        for t, s in zip(per, sizes):
            out[off:off + s] = -np.inf if t is None else float(t)
            off += s
        return out

    @staticmethod
    def _split_answer(ans: Answer, reqs: Sequence[QueryRequest],
                      sizes: Sequence[int], esc_rows: np.ndarray,
                      dt: float) -> List[Answer]:
        parts = split(ans.value, sizes)
        offs = np.cumsum([0] + list(sizes))
        cascaded = RFF_TIER in ans.path
        out = []
        for i, dens in enumerate(parts):
            lo, hi = int(offs[i]), int(offs[i + 1])
            rows = hi - lo
            b = ans.rel_err_bounds[lo:hi]
            esc = int(esc_rows[lo:hi].sum()) if cascaded else 0
            hits = rows - esc if cascaded else 0
            path = (RFF_TIER,) if cascaded and not esc else ans.path
            out.append(Answer(
                value=dens, key=ans.key, tier=path[-1], path=path,
                rel_err_bound=float(b.max()) if rows else 0.0,
                rel_err_bounds=b, rff_hits=hits, escalated=esc,
                staleness=ans.staleness, plan_id=ans.plan_id,
                latency_s=dt, batch_requests=len(reqs)))
        return out

    @staticmethod
    def _check_query(prep: PreparedEstimator, y: jnp.ndarray) -> None:
        if y.ndim != 2 or y.shape[0] == 0 or y.shape[-1] != prep.d:
            raise BadRequest(
                f"query shape {tuple(y.shape)} does not match estimator "
                f"{prep.key!r} (expected (m, {prep.d}) with m >= 1)"
            )

    @staticmethod
    def _check_deadline(key: str, deadline_s: Optional[float],
                        phase: str) -> None:
        if deadline_s is None:
            return
        late = time.monotonic() - deadline_s
        if late >= 0:
            obs.counter("serve.deadline_exceeded",
                        "requests past their deadline at the plain engine",
                        labels={"phase": phase}).inc()
            raise DeadlineExceeded(
                f"request for {key!r} missed its deadline by "
                f"{1e3 * late:.1f}ms "
                + ("before dispatch" if phase == "dispatch"
                   else "(answer completed late)")
            )

    def _note_served(self, seconds: float, rows: int, requests: int) -> None:
        self.latency.record(seconds, rows, requests)
        obs.counter("serve.requests", "requests admitted").inc(requests)
        obs.counter("serve.queries", "density rows served").inc(rows)

    # -- telemetry --------------------------------------------------------

    def metrics(self) -> dict:
        """One JSON-safe view of everything this engine can observe:
        per-engine latency (bounded histogram), bucket-cache efficiency,
        streaming staleness, and the process-wide obs registry (kernel
        prune occupancy, autotune decisions, stream gauges, ...)."""
        return {
            "latency": self.latency.summary().as_dict(),
            "latency_hist": self.latency.histogram_snapshot(),
            "bucket_cache": {
                "hits": self.cache.hits,
                "misses": self.cache.misses,
                "evictions": self.cache.evictions,
                "resident": len(self.cache),
            },
            "staleness": self.staleness_summary(),
            "registry": obs.metrics_snapshot(),
        }

    def trace_events(self) -> list:
        """The buffered obs span events (enable with
        ``obs.configure(trace=True)``)."""
        return obs.trace_events()

    # -- streaming telemetry ---------------------------------------------

    def staleness_summary(self) -> dict:
        """p50/p99/max of how many generations behind live each streaming
        dispatch was served (empty dict when nothing streamed)."""
        if not self.staleness_log:
            return {}
        xs = sorted(self.staleness_log)

        def pct(q):
            return xs[min(len(xs) - 1, max(0, round(q * (len(xs) - 1))))]

        return {"count": len(xs), "p50": pct(0.5), "p99": pct(0.99),
                "max": xs[-1]}

    # -- internals -------------------------------------------------------

    def _dispatch(self, prep: PreparedEstimator, y: jnp.ndarray,
                  precision: Optional[str] = None) -> jnp.ndarray:
        cfg = prep.config
        # _dispatch is the *exact* dispatcher — the RFF fast tier routes
        # through serve/cascade.py, which calls back here only for
        # escalated rows at the escalation tier
        tier = precision or cfg.exact_precision
        if tier == RFF_TIER:
            raise BadRequest("the RFF tier has no exact dispatch path")
        snap = None
        sp = obs.span("serve.dispatch", key=prep.key, backend=cfg.backend,
                      tier=tier, rows=int(y.shape[0]))
        with sp:
            # chaos hook: a killed replica raises InjectedFailure here, a
            # slow one sleeps — before any compute, like a dead device
            fault_injection.fire("serve.dispatch", key=prep.key)
            if prep.plan is not None:
                # every served request traces back to the plan that
                # shaped its execution
                sp.set(plan=prep.plan.plan_id)
            if prep.stream is not None:
                # the staleness gate: get a snapshot at most ``staleness_
                # budget`` generations behind live (waiting for /
                # performing a flush only past the budget), then pin the
                # whole dispatch to it — concurrent appends/evictions
                # publish NEW snapshots and can never mutate the one in
                # flight
                snap = prep.stream.ensure(cfg.staleness_budget)
                lag = prep.stream.gen - snap.gen
                self.staleness_log.append(lag)
                obs.histogram("serve.staleness_gen",
                              "generations behind live per streaming "
                              "dispatch", lo=1, hi=1e4,
                              per_decade=8).observe(lag)
                sp.set(staleness=lag, stream_gen=snap.gen,
                       layout_epoch=snap.layout_epoch)
            top = cfg.bucket_sizes(prep.ring_size, prep.block_m)[-1]
            m = y.shape[0]
            if m <= top:
                return self._run_bucket(prep, y, tier, snap)
            # oversize batch: chunk at the largest bucket (each chunk
            # jit-stable)
            sp.set(chunks=-(-m // top))
            parts = [
                self._run_bucket(prep, y[off:off + top], tier, snap)
                for off in range(0, m, top)
            ]
            return jnp.concatenate(parts)

    def _run_bucket(self, prep: PreparedEstimator, y: jnp.ndarray,
                    tier: str, snap=None):
        cfg = prep.config
        m = y.shape[0]
        bucket = cfg.bucket_for(m, prep.ring_size, prep.block_m)
        if prep.stream is not None:
            # Streaming executables read train tensors from the pinned
            # snapshot per call, so value-only generation bumps reuse the
            # compiled program untouched; the layout epoch joins the key
            # because only a rebuild changes the column *shapes* — that is
            # the one event that actually invalidates an executable.
            ck = (prep.key, prep.generation, "stream", snap.layout_epoch,
                  tier, bucket)
            build = lambda: self._build_stream_executable(prep, tier)  # noqa: E731
        else:
            # Keyed on the fit generation: a refit (or evict + re-register)
            # produces a new generation, so stale executables can never
            # serve it.  The tier is part of the key — each precision gets
            # its own bucket executable against its own prepared train
            # tensors.
            ck = (prep.key, prep.generation, tier, bucket)
            build = lambda: self._build_executable(prep, tier)  # noqa: E731
        hit = ck in self.cache
        obs.histogram("serve.pad_ratio",
                      "bucket rows / real rows per dispatch",
                      lo=1.0, hi=1e4, per_decade=12).observe(bucket / m)
        with obs.span("serve.bucket", key=prep.key, bucket=bucket, rows=m,
                      pad_ratio=round(bucket / m, 4),
                      cache="hit" if hit else "miss"):
            fn = self.cache.get_or_build(
                ck, lambda: self._timed_build(build, prep, bucket)
            )
            if prep.stream is not None:
                return fn(pad_queries(y, bucket), m, snap)[:m]
            return fn(pad_queries(y, bucket), m)[:m]

    def _timed_build(self, build, prep: PreparedEstimator, bucket: int):
        """Build a bucket executable under a compile span + histogram, so
        a recompile storm is visible as `serve.compile_s` mass."""
        t0 = time.perf_counter()
        with obs.span("serve.compile", key=prep.key, bucket=bucket):
            fault_injection.fire("serve.compile", key=prep.key)
            fn = build()
        obs.histogram("serve.compile_s", "bucket-executable build seconds",
                      lo=1e-5, hi=1e3).observe(time.perf_counter() - t0)
        return fn

    def _build_stream_executable(self, prep: PreparedEstimator, tier: str):
        """Bucket executable for a streaming estimator: fn(yp, n_real, snap).

        Unlike the static path, no train tensor is closed over — each call
        reads the snapshot its dispatch is pinned to.  Normalization uses
        the snapshot's live count (appends/evictions move it), and the
        prune decision re-resolves per call because the live count drifts
        across the auto threshold as points come and go.
        """
        cfg = prep.config
        laplace = cfg.method == "laplace"

        if cfg.backend == "pallas":
            from repro.kernels import ops

            jfn = jax.jit(lambda yp, xt, nrm_x, xt_lo: ops.flash_kde_prepared(
                yp, xt, nrm_x, prep.h, xt_lo,
                precision=tier,
                block_m=prep.block_m, block_n=prep.block_n,
                interpret=cfg.interpret, laplace=laplace,
            ))

            def fn(yp, n_real, snap):
                cols = prep.stream.columns_for(tier, snap)
                eps = ops.resolve_prune(cfg.prune, snap.n_live,
                                        prep.block_n)
                if eps is not None and cols.meta is not None:
                    sums = ops.flash_kde_prepared(
                        yp, cols.xt, cols.nrm_x, prep.h, cols.xt_lo,
                        precision=tier,
                        block_m=prep.block_m, block_n=prep.block_n,
                        interpret=cfg.interpret, laplace=laplace,
                        prune=cfg.prune, columns=cols, n_real=n_real,
                    )
                else:
                    sums = jfn(yp, cols.xt, cols.nrm_x, cols.xt_lo)
                return sums / snap.norm

            return fn

        from repro.core import kde as ref

        eval_fn = ref.laplace_kde_eval if laplace else ref.kde_eval
        jfn = jax.jit(
            lambda yp, pts: eval_fn(pts, yp, prep.h, block=cfg.block)
        )
        # snap.xp is the live set padded to a pow2 row bucket (bounded
        # retraces across generations); sentinel rows contribute exactly
        # 0.0 to the sums but inflate eval_fn's 1/n normalization, so
        # rescale padded-n back to the live count
        return lambda yp, n_real, snap: jfn(yp, snap.xp) * (
            snap.xp.shape[0] / snap.n_live
        )

    def _build_executable(self, prep: PreparedEstimator, tier: str):
        """Bucket executable: padded (bucket, d) queries → (bucket,) dens.

        The executable signature is ``fn(yp, n_real)`` — ``n_real`` is the
        true (pre-padding) query count; the pruned pallas path needs it to
        keep sentinel rows out of the row-tile geometry, every other
        backend ignores it.

        Each executable owns its jit wrapper (train tensors passed as
        arguments, not baked as constants), so evicting an entry from the
        LRU releases its compiled program — the cache bounds compilations,
        not just Python closures.
        """
        cfg = prep.config
        laplace = cfg.method == "laplace"

        if cfg.backend == "pallas":
            from repro.kernels import ops

            cols = prep.columns_for(tier)
            # decide pruning ONCE per executable: "auto" below the size
            # threshold means every request takes the plain jitted dense
            # path — no per-request python dispatch overhead
            eps = ops.resolve_prune(cfg.prune, prep.n_true, prep.block_n)
            if eps is not None and cols.meta is not None:
                # Pruned path: not a single jit program — the per-batch
                # bounds prepass host-syncs for the visit lists' extent, and
                # flash_kde_prepared jit-caches the kernel per bucketed
                # visit extent underneath.
                def pruned_fn(yp, n_real):
                    sums = ops.flash_kde_prepared(
                        yp, cols.xt, cols.nrm_x, prep.h, cols.xt_lo,
                        precision=tier,
                        block_m=prep.block_m, block_n=prep.block_n,
                        interpret=cfg.interpret, laplace=laplace,
                        prune=cfg.prune, columns=cols, n_real=n_real,
                    )
                    return sums / prep.norm

                return pruned_fn
            jfn = jax.jit(lambda yp, xt, nrm_x, xt_lo: ops.flash_kde_prepared(
                yp, xt, nrm_x, prep.h, xt_lo,
                precision=tier,
                block_m=prep.block_m, block_n=prep.block_n,
                interpret=cfg.interpret, laplace=laplace,
            ) / prep.norm)
            return lambda yp, n_real: jfn(yp, cols.xt, cols.nrm_x,
                                          cols.xt_lo)

        if cfg.backend == "ring":
            from repro.distributed import ring

            eval_fn = ring.ring_laplace_kde if laplace else ring.ring_kde
            jfn = jax.jit(lambda yp, xs: eval_fn(
                xs, yp, prep.h, n_true=prep.n_true, mesh=prep.mesh,
            ))
            return lambda yp, n_real: jfn(yp, prep.x_sharded)

        from repro.core import kde as ref

        eval_fn = ref.laplace_kde_eval if laplace else ref.kde_eval
        jfn = jax.jit(
            lambda yp, pts: eval_fn(pts, yp, prep.h, block=cfg.block)
        )
        return lambda yp, n_real: jfn(yp, prep.points)


__all__ = ["ServeEngine"]
