"""The general traffic generator: one driver per kind of mix.

A traffic mix is a data file (``bench/traffic/<mix>.json``) whose ``kind``
picks the driver and whose other keys are its parameters:

  ``jobs``       closed loop of whole offline jobs: ``fit`` on a fresh
                 estimator, then ``evaluate`` of every query, back to back;
  ``open_loop``  requests through the async front end on a schedule fixed
                 in advance: Poisson arrivals, rows per request log-uniform
                 over ``[rows_min, rows_max]``, every request pinned to the
                 configuration's tier.

Each driver makes its inputs from the seed, warms up in ``setup``, runs
the measured ``window``, and hands what the window produced to ``check``,
which compares it with the plain reference once the program's state is
freed.  Every call into the system sits inside a named host annotation
(``bench.*``), so the trace can say what the host did in each idle gap.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from kdebench import data, window


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _wait(fut, deadline: float):
    """Block until ``fut`` resolves or the monotonic ``deadline`` passes."""
    import concurrent.futures

    try:
        fut.exception(timeout=max(deadline - time.perf_counter(), 0.0))
    except concurrent.futures.TimeoutError:
        pass


class Jobs:
    """Closed loop of whole jobs: ``SDKDE.fit(x)`` then ``evaluate(y)`` on a
    fresh estimator, back to back.

    Every job of a run, the warm-up job included, fits the seed's train
    points (``data.train_points``) and evaluates the same queries: the
    program compiles programs whose shapes follow the data (pruned layouts,
    visit extents), so a job on new points would compile inside the
    window.  No state outlives an estimator, so each job does the whole
    work again."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, tier: str,
                 seconds: float):
        self.cfg, self.traffic, self.seed, self.tier = cfg, traffic, seed, tier
        self.outputs: List[np.ndarray] = []
        self.failed = 0
        self.elapsed = 0.0

    def _draw(self):
        import jax

        n, m = self.cfg["data"]["n"], self.cfg["data"]["queries"]
        x = data.train_points(self.cfg, self.seed, n)
        y = data.mixture(self.cfg, data.key(self.seed, data.WINDOW), m)
        return jax.block_until_ready((x, y))

    def _job(self) -> np.ndarray:
        from repro.core.estimator import SDKDE, EstimatorConfig

        est = SDKDE(config=EstimatorConfig(
            **self.cfg["system"]["estimator"], precision=self.tier))
        with annotate("bench.fit"):
            est.fit(self.x).x_sd.block_until_ready()
        with annotate("bench.evaluate"):
            dens = est.evaluate(self.y)
            dens.block_until_ready()
        return np.asarray(dens)

    def setup(self) -> None:
        self.x, self.y = self._draw()
        with annotate("bench.warmup"):
            self._job()

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        with annotate("bench.window"):
            while True:
                with annotate("bench.job"):
                    try:
                        self.outputs.append(self._job())
                    except Exception as e:  # noqa: BLE001 - counted, reported
                        print(f"job {len(self.outputs)} failed: {e!r}",
                              flush=True)
                        self.failed += 1
                        self.outputs.append(None)
                if time.perf_counter() - t0 >= seconds:
                    break
        self.elapsed = time.perf_counter() - t0

    @property
    def attempted(self) -> int:
        return len(self.outputs)

    def end_to_end(self) -> Dict[str, float]:
        # seconds per job: the inverse of the jobs completed over all
        # the window's time
        return {"job_s": 1.0 / window.rate(self.attempted, self.elapsed)}

    def free(self) -> None:
        self.x, self.y = np.asarray(self.x), np.asarray(self.y)
        gc.collect()

    def check(self):
        """(got, want) pairs: every answer of one job of the window, drawn
        from the seed, against the reference from the raw points."""
        import reference

        rng = np.random.default_rng([self.seed, data.SAMPLE])
        index = int(rng.integers(self.attempted))
        got = self.outputs[index]
        want = reference.sdkde(self.x, self.y)
        if got is None:
            got = np.full_like(want, np.nan)
        return [(got, want)], {"job": index}


class _Record:
    __slots__ = ("due", "sent", "done", "ok", "tier", "value", "start",
                 "rows", "error")

    def __init__(self, due, start, rows):
        self.due, self.start, self.rows = due, start, rows
        self.sent = self.done = None
        self.ok = False
        self.tier = None
        self.value = None
        self.error = None


class OpenLoop:
    """Open-loop requests through ``AsyncFrontend.submit`` on a fixed
    schedule; latency runs from each request's due time."""

    KEY = "bench"

    def __init__(self, cfg: dict, traffic: dict, seed: int, tier: str,
                 seconds: float):
        self.cfg, self.traffic, self.seed, self.tier = cfg, traffic, seed, tier
        self.records: List[_Record] = []
        self.frontend = None
        self.lateness: List[float] = []
        self._lock = threading.Lock()

    # -- inputs ------------------------------------------------------------

    def _pool(self):
        n = int(self.traffic["pool_rows"])
        return np.asarray(data.mixture(
            self.cfg, data.key(self.seed, data.QUERY_POOL), n))

    def _schedule(self, stream: int, seconds: float):
        """Due offsets, rows and pool offsets of one stretch of the mix."""
        t = self.traffic
        rng = np.random.default_rng([self.seed, stream])
        due = window.arrival_offsets(t["arrivals"], seconds, rng)
        rows = window.log_uniform_sizes(window.strata(len(due), rng),
                                        int(t["rows_min"]),
                                        int(t["rows_max"]))
        starts = rng.integers(0, self.pool.shape[0] - rows + 1)
        return due, rows, starts

    # -- driving -------------------------------------------------------------

    def _on_done(self, rec: _Record, fut) -> None:
        # an answer has resolved once its densities are on the host
        err = fut.exception()
        value = None if err else np.asarray(fut.result().value)
        now = time.perf_counter()
        with self._lock:
            rec.done = now
            if value is not None:
                rec.tier = fut.result().tier
                rec.value = value
                rec.ok = rec.tier == self.tier
                if not rec.ok:
                    rec.error = f"tier {rec.tier}"
            else:
                rec.error = type(err).__name__

    def run_schedule(self, stream: int, seconds: float, record: bool) -> None:
        from repro.serve import QueryRequest
        from repro.serve.errors import ServeError

        due, rows, starts = self._schedule(stream, seconds)
        recs, futs = [], []
        t0 = time.perf_counter()
        for off, k, a in zip(due, rows, starts):
            rec = _Record(t0 + off, int(a), int(k))
            recs.append(rec)
            delay = rec.due - time.perf_counter()
            if delay > 0:
                with annotate("bench.wait"):
                    time.sleep(delay)
            rec.sent = time.perf_counter()
            req = QueryRequest(key=self.KEY, points=self.pool[a:a + k],
                               precision=self.tier)
            with annotate("bench.submit"):
                try:
                    fut = self.frontend.submit(req)
                except ServeError as e:
                    rec.done = time.perf_counter()
                    rec.error = type(e).__name__
                    continue
            fut.add_done_callback(lambda f, r=rec: self._on_done(r, f))
            futs.append(fut)
        end = t0 + seconds
        with annotate("bench.drain"):
            for f in futs:
                _wait(f, end + 60.0)
        if record:
            self.records = recs
            self.lateness = [r.sent - r.due for r in recs]

    def setup(self) -> None:
        import jax

        from repro.serve import AsyncFrontend, FrontendConfig
        from repro.serve import ServeConfig, ServeEngine

        sys_cfg = self.cfg["system"]
        x = data.train_points(self.cfg, self.seed, self.cfg["data"]["n"])
        self.x = np.asarray(x)
        self.pool = self._pool()
        engine = ServeEngine(ServeConfig(**sys_cfg["serve"],
                                         precision=self.tier,
                                         fit_precision=self.tier))
        with annotate("bench.fit"):
            engine.register(self.KEY, x)
        with annotate("bench.prewarm"):
            # the largest bucket always (oversize batches chunk at it);
            # the whole ladder only where the mix sends smaller requests
            prep = engine.registry.get(self.KEY)
            top = engine.config.bucket_sizes(prep.ring_size,
                                             prep.block_m)[-1]
            engine.prewarm(self.KEY,
                           all_buckets=int(self.traffic["rows_min"]) < top)
        self.engine = engine
        self.frontend = AsyncFrontend(engine, FrontendConfig(
            **sys_cfg.get("frontend", {})))
        with annotate("bench.warmup"):
            # the mix under load, from a stream of its own: the window's
            # requests are never sent before the window
            self.run_schedule(data.WARM, float(self.traffic["warm_seconds"]),
                              record=False)
        jax.block_until_ready(x)

    def window(self, seconds: float) -> None:
        with annotate("bench.window"):
            self.run_schedule(data.WINDOW, seconds, record=True)

    @property
    def attempted(self) -> int:
        return len(self.records)

    @property
    def failed(self) -> int:
        return sum(not r.ok for r in self.records)

    def latencies(self) -> List[float]:
        """Seconds from due to resolved of every recorded request; a
        failed one counts at the front end's deadline."""
        limit = float(self.cfg["system"].get("frontend", {}).get(
            "default_deadline_ms", 1000.0)) / 1e3
        with self._lock:
            return window.latencies_from_due(
                [r.due for r in self.records],
                [r.done if r.ok else None for r in self.records], limit)

    def end_to_end(self) -> Dict[str, float]:
        lat = self.latencies()
        return {"latency_p50_ms": 1e3 * window.percentile(lat, 50),
                "latency_p95_ms": 1e3 * window.percentile(lat, 95)}

    def generator_lateness(self) -> Dict[str, float]:
        late = self.lateness or [0.0]
        return {"max_ms": 1e3 * max(late),
                "p95_ms": 1e3 * window.percentile(late, 95),
                "requests": len(self.lateness)}

    def failures(self) -> Dict[str, int]:
        """Failed requests of the window by cause (the exception type, or
        the tier of an answer at another tier than the configuration's)."""
        out: Dict[str, int] = {}
        with self._lock:
            for r in self.records:
                if not r.ok:
                    why = r.error or "unresolved"
                    out[why] = out.get(why, 0) + 1
        return out

    def free(self) -> None:
        if self.frontend is not None:
            self.frontend.close(timeout=60.0)
        self.frontend = self.engine = None
        gc.collect()

    def check(self):
        """(got, want) pairs: every answer the window's requests got,
        against the reference densities at the same pool rows."""
        import reference

        answered = [r for r in self.records if r.value is not None]
        unresolved = sum(r.done is None for r in self.records)
        want = reference.sdkde(self.x, self.pool)
        pairs = [(r.value, want[r.start:r.start + r.rows]) for r in answered]
        if unresolved or not pairs:
            pairs.append((np.full(1, np.nan), want[:1]))
        return pairs, {"answers": len(answered), "unresolved": unresolved,
                       "floor_from": want}


DRIVERS = {"jobs": Jobs, "open_loop": OpenLoop}


def driver(cfg: dict, traffic: dict, seed: int, seconds: float,
           tier: Optional[str] = None):
    """The driver a mix's ``kind`` names, for a window of ``seconds``, at
    ``tier`` (the configuration's stated tier unless the control
    substitutes a lower one)."""
    kind = traffic["kind"]
    if kind not in DRIVERS:
        raise KeyError(f"unknown traffic kind {kind!r}")
    return DRIVERS[kind](cfg, traffic, seed, tier or cfg["precision"],
                         seconds)
