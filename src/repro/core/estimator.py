"""High-level estimator API: KDE / SDKDE / LaplaceKDE with backend dispatch.

Backends:
  * ``jnp``    — streaming GEMM-form pure JAX (works everywhere, any scale).
  * ``pallas`` — the Flash kernels (``repro.kernels``): explicit VMEM tiling,
                 MXU GEMMs, sequential-grid streaming accumulation.  On CPU
                 they run in interpret mode (validation); on TPU, compiled.
  * ``ring``   — multi-device ring-sharded execution (``repro.distributed``).

This is the "paper's contribution as a composable JAX module": estimators are
pytrees of arrays + static config, usable under jit/vmap/shard_map.
"""

from __future__ import annotations

import dataclasses
from typing import Literal, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bandwidth as bw
from repro.core import kde as ref

Backend = Literal["jnp", "pallas", "ring"]


@dataclasses.dataclass
class EstimatorConfig:
    backend: Backend = "jnp"
    block: int = 1024            # streaming column-block size (jnp backend)
    block_m: "int | str" = 128   # Pallas row tile (int or "auto" = autotuned)
    block_n: "int | str" = 512   # Pallas column tile (int or "auto")
    interpret: Optional[bool] = None  # Pallas interpret mode (None = platform)
    score_h: Optional[float] = None  # score-estimation bandwidth (None = h)
    dtype: jnp.dtype = jnp.float32
    precision: str = "f32"       # Pallas GEMM-operand tier (kernels/precision)
    prune: "str | float" = "auto"  # cluster pruning (kernels/spatial):
    #                              # "auto" | "off" | certified epsilon >= 0


class KDE:
    """Classical Gaussian KDE."""

    def __init__(self, h=None, config: EstimatorConfig | None = None):
        self.h = h
        self.config = config or EstimatorConfig()
        self.x_train: jnp.ndarray | None = None

    def fit(self, x: jnp.ndarray) -> "KDE":
        self.x_train = jnp.asarray(x, self.config.dtype)
        if self.h is None:
            self.h = bw.silverman_bandwidth(self.x_train)
        return self

    def _train_points(self) -> jnp.ndarray:
        assert self.x_train is not None, "call fit() first"
        return self.x_train

    def evaluate(self, y: jnp.ndarray) -> jnp.ndarray:
        x = self._train_points()
        y = jnp.asarray(y, self.config.dtype)
        cfg = self.config
        if cfg.backend == "pallas":
            from repro.kernels import ops

            return ops.flash_kde(
                x, y, self.h, precision=cfg.precision,
                block_m=cfg.block_m, block_n=cfg.block_n,
                interpret=cfg.interpret, prune=cfg.prune,
            )
        if cfg.backend == "ring":
            from repro.distributed import ring

            return ring.ring_kde(x, y, self.h)
        return ref.kde_eval(x, y, self.h, block=cfg.block)

    __call__ = evaluate


class SDKDE(KDE):
    """Score-debiased KDE: empirical-score shift + KDE on debiased samples.

    ``fit`` performs the quadratic score pass (the paper's hot spot) and
    caches the debiased samples; ``evaluate`` is then a standard KDE pass.

    ``append``/``evict`` update a fitted estimator *incrementally* — the
    O(n·b·d) delta score pass of ``repro.stream.delta`` instead of a fresh
    O(n²·d) fit.  The first incremental call pays one full pass to seed
    float64 score statistics; every later update is a delta against them,
    and the debiased samples are recomputed from the maintained statistics
    (matching a from-scratch refit to float tolerance).  The bandwidth
    stays the fit-time one — streaming updates change the data, not ``h``.
    """

    def __init__(self, h=None, config: EstimatorConfig | None = None):
        super().__init__(h, config)
        self.x_sd: jnp.ndarray | None = None
        self._s0 = self._s1 = None       # f64 score stats (lazy, streaming)

    def fit(self, x: jnp.ndarray) -> "SDKDE":
        self.x_train = jnp.asarray(x, self.config.dtype)
        self._s0 = self._s1 = None       # a refit invalidates seeded stats
        if self.h is None:
            self.h = bw.sdkde_bandwidth(self.x_train)
        cfg = self.config
        if cfg.backend == "pallas":
            from repro.kernels import ops

            self.x_sd = ops.flash_sdkde_shift(
                self.x_train, self.h, score_h=cfg.score_h,
                precision=cfg.precision,
                block_m=cfg.block_m, block_n=cfg.block_n,
                interpret=cfg.interpret, prune=cfg.prune,
            )
        elif cfg.backend == "ring":
            from repro.distributed import ring

            self.x_sd = ring.ring_sdkde_shift(
                self.x_train, self.h, score_h=cfg.score_h
            )
        else:
            self.x_sd = ref.sdkde_shift(
                self.x_train, self.h, score_h=cfg.score_h, block=cfg.block
            )
        return self

    def _train_points(self) -> jnp.ndarray:
        assert self.x_sd is not None, "call fit() first"
        return self.x_sd

    # -- incremental updates (repro.stream.delta) ------------------------

    def _score_h(self) -> float:
        sh = self.config.score_h
        return float(self.h if sh is None else sh)

    def _seed_stats(self, x_live):
        from repro.stream import delta

        if self._s0 is None:
            self._s0, self._s1 = delta.initial_stats(x_live, self._score_h())

    def _refresh_shift(self) -> None:
        from repro.stream import delta

        x_live = np.asarray(self.x_train, np.float32)
        self.x_sd = jnp.asarray(
            delta.apply_shift(
                x_live, self._s0, self._s1, float(self.h), self._score_h()
            ).astype(np.float32)
        )

    def append(self, x_new) -> "SDKDE":
        """Fold new points into a fitted estimator without a refit."""
        from repro.stream import delta

        assert self.x_sd is not None, "call fit() first"
        x_new = np.atleast_2d(np.asarray(x_new, np.float32))
        x_live = np.asarray(self.x_train, np.float32)
        self._seed_stats(x_live)
        ds0, ds1, s0n, s1n = delta.append_delta(
            x_live, x_new, self._score_h()
        )
        self._s0 = np.concatenate([self._s0 + ds0, s0n])
        self._s1 = np.concatenate([self._s1 + ds1, s1n])
        self.x_train = jnp.concatenate(
            [self.x_train, jnp.asarray(x_new, self.config.dtype)]
        )
        self._refresh_shift()
        return self

    def evict(self, idx) -> "SDKDE":
        """Remove train rows (by position) without a refit."""
        from repro.stream import delta

        assert self.x_sd is not None, "call fit() first"
        x_live = np.asarray(self.x_train, np.float32)
        out = np.zeros(x_live.shape[0], bool)
        out[np.atleast_1d(np.asarray(idx, np.int64))] = True
        if out.all():
            raise ValueError("cannot evict every train point")
        self._seed_stats(x_live)
        ds0, ds1 = delta.evict_delta(
            x_live[~out], x_live[out], self._score_h()
        )
        self._s0 = self._s0[~out] - ds0
        self._s1 = self._s1[~out] - ds1
        self.x_train = self.x_train[jnp.asarray(~out)]
        self._refresh_shift()
        return self


class LaplaceKDE(KDE):
    """Laplace-corrected KDE (Flash-Laplace-KDE when fused)."""

    def __init__(self, h=None, config: EstimatorConfig | None = None,
                 fused: bool = True):
        super().__init__(h, config)
        self.fused = fused

    def evaluate(self, y: jnp.ndarray) -> jnp.ndarray:
        x = self._train_points()
        y = jnp.asarray(y, self.config.dtype)
        cfg = self.config
        if cfg.backend == "pallas":
            from repro.kernels import ops

            if self.fused:
                return ops.flash_laplace_kde(
                    x, y, self.h, precision=cfg.precision,
                    block_m=cfg.block_m, block_n=cfg.block_n,
                    interpret=cfg.interpret, prune=cfg.prune,
                )
            return ops.laplace_kde_nonfused(
                x, y, self.h, precision=cfg.precision,
                block_m=cfg.block_m, block_n=cfg.block_n,
                interpret=cfg.interpret,
            )
        if cfg.backend == "ring":
            from repro.distributed import ring

            return ring.ring_laplace_kde(x, y, self.h)
        if self.fused:
            return ref.laplace_kde_eval(x, y, self.h, block=cfg.block)
        return ref.laplace_kde_eval_nonfused(x, y, self.h, block=cfg.block)

    __call__ = evaluate
