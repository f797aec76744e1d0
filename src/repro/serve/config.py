"""Serving configuration: backend, estimator method, batching policy.

One frozen config object controls the whole request path — which density
method is served, which execution backend evaluates it, and how ragged query
traffic is coalesced into jit-stable shape buckets.
"""

from __future__ import annotations

import dataclasses
from typing import Literal, Optional, Tuple, Union

from repro.kernels.precision import validate as _validate_precision

Backend = Literal["jnp", "pallas", "ring"]
Method = Literal["kde", "sdkde", "laplace"]
Precision = Literal["f32", "bf16", "bf16x2"]   # = kernels.precision.PRECISIONS
# a *serving* tier is an exact GEMM tier or the RFF fast tier; the fit
# tier and the feature-GEMM tier stay exact
ServeTier = Literal["f32", "bf16", "bf16x2", "rff"]
BlockArg = Union[int, Literal["auto"]]


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static serving configuration (hashable; safe to close over in jit).

    Batching: a query batch of ``m`` rows is padded up to the smallest shape
    bucket ≥ m.  Buckets double geometrically from ``min_batch`` to
    ``max_batch`` and are rounded up to tile/ring multiples, so arbitrary
    ragged traffic hits at most ``log2(max/min)+1`` distinct compiled shapes
    per estimator instead of one compile per distinct batch size.
    """

    backend: Backend = "jnp"
    method: Method = "sdkde"

    # estimator knobs (mirrors repro.core.estimator.EstimatorConfig)
    block: int = 1024            # jnp streaming column-block size
    block_m: BlockArg = 128      # Pallas row tile (int or "auto" = autotuned)
    block_n: BlockArg = 512      # Pallas column tile (int or "auto")
    interpret: Optional[bool] = None  # Pallas interpret mode (None = platform)
    score_h: Optional[float] = None
    # Default serving tier: a GEMM-operand tier (kernels/precision.py) or
    # "rff", the random-feature fast tier (kernels/flash_rff.py).  A
    # QueryRequest precision pin overrides per request (precedence:
    # request pin > explicit config > planner); the registry caches
    # prepared train tensors per exact tier.
    precision: ServeTier = "f32"
    # Tier for the one-time O(n²·d) debias fit.  The fit is amortized off
    # the latency path, so it defaults to full precision regardless of the
    # serving tier — reduced-precision *queries* perturb one GEMM, while a
    # reduced-precision fit would bake its error into every future answer.
    fit_precision: Precision = "f32"
    # Cluster pruning (kernels/spatial.py, pallas backend): "auto" prunes
    # exactly (epsilon=0, certified-underflow tiles only) once the train
    # set is large enough; "off" streams every tile; a float is the
    # per-point contribution threshold epsilon.  The registry caches the
    # clustered ordering + tile metadata per tier at fit time, so pruning
    # costs only the cheap bounds prepass on the query path.
    prune: Union[str, float] = "auto"

    # micro-batching policy
    min_batch: int = 128         # smallest shape bucket
    max_batch: int = 4096        # largest shape bucket (larger batches chunk)
    cache_buckets: int = 8       # LRU capacity of jitted shape buckets

    # streaming (repro.stream): maintain the registered dataset
    # incrementally under registry.append()/evict_ids() instead of
    # refitting.  ``staleness_budget`` is how many applied update
    # generations a query may be served across before the engine must
    # publish a fresh snapshot (0 = always fresh); ``stream_slack`` is the
    # per-cluster append headroom of the Pallas layout;
    # ``stream_background`` builds snapshots on a worker thread so queries
    # keep serving generation g while g+1 prepares.
    stream: bool = False
    staleness_budget: int = 0
    stream_slack: float = 0.5
    stream_background: bool = False

    # execution planning (repro.plan): "auto" resolves every knob still at
    # its dataclass default through the cost-model planner at fit time —
    # explicitly-set knobs always win (override precedence, see
    # docs/architecture.md "Execution planning").  ``accuracy_target`` is
    # the planner's relative-accuracy budget; None = f32-grade (1e-5).
    plan: Literal["off", "auto"] = "off"
    accuracy_target: Optional[float] = None

    # RFF fast tier + accuracy cascade (kernels/flash_rff.py,
    # serve/cascade.py).  "auto" fits the per-generation RFF state lazily
    # on the first cascade-routed request (requests without an accuracy
    # target never pay for it); "on" fits it eagerly with the debias
    # pass; "off" disables the tier (an "rff" pin then raises).
    rff: Literal["off", "auto", "on"] = "auto"
    rff_features: int = 8192     # D: total cos+sin features per dataset
    rff_pilot: int = 256         # pilot control-variate mixture size
    rff_groups: int = 32         # frequency groups behind the band (the
                                 # band's t-statistic dof; see flash_rff)
    rff_precision: Precision = "f32"   # feature-GEMM operand tier

    def __post_init__(self):
        if self.min_batch <= 0 or self.max_batch < self.min_batch:
            raise ValueError(
                f"bad bucket range [{self.min_batch}, {self.max_batch}]"
            )
        if self.cache_buckets < 1:
            raise ValueError("cache_buckets must be >= 1")
        if self.precision != "rff":
            _validate_precision(self.precision)
        for p in (self.fit_precision, self.rff_precision):
            _validate_precision(p)
        if self.rff not in ("off", "auto", "on"):
            raise ValueError(
                f"bad rff {self.rff!r} ('off', 'auto', or 'on')")
        if self.precision == "rff" and self.rff == "off":
            raise ValueError(
                "precision='rff' needs the RFF tier enabled (rff='auto' "
                "or 'on')")
        if self.rff_pilot < 1 or self.rff_groups < 2:
            raise ValueError("need rff_pilot >= 1 and rff_groups >= 2")
        if self.rff_features < 2 * self.rff_groups \
                or self.rff_features % (2 * self.rff_groups):
            raise ValueError(
                f"rff_features must be a positive multiple of "
                f"2·rff_groups, got {self.rff_features} with "
                f"groups={self.rff_groups}")
        for b in (self.block_m, self.block_n):
            if not (b == "auto" or (isinstance(b, int) and b > 0)):
                raise ValueError(f"bad Pallas block {b!r} (int or 'auto')")
        p = self.prune
        if not (p in ("auto", "off")
                or (isinstance(p, (int, float)) and not isinstance(p, bool)
                    and p >= 0)):
            raise ValueError(
                f"bad prune {p!r} ('auto', 'off', or epsilon >= 0)"
            )
        if self.staleness_budget < 0:
            raise ValueError("staleness_budget must be >= 0")
        if self.stream_slack < 0:
            raise ValueError("stream_slack must be >= 0")
        if self.plan not in ("off", "auto"):
            raise ValueError(f"bad plan {self.plan!r} ('off' or 'auto')")
        if self.accuracy_target is not None \
                and not (self.accuracy_target > 0):
            raise ValueError(
                f"accuracy_target must be > 0, got {self.accuracy_target!r}"
            )
        if self.stream and self.backend == "ring":
            raise ValueError(
                "streaming estimators support the jnp/pallas backends "
                "(the ring shards at fit time; re-sharding per append is "
                "a full refit by construction)"
            )

    @property
    def exact_precision(self) -> str:
        """The exact GEMM tier behind the default serving tier — what the
        registry prepares train columns at and what cascade escalations
        run when the default tier is ``"rff"``."""
        return "f32" if self.precision == "rff" else self.precision

    def row_multiple(self, ring_size: int = 1,
                     block_m: Optional[int] = None) -> int:
        """Row-count multiple every dispatched batch must honor.

        Pallas tiles rows by ``block_m``; the ring shards rows over
        ``ring_size`` devices; the jnp path is shape-agnostic but still
        bucketed for jit-cache stability.  When the config says
        ``block_m="auto"`` the caller passes the fit-time tuned tile
        (``PreparedEstimator.block_m``) — before a fit resolves it, the
        ladder falls back to the 128-row default tile.
        """
        if self.backend == "pallas":
            bm = block_m if block_m is not None else self.block_m
            return bm if isinstance(bm, int) else 128
        if self.backend == "ring":
            return max(1, ring_size)
        return 1

    def bucket_sizes(self, ring_size: int = 1,
                     block_m: Optional[int] = None) -> Tuple[int, ...]:
        """The geometric ladder of padded batch shapes this config serves."""
        mult = self.row_multiple(ring_size, block_m)
        sizes, b = [], self.min_batch
        while True:
            sizes.append(_round_up(min(b, self.max_batch), mult))
            if b >= self.max_batch:
                break
            b *= 2
        return tuple(dict.fromkeys(sizes))

    def bucket_for(self, m: int, ring_size: int = 1,
                   block_m: Optional[int] = None) -> int:
        """Smallest shape bucket that fits an ``m``-row query batch."""
        if m <= 0:
            raise ValueError(f"empty query batch (m={m})")
        sizes = self.bucket_sizes(ring_size, block_m)
        for b in sizes:
            if m <= b:
                return b
        return sizes[-1]  # chunked by the engine


__all__ = ["Backend", "Method", "Precision", "ServeTier", "BlockArg",
           "ServeConfig"]
