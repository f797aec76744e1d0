"""Random-Fourier-feature fast tier: one small GEMM per query, banded.

Every exact serving tier answers a query by streaming the *whole* train
set through the pairwise kernel — O(n·d) MXU work plus n exponentials per
query row, however well tiled.  Random Fourier features (Rahimi–Recht;
Gallego et al.'s RFF/density-matrix KDE, PAPERS.md arxiv 2208.01206)
collapse that to a train-independent cost: with frequencies
``w_j ~ N(0, I/h²)`` the Gaussian kernel is the expectation
``k(y,x) = E_w[cos(w·y)cos(w·x) + sin(w·y)sin(w·x)]``, so the kernel sum
``S(y) = Σ_i k(y, x_i)`` is estimated from per-dataset *feature sums*

    z_cos[j] = Σ_i cos(w_j·x_i),   z_sin[j] = Σ_i sin(w_j·x_i)

as ``Ŝ(y) = mean_j [cos(w_j·y)·z_cos[j] + sin(w_j·y)·z_sin[j]]`` — one
(m×d)@(d×D/2) feature GEMM plus trig per query batch, independent of n.

Two additions make this a *certifiable* serving tier rather than a heuristic:

**Pilot control variate.**  The vanilla estimator's variance is hopeless
for tight targets (relative error ~1/√(D·k̄), orders of magnitude above
1e-2 at practical D).  We therefore fit per-cluster Gaussian moments
(counts, means, mean per-dim variances over the k-means cells of
``kernels.spatial`` — the same geometry the pruning certificates use) and
split the kernel sum into an *analytic* pilot term plus an RFF-estimated
*residual*: a mixture of isotropic Gaussians has a closed-form Gaussian
convolution AND a closed-form characteristic function, so

    S(y) ≈ S_pilot(y) + mean_j [cos(w_j·y)·rc[j] + sin(w_j·y)·rs[j]]

with ``rc = z_cos − z_pilot_cos`` the residual feature sums.  The RFF
noise now scales with the residual mass (how non-Gaussian each cell is),
typically 1–2 orders below the raw sums — that is what brings 1e-2
certificates into reach at D ≈ 8192.

**Per-query uncertainty band.**  The D/2 frequencies are split into
``groups`` independent batches; the spread of the per-group estimates
gives a standard error, and the certified relative band is

    band(y) = E(y) / max(p̂(y) − E(y), TAIL_FRAC · p_scale),
    E(y)    = Z · stderr(y)

with the same tail floor the realized-error metric uses (``p_scale`` is a
high-percentile train density fitted once).  The denominator is the least
density consistent with the absolute band E, so wherever ``|p̂ − p| ≤ E``
the band bounds ``|p̂ − p| / max(p, floor)`` — the realized error — too.  The serving cascade
(``serve/cascade.py``) answers a query at this tier only when ``band``
fits the request's accuracy target, so the band being *honest* — never
exceeded by realized error — is the acceptance-gated contract
(``benchmarks/rff_cascade.py``).

Fit is O(n·D·d/2) once per dataset generation — amortized alongside the
debias pass in the serving registry — and the accumulators are exact
sums, so streaming append/evict folds in as an O(b·D·d/2) delta
(:func:`update`) with a full refit only on layout-epoch rebuilds.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from repro.core.bandwidth import gaussian_norm_const
from repro.kernels import precision as prec
from repro.kernels import spatial

#: Total feature count D (cos+sin pair per frequency → D/2 frequencies).
DEFAULT_FEATURES = 8192
#: Pilot mixture size (k-means cells whose Gaussian moments we fit).
DEFAULT_PILOT = 256
#: Independent frequency groups for the per-query standard error.  The
#: group count is the *degrees of freedom* behind the band: with G
#: groups the band is effectively a (G−1)-dof t-statistic scaled by Z,
#: and P(|t₇| > 5) ≈ 2e-3 — a few violations per thousand queries at
#: G=8, observed in practice at acceptance scale.  G=32 pushes the same
#: Z=5 to P(|t₃₁| > 5) ≈ 1e-5 while leaving the band width itself
#: unchanged in expectation (the overall stderr does not depend on how
#: the D/2 frequencies are grouped).
DEFAULT_GROUPS = 32
#: Band factor Z: certified band = Z · group stderr (empirically Z=4
#: still shows rare violations; Z=5 held with margin across sweeps at
#: :data:`DEFAULT_GROUPS`-many groups).
BAND_Z = 5.0
#: Relative-error tail floor, as a fraction of the fitted density scale:
#: band and realized error are both measured against
#: ``max(p, TAIL_FRAC·p_scale)`` so near-zero tails don't blow up ratios.
TAIL_FRAC = 0.01
#: Bandwidth scale for the frequency distribution.  MUST stay 1.0 for a
#: sound cascade: sampling from a widened 1/(s·h) distribution estimates
#: the kernel sum at bandwidth s·h — a *different estimand* than the
#: exact tier the cascade escalates to, and the group-spread band only
#: certifies Monte-Carlo error, never that smoothing bias.  (Importance
#: weights can't rescue it either: the weight's second moment diverges
#: for s ≥ √2 and inflates variance ~30× already at s=1.3.)  The
#: variance a widened kernel used to hide is bought back with a finer
#: pilot mixture instead (:data:`DEFAULT_PILOT`).
H_SCALE = 1.0

_FIT_BLOCK = 16384
_P_SCALE_SAMPLE = 512
_P_SCALE_PCT = 99.0


@dataclasses.dataclass(frozen=True)
class RFFServing:
    """Immutable per-generation serving tensors (a jit-friendly pytree).

    Everything :func:`eval_density` needs, finalized from the exact
    accumulators of :class:`RFFState`: f32 frequencies/residuals for the
    feature GEMM, the pilot mixture in query-evaluation form, and the
    normalization/floor scalars.  Registered as a pytree with ``groups``
    as *static* aux data — it shapes the reshape inside
    :func:`eval_density`, so it must stay concrete under jit.
    """

    wt: jnp.ndarray        # (d, D/2) f32 — feature GEMM operand
    res_cos: jnp.ndarray   # (D/2,) f32 residual feature sums
    res_sin: jnp.ndarray   # (D/2,) f32
    mu: jnp.ndarray        # (K, d) f32 live pilot means
    beta: jnp.ndarray      # (K,) f32 pilot amplitudes n_k·(h²/s²_k)^{d/2}
    inv2s2: jnp.ndarray    # (K,) f32 1/(2s²_k), s²_k = h² + var_k
    norm: jnp.ndarray      # () f32 n · (2π)^{d/2} h^d
    p_floor: jnp.ndarray   # () f32 TAIL_FRAC · p_scale
    groups: int            # static: frequency groups for the stderr


_SERVING_LEAVES = ("wt", "res_cos", "res_sin", "mu", "beta", "inv2s2",
                   "norm", "p_floor")

jax.tree_util.register_pytree_node(
    RFFServing,
    lambda s: (tuple(getattr(s, f) for f in _SERVING_LEAVES), s.groups),
    lambda groups, leaves: RFFServing(*leaves, groups=groups),
)


@dataclasses.dataclass
class RFFState:
    """Exact fit-time accumulators of the RFF tier (streaming-updatable).

    All sums are float64 and *exact* for the frequencies ``w`` actually
    used, so append/evict deltas commute with refits; the derived serving
    tensors are cached and invalidated on every update.
    """

    h: float               # the serving bandwidth (== the exact tier's h)
    d: int
    n: int                 # live train count the sums cover
    groups: int
    seed: int
    npp: float             # per-point normalizer (2π)^{d/2} h^d
    w: np.ndarray          # (D/2, d) f64 frequencies (fixed per fit)
    z_cos: np.ndarray      # (D/2,) f64 train feature sums
    z_sin: np.ndarray
    centroids: np.ndarray  # (K, d) f64 pilot anchors (fixed per fit)
    pilot_n: np.ndarray    # (K,) f64 per-cell counts
    pilot_s1: np.ndarray   # (K, d) f64 per-cell coordinate sums
    pilot_ss: np.ndarray   # (K,) f64 per-cell Σ‖x‖²
    p_scale: float = 0.0   # high-percentile fit density (band floor scale)
    _serving: Optional[RFFServing] = dataclasses.field(
        default=None, repr=False)

    @property
    def n_features(self) -> int:
        return 2 * self.w.shape[0]

    def serving(self) -> RFFServing:
        """Finalized serving tensors (cached until the next update)."""
        if self._serving is None:
            self._serving = _finalize(self)
        return self._serving


def supports(method: str, backend: str) -> bool:
    """Whether the RFF tier can serve this estimator configuration.

    sd-kde serves its *debiased* points as a plain Gaussian KDE, so the
    tier covers kde and sdkde alike; the Laplace-corrected kernel's
    spectral weight (1 + h²‖w‖²/2) inflates exactly the high-frequency
    residuals the pilot cannot absorb, and the ring backend shards points
    at fit time — both fall back to their exact tiers.
    """
    return method in ("kde", "sdkde") and backend in ("jnp", "pallas")


def fit(points, h: float, *, n_features: int = DEFAULT_FEATURES,
        n_pilot: int = DEFAULT_PILOT, groups: int = DEFAULT_GROUPS,
        h_scale: float = H_SCALE, seed: int = 0) -> RFFState:
    """Fit the RFF tier over a (debiased) train set — once per generation.

    ``h`` is the exact tier's bandwidth and (with ``h_scale`` at its 1.0
    default) the tier's estimand too — the same kernel sum the cascade's
    escalation tier computes, which is what makes the band a certificate
    rather than a heuristic (see :data:`H_SCALE`).  O(n·D·d/2) feature
    sums in f64 plus one O(n·K·d) pilot pass.
    """
    x = np.asarray(points, np.float64)
    n, d = x.shape
    if n_features % (2 * groups):
        raise ValueError(
            f"n_features must be a multiple of 2·groups, got "
            f"{n_features} with groups={groups}")
    h_rff = float(h) * float(h_scale)
    n_half = n_features // 2

    # frequencies are drawn once and stored at f32 *values* (in f64 for
    # the fit math): serving casts them per tier, and using the identical
    # values at fit and query time keeps the accumulators exact for the
    # frequencies actually served
    rng = np.random.default_rng(seed)
    w = (rng.standard_normal((n_half, d)) / h_rff).astype(
        np.float32).astype(np.float64)

    # pilot anchors: the same k-means machinery the pruning certificates
    # use; labels ARE argmin-to-centroid, so streaming updates assigning
    # deltas to their nearest anchor stay consistent with the fit
    idx = spatial.build_index(jnp.asarray(x, jnp.float32),
                              n_clusters=max(1, min(n_pilot, n)), seed=seed)
    labels = np.asarray(idx.labels)
    # the anchors ARE the full centroid set: fit labels and streaming
    # deltas then share one assignment rule (argmin-to-anchor), so an
    # evicted point subtracts from exactly the cell its append filled
    centroids = np.asarray(idx.centroids, np.float64)
    k = centroids.shape[0]
    pilot_n = np.bincount(labels, minlength=k).astype(np.float64)
    pilot_s1 = np.zeros((k, d))
    np.add.at(pilot_s1, labels, x)
    pilot_ss = np.bincount(labels, weights=(x * x).sum(1),
                           minlength=k).astype(np.float64)

    # the O(n·D/2·d) feature-sum pass runs f32 under jit with f64 block
    # accumulation: phase rounding perturbs the sums orders of magnitude
    # below the pilot residuals the band measures, and the XLA path is
    # what the paper's "fit is one featurization GEMM" story models
    def feat_sums(xb, wt):
        t = jnp.matmul(xb, wt, precision=lax.Precision.HIGHEST)
        return jnp.cos(t).sum(0), jnp.sin(t).sum(0)

    feat = jax.jit(feat_sums)
    x32 = jnp.asarray(x, jnp.float32)
    w32 = jnp.asarray(w.T, jnp.float32)
    z_cos = np.zeros(n_half)
    z_sin = np.zeros(n_half)
    for off in range(0, n, _FIT_BLOCK):
        blk = x32[off:off + _FIT_BLOCK]
        if blk.shape[0] != _FIT_BLOCK:       # ragged tail: pad to one shape
            pad = _FIT_BLOCK - blk.shape[0]
            zc, zs = feat(jnp.pad(blk, ((0, pad), (0, 0))), w32)
            # padded rows contribute cos(0)=1 per frequency — subtract
            z_cos += np.asarray(zc, np.float64) - pad
            z_sin += np.asarray(zs, np.float64)
        else:
            zc, zs = feat(blk, w32)
            z_cos += np.asarray(zc, np.float64)
            z_sin += np.asarray(zs, np.float64)

    state = RFFState(
        h=h_rff, d=d, n=n, groups=groups, seed=seed,
        npp=gaussian_norm_const(d, 1.0) * h_rff ** d,
        w=w, z_cos=z_cos, z_sin=z_sin, centroids=centroids,
        pilot_n=pilot_n, pilot_s1=pilot_s1, pilot_ss=pilot_ss,
    )
    # band floor scale: the tier's own density at a train subsample — the
    # high percentile is the "typical peak" the tail floor is relative to
    sample = x[rng.choice(n, size=min(_P_SCALE_SAMPLE, n), replace=False)]
    p, _ = eval_density(state.serving(),
                        jnp.asarray(sample, jnp.float32))
    state.p_scale = float(np.percentile(np.asarray(p), _P_SCALE_PCT))
    state._serving = None          # rebuild with the real floor
    return state


def update(state: RFFState, added=None, removed=None) -> None:
    """Fold a streaming delta into the accumulators — O(b·D·d/2).

    ``added``/``removed`` are (b, d) point batches.  Sums are exact, so
    updates commute; eviction subtracts exactly what an earlier append
    (or the fit) added, because pilot assignment is argmin-to-anchor on
    both sides.  Invalidates the cached serving tensors.
    """
    for sign, pts in ((1.0, added), (-1.0, removed)):
        if pts is None:
            continue
        p = np.asarray(pts, np.float64)
        if p.size == 0:
            continue
        p = np.atleast_2d(p)
        for off in range(0, p.shape[0], _FIT_BLOCK):
            blk = p[off:off + _FIT_BLOCK]
            t = blk @ state.w.T
            state.z_cos += sign * np.cos(t).sum(0)
            state.z_sin += sign * np.sin(t).sum(0)
            d2 = ((blk[:, None, :] - state.centroids[None]) ** 2).sum(-1)
            lab = d2.argmin(1)
            state.pilot_n += sign * np.bincount(
                lab, minlength=state.centroids.shape[0])
            np.add.at(state.pilot_s1, lab, sign * blk)
            state.pilot_ss += sign * np.bincount(
                lab, weights=(blk * blk).sum(1),
                minlength=state.centroids.shape[0])
            state.n += int(sign * blk.shape[0])
    state.pilot_n = np.maximum(state.pilot_n, 0.0)
    state._serving = None


def _finalize(state: RFFState) -> RFFServing:
    """Exact accumulators → f32 serving tensors (residuals, pilot form)."""
    nk = state.pilot_n
    live = nk > 0
    mu = np.zeros_like(state.pilot_s1)
    mu[live] = state.pilot_s1[live] / nk[live, None]
    var = np.zeros_like(nk)
    var[live] = np.maximum(
        state.pilot_ss[live] / nk[live] - (mu[live] ** 2).sum(1), 0.0
    ) / state.d
    h2 = state.h * state.h
    s2 = h2 + var
    beta = np.where(live, nk * (h2 / s2) ** (state.d / 2.0), 0.0)

    # analytic pilot characteristic-function sums → residual feature sums
    w2 = (state.w ** 2).sum(1)                       # (D/2,)
    att = np.exp(-var[None, :] * w2[:, None] / 2.0)  # (D/2, K)
    tm = state.w @ mu.T                              # (D/2, K)
    amp = np.where(live, nk, 0.0)[None, :] * att
    zpc = (amp * np.cos(tm)).sum(1)
    zps = (amp * np.sin(tm)).sum(1)

    return RFFServing(
        wt=jnp.asarray(state.w.T, jnp.float32),
        res_cos=jnp.asarray(state.z_cos - zpc, jnp.float32),
        res_sin=jnp.asarray(state.z_sin - zps, jnp.float32),
        mu=jnp.asarray(mu, jnp.float32),
        beta=jnp.asarray(beta, jnp.float32),
        inv2s2=jnp.asarray(1.0 / (2.0 * s2), jnp.float32),
        norm=jnp.float32(max(state.n, 1) * state.npp),
        p_floor=jnp.float32(TAIL_FRAC * max(state.p_scale, 0.0)),
        groups=state.groups,
    )


def _feature_phases(y: jnp.ndarray, wt: jnp.ndarray,
                    precision: str) -> jnp.ndarray:
    """The (m, D/2) phase GEMM ``y @ wt`` at a GEMM-operand tier.

    The one MXU-shaped op of the tier — same operand-cast discipline as
    the exact kernels (``kernels/precision.py``): reduced tiers perturb
    the phases like a data perturbation; trig and everything after stay
    f32.
    """
    y_hi, y_lo = prec.cast_operand(y, precision)
    w_hi, w_lo = prec.cast_operand(wt, precision)
    if y_lo is not None:
        return prec.gram_compensated(y_hi, y_lo, w_hi, w_lo)
    return prec.dot_f32(y_hi, w_hi)


def eval_density(serving: RFFServing, y: jnp.ndarray, *,
                 precision: str = "f32",
                 z: float = BAND_Z) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Densities and certified relative bands for a query batch.

    Pure in ``(serving, y)`` — safe to close over nothing and jit with
    ``serving`` passed as a pytree argument.  Returns ``(p, band)``, both
    (m,): ``p`` clipped at 0, ``band`` the Z-sigma relative band against
    the tail-floored denominator (the cascade compares it to the
    request's accuracy target).
    """
    y = jnp.asarray(y, jnp.float32)
    t = _feature_phases(y, serving.wt, precision)      # (m, D/2)
    contrib = (jnp.cos(t) * serving.res_cos
               + jnp.sin(t) * serving.res_sin)         # (m, D/2)
    m = contrib.shape[0]
    g = serving.groups
    per_group = contrib.reshape(m, g, -1).mean(axis=2)  # (m, g)

    # analytic pilot kernel sum at the queries: tiny (m, K) pass
    d2 = (jnp.sum(y * y, axis=1, keepdims=True)
          + jnp.sum(serving.mu * serving.mu, axis=1)[None, :]
          - 2.0 * prec.dot_f32(y, serving.mu.T))
    s_pilot = jnp.sum(serving.beta[None, :]
                      * jnp.exp(-jnp.maximum(d2, 0.0)
                                * serving.inv2s2[None, :]), axis=1)

    p_g = (s_pilot[:, None] + per_group) / serving.norm
    p_hat = jnp.mean(p_g, axis=1)
    err = z * jnp.std(p_g, axis=1, ddof=1) / np.sqrt(g)   # absolute band
    # the realized error is relative to the TRUE density, which is at least
    # p̂ − err: dividing by p̂ itself understates the band wherever p̂
    # overestimates (a tail query read 4x too high broke it by 1.7)
    denom = jnp.maximum(p_hat - err, serving.p_floor)
    band = err / denom
    return jnp.maximum(p_hat, 0.0), band


def realized_error(p_hat, p_exact, p_scale: float) -> np.ndarray:
    """The tail-floored relative error the band certifies against.

    One definition, used by the cascade tests and the acceptance
    benchmark alike: ``|p̂ − p| / max(p, TAIL_FRAC·p_scale)``.
    """
    p_hat = np.asarray(p_hat, np.float64)
    p_exact = np.asarray(p_exact, np.float64)
    return np.abs(p_hat - p_exact) / np.maximum(
        p_exact, TAIL_FRAC * max(p_scale, 0.0))


def modeled_query_cost_us(rows: int, d: int, *,
                          n_features: int = DEFAULT_FEATURES,
                          n_pilot: int = 0,
                          precision: str = "f32") -> float:
    """Modeled per-batch step time of the RFF tier, microseconds.

    Reuses the autotune pair-pass cost model with the feature matrix as
    the "train" operand — the tier's hot loop IS a (m×d)@(d×D/2) pass
    with an elementwise plane on top.  The ×2 covers the cos+sin planes
    (two VPU transcendental passes over the (m, D/2) phase plane where
    the exact kernel runs one exp).  ``n_pilot`` adds one (m, K)
    pilot-mixture pass — negligible at the K≈256 default (the planner
    omits it), but a real fraction of the feature GEMM once K rivals
    D/2, so cost-sensitive callers pass their pilot size.
    """
    from repro.kernels import autotune

    def _pass(cols: int) -> float:
        block_n = min(512, max(128, cols))
        c = autotune.modeled_cost(rows, cols, d, block_m=128,
                                  block_n=block_n, precision=precision)
        if c is None:                   # over VMEM: model at minimum tile
            c = autotune.modeled_cost(rows, cols, d, block_m=8,
                                      block_n=128, precision=precision)
        return c.step_time

    t = 2.0 * _pass(n_features // 2)
    if n_pilot > 0:
        t += _pass(n_pilot)
    return 1e6 * t


__all__ = [
    "DEFAULT_FEATURES", "DEFAULT_PILOT", "DEFAULT_GROUPS", "BAND_Z",
    "TAIL_FRAC", "H_SCALE", "RFFServing", "RFFState", "supports", "fit",
    "update", "eval_density", "realized_error", "modeled_query_cost_us",
]
