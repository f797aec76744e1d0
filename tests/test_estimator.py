"""The estimator layer the benchmark drives: ``KDE``, ``SDKDE`` and
``LaplaceKDE`` (``core/estimator.py``) on every backend, tier and pruning
mode the cells run, held to a plain float64 reference; the ring backend
on one device; incremental updates; the benchmark mixture the cells copy;
and the OOD monitor built on the estimator.

The data are clustered draws of ``core.mixtures.mixture_for_dim(d)``:
3000 train points and 300 queries, neither a multiple of the 32 × 128
tiles, so 24 column tiles.  Each ``h`` is small enough that exact pruning
(``prune=0.0``) skips column tiles, which every such case asserts from the
``kernels.pruned_*`` spans' ``occupancy``.
"""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.estimator import KDE, SDKDE, EstimatorConfig, LaplaceKDE
from repro.core.mixtures import mixture_for_dim
from repro.core.monitor import ActivationMonitor, pool_activations
from repro.data.density import DensityWeighting, density_weights

N, M = 3000, 300
TILES = dict(block_m=32, block_n=128)
H = {1: 0.2, 2: 0.25, 16: 0.45}
CLASSES = {"KDE": KDE, "SDKDE": SDKDE, "LaplaceKDE": LaplaceKDE}

#: Every path a cell can take through the estimator: the jnp reference
#: path, and the Pallas kernels at the f32 and bf16x2 tiers, dense and
#: exactly pruned.
PATHS = {
    "jnp": dict(backend="jnp"),
    "f32-dense": dict(backend="pallas", precision="f32", prune="off"),
    "f32-eps0": dict(backend="pallas", precision="f32", prune=0.0),
    "bf16x2-dense": dict(backend="pallas", precision="bf16x2", prune="off"),
    "bf16x2-eps0": dict(backend="pallas", precision="bf16x2", prune=0.0),
}

#: (rtol, atol as a fraction of the peak) against float64: the tiers'
#: documented bars (tests/test_precision_autotune.py's TIER_TOL).
TIER_BAR = {"f32": (2e-4, 1e-6), "bf16x2": (1e-4, 1e-5)}

#: The path whose error each path's error is held to (``assert_as_accurate``):
#: the two f32 HIGHEST paths to each other, the packed pruned f32 kernels
#: to HIGHEST, and the pruned bf16x2 kernels to the dense ones.  The dense
#: bf16x2 path, whose operands keep ~16 of f32's 24 mantissa bits, is held
#: to its tier's bar alone.
AS_ACCURATE_AS = {"jnp": "f32-dense", "f32-dense": "jnp", "f32-eps0": "jnp",
                  "bf16x2-eps0": "bf16x2-dense"}


@pytest.fixture(autouse=True)
def _traced():
    m0, t0 = obs.state.metrics_on, obs.state.trace_on
    obs.configure(metrics=True, trace=True)
    obs.clear_trace()
    yield
    obs.configure(metrics=m0, trace=t0)
    obs.clear_trace()


def _config(path):
    return EstimatorConfig(**PATHS[path], **TILES)


def _tier(path):
    return PATHS[path].get("precision", "f32")


def _traced_occupancy(fn):
    """``fn()``'s result, and the ``occupancy`` of every pruned launch it
    made."""
    obs.clear_trace()
    out = np.asarray(fn())
    occ = [e["attrs"]["occupancy"] for e in obs.trace_events()
           if e["name"].startswith("kernels.pruned_")]
    return out, occ


def _assert_skips_tiles(occ):
    """The pruned kernels ran, and skipped at least one column tile."""
    assert occ and min(occ) < 1.0, occ


@functools.lru_cache(maxsize=None)
def _data(d):
    kx, ky = jax.random.split(jax.random.PRNGKey(d))
    mix = mixture_for_dim(d)
    return (np.asarray(mix.sample(kx, N), np.float32),
            np.asarray(mix.sample(ky, M), np.float32))


@functools.lru_cache(maxsize=None)
def _estimate(cls, d, path):
    x, y = _data(d)
    fit = lambda: CLASSES[cls](H[d], _config(path)).fit(x).evaluate(y)  # noqa
    return _traced_occupancy(fit)


def _f64_estimate(f64, cls, x, y, h):
    """The plain float64 estimate: SD-KDE's shift x + (h²/2)·ŝ(x), with
    ŝ = (S1 − x·S0) / (h²·S0), then the Gaussian (or Laplace) KDE."""
    x = np.asarray(x, np.float64)
    if cls == "SDKDE":
        s0, s1 = f64.score(x, h)
        x = x + 0.5 * (s1 / s0[:, None] - x)
    return (f64.laplace if cls == "LaplaceKDE" else f64.kde)(x, y, h)


@functools.lru_cache(maxsize=None)
def _reference(f64, cls, d):
    x, y = _data(d)
    return _f64_estimate(f64, cls, x, y, H[d])


# -- A: every path against the float64 reference -----------------------------


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("d", sorted(H))
@pytest.mark.parametrize("cls", list(CLASSES))
def test_matches_the_plain_reference(cls, d, path, f64, assert_as_accurate):
    got, occ = _estimate(cls, d, path)
    want = _reference(f64, cls, d)
    rtol, atol_frac = TIER_BAR[_tier(path)]
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=atol_frac * np.max(np.abs(want)))
    if path in AS_ACCURATE_AS:
        assert_as_accurate(got, _estimate(cls, d, AS_ACCURATE_AS[path])[0],
                           want)
    if PATHS[path].get("prune") == 0.0:
        _assert_skips_tiles(occ)
    else:
        assert occ == []


# -- B: the ring backend on one device -----------------------------------------


@pytest.mark.parametrize("d", [2, 16])
@pytest.mark.parametrize("prune", ["off", 0.0])
@pytest.mark.parametrize("precision", ["f32", "bf16x2"])
@pytest.mark.parametrize("cls", ["KDE", "SDKDE"])
def test_ring_on_one_device_is_the_pallas_path(cls, precision, prune, d):
    """On one device the sharded Flash path is the pallas path, bit for
    bit, pruning included.  (``LaplaceKDE``'s ring branch is the XLA ring,
    which takes neither a tier nor pruning; the four-device run is
    tests/test_shard_kde.py's.)"""
    assert jax.device_count() == 1
    path = f"{precision}-{'eps0' if prune == 0.0 else 'dense'}"
    x, y = _data(d)
    cfg = EstimatorConfig(backend="ring", precision=precision, prune=prune,
                          **TILES)
    got, occ = _traced_occupancy(
        lambda: CLASSES[cls](H[d], cfg).fit(x).evaluate(y))
    want, _ = _estimate(cls, d, path)
    np.testing.assert_array_equal(got, want)
    if prune == 0.0:
        _assert_skips_tiles(occ)


# -- C: incremental updates against a refit ------------------------------------

#: (rtol, atol as a fraction of the peak) of an updated estimator against
#: a refit: the streaming bars of tests/test_streaming.py.  The update's
#: shift comes from float64 statistics, the refit's from the tier's own
#: score pass.
REFIT_BAR = {"f32": (1e-5, 1e-6), "bf16x2": (5e-4, 1e-5)}
D_C, N_C = 2, 2800


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("op", ["append", "evict"])
def test_append_then_evict_matches_a_refit(op, path):
    """The live set is the first 2800 train points or all 3000: appending
    the last 200 to a fit of the first, or evicting them from a fit of
    all, against a fresh fit of what is left."""
    x, y = _data(D_C)
    est = SDKDE(H[D_C], _config(path))
    if op == "append":
        est.fit(x[:N_C]).append(x[N_C:])
        live, (want, _) = x, _estimate("SDKDE", D_C, path)
    else:
        est.fit(x).evict(np.arange(N_C, N))
        live = x[:N_C]
        want = np.asarray(SDKDE(H[D_C], _config(path)).fit(live).evaluate(y))
    np.testing.assert_array_equal(est.x_train, live)
    got, occ = _traced_occupancy(lambda: est.evaluate(y))
    rtol, atol_frac = REFIT_BAR[_tier(path)]
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=atol_frac * float(want.max()))
    if PATHS[path].get("prune") == 0.0:
        _assert_skips_tiles(occ)


# -- D: the benchmark mixture ----------------------------------------------------


def _mixture64(mix):
    return (np.asarray(mix.means, np.float64), np.asarray(mix.stds, np.float64),
            np.asarray(mix.weights, np.float64))


@pytest.mark.parametrize("check", ["moments", "score"])
@pytest.mark.parametrize("d", [1, 2, 4, 8, 16])
def test_benchmark_mixture(d, check):
    """``mixture_for_dim(d)``, the data ``bench/configs/*.json`` copy:
    its samples have the components' mean and covariance (within 5
    standard errors), and ``log_pdf`` and ``score`` are the mixture's
    closed forms, evaluated in float64."""
    mix = mixture_for_dim(d)
    mu, sd, w = _mixture64(mix)
    if check == "moments":
        n = 20000
        z = np.asarray(mix.sample(jax.random.PRNGKey(100 + d), n), np.float64)
        mean = w @ mu
        cov = sum(wk * (s * s * np.eye(d) + np.outer(m, m))
                  for wk, s, m in zip(w, sd, mu)) - np.outer(mean, mean)
        zc = z - z.mean(0)
        prods = zc[:, :, None] * zc[:, None, :]
        assert np.all(np.abs(z.mean(0) - mean)
                      <= 5 * np.sqrt(np.diag(cov) / n))
        assert np.all(np.abs(prods.mean(0) - cov)
                      <= 5 * prods.std(0) / np.sqrt(n))
        for cfg in Path(__file__).parents[1].glob("bench/configs/*.json"):
            data = json.loads(cfg.read_text())["data"]
            if data["d"] == d:
                m = data["mixture"]
                np.testing.assert_array_equal(m["means"], mu)
                np.testing.assert_array_equal(m["stds"], sd)
                np.testing.assert_array_equal(m["weights"], w)
    else:
        x = np.asarray(mix.sample(jax.random.PRNGKey(200 + d), 256))
        x64 = x.astype(np.float64)
        sq = ((x64[:, None, :] - mu[None]) ** 2).sum(-1)          # (m, k)
        log_comp = (np.log(w) - d * np.log(sd) - 0.5 * d * np.log(2 * np.pi)
                    - 0.5 * sq / sd ** 2)
        top = log_comp.max(1, keepdims=True)
        log_p = top[:, 0] + np.log(np.exp(log_comp - top).sum(1))
        resp = np.exp(log_comp - log_p[:, None])                  # (m, k)
        score = np.einsum("mk,mkd->md", resp / sd ** 2,
                          mu[None] - x64[:, None, :])
        np.testing.assert_allclose(np.asarray(jax.jit(mix.log_pdf)(x)),
                                   log_p, rtol=1e-6, atol=1e-5)
        np.testing.assert_allclose(np.asarray(jax.jit(mix.score)(x)),
                                   score, rtol=1e-5,
                                   atol=1e-5 * np.abs(score).max())


# -- E: the OOD monitor through the estimator ------------------------------------


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_monitor_flags_ood_through_the_estimator(backend):
    """Pooled synthetic activations — each sequence's hidden states are its
    latent vector plus per-token noise — fitted through ``SDKDE`` on the
    backend; shifted, widened sequences are flagged and in-distribution
    ones are not."""
    d, seq = 64, 16
    rng = np.random.default_rng(0)

    def hidden(batch, scale=1.0, shift=0.0):
        latent = rng.standard_normal((batch, 1, d)) * scale + shift
        return jnp.asarray(
            latent + 0.5 * rng.standard_normal((batch, seq, d)), jnp.float32)

    cfg = EstimatorConfig(backend=backend, **TILES)
    mon = ActivationMonitor(proj_dim=8, quantile=0.02, config=cfg)
    mon.fit(pool_activations(hidden(2000)))
    in_dist = pool_activations(hidden(200))
    ood = pool_activations(hidden(200, 4.0, 6.0))
    assert np.asarray(mon.flag(in_dist)).mean() < 0.15
    assert np.asarray(mon.flag(ood)).mean() > 0.9
    s_in = np.asarray(mon.score(in_dist)).mean()
    s_ood = np.asarray(mon.score(ood)).mean()
    assert s_in > s_ood + 5.0


# -- density weighting and bandwidth selection ---------------------------------


def test_density_weights_upweight_tails():
    key = jax.random.PRNGKey(0)
    dense = jax.random.normal(key, (400, 4)) * 0.1        # tight cluster
    sparse = jax.random.normal(jax.random.fold_in(key, 1), (40, 4)) * 3 + 5
    emb = jnp.concatenate([dense, sparse])
    w = density_weights(emb, alpha=0.5)
    assert float(w[400:].mean()) > 2.0 * float(w[:400].mean())
    assert abs(float(w.mean()) - 1.0) < 1e-3


def test_density_weighting_pipeline_stage():
    key = jax.random.PRNGKey(1)
    corpus = jax.random.normal(key, (500, 8))
    stage = DensityWeighting(alpha=0.5).fit(corpus)
    batch = jax.random.normal(jax.random.fold_in(key, 2), (64, 8))
    w = stage(batch)
    assert w.shape == (64,) and np.isfinite(np.asarray(w)).all()
    idx = stage.resample_indices(batch, jax.random.PRNGKey(3), 16)
    assert idx.shape == (16,) and len(set(np.asarray(idx).tolist())) == 16


def test_estimator_auto_bandwidth():
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 4))
    est = SDKDE().fit(x)
    assert est.h is not None and float(est.h) > 0
