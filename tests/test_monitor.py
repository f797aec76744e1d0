"""Activation-density OOD monitor: separates in- from out-of-distribution."""

import jax
import numpy as np

from repro.core.monitor import ActivationMonitor, pool_activations


def test_pooling_shape():
    h = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 64))
    assert pool_activations(h).shape == (4, 64)


def test_monitor_flags_ood():
    key = jax.random.PRNGKey(0)
    d = 64
    ref = jax.random.normal(key, (2000, d))                # in-distribution
    mon = ActivationMonitor(proj_dim=8, quantile=0.02).fit(ref)

    in_dist = jax.random.normal(jax.random.fold_in(key, 1), (200, d))
    ood = jax.random.normal(jax.random.fold_in(key, 2), (200, d)) * 4 + 6

    flags_in = np.asarray(mon.flag(in_dist))
    flags_ood = np.asarray(mon.flag(ood))
    assert flags_in.mean() < 0.15, flags_in.mean()
    assert flags_ood.mean() > 0.9, flags_ood.mean()

    # scores are ordered: in-distribution scores higher on average
    s_in = np.asarray(mon.score(in_dist)).mean()
    s_ood = np.asarray(mon.score(ood)).mean()
    assert s_in > s_ood + 5.0

