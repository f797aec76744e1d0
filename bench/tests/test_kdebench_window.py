"""Window arithmetic: schedules, latency from the due time, tails, rates."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from kdebench import data, window  # noqa: E402


def test_percentile_is_nearest_rank_over_every_value():
    xs = list(range(1, 101))
    assert window.percentile(xs, 50) == 50
    assert window.percentile(xs, 95) == 95
    assert window.percentile(xs, 100) == 100
    assert window.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        window.percentile([], 50)


def test_rate_is_work_over_the_whole_window():
    assert window.rate(300, 10.0) == 30.0
    with pytest.raises(ValueError):
        window.rate(1, 0.0)


def test_latency_runs_from_the_due_time_and_a_stall_raises_the_tail():
    due = np.arange(100) * 0.1               # 10 requests/s
    service = 0.02
    # a server that keeps up: every answer 20 ms after its due time
    done = [t + service for t in due]
    lat = window.latencies_from_due(due, done, limit=1.0)
    assert window.percentile(lat, 95) == pytest.approx(service)
    # a 1.5 s stall at t=5 s: the requests due inside it are served one
    # after another once it ends, so each waits from its due time, not
    # from when it was finally sent
    stalled, free = [], 0.0
    for t in due:
        start = max(t, free, 6.5 if 5.0 <= t < 6.5 else 0.0)
        free = start + service
        stalled.append(free)
    lat2 = window.latencies_from_due(due, stalled, limit=10.0)
    assert window.percentile(lat2, 95) > 0.5
    assert window.percentile(lat2, 50) == pytest.approx(service)
    # timing from the send instead would hide the stall entirely
    sent = [d - service for d in stalled]
    assert max(d - s for s, d in zip(sent, stalled)) == pytest.approx(
        service)


def test_a_failed_request_counts_at_the_limit():
    lat = window.latencies_from_due([0.0, 1.0], [0.01, None], limit=1.0)
    assert lat == [pytest.approx(0.01), 1.0]


def test_every_seed_offers_the_same_load_in_another_order():
    arr = {"kind": "poisson", "rate": 25.0}
    outs = []
    for seed in (1, 2, 2 ** 33 + 5):
        rng = np.random.default_rng([seed, data.WINDOW])
        t = window.arrival_offsets(arr, 10.0, rng)
        sizes = window.log_uniform_sizes(window.strata(len(t), rng), 1, 4096)
        assert len(t) == 250 and t[0] == 0.0 and t[-1] < 10.0
        assert np.all(np.diff(t) > 0)
        assert sizes.min() >= 1 and sizes.max() <= 4096
        outs.append((np.sort(np.diff(np.append(t, 10.0))), np.sort(sizes)))
    for gaps, sizes in outs[1:]:
        np.testing.assert_allclose(gaps, outs[0][0])
        np.testing.assert_array_equal(sizes, outs[0][1])


def test_log_uniform_sizes_spread_over_decades():
    sizes = window.log_uniform_sizes(window.strata(
        1000, np.random.default_rng(0)), 1, 4096)
    for lo, hi in ((1, 10), (10, 100), (100, 1000), (1000, 4097)):
        share = np.mean((sizes >= lo) & (sizes < hi))
        assert 0.15 < share < 0.40


def test_an_unknown_arrival_kind_is_refused():
    with pytest.raises(ValueError):
        window.arrival_offsets({"kind": "on_off"}, 1.0,
                               np.random.default_rng(3))


def test_seeds_past_32_bits_give_distinct_repeatable_keys():
    import jax

    a = jax.random.key_data(data.key(2 ** 31 + 7, data.WINDOW, 0))
    b = jax.random.key_data(data.key(2 ** 31 + 7, data.WINDOW, 0))
    c = jax.random.key_data(data.key(7, data.WINDOW, 0))
    d = jax.random.key_data(data.key(2 ** 31 + 7, data.WINDOW, 1))
    assert (a == b).all() and not (a == c).all() and not (a == d).all()
    with pytest.raises(ValueError):
        data.key(-1, 0)


CFG = json.loads((BENCH / "configs" / "sdkde_1m_d16.json").read_text())


def test_every_seed_reflects_one_shared_train_draw():
    a = np.asarray(data.train_points(CFG, 2 ** 33 + 1, 512))
    b = np.asarray(data.train_points(CFG, 2 ** 33 + 1, 512))
    np.testing.assert_array_equal(a, b)
    signs = [data.reflection(CFG, s) for s in range(2, 10)]
    assert len({tuple(v) for v in signs}) > 1
    means = np.asarray(CFG["data"]["mixture"]["means"])
    for s, v in zip(range(2, 10), signs):
        assert set(v) <= {-1.0, 1.0}
        # only axes the mixture is symmetric about are ever flipped
        assert np.all(v[np.any(means != 0, axis=0)] == 1.0)
        c = np.asarray(data.train_points(CFG, s, 512))
        np.testing.assert_array_equal(c, a * data.reflection(
            CFG, 2 ** 33 + 1) * v)


def test_a_reflected_train_set_builds_the_same_pruning_layout():
    """Flipping signs is exact, so the program's clustering, and with it
    every shape its fit compiles, is the same for every seed."""
    from repro.kernels import spatial

    # past the program's k-means subsample (16384 points), as at 1M
    n = 32768
    k = spatial.default_n_clusters(n)
    sizes = []
    for seed in (11, 12, 2 ** 32 + 13):
        assert np.any(data.reflection(CFG, seed) < 0)
        index = spatial.build_index(data.train_points(CFG, seed, n))
        sizes.append(np.bincount(np.asarray(index.labels), minlength=k))
    for got in sizes[1:]:
        np.testing.assert_array_equal(got, sizes[0])
