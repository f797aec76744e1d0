"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real single CPU device; a test that needs several host devices
forces them in a child process of its own."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")


@contextlib.contextmanager
def _visit_every_tile():
    """Under this the pruned path launches every column tile (the bounds
    prepass still runs; its verdict is ignored): the dense sums at the
    pruned kernels' own GEMM arithmetic, for checking that eps=0 skips
    only tiles whose every term is exactly zero."""
    from repro.kernels import autotune, spatial

    real = spatial.visit_lists
    spatial.visit_lists = lambda keep, **kw: real(jnp.ones_like(keep), **kw)
    try:
        yield
    finally:
        spatial.visit_lists = real
        autotune.clear_cache()      # forget the occupancy 1.0 it recorded


@pytest.fixture(scope="session")
def visit_every_tile():
    return _visit_every_tile


def _rel_errs(got, want, floor):
    """|got − want| / max(|want|, floor), per entry."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want) / np.maximum(np.abs(want), floor)


def _assert_as_accurate(got, highest, want, factor=2.0, max_factor=5.0):
    """``got`` errs against the float64 ``want`` by at most ``factor`` times
    what the f32 HIGHEST path ``highest`` errs on the mean over the
    entries, and by at most ``max_factor`` times on the largest entry;
    each entry's error relative to max(|want|, 1e-6 · peak).  How the f32
    tier's packed pruned kernels are held to the dense kernels, whose GEMM
    rounding they do not share.  The largest entry's error of two
    roundings of the same sums varies by several times from draw to draw
    (up to 3.9× over 240 draws of the planner's case; the mean, 1.26×),
    so it has the wider bar; it keeps any one row from drifting unseen
    in the mean."""
    floor = 1e-6 * float(np.max(np.abs(np.asarray(want))))
    e_got = _rel_errs(got, want, floor)
    e_hi = _rel_errs(highest, want, floor)
    assert e_got.mean() <= factor * max(e_hi.mean(), 1e-8), (
        e_got.mean(), e_hi.mean())
    assert e_got.max() <= max_factor * max(e_hi.max(), 1e-8), (
        e_got.max(), e_hi.max())


@pytest.fixture(scope="session")
def assert_as_accurate():
    return _assert_as_accurate


def _sums64(x, y, h):
    """float64 (φ, sq/(2h²)) of every (query, train) pair."""
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    sq = ((y[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    scaled = sq / (2.0 * h * h)
    return np.exp(-scaled), scaled


def _by_rows(fn, y, rows=256):
    """``fn`` over blocks of ``rows`` query rows, concatenated: each row's
    sums are independent, and a block bounds the (rows, n, d) float64
    temporaries."""
    y = np.asarray(y)
    parts = [fn(y[i:i + rows]) for i in range(0, len(y), rows)]
    return tuple(np.concatenate(p) for p in zip(*parts))


class F64:
    """float64 references of what the kernels' wrappers return."""

    @staticmethod
    def kde(x, y, h):
        n, d = np.shape(x)
        (s,) = _by_rows(lambda yb: (_sums64(x, yb, h)[0].sum(1),), y)
        return s / (n * (2 * np.pi) ** (d / 2) * h ** d)

    @staticmethod
    def laplace(x, y, h):
        n, d = np.shape(x)

        def sums(yb):
            phi, scaled = _sums64(x, yb, h)
            return ((phi * (1 + d / 2 - scaled)).sum(1),)

        (s,) = _by_rows(sums, y)
        return s / (n * (2 * np.pi) ** (d / 2) * h ** d)

    @staticmethod
    def score(x, h):
        """(S0, S1) of the score pass, train against itself."""
        x64 = np.asarray(x, np.float64)

        def stats(xb):
            phi, _ = _sums64(x64, xb, h)
            return phi.sum(1), phi @ x64

        return _by_rows(stats, x64)


@pytest.fixture(scope="session")
def f64():
    return F64
