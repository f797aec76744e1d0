"""Inputs made from the seed, on the device, in one jitted call each.

The configuration file states the data generator: an isotropic Gaussian
mixture (means, per-component std, weights), the paper's 16-d benchmark
family.  A draw is named by ``(seed, stream, index)``: the same names give
the same points, different names give independent ones.

Train points are the exception (``train_points``): every seed reflects one
shared draw about the mixture's axes of symmetry, so every seed gives the
program the same work.
"""

from __future__ import annotations

import functools

import numpy as np

#: Streams of one seed.  The window's jobs and requests, the set-up's warm
#: traffic and the train set never share a stream.
TRAIN, WINDOW, WARM, QUERY_POOL, SAMPLE = 1, 2, 3, 4, 5

#: The seed of the one train draw that every seed reflects.
SHARED_TRAIN_SEED = 0


def key(seed: int, stream: int, index: int = 0):
    """A PRNG key for any whole-number seed, including ones past 32 bits."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    for word in (seed >> 31, stream, index):
        k = jax.random.fold_in(k, word & 0x7FFFFFFF)
        k = jax.random.fold_in(k, word >> 31)
    return k


@functools.lru_cache(maxsize=None)
def _sampler(means: tuple, stds: tuple, weights: tuple, n: int):
    import jax
    import jax.numpy as jnp

    mu = jnp.asarray(np.array(means, np.float32))
    sd = jnp.asarray(np.array(stds, np.float32))
    w = jnp.asarray(np.array(weights, np.float32))

    @jax.jit
    def sample(k, signs):
        kc, kn = jax.random.split(k)
        comp = jax.random.choice(kc, mu.shape[0], shape=(n,), p=w)
        noise = jax.random.normal(kn, (n, mu.shape[1]), jnp.float32)
        return (mu[comp] + sd[comp][:, None] * noise) * signs

    return sample


def _draw(cfg: dict, k, n: int, signs: np.ndarray):
    mix = cfg["data"]["mixture"]
    to_t = lambda a: tuple(map(tuple, a)) if np.ndim(a) == 2 else tuple(a)  # noqa: E731
    return _sampler(to_t(mix["means"]), to_t(mix["stds"]),
                    to_t(mix["weights"]), int(n))(k, signs)


def mixture(cfg: dict, k, n: int):
    """``n`` points of the configuration's mixture, as a device array."""
    d = int(cfg["data"]["d"])
    return _draw(cfg, k, n, np.ones(d, np.float32))


def reflection(cfg: dict, seed: int) -> np.ndarray:
    """Signs (+1 or -1 per axis) drawn from the seed on every axis where all
    of the mixture's means are 0, +1 elsewhere.  The isotropic mixture is
    unchanged by flipping those axes."""
    free = np.all(np.asarray(cfg["data"]["mixture"]["means"]) == 0, axis=0)
    flips = np.random.default_rng([int(seed), TRAIN]).integers(
        0, 2, size=free.shape)
    return np.where(free & (flips == 1), -1.0, 1.0).astype(np.float32)


def train_points(cfg: dict, seed: int, n: int):
    """The train set of a seed: one draw of the mixture shared by every
    seed, reflected by ``reflection(cfg, seed)``.

    The program's fit compiles programs whose shapes follow the exact
    cluster sizes of the points (the padded pruning layout).  A reflection
    changes no distance, and flipping a sign is exact in floating point, so
    every seed's fit builds the same layout: the same work and the same
    compiled programs, while each seed still has its own points."""
    return _draw(cfg, key(SHARED_TRAIN_SEED, TRAIN), n,
                 reflection(cfg, seed))
