"""Plain SD-KDE reference, written from the paper's equations.

It imports nothing of the program and takes nothing the program made: the
bandwidth comes from the raw points by the SD-KDE rule, the score pass and
the density pass are straightforward ``jax.numpy``, streamed in blocks so any
n fits one device.  Squared distances are summed from coordinate
differences, not from the Gram form |a|^2 + |b|^2 - 2 a.b, whose f32
cancellation would put the reference's own error at the size of the limits
it is held to; the one matmul left, the score numerator, runs at the
highest precision.

    h      = (4/(d+2))^(1/(d+4)) * n^(-1/(d+8)) * mean_k std(x[:, k])
    phi_ij = exp(-|x_i - x_j|^2 / (2 h^2))
    s(x_i) = (sum_j phi_ij x_j - x_i sum_j phi_ij) / (h^2 sum_j phi_ij)
    x_sd   = x + (h^2 / 2) s(x)
    p(y)   = sum_i exp(-|y - x_sd_i|^2 / (2 h^2)) / (n (2 pi)^(d/2) h^d)

Block sums are carried with Kahan compensation, so the reference's own
rounding stays well under the limits it is held to.
"""

from __future__ import annotations

import functools
import math

import numpy as np

#: Rows evaluated per device call, and train columns per streamed block.
ROW_CHUNK = 8192
COL_BLOCK = 4096
#: Sentinel coordinate for padding: its kernel weight underflows to 0.
PAD = 1.0e6


def sdkde_bandwidth(x: np.ndarray) -> float:
    """The SD-KDE bandwidth rule, in float64 from the raw points."""
    x64 = np.asarray(x, np.float64)
    n, d = x64.shape
    sigma = float(x64.std(axis=0).mean())
    return (4.0 / (d + 2.0)) ** (1.0 / (d + 4.0)) * n ** (-1.0 / (d + 8.0)) \
        * sigma


def _blocks(x, block: int):
    import jax.numpy as jnp

    n, d = x.shape
    pad = (-n) % block
    xp = jnp.pad(x, ((0, pad), (0, 0)), constant_values=PAD)
    return xp.reshape(-1, block, d)


@functools.lru_cache(maxsize=None)
def _programs():
    """The two jitted passes (built once)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    prec = lax.Precision.HIGHEST

    def sqdist(a, b):
        bt = b.T
        acc = jnp.zeros((a.shape[0], b.shape[0]), jnp.float32)
        for k in range(a.shape[1]):
            t = a[:, k:k + 1] - bt[k:k + 1, :]
            acc = acc + t * t
        return acc

    def kahan(carry, term):
        s, c = carry
        y = term - c
        t = s + y
        return t, (t - s) - y

    @jax.jit
    def score_stats(rows, xb, inv2h2):
        d = rows.shape[1]

        def step(carry, blk):
            (s0, c0), (s1, c1) = carry
            phi = jnp.exp(-sqdist(rows, blk) * inv2h2)
            s0c = kahan((s0, c0), jnp.sum(phi, axis=1))
            s1c = kahan((s1, c1), jnp.matmul(phi, blk, precision=prec))
            return (s0c, s1c), None

        m = rows.shape[0]
        z0 = jnp.zeros((m,), jnp.float32)
        z1 = jnp.zeros((m, d), jnp.float32)
        ((s0, _), (s1, _)), _ = lax.scan(step, ((z0, z0), (z1, z1)), xb)
        return s0, s1

    @jax.jit
    def density_sums(rows, xb, inv2h2):
        def step(carry, blk):
            return kahan(carry, jnp.sum(
                jnp.exp(-sqdist(rows, blk) * inv2h2), axis=1)), None

        z = jnp.zeros((rows.shape[0],), jnp.float32)
        (s, _), _ = lax.scan(step, (z, z), xb)
        return s

    return score_stats, density_sums


def _pad_rows(a: np.ndarray, mult: int) -> np.ndarray:
    pad = (-a.shape[0]) % mult
    return np.pad(a, ((0, pad), (0, 0))) if pad else a


def debiased_points(x: np.ndarray, h: float) -> np.ndarray:
    """x_sd = x + (h^2/2) s(x), the score pass over every train point."""
    import jax.numpy as jnp

    score_stats, _ = _programs()
    x32 = np.asarray(x, np.float32)
    n = x32.shape[0]
    xb = _blocks(jnp.asarray(x32), COL_BLOCK)
    inv2h2 = jnp.float32(1.0 / (2.0 * h * h))
    chunk = min(ROW_CHUNK, n)
    rows = _pad_rows(x32, chunk)
    out = np.empty_like(rows)
    for i in range(0, rows.shape[0], chunk):
        r = rows[i:i + chunk]
        s0, s1 = score_stats(jnp.asarray(r), xb, inv2h2)
        s0 = np.asarray(s0, np.float64)[:, None]
        s1 = np.asarray(s1, np.float64)
        score = (s1 - r * s0) / (h * h * s0)
        out[i:i + chunk] = r + 0.5 * h * h * score
    return out[:n]


def densities(x_sd: np.ndarray, y: np.ndarray, h: float) -> np.ndarray:
    """Normalized Gaussian KDE of the debiased points at the rows ``y``."""
    import jax.numpy as jnp

    _, density_sums = _programs()
    n, d = x_sd.shape
    xb = _blocks(jnp.asarray(x_sd, jnp.float32), COL_BLOCK)
    inv2h2 = jnp.float32(1.0 / (2.0 * h * h))
    y32 = np.asarray(y, np.float32)
    m = y32.shape[0]
    chunk = min(ROW_CHUNK, max(m, 1))
    rows = _pad_rows(y32, chunk)
    sums = np.concatenate([
        np.asarray(density_sums(jnp.asarray(rows[i:i + chunk]), xb, inv2h2),
                   np.float64)
        for i in range(0, rows.shape[0], chunk)])[:m]
    return sums / (n * (2.0 * math.pi) ** (d / 2.0) * h ** d)


def sdkde(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """SD-KDE densities at ``y`` from the raw train points ``x``."""
    h = sdkde_bandwidth(x)
    return densities(debiased_points(x, h), y, h)
