"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``ref_*`` function computes exactly what the corresponding kernel
computes (same inputs, same outputs, same padding semantics), with no tiling
— the ground truth for the allclose sweeps in ``tests/test_kernels_*.py``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def _sqdist(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    an = jnp.sum(a.astype(jnp.float32) ** 2, axis=-1)[:, None]
    bn = jnp.sum(b.astype(jnp.float32) ** 2, axis=-1)[None, :]
    g = jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32).T,
                   precision=lax.Precision.HIGHEST)
    return an + bn - 2.0 * g


def ref_score_stats(x: jnp.ndarray, h: float):
    """(S0, S1): S0_i = Σ_j φ_ij, S1_i = Σ_j φ_ij x_j (train×train)."""
    sq = _sqdist(x, x)
    phi = jnp.exp(-sq / (2.0 * h * h))
    s0 = jnp.sum(phi, axis=1)
    s1 = jnp.matmul(phi, x.astype(jnp.float32),
                    precision=lax.Precision.HIGHEST)
    return s0, s1


def ref_kde_sums(x: jnp.ndarray, y: jnp.ndarray, h: float) -> jnp.ndarray:
    """Unnormalized KDE sums at queries: p_j = Σ_i φ(y_j, x_i)."""
    sq = _sqdist(y, x)
    return jnp.sum(jnp.exp(-sq / (2.0 * h * h)), axis=1)


def ref_laplace_sums(x: jnp.ndarray, y: jnp.ndarray, h: float) -> jnp.ndarray:
    """Unnormalized Laplace-corrected sums: Σ_i φ·(1 + d/2 − sqd/(2h²))."""
    d = x.shape[-1]
    sq = _sqdist(y, x)
    phi = jnp.exp(-sq / (2.0 * h * h))
    return jnp.sum(phi * (1.0 + d / 2.0 - sq / (2.0 * h * h)), axis=1)


def ref_sdkde_shift(x: jnp.ndarray, h: float, score_h: float | None = None):
    """Debiased samples via the empirical score (matches ops.flash_sdkde_shift)."""
    sh = h if score_h is None else score_h
    s0, s1 = ref_score_stats(x, sh)
    score = (s1 - x.astype(jnp.float32) * s0[:, None]) / (
        sh * sh * s0[:, None]
    )
    return x.astype(jnp.float32) + 0.5 * h * h * score


def ref_selective_scan(xi, dt, b, c, a, h0):
    """Oracle for kernels/selective_scan.py: plain sequential recurrence.

    h_t = exp(Δ_t A) ⊙ h_{t-1} + (Δ_t x_t)·B_t ;  y_t = C_t · h_t.
    Shapes: xi/dt (B,S,D), b/c (B,S,N), a (D,N), h0 (B,D,N).
    Returns (y (B,S,D) f32, h_final (B,D,N) f32).
    """
    xi = xi.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    b = b.astype(jnp.float32)
    c = c.astype(jnp.float32)
    a = a.astype(jnp.float32)

    def step(h, inputs):
        xi_t, dt_t, b_t, c_t = inputs
        decay = jnp.exp(dt_t[:, :, None] * a[None])        # (B,D,N)
        h = decay * h + (dt_t * xi_t)[:, :, None] * b_t[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    import jax

    h, ys = jax.lax.scan(
        step, h0.astype(jnp.float32),
        (xi.swapaxes(0, 1), dt.swapaxes(0, 1),
         b.swapaxes(0, 1), c.swapaxes(0, 1)),
    )
    return ys.swapaxes(0, 1), h
