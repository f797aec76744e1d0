"""The delta score pass: incremental (S0, S1) maintenance.

SD-KDE's debias shift of point i is a function of the score statistics

    S0_i = Σ_j φ(x_i, x_j)        S1_i = Σ_j φ(x_i, x_j) · x_j

over the *whole* live set — so appending or evicting points perturbs every
other point's statistics, and a naive refresh is the full O(n²·d) pass the
streaming layer exists to avoid.  But the perturbation is a *sum of the
changed points' contributions*: an append adds ``Σ_{b∈batch} φ(x_i, b)``
to S0_i (one O(n·b·d) cross GEMM), an eviction subtracts the same terms.

Two numeric choices make the incremental stats track a from-scratch pass:

  * **φ in f32, exactly as the dense pass computes it** — GEMM-form
    distances with the norm trick, clamped at 0 — so each individual term
    matches the refit's to f32 rounding.
  * **accumulation in float64** — the running S0/S1 live in f64, so a long
    interleaving of ``+=`` / ``-=`` cancels to f64 rounding instead of
    compounding f32 error, and an append-then-evict round trip restores
    the statistics to ~1e-16 relative.

Everything here is also the basis of ``core.estimator.SDKDE.append`` — the
offline face of the same math.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np


@jax.jit
def _phi_cross(a: jnp.ndarray, b: jnp.ndarray, inv2h2) -> jnp.ndarray:
    """f32 kernel weights φ(a_i, b_j), GEMM-form (matches the dense pass)."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    an = jnp.sum(a * a, axis=-1)[:, None]
    bn = jnp.sum(b * b, axis=-1)[None, :]
    g = jnp.matmul(a, b.T, precision=lax.Precision.HIGHEST)
    sq = jnp.maximum(an + bn - 2.0 * g, 0.0)
    return jnp.exp(-sq * inv2h2)


def cross_stats(
    a: np.ndarray,
    b: np.ndarray,
    sh: float,
    *,
    block: int = 4096,
) -> Tuple[np.ndarray, np.ndarray]:
    """(ΔS0, ΔS1): the contributions of point set ``b`` to ``a``'s stats.

    Returns float64 ``(len(a),)`` and ``(len(a), d)`` arrays, f64-summed
    from f32 kernel weights.  Blocked on both axes so the φ working set
    stays ≤ block² regardless of how large either side is.
    """
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    na, d = a.shape
    inv2h2 = jnp.float32(1.0 / (2.0 * float(sh) ** 2))
    s0 = np.zeros(na, np.float64)
    s1 = np.zeros((na, d), np.float64)
    for i in range(0, na, block):
        ai = a[i:i + block]
        for j in range(0, b.shape[0], block):
            bj = b[j:j + block]
            phi = np.asarray(_phi_cross(ai, bj, inv2h2), np.float64)
            s0[i:i + block] += phi.sum(axis=1)
            s1[i:i + block] += phi @ bj.astype(np.float64)
    return s0, s1


def initial_stats(
    x: np.ndarray, sh: float, *, block: int = 4096
) -> Tuple[np.ndarray, np.ndarray]:
    """Full (S0, S1) of a point set against itself (the stream's one full
    pass, at fit time — every later update is a delta)."""
    return cross_stats(x, x, sh, block=block)


def append_delta(
    x_live: np.ndarray,
    x_new: np.ndarray,
    sh: float,
    *,
    block: int = 4096,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Stat updates for appending ``x_new`` to a live set ``x_live``.

    Returns ``(ds0_live, ds1_live, s0_new, s1_new)``: the deltas to *add*
    to the existing points' statistics, and the new points' own full
    statistics over the post-append set (existing + batch, including the
    within-batch and self terms φ=1 — exactly the terms a from-scratch
    pass over the grown set would include).
    """
    ds0, ds1 = cross_stats(x_live, x_new, sh, block=block)
    s0_new_a, s1_new_a = cross_stats(x_new, x_live, sh, block=block)
    s0_new_b, s1_new_b = cross_stats(x_new, x_new, sh, block=block)
    return ds0, ds1, s0_new_a + s0_new_b, s1_new_a + s1_new_b


def evict_delta(
    x_keep: np.ndarray,
    x_out: np.ndarray,
    sh: float,
    *,
    block: int = 4096,
) -> Tuple[np.ndarray, np.ndarray]:
    """Stat updates for evicting ``x_out``: the deltas to *subtract* from
    the kept points' statistics (the evicted rows' stats are dropped)."""
    return cross_stats(x_keep, x_out, sh, block=block)


def apply_shift(
    x: np.ndarray,
    s0: np.ndarray,
    s1: np.ndarray,
    h: float,
    sh: float,
) -> np.ndarray:
    """f64 debiased positions x^SD = x + (h²/2)·(S1 − x·S0)/(sh²·S0).

    Same formula as ``kernels.ops._apply_score_shift``; f64 end to end so
    a point whose statistics did not change reproduces its previous
    position bit-for-bit (the streaming layer's clean-tile invariant).
    """
    x64 = np.asarray(x, np.float64)
    s0c = s0[:, None]
    score = (s1 - x64 * s0c) / (float(sh) ** 2 * s0c)
    return x64 + 0.5 * float(h) ** 2 * score


__all__ = [
    "cross_stats", "initial_stats", "append_delta", "evict_delta",
    "apply_shift",
]
