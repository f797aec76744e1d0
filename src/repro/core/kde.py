"""Pure-JAX GEMM-form KDE / SD-KDE / Laplace-KDE (the reference path).

This module is the paper's computation expressed with `jnp` matmuls and a
streaming (chunked) accumulation so that the n×n pairwise matrices are never
materialized — the same re-ordering that enables Tensor Cores / the TPU MXU,
but at the XLA level.  The Pallas kernels in ``repro.kernels`` implement the
same math with explicit VMEM tiling; ``repro.distributed.ring`` shards it
over a device mesh.  All three paths agree to float tolerance (tested).

Math (Gaussian kernel, bandwidth h):

  p̂(y)    = 1/(n (2π)^{d/2} h^d) · Σ_i exp(-‖y-x_i‖²/(2h²))
  ŝ(x)    = Σ_j -(x-x_j)·φ_j(x) / (h² Σ_j φ_j(x))      [empirical score]
          = (S1(x) - x·S0(x)) / (h² S0(x)),   S0 = Σφ, S1 = Σφx_j
  x^SD    = x + (h²/2)·ŝ(x)
  K^LC(u) = K_h(u)·(1 + d/2 - ‖u‖²/(2h²))              [Laplace-corrected]

The GEMM structure: ‖x-y‖² = ‖x‖² + ‖y‖² - 2·x·y  (Gram matrix), and
S1 = Φ X (the score-numerator GEMM) — Section 4 of the paper.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.bandwidth import gaussian_norm_const

# Far-away coordinate used to pad point sets: exp(-‖pad - x‖²/(2h²)) == 0.0
# exactly in f32 for any realistic data scale, so padded points contribute
# nothing to any accumulated statistic.
PAD_VALUE = 1.0e6


def pad_rows(x: jnp.ndarray, block: int, value: float = PAD_VALUE) -> jnp.ndarray:
    """Pad the leading axis of ``x`` up to a multiple of ``block``."""
    n = x.shape[0]
    rem = (-n) % block
    if rem == 0:
        return x
    pad_width = [(0, rem)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad_width, constant_values=value)


def sqdist(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """GEMM-form pairwise squared distances, shape (n, m).

    ‖x_i - y_j‖² = ‖x_i‖² + ‖y_j‖² - 2 x_i·y_j — the re-ordering that maps
    the quadratic interaction onto a matrix multiply.
    """
    xn = jnp.sum(x * x, axis=-1)[:, None]
    yn = jnp.sum(y * y, axis=-1)[None, :]
    g = jnp.matmul(x, y.T, precision=lax.Precision.HIGHEST)
    return jnp.maximum(xn + yn - 2.0 * g, 0.0)


def _phi(sq: jnp.ndarray, h) -> jnp.ndarray:
    return jnp.exp(-sq / (2.0 * h * h))


# ---------------------------------------------------------------------------
# Streaming accumulation over train-column blocks.
# ---------------------------------------------------------------------------


def _stream_blocks(x_train: jnp.ndarray, block: int, body, init):
    """Fold ``body(carry, x_block)`` over column blocks of the train set.

    ``x_train`` is padded (with PAD_VALUE sentinels) to a block multiple and
    reshaped to (num_blocks, block, d); ``lax.scan`` streams the blocks so
    peak memory is O(rows · block) rather than O(rows · n).
    """
    xp = pad_rows(x_train, block)
    nb = xp.shape[0] // block
    xb = xp.reshape(nb, block, x_train.shape[-1])

    def step(carry, xblk):
        return body(carry, xblk), None

    carry, _ = jax.lax.scan(step, init, xb)
    return carry


# ---------------------------------------------------------------------------
# KDE evaluation.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block",))
def kde_eval(
    x_train: jnp.ndarray,
    y_query: jnp.ndarray,
    h,
    *,
    block: int = 1024,
) -> jnp.ndarray:
    """Gaussian KDE densities at ``y_query`` — streaming GEMM form."""
    n, d = x_train.shape

    def body(acc, xblk):
        sq = sqdist(y_query, xblk)
        return acc + jnp.sum(_phi(sq, h), axis=1)

    s = _stream_blocks(x_train, block, body, jnp.zeros(y_query.shape[0]))
    return s / (n * gaussian_norm_const(d, 1.0) * h**d)


def kde_eval_naive(x_train: jnp.ndarray, y_query: jnp.ndarray, h) -> jnp.ndarray:
    """Naive O(n·m·d) elementwise KDE (no GEMM re-ordering) — the slow
    baseline used in the Fig. 1 runtime reproduction."""
    n, d = x_train.shape
    diff = y_query[:, None, :] - x_train[None, :, :]
    sq = jnp.sum(diff * diff, axis=-1)
    s = jnp.sum(_phi(sq, h), axis=1)
    return s / (n * gaussian_norm_const(d, 1.0) * h**d)


# ---------------------------------------------------------------------------
# Empirical score and SD-KDE shift.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block",))
def score_stats(
    x_eval: jnp.ndarray,
    x_train: jnp.ndarray,
    h,
    *,
    block: int = 1024,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Streaming (S0, S1) = (Σ_j φ_ij, Σ_j φ_ij x_j) for rows ``x_eval``.

    This is the paper's score-numerator identity: instead of forming
    Σ_j (x_i - x_j) φ_ij elementwise, accumulate the GEMM T = Φ X and the
    row-sum S0, then combine as x_i·S0_i - S1_i.
    """
    m, d = x_eval.shape

    def body(carry, xblk):
        s0, s1 = carry
        sq = sqdist(x_eval, xblk)                       # (m, block) via GEMM
        phi = _phi(sq, h)
        s0 = s0 + jnp.sum(phi, axis=1)                  # Σ_j φ_ij
        s1 = s1 + jnp.matmul(phi, xblk,                 # Φ X   (MXU GEMM)
                             precision=lax.Precision.HIGHEST)
        return s0, s1

    init = (jnp.zeros(m), jnp.zeros((m, d)))
    return _stream_blocks(x_train, block, body, init)


def empirical_score(
    x_eval: jnp.ndarray,
    x_train: jnp.ndarray,
    h,
    *,
    block: int = 1024,
    eps: float = 1e-30,
) -> jnp.ndarray:
    """Empirical KDE score ŝ(x) = (S1 - x·S0) / (h² S0)."""
    s0, s1 = score_stats(x_eval, x_train, h, block=block)
    return (s1 - x_eval * s0[:, None]) / (h * h * s0[:, None] + eps)


def sdkde_shift(
    x_train: jnp.ndarray,
    h,
    *,
    score_h=None,
    block: int = 1024,
) -> jnp.ndarray:
    """Debiased samples x^SD = x + (h²/2)·ŝ(x).

    ``score_h`` is the bandwidth of the score-estimation KDE; the paper's
    Section-1 formula uses ``h`` (default), while the Section-5 semigroup
    analysis suggests ``h/sqrt(2)`` (``repro.core.bandwidth.score_bandwidth``).
    """
    sh = h if score_h is None else score_h
    s = empirical_score(x_train, x_train, sh, block=block)
    return x_train + 0.5 * h * h * s


def sdkde_eval(
    x_train: jnp.ndarray,
    y_query: jnp.ndarray,
    h,
    *,
    score_h=None,
    block: int = 1024,
) -> jnp.ndarray:
    """Full empirical SD-KDE: score pass + shift + KDE on debiased samples."""
    x_sd = sdkde_shift(x_train, h, score_h=score_h, block=block)
    return kde_eval(x_sd, y_query, h, block=block)


def sdkde_eval_oracle(
    x_train: jnp.ndarray,
    y_query: jnp.ndarray,
    h,
    oracle_score_fn,
    *,
    block: int = 1024,
) -> jnp.ndarray:
    """SD-KDE with an oracle score (ablation: removes score-estimation error)."""
    x_sd = x_train + 0.5 * h * h * oracle_score_fn(x_train)
    return kde_eval(x_sd, y_query, h, block=block)


# ---------------------------------------------------------------------------
# Laplace-corrected KDE (Section 5).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block",))
def laplace_kde_eval(
    x_train: jnp.ndarray,
    y_query: jnp.ndarray,
    h,
    *,
    block: int = 1024,
) -> jnp.ndarray:
    """Fused Laplace-corrected KDE (Flash-Laplace-KDE math).

    K^LC(u) = K_h(u)·(1 + d/2 - ‖u‖²/(2h²)); the affine factor is applied in
    the same streaming pass that computes the distances and exponentials.
    May be slightly negative for large ‖u‖ — by design (signed estimator).
    """
    n, d = x_train.shape
    c0 = 1.0 + d / 2.0

    def body(acc, xblk):
        sq = sqdist(y_query, xblk)
        phi = _phi(sq, h)
        return acc + jnp.sum(phi * (c0 - sq / (2.0 * h * h)), axis=1)

    s = _stream_blocks(x_train, block, body, jnp.zeros(y_query.shape[0]))
    return s / (n * gaussian_norm_const(d, 1.0) * h**d)


def laplace_kde_eval_nonfused(
    x_train: jnp.ndarray,
    y_query: jnp.ndarray,
    h,
    *,
    block: int = 1024,
) -> jnp.ndarray:
    """Non-fused Laplace correction: two separate quadratic passes.

    Pass 1 computes the plain KDE; pass 2 recomputes distances to form the
    Laplacian term Σ φ·‖u‖²/(2h²).  Statistically identical to the fused
    version (Fig. 2/3 overlap) but with ~2× the memory traffic and kernel
    launches — the baseline for the Fig. 4 fusion-speedup reproduction.
    """
    n, d = x_train.shape
    base = kde_eval(x_train, y_query, h, block=block)

    def body(acc, xblk):
        sq = sqdist(y_query, xblk)
        phi = _phi(sq, h)
        return acc + jnp.sum(phi * sq, axis=1)

    sq_term = _stream_blocks(x_train, block, body, jnp.zeros(y_query.shape[0]))
    sq_term = sq_term / (n * gaussian_norm_const(d, 1.0) * h**d)
    return base * (1.0 + d / 2.0) - sq_term / (2.0 * h * h)


__all__ = [
    "PAD_VALUE",
    "pad_rows",
    "sqdist",
    "kde_eval",
    "kde_eval_naive",
    "score_stats",
    "empirical_score",
    "sdkde_shift",
    "sdkde_eval",
    "sdkde_eval_oracle",
    "laplace_kde_eval",
    "laplace_kde_eval_nonfused",
]
