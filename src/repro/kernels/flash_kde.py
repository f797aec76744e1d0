"""Flash KDE evaluation kernel: Gaussian kernel sums at query points.

Computes p_j = Σ_i exp(-‖y_j - x_i‖²/(2h²)) for query rows y_j against the
(debiased) train set, streaming train column tiles through VMEM and
accumulating the (BLOCK_M, 1) partial sums in place across the innermost
grid dimension (sequential-grid accumulation — see flash_score.py).

The Gram tile (BLOCK_M×d)@(d×BLOCK_N) runs on the MXU; the exponential and
row reduction run on the VPU.  Normalization (1/(n (2π)^{d/2} h^d)) is
applied by the ops.py wrapper.

Mixed precision (kernels/precision.py): the Gram operands may arrive bf16
(full-rate MXU) or as split hi–lo bf16 pairs (``y_lo``/``xt_lo`` — the
compensated bf16x2 tier).  Norms, ``sq``, the exponential, and the
accumulator are f32 at every tier; ``sq`` is clamped at 0 so low-precision
Gram round-off can never turn a self-distance into exp overflow.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compiler_params, launch_interpret
from repro.kernels.precision import dot_f32, gram_compensated


def _kde_kernel(y_m_ref, nrm_m_ref, xt_n_ref, nrm_n_ref, inv2h2_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = dot_f32(y_m_ref[...], xt_n_ref[...])
    sq = jnp.maximum(nrm_m_ref[...] + nrm_n_ref[...] - 2.0 * g, 0.0)
    phi = jnp.exp(-sq * inv2h2_ref[0, 0])
    out_ref[...] += jnp.sum(phi, axis=1, keepdims=True)


def _kde_kernel_x2(y_hi_ref, y_lo_ref, nrm_m_ref, xt_hi_ref, xt_lo_ref,
                   nrm_n_ref, inv2h2_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = gram_compensated(y_hi_ref[...], y_lo_ref[...],
                         xt_hi_ref[...], xt_lo_ref[...])
    sq = jnp.maximum(nrm_m_ref[...] + nrm_n_ref[...] - 2.0 * g, 0.0)
    phi = jnp.exp(-sq * inv2h2_ref[0, 0])
    out_ref[...] += jnp.sum(phi, axis=1, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret")
)
def flash_kde_pallas(
    y: jnp.ndarray,        # (m, d)  queries, padded to block_m multiple
    nrm_y: jnp.ndarray,    # (m, 1)  f32
    xt: jnp.ndarray,       # (d, n)  train (transposed), padded to block_n
    nrm_x: jnp.ndarray,    # (1, n)  f32
    inv2h2: jnp.ndarray,   # (1, 1)  f32
    y_lo: jnp.ndarray | None = None,    # (m, d) bf16 lo plane (bf16x2)
    xt_lo: jnp.ndarray | None = None,   # (d, n) bf16 lo plane (bf16x2)
    *,
    block_m: int = 128,
    block_n: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Raw kernel launch; returns unnormalized sums (m, 1) f32."""
    m, d = y.shape
    n = xt.shape[1]
    assert m % block_m == 0 and n % block_n == 0, (m, n, block_m, block_n)
    assert (y_lo is None) == (xt_lo is None), "bf16x2 needs both lo planes"
    grid = (m // block_m, n // block_n)

    row = pl.BlockSpec((block_m, d), lambda i, j: (i, 0))
    nrm_row = pl.BlockSpec((block_m, 1), lambda i, j: (i, 0))
    col = pl.BlockSpec((d, block_n), lambda i, j: (0, j))
    nrm_col = pl.BlockSpec((1, block_n), lambda i, j: (0, j))
    scalar = pl.BlockSpec((1, 1), lambda i, j: (0, 0))

    if y_lo is None:
        kernel, in_specs = _kde_kernel, [row, nrm_row, col, nrm_col, scalar]
        args = (y, nrm_y, xt, nrm_x, inv2h2)
    else:
        kernel = _kde_kernel_x2
        in_specs = [row, row, nrm_row, col, col, nrm_col, scalar]
        args = (y, y_lo, nrm_y, xt, xt_lo, nrm_x, inv2h2)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        interpret=launch_interpret(interpret),
        compiler_params=compiler_params(),
    )(*args)
