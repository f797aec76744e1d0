"""Flash-Laplace-KDE kernels (fused + the non-fused second pass).

Fused kernel: applies the Laplace correction factor inside the same
distance/exponential pass as the plain KDE —

    out_j += Σ_i φ_ij · (1 + d/2 − sqd_ij/(2h²))

reusing the already-computed scaled distances, exactly the "kernel fusion
opportunity" of Section 5.  The non-fused baseline (Fig. 4) instead runs the
plain KDE kernel and then ``_sq_moment_kernel`` below, which *recomputes*
the distances to form Σ φ·sqd — a second full quadratic pass with its own
HBM traffic and launch, combined on the host as

    (1 + d/2)·S − M/(2h²),   S = Σφ,  M = Σφ·sqd.

Mixed precision: the Gram operands may arrive bf16 or as split hi–lo bf16
pairs (the ``*_lo`` planes — kernels/precision.py); the correction factor,
exponential, and accumulators stay f32 at every tier.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compiler_params, launch_interpret
from repro.kernels.precision import dot_f32, gram_compensated


def _sq_tile(y_ref, nrm_m_ref, xt_ref, nrm_n_ref, y_lo_ref=None,
             xt_lo_ref=None):
    """The f32 squared-distance tile at whatever operand tier the refs carry."""
    if y_lo_ref is None:
        g = dot_f32(y_ref[...], xt_ref[...])
    else:
        g = gram_compensated(y_ref[...], y_lo_ref[...],
                             xt_ref[...], xt_lo_ref[...])
    return jnp.maximum(nrm_m_ref[...] + nrm_n_ref[...] - 2.0 * g, 0.0)


def _make_laplace_kernel(compensated: bool):
    def kernel(*refs):
        if compensated:
            (y_ref, y_lo_ref, nrm_m_ref, xt_ref, xt_lo_ref, nrm_n_ref,
             inv2h2_ref, out_ref) = refs
        else:
            y_ref, nrm_m_ref, xt_ref, nrm_n_ref, inv2h2_ref, out_ref = refs
            y_lo_ref = xt_lo_ref = None

        @pl.when(pl.program_id(1) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        d = xt_ref.shape[0]
        sq = _sq_tile(y_ref, nrm_m_ref, xt_ref, nrm_n_ref, y_lo_ref,
                      xt_lo_ref)
        scaled = sq * inv2h2_ref[0, 0]            # ‖u‖²/(2h²), reused twice
        phi = jnp.exp(-scaled)
        corr = phi * (1.0 + d / 2.0 - scaled)     # fused Laplace factor
        out_ref[...] += jnp.sum(corr, axis=1, keepdims=True)

    return kernel


def _make_sq_moment_kernel(compensated: bool):
    def kernel(*refs):
        if compensated:
            (y_ref, y_lo_ref, nrm_m_ref, xt_ref, xt_lo_ref, nrm_n_ref,
             inv2h2_ref, out_ref) = refs
        else:
            y_ref, nrm_m_ref, xt_ref, nrm_n_ref, inv2h2_ref, out_ref = refs
            y_lo_ref = xt_lo_ref = None

        @pl.when(pl.program_id(1) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        sq = _sq_tile(y_ref, nrm_m_ref, xt_ref, nrm_n_ref, y_lo_ref,
                      xt_lo_ref)
        phi = jnp.exp(-sq * inv2h2_ref[0, 0])
        out_ref[...] += jnp.sum(phi * sq, axis=1, keepdims=True)

    return kernel


_LAPLACE = {False: _make_laplace_kernel(False), True: _make_laplace_kernel(True)}
_SQ_MOMENT = {False: _make_sq_moment_kernel(False),
              True: _make_sq_moment_kernel(True)}


def _launch(kernels, y, nrm_y, xt, nrm_x, inv2h2, y_lo, xt_lo,
            block_m, block_n, interpret):
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
        in_specs = [row, nrm_row, col, nrm_col, scalar]
        args = (y, nrm_y, xt, nrm_x, inv2h2)
    else:
        in_specs = [row, row, nrm_row, col, col, nrm_col, scalar]
        args = (y, y_lo, nrm_y, xt, xt_lo, nrm_x, inv2h2)

    return pl.pallas_call(
        kernels[y_lo is not None],
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        interpret=launch_interpret(interpret),
        compiler_params=compiler_params(),
    )(*args)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret")
)
def flash_laplace_pallas(y, nrm_y, xt, nrm_x, inv2h2, y_lo=None, xt_lo=None,
                         *, block_m: int = 128, block_n: int = 512,
                         interpret: Optional[bool] = None):
    """Fused Laplace-corrected sums (m, 1) f32 — one quadratic pass."""
    return _launch(_LAPLACE, y, nrm_y, xt, nrm_x, inv2h2, y_lo, xt_lo,
                   block_m, block_n, interpret)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret")
)
def sq_moment_pallas(y, nrm_y, xt, nrm_x, inv2h2, y_lo=None, xt_lo=None,
                     *, block_m: int = 128, block_n: int = 512,
                     interpret: Optional[bool] = None):
    """Second pass of the non-fused baseline: Σ φ·sqd (m, 1) f32."""
    return _launch(_SQ_MOMENT, y, nrm_y, xt, nrm_x, inv2h2, y_lo, xt_lo,
                   block_m, block_n, interpret)
