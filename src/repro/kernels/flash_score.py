"""Flash score kernel: the SD-KDE empirical-score hot spot on the TPU MXU.

Computes, for every training row i, the fused statistics

    S1aug_i = Σ_j φ_ij · [x_j | 1]  ∈ R^{d+1}

i.e. the score-numerator GEMM ``T = Φ X`` and the denominator row-sum
``S0 = Φ·1`` in a single MXU matmul against the ones-augmented train matrix.
φ_ij = exp(-‖x_i - x_j‖² / (2h²)) is never materialized globally: column
tiles of the train set are streamed through VMEM and the (BLOCK_M, d+1)
output block is accumulated in place across the innermost grid dimension —
the TPU-idiomatic replacement for the paper's atomic-add streaming
accumulation (TPU Pallas grids execute sequentially per core, so revisiting
the same output block is race-free and deterministic).

Tile layout (one grid step, all in VMEM):
    x_m    (BLOCK_M, d)      row tile of X
    nrm_m  (BLOCK_M, 1)      precomputed ‖x_i‖²
    xt_n   (d, BLOCK_N)      column tile of Xᵀ  (lane axis = BLOCK_N)
    xaug_n (BLOCK_N, d+1)    column tile of [X | 1]
    nrm_n  (1, BLOCK_N)      precomputed ‖x_j‖²
    out    (BLOCK_M, d+1)    accumulator (f32)

MXU work per step: (BLOCK_M×d)@(d×BLOCK_N) Gram + (BLOCK_M×BLOCK_N)@(BLOCK_N×(d+1)).
VPU work: broadcasted adds + one exp per pair.

Mixed precision (kernels/precision.py): BOTH MXU GEMMs — the Gram and the
φ@[X|1] accumulator — take low-precision operands when the wrapper selects
the bf16 / bf16x2 tiers (the ``*_lo`` planes carry the compensated split).
φ itself is exp output and is split/cast on the fly; norms, ``sq``, exp,
and the accumulator stay f32 at every tier.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compiler_params, launch_interpret
from repro.kernels.precision import dot_f32, gram_compensated, weighted_accum


def _score_kernel(x_m_ref, nrm_m_ref, xt_n_ref, xaug_n_ref, nrm_n_ref,
                  inv2h2_ref, out_ref):
    # Initialize the accumulator on the first column tile of each row block.
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Gram tile on the MXU; accumulate in f32 regardless of input dtype.
    g = dot_f32(x_m_ref[...], xt_n_ref[...])
    sq = jnp.maximum(nrm_m_ref[...] + nrm_n_ref[...] - 2.0 * g, 0.0)
    phi = jnp.exp(-sq * inv2h2_ref[0, 0])
    # Fused numerator + denominator GEMM against [X | 1]; the tier is
    # implied by xaug's dtype (f32 → f32 GEMM, bf16 → φ cast to bf16).
    out_ref[...] += weighted_accum(phi, xaug_n_ref[...])


def _score_kernel_x2(x_hi_ref, x_lo_ref, nrm_m_ref, xt_hi_ref, xt_lo_ref,
                     xaug_hi_ref, xaug_lo_ref, nrm_n_ref, inv2h2_ref,
                     out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = gram_compensated(x_hi_ref[...], x_lo_ref[...],
                         xt_hi_ref[...], xt_lo_ref[...])
    sq = jnp.maximum(nrm_m_ref[...] + nrm_n_ref[...] - 2.0 * g, 0.0)
    phi = jnp.exp(-sq * inv2h2_ref[0, 0])
    out_ref[...] += weighted_accum(phi, xaug_hi_ref[...], xaug_lo_ref[...])


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret")
)
def flash_score_pallas(
    x: jnp.ndarray,        # (n, d)   padded to block_m/block_n multiples
    nrm: jnp.ndarray,      # (n, 1)   f32 squared norms
    xt: jnp.ndarray,       # (d, n)
    xaug: jnp.ndarray,     # (n, d+1) [X | 1]
    inv2h2: jnp.ndarray,   # (1, 1)   1/(2h²), f32
    x_lo: jnp.ndarray | None = None,     # (n, d)   bf16 lo plane (bf16x2)
    xt_lo: jnp.ndarray | None = None,    # (d, n)   bf16 lo plane (bf16x2)
    xaug_lo: jnp.ndarray | None = None,  # (n, d+1) bf16 lo plane (bf16x2)
    *,
    nrm_cols: jnp.ndarray | None = None,  # (1, n) f32; None: rows == columns
    block_m: int = 128,
    block_n: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Raw kernel launch; returns S1aug (m, d+1) f32 of the m rows of ``x``
    against the n columns of ``xt``.  Without ``nrm_cols`` the rows are the
    columns; with it, ``x`` may be a contiguous range of the column layout.
    See ops.flash_score_stats for the padded/normalized public wrapper."""
    m, d = x.shape
    n = xt.shape[1]
    assert m % block_m == 0 and n % block_n == 0, (m, n, block_m, block_n)
    assert nrm_cols is not None or m == n, (m, n)
    los = (x_lo, xt_lo, xaug_lo)
    assert all(v is None for v in los) or all(v is not None for v in los), \
        "bf16x2 needs all three lo planes"
    grid = (m // block_m, n // block_n)

    row = pl.BlockSpec((block_m, d), lambda m, j: (m, 0))
    nrm_row = pl.BlockSpec((block_m, 1), lambda m, j: (m, 0))
    col = pl.BlockSpec((d, block_n), lambda m, j: (0, j))
    aug = pl.BlockSpec((block_n, d + 1), lambda m, j: (j, 0))
    nrm_col = pl.BlockSpec((1, block_n), lambda m, j: (0, j))
    scalar = pl.BlockSpec((1, 1), lambda m, j: (0, 0))

    nrm_bcast = jnp.broadcast_to(nrm.reshape(1, -1), (1, n)) \
        if nrm_cols is None else nrm_cols
    if x_lo is None:
        kernel = _score_kernel
        in_specs = [row, nrm_row, col, aug, nrm_col, scalar]
        args = (x, nrm, xt, xaug, nrm_bcast, inv2h2)
    else:
        kernel = _score_kernel_x2
        in_specs = [row, row, nrm_row, col, col, aug, aug, nrm_col, scalar]
        args = (x, x_lo, nrm, xt, xt_lo, xaug, xaug_lo, nrm_bcast, inv2h2)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, d + 1), lambda m, j: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d + 1), jnp.float32),
        interpret=launch_interpret(interpret),
        compiler_params=compiler_params(),
    )(*args)
