"""Pruned flash kernels: scalar-prefetched visit lists over column tiles.

The dense kernels run a rectangular ``(m/block_m, n/block_n)`` grid; these
variants run ``(m/block_m, max_visits)`` and fetch, per grid step, the
column tile named by a prefetched per-row-tile visit list
(``kernels/spatial.py``).  BlockSpec index maps read the prefetched scalars
— the canonical TPU block-sparse pattern — so the skipped tiles are never
DMA'd at all: the win is HBM traffic *and* MXU/VPU work, proportional to
(1 − occupancy).

Layout per grid step (i = row tile, k = visit slot):

    counts   (mt,)            int32   visits of row tile i  (scalar prefetch)
    tile_map (mt, max_visits) int32   k-th column tile to stream  (prefetch)
    row/col tensors                   exactly the dense kernels' tiles, but
                                      the column index is tile_map[i, k]

Visit slots past ``counts[i]`` replay the row's first kept tile; the kernel
body masks their accumulation with ``pl.when(k < counts[i])``, so bucketed
(power-of-two) visit extents stay exact.  Accumulators initialize at
``k == 0`` — the visit axis is the innermost sequential grid dimension,
same revisiting-output-block scheme as the dense kernels.

Precision tiers compose unchanged: the ``*_lo`` planes ride along and the
bodies reuse the dense kernels' compensated-Gram helpers.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compiler_params, launch_interpret
from repro.kernels.flash_laplace import _sq_tile
from repro.kernels.precision import weighted_accum


def _make_eval_kernel(compensated: bool, laplace: bool):
    """KDE / fused-Laplace body with visit-count masking."""

    def kernel(cnt_ref, tmap_ref, *refs):
        del tmap_ref  # consumed by the BlockSpec index maps
        if compensated:
            (y_ref, y_lo_ref, nrm_m_ref, xt_ref, xt_lo_ref, nrm_n_ref,
             inv2h2_ref, out_ref) = refs
        else:
            y_ref, nrm_m_ref, xt_ref, nrm_n_ref, inv2h2_ref, out_ref = refs
            y_lo_ref = xt_lo_ref = None
        i, k = pl.program_id(0), pl.program_id(1)

        @pl.when(k == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(k < cnt_ref[i])
        def _accumulate():
            sq = _sq_tile(y_ref, nrm_m_ref, xt_ref, nrm_n_ref, y_lo_ref,
                          xt_lo_ref)
            scaled = sq * inv2h2_ref[0, 0]
            phi = jnp.exp(-scaled)
            if laplace:
                d = xt_ref.shape[0]
                phi = phi * (1.0 + d / 2.0 - scaled)
            out_ref[...] += jnp.sum(phi, axis=1, keepdims=True)

    return kernel


_EVAL = {(c, l): _make_eval_kernel(c, l)
         for c in (False, True) for l in (False, True)}

#: Bytes of scalar-prefetched tile map one launch may hold.  The prefetched
#: operands live in SMEM (1 MiB on a v5e core, shared with Mosaic's own
#: scalars); a visit list that wide — a 1M-point set whose row tiles each
#: visit hundreds of column tiles — runs as several launches over groups
#: of row tiles instead.
SMEM_TILE_MAP_BYTES = 256 * 1024


def _by_row_groups(launch, counts, tile_map, rows, block_m: int):
    """``launch(counts, tile_map, *rows)`` over row-tile groups whose tile
    map fits :data:`SMEM_TILE_MAP_BYTES`; ``rows`` are the row-tiled
    operands (None passes through), outputs concatenate by row."""
    mt, visits = tile_map.shape
    per = max(1, SMEM_TILE_MAP_BYTES // (4 * visits))
    if per >= mt:
        return launch(counts, tile_map, *rows)
    outs = []
    for g in range(0, mt, per):
        e = min(g + per, mt)
        part = [None if a is None else a[g * block_m:e * block_m]
                for a in rows]
        outs.append(launch(counts[g:e], tile_map[g:e], *part))
    return jnp.concatenate(outs)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "max_visits", "interpret",
                     "laplace"),
)
def flash_kde_pallas_pruned(
    counts: jnp.ndarray,     # (mt,) int32 visits per row tile
    tile_map: jnp.ndarray,   # (mt, max_visits) int32 column-tile indices
    y: jnp.ndarray,          # (m, d) queries, padded to block_m multiple
    nrm_y: jnp.ndarray,      # (m, 1) f32
    xt: jnp.ndarray,         # (d, n) train columns, padded to block_n
    nrm_x: jnp.ndarray,      # (1, n) f32
    inv2h2: jnp.ndarray,     # (1, 1) f32
    y_lo: jnp.ndarray | None = None,
    xt_lo: jnp.ndarray | None = None,
    *,
    block_m: int = 128,
    block_n: int = 512,
    max_visits: int = 1,
    interpret: Optional[bool] = None,
    laplace: bool = False,
) -> jnp.ndarray:
    """Pruned KDE / fused-Laplace sums (m, 1) f32 (unnormalized)."""
    m, d = y.shape
    n = xt.shape[1]
    assert m % block_m == 0 and n % block_n == 0, (m, n, block_m, block_n)
    assert (y_lo is None) == (xt_lo is None), "bf16x2 needs both lo planes"
    mt = m // block_m
    assert counts.shape == (mt,) and tile_map.shape == (mt, max_visits), (
        counts.shape, tile_map.shape, mt, max_visits)

    row = pl.BlockSpec((block_m, d), lambda i, k, cnt, tm: (i, 0))
    nrm_row = pl.BlockSpec((block_m, 1), lambda i, k, cnt, tm: (i, 0))
    col = pl.BlockSpec((d, block_n), lambda i, k, cnt, tm: (0, tm[i, k]))
    nrm_col = pl.BlockSpec((1, block_n), lambda i, k, cnt, tm: (0, tm[i, k]))
    scalar = pl.BlockSpec((1, 1), lambda i, k, cnt, tm: (0, 0))
    out = pl.BlockSpec((block_m, 1), lambda i, k, cnt, tm: (i, 0))
    kernel = _EVAL[(y_lo is not None, laplace)]

    def launch(cnt, tm, y, nrm_y, y_lo):
        if y_lo is None:
            in_specs = [row, nrm_row, col, nrm_col, scalar]
            args = (y, nrm_y, xt, nrm_x, inv2h2)
        else:
            in_specs = [row, row, nrm_row, col, col, nrm_col, scalar]
            args = (y, y_lo, nrm_y, xt, xt_lo, nrm_x, inv2h2)
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=tm.shape, in_specs=in_specs,
                out_specs=out),
            out_shape=jax.ShapeDtypeStruct((y.shape[0], 1), jnp.float32),
            interpret=launch_interpret(interpret),
            compiler_params=compiler_params(),
        )(cnt, tm, *args)

    return _by_row_groups(launch, counts, tile_map, (y, nrm_y, y_lo),
                          block_m)


def _make_score_kernel(compensated: bool):
    def kernel(cnt_ref, tmap_ref, *refs):
        del tmap_ref
        if compensated:
            (x_hi_ref, x_lo_ref, nrm_m_ref, xt_hi_ref, xt_lo_ref,
             xaug_hi_ref, xaug_lo_ref, nrm_n_ref, inv2h2_ref,
             out_ref) = refs
        else:
            (x_hi_ref, nrm_m_ref, xt_hi_ref, xaug_hi_ref, nrm_n_ref,
             inv2h2_ref, out_ref) = refs
            x_lo_ref = xt_lo_ref = xaug_lo_ref = None
        i, k = pl.program_id(0), pl.program_id(1)

        @pl.when(k == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(k < cnt_ref[i])
        def _accumulate():
            sq = _sq_tile(x_hi_ref, nrm_m_ref, xt_hi_ref, nrm_n_ref,
                          x_lo_ref, xt_lo_ref)
            phi = jnp.exp(-sq * inv2h2_ref[0, 0])
            if compensated:
                out_ref[...] += weighted_accum(phi, xaug_hi_ref[...],
                                               xaug_lo_ref[...])
            else:
                out_ref[...] += weighted_accum(phi, xaug_hi_ref[...])

    return kernel


_SCORE = {c: _make_score_kernel(c) for c in (False, True)}


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "max_visits", "interpret"),
)
def flash_score_pallas_pruned(
    counts: jnp.ndarray,     # (nt_rows,) int32
    tile_map: jnp.ndarray,   # (nt_rows, max_visits) int32
    x: jnp.ndarray,          # (n, d) padded to block_m/block_n multiples
    nrm: jnp.ndarray,        # (n, 1) f32
    xt: jnp.ndarray,         # (d, n)
    xaug: jnp.ndarray,       # (n, d+1) [X | 1]
    inv2h2: jnp.ndarray,     # (1, 1) f32
    x_lo: jnp.ndarray | None = None,
    xt_lo: jnp.ndarray | None = None,
    xaug_lo: jnp.ndarray | None = None,
    *,
    block_m: int = 128,
    block_n: int = 512,
    max_visits: int = 1,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Pruned score statistics S1aug (n, d+1) f32."""
    n, d = x.shape
    assert n % block_m == 0 and n % block_n == 0, (n, block_m, block_n)
    los = (x_lo, xt_lo, xaug_lo)
    assert all(v is None for v in los) or all(v is not None for v in los), \
        "bf16x2 needs all three lo planes"
    mt = n // block_m
    assert counts.shape == (mt,) and tile_map.shape == (mt, max_visits), (
        counts.shape, tile_map.shape, mt, max_visits)

    row = pl.BlockSpec((block_m, d), lambda i, k, cnt, tm: (i, 0))
    nrm_row = pl.BlockSpec((block_m, 1), lambda i, k, cnt, tm: (i, 0))
    col = pl.BlockSpec((d, block_n), lambda i, k, cnt, tm: (0, tm[i, k]))
    aug = pl.BlockSpec((block_n, d + 1), lambda i, k, cnt, tm: (tm[i, k], 0))
    nrm_col = pl.BlockSpec((1, block_n), lambda i, k, cnt, tm: (0, tm[i, k]))
    scalar = pl.BlockSpec((1, 1), lambda i, k, cnt, tm: (0, 0))
    out = pl.BlockSpec((block_m, d + 1), lambda i, k, cnt, tm: (i, 0))
    kernel = _SCORE[x_lo is not None]
    nrm_bcast = jnp.broadcast_to(nrm.reshape(1, -1), (1, n))

    def launch(cnt, tm, x, nrm, x_lo):
        if x_lo is None:
            in_specs = [row, nrm_row, col, aug, nrm_col, scalar]
            args = (x, nrm, xt, xaug, nrm_bcast, inv2h2)
        else:
            in_specs = [row, row, nrm_row, col, col, aug, aug, nrm_col,
                        scalar]
            args = (x, x_lo, nrm, xt, xt_lo, xaug, xaug_lo, nrm_bcast,
                    inv2h2)
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=tm.shape, in_specs=in_specs,
                out_specs=out),
            out_shape=jax.ShapeDtypeStruct((x.shape[0], d + 1), jnp.float32),
            interpret=launch_interpret(interpret),
            compiler_params=compiler_params(),
        )(cnt, tm, *args)

    return _by_row_groups(launch, counts, tile_map, (x, nrm, x_lo), block_m)


__all__ = ["flash_kde_pallas_pruned", "flash_score_pallas_pruned"]
