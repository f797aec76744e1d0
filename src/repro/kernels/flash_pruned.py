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

Precision tiers: the ``*_lo`` planes ride along and the bodies reuse the
dense kernels' compensated-Gram helpers.  At the f32 tier the GEMMs run
packed (kernels/precision.py), which the caller says with the static
``packed``: the row operand arrives as ``pack_rows`` (m, 6d) bf16 and the
column operand as ``column_planes`` (plane_rows(d), n) bf16,
``Precision.HIGHEST``'s six split products in one bf16 GEMM each.  The
trace-time counter ``kernels.f32_gemm_path{kernel, gemm, path}`` counts
each traced packed kernel program's GEMMs.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels import compiler_params, launch_interpret
from repro.kernels import precision as prec
from repro.kernels.flash_laplace import _sq_tile

def _gemm(packed: bool, rows: jnp.ndarray, cols: jnp.ndarray,
          lo) -> Tuple[str, int]:
    """(how the body forms its GEMMs, d): ``packed`` (the f32 tier's split
    products, (m, 6d) rows against (plane_rows(d), n) planes), else
    ``dot`` (bf16 in one pass) or ``split2`` (bf16x2's compensated planes)
    over bf16 rows (m, d) and columns (d, n): the f32 tier always packs."""
    if not packed:
        assert rows.shape[1] == cols.shape[0] \
            and rows.dtype == cols.dtype == jnp.bfloat16, \
            ("f32 operands run packed", rows.dtype, cols.dtype)
        return ("dot" if lo is None else "split2"), rows.shape[1]
    d = rows.shape[1] // 6
    assert rows.shape[1] == 6 * d and cols.shape[0] == prec.plane_rows(d) \
        and rows.dtype == cols.dtype == jnp.bfloat16 and lo is None, \
        (rows.shape, cols.shape, rows.dtype, cols.dtype)
    return "packed", d


def _note_packed(kernel: str, *gemms: str) -> None:
    """Count one traced kernel program's packed f32 GEMMs."""
    for gemm in gemms:
        obs.counter("kernels.f32_gemm_path",
                    "f32-tier GEMMs of traced pruned kernel programs, by path",
                    labels={"kernel": kernel, "gemm": gemm,
                            "path": "packed"}).inc()


def _sq_packed(rows_ref, nrm_m_ref, planes_ref, nrm_n_ref):
    """The f32 squared-distance tile from the packed one-pass Gram."""
    g = prec.gram_packed(rows_ref[...], planes_ref[...])
    return jnp.maximum(nrm_m_ref[...] + nrm_n_ref[...] - 2.0 * g, 0.0)


def _make_eval_kernel(gemm: str, laplace: bool):
    """KDE / fused-Laplace body with visit-count masking."""

    def kernel(cnt_ref, tmap_ref, *refs):
        del tmap_ref  # consumed by the BlockSpec index maps
        if gemm == "split2":
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
            if gemm == "packed":
                sq = _sq_packed(y_ref, nrm_m_ref, xt_ref, nrm_n_ref)
                d = y_ref.shape[1] // 6
            else:
                sq = _sq_tile(y_ref, nrm_m_ref, xt_ref, nrm_n_ref, y_lo_ref,
                              xt_lo_ref)
                d = xt_ref.shape[0]
            scaled = sq * inv2h2_ref[0, 0]
            phi = jnp.exp(-scaled)
            if laplace:
                phi = phi * (1.0 + d / 2.0 - scaled)
            out_ref[...] += jnp.sum(phi, axis=1, keepdims=True)

    return kernel


_EVAL = {(g, l): _make_eval_kernel(g, l)
         for g in ("dot", "split2", "packed") for l in (False, True)}

#: Bytes of scalar-prefetched tile map one launch may hold.  The prefetched
#: operands live in SMEM (1 MiB on a v5e core, shared with Mosaic's own
#: scalars); a visit list that wide — a 1M-point set whose row tiles each
#: visit hundreds of column tiles — runs as several launches over groups
#: of row tiles instead.
SMEM_TILE_MAP_BYTES = 256 * 1024


def _by_row_groups(launch, counts, tile_map, rows, block_m: int, *,
                   scan_as: Optional[str] = None):
    """``launch(counts, tile_map, *rows)`` over row-tile groups whose tile
    map fits :data:`SMEM_TILE_MAP_BYTES`; ``rows`` are the row-tiled
    operands (None passes through), outputs concatenate by row.

    One launch per group by default.  With ``scan_as`` (the kernel's
    name) the whole groups run as one ``lax.scan`` over a launch lowered
    once, a shorter last group as one more launch: the score pass's 256
    unrolled launches at 1M took ~10 s to lower, once per chip on the
    ``ring`` backend.  The eval kernel stays unrolled: in a scan it ran
    15% slower on a v5e, where the score kernel ran as fast."""
    mt, visits = tile_map.shape
    per = max(1, SMEM_TILE_MAP_BYTES // (4 * visits))
    if per >= mt:
        return launch(counts, tile_map, *rows)
    if scan_as is None:
        outs = []
        for g in range(0, mt, per):
            e = min(g + per, mt)
            part = [None if a is None else a[g * block_m:e * block_m]
                    for a in rows]
            outs.append(launch(counts[g:e], tile_map[g:e], *part))
        return jnp.concatenate(outs)
    full = mt // per

    def group(carry, g):
        t0 = g * per
        part = [None if a is None else
                lax.dynamic_slice_in_dim(a, t0 * block_m, per * block_m)
                for a in rows]
        out = launch(lax.dynamic_slice_in_dim(counts, t0, per),
                     lax.dynamic_slice_in_dim(tile_map, t0, per), *part)
        # each launch writes a buffer of its own, as an unrolled launch
        # does: fused into the scan's stacking of the outputs, a launch at
        # the bf16x2 tier's 512 x 4096 tiles ran out of scoped VMEM
        return carry, lax.optimization_barrier(out)

    # the launch keeps its kernel's name in the compiled program (the
    # device trace names each kernel operation by it)
    group = jax.named_call(group, name=scan_as)
    _, outs = lax.scan(group, None, jnp.arange(full))
    out = outs.reshape(full * per * block_m, outs.shape[-1])
    t0 = full * per
    if t0 == mt:
        return out
    tail = launch(counts[t0:], tile_map[t0:],
                  *[None if a is None else a[t0 * block_m:] for a in rows])
    return jnp.concatenate([out, tail])


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "max_visits", "interpret",
                     "laplace", "packed"),
)
def flash_kde_pallas_pruned(
    counts: jnp.ndarray,     # (mt,) int32 visits per row tile
    tile_map: jnp.ndarray,   # (mt, max_visits) int32 column-tile indices
    y: jnp.ndarray,          # (m, d) queries, padded to block_m multiple
    nrm_y: jnp.ndarray,      # (m, 1) f32
    xt: jnp.ndarray,         # (d, n) train columns, padded to block_n
                             # (packed: y (m, 6d), xt (plane_rows(d), n))
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
    packed: bool = False,
) -> jnp.ndarray:
    """Pruned KDE / fused-Laplace sums (m, 1) f32 (unnormalized);
    ``packed``: the f32 tier's packed operands."""
    m, width = y.shape
    n = xt.shape[1]
    assert m % block_m == 0 and n % block_n == 0, (m, n, block_m, block_n)
    assert (y_lo is None) == (xt_lo is None), "bf16x2 needs both lo planes"
    mt = m // block_m
    assert counts.shape == (mt,) and tile_map.shape == (mt, max_visits), (
        counts.shape, tile_map.shape, mt, max_visits)
    gemm, _ = _gemm(packed, y, xt, y_lo)

    row = pl.BlockSpec((block_m, width), lambda i, k, cnt, tm: (i, 0))
    nrm_row = pl.BlockSpec((block_m, 1), lambda i, k, cnt, tm: (i, 0))
    col = pl.BlockSpec((xt.shape[0], block_n),
                       lambda i, k, cnt, tm: (0, tm[i, k]))
    nrm_col = pl.BlockSpec((1, block_n), lambda i, k, cnt, tm: (0, tm[i, k]))
    scalar = pl.BlockSpec((1, 1), lambda i, k, cnt, tm: (0, 0))
    out = pl.BlockSpec((block_m, 1), lambda i, k, cnt, tm: (i, 0))
    kernel = _EVAL[(gemm, laplace)]

    def launch(cnt, tm, y, nrm_y, y_lo):
        if packed:
            _note_packed("flash_kde_pallas_pruned", "gram")
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
                out_ref[...] += prec.weighted_accum(phi, xaug_hi_ref[...],
                                                    xaug_lo_ref[...])
            else:
                out_ref[...] += prec.weighted_accum(phi, xaug_hi_ref[...])

    return kernel


def _packed_score_kernel(cnt_ref, tmap_ref, rows_ref, nrm_m_ref, planes_ref,
                         nrm_n_ref, inv2h2_ref, out_ref, acc_ref):
    """Score body of the packed f32 path.  The numerator accumulates
    transposed, one row per plane row, in a (plane_rows, block_m) VMEM
    scratch, and is written out as (block_m, plane_rows) once, at the last
    visit slot."""
    del tmap_ref
    i, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k < cnt_ref[i])
    def _accumulate():
        sq = _sq_packed(rows_ref, nrm_m_ref, planes_ref, nrm_n_ref)
        phi = jnp.exp(-sq * inv2h2_ref[0, 0])
        acc_ref[...] += prec.weighted_accum_packed(phi, planes_ref[...])

    @pl.when(k == pl.num_programs(1) - 1)
    def _write():
        out_ref[...] = acc_ref[...].T


_SCORE = {"dot": _make_score_kernel(False), "split2": _make_score_kernel(True),
          "packed": _packed_score_kernel}


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "max_visits", "interpret",
                     "packed"),
)
def flash_score_pallas_pruned(
    counts: jnp.ndarray,     # (nt_rows,) int32
    tile_map: jnp.ndarray,   # (nt_rows, max_visits) int32
    x: jnp.ndarray,          # (m, d) rows, padded to a block_m multiple
    nrm: jnp.ndarray,        # (m, 1) f32
    xt: jnp.ndarray,         # (d, n) columns, padded to a block_n multiple
    xaug: Optional[jnp.ndarray],  # (n, d+1) [X | 1]; None when packed:
                             # x (m, 6d) and xt (plane_rows(d), n)
    inv2h2: jnp.ndarray,     # (1, 1) f32
    x_lo: jnp.ndarray | None = None,
    xt_lo: jnp.ndarray | None = None,
    xaug_lo: jnp.ndarray | None = None,
    *,
    nrm_cols: jnp.ndarray | None = None,  # (1, n) f32; None: rows == columns
    block_m: int = 128,
    block_n: int = 512,
    max_visits: int = 1,
    interpret: Optional[bool] = None,
    packed: bool = False,
) -> jnp.ndarray:
    """Pruned score statistics S1aug (m, d+1) f32 of the rows against the
    columns; ``packed``: the f32 tier's packed operands.  Without
    ``nrm_cols`` the rows are the columns (the train set against itself);
    with it, the rows are a contiguous range of the column layout."""
    m, width = x.shape
    n = xt.shape[1]
    assert m % block_m == 0 and n % block_n == 0, (m, n, block_m, block_n)
    assert nrm_cols is not None or m == n, (m, n)
    los = (x_lo, xt_lo, xaug_lo)
    assert all(v is None for v in los) or all(v is not None for v in los), \
        "bf16x2 needs all three lo planes"
    mt = m // block_m
    assert counts.shape == (mt,) and tile_map.shape == (mt, max_visits), (
        counts.shape, tile_map.shape, mt, max_visits)
    gemm, d = _gemm(packed, x, xt, x_lo)
    assert packed == (xaug is None), "the packed planes replace [X | 1]"
    # the packed numerator accumulates one column per plane row
    out_w = xt.shape[0] if packed else d + 1

    row = pl.BlockSpec((block_m, width), lambda i, k, cnt, tm: (i, 0))
    nrm_row = pl.BlockSpec((block_m, 1), lambda i, k, cnt, tm: (i, 0))
    col = pl.BlockSpec((xt.shape[0], block_n),
                       lambda i, k, cnt, tm: (0, tm[i, k]))
    aug = pl.BlockSpec((block_n, d + 1), lambda i, k, cnt, tm: (tm[i, k], 0))
    nrm_col = pl.BlockSpec((1, block_n), lambda i, k, cnt, tm: (0, tm[i, k]))
    scalar = pl.BlockSpec((1, 1), lambda i, k, cnt, tm: (0, 0))
    out = pl.BlockSpec((block_m, out_w), lambda i, k, cnt, tm: (i, 0))
    kernel = _SCORE[gemm]
    nrm_bcast = jnp.broadcast_to(nrm.reshape(1, -1), (1, n)) \
        if nrm_cols is None else nrm_cols

    def launch(cnt, tm, x, nrm, x_lo):
        if packed:
            _note_packed("flash_score_pallas_pruned", "gram", "numerator")
            in_specs = [row, nrm_row, col, nrm_col, scalar]
            args = (x, nrm, xt, nrm_bcast, inv2h2)
        elif gemm == "dot":
            in_specs = [row, nrm_row, col, aug, nrm_col, scalar]
            args = (x, nrm, xt, xaug, nrm_bcast, inv2h2)
        else:
            in_specs = [row, row, nrm_row, col, col, aug, aug, nrm_col,
                        scalar]
            args = (x, x_lo, nrm, xt, xt_lo, xaug, xaug_lo, nrm_bcast,
                    inv2h2)
        scratch = [pltpu.VMEM((out_w, block_m), jnp.float32)] if packed \
            else []
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=tm.shape, in_specs=in_specs,
                out_specs=out, scratch_shapes=scratch),
            out_shape=jax.ShapeDtypeStruct((x.shape[0], out_w), jnp.float32),
            interpret=launch_interpret(interpret),
            compiler_params=compiler_params(),
        )(cnt, tm, *args)

    acc = _by_row_groups(launch, counts, tile_map, (x, nrm, x_lo), block_m,
                         scan_as="flash_score_pallas_pruned")
    return prec.reduce_planes(acc, d) if packed else acc


__all__ = ["flash_kde_pallas_pruned", "flash_score_pallas_pruned"]
