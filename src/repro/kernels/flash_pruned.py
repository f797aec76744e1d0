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

The score kernel runs V visit slots a step (:func:`score_slots`): its grid
is ``(mt, ceil(max_visits / V))``, step k streams the column tiles of slots
``V·k … V·k + V − 1`` through V BlockSpecs of the same arrays, and adds
them in slot order: under one branch where all V lie within the row's
count, else each under its own mask.

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
from repro.kernels import tuning
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


#: Most visit slots one grid step of the score kernel runs.  A step pays a
#: cost of its own that does not scale with its tiles, and its slots run
#: under one branch where the compiler overlaps them.  Set from a sweep of
#: V in {1, 2, 4, 8} on the 1M x 16-d launches on a v5e (PERF.md).
SCORE_SLOTS_MAX = 8


def score_slots(max_visits: int, *, block_m: int, block_n: int, d: int,
                itemsize: int) -> int:
    """Visit slots V per grid step of the score kernel: the largest power
    of two up to :data:`SCORE_SLOTS_MAX` and ``max_visits`` whose V
    sub-slots fit the tile model's VMEM budget (``tuning.VMEM_BUDGET``),
    each priced as one step of the model (``tuning.pair_pass_cost``: its
    operand tiles, φ tile and accumulator) with a second copy of its
    column tiles for the double buffer.  ``itemsize`` is the tier's
    operand bytes (``precision.operand_bytes``).

    The count is conservative: compiled for a v5e, the sub-slots share
    one body's Gram and φ tiles and add only their column buffers.  It
    keeps the large tiles, whose steps have little fixed cost to share,
    at one slot a step (bf16x2's 512 x 4096 tiles: V = 1)."""
    step = tuning.pair_pass_cost(block_m, block_n, d, block_m=block_m,
                                 block_n=block_n, out_width=d + 1,
                                 itemsize=itemsize)
    cols = itemsize * block_n * (2 * d + 1) + 4 * block_n
    slots = SCORE_SLOTS_MAX
    while slots > 1 and (slots > max_visits or
                         slots * (step.vmem_bytes + cols)
                         > tuning.VMEM_BUDGET):
        slots //= 2
    return slots


#: Column refs of one visit slot, and row refs, by GEMM form.
_SLOT_REFS = {"packed": 2, "dot": 3, "split2": 5}
_ROW_REFS = {"packed": 2, "dot": 2, "split2": 3}


def _score_slot(gemm: str, rows, cols, inv2h2_ref):
    """One visit slot's contribution to the score accumulator: S1aug
    (block_m, d+1), or at the packed f32 tier the transposed plane sums
    (plane_rows, block_m)."""
    if gemm == "packed":
        (rows_ref, nrm_m_ref), (planes_ref, nrm_n_ref) = rows, cols
        sq = _sq_packed(rows_ref, nrm_m_ref, planes_ref, nrm_n_ref)
        phi = jnp.exp(-sq * inv2h2_ref[0, 0])
        return prec.weighted_accum_packed(phi, planes_ref[...])
    if gemm == "dot":
        (x_ref, nrm_m_ref), (xt_ref, xaug_ref, nrm_n_ref) = rows, cols
        x_lo_ref = xt_lo_ref = xaug_lo_ref = None
    else:
        x_ref, x_lo_ref, nrm_m_ref = rows
        xt_ref, xt_lo_ref, xaug_ref, xaug_lo_ref, nrm_n_ref = cols
    sq = _sq_tile(x_ref, nrm_m_ref, xt_ref, nrm_n_ref, x_lo_ref, xt_lo_ref)
    phi = jnp.exp(-sq * inv2h2_ref[0, 0])
    return prec.weighted_accum(
        phi, xaug_ref[...], None if xaug_lo_ref is None else xaug_lo_ref[...])


@functools.lru_cache(maxsize=None)
def _score_kernel(gemm: str, slots: int):
    """Score body over ``slots`` visit slots per grid step.  Sub-slot j of
    step k is visit slot ``slots·k + j``, masked by the row's count (the
    whole step under one mask where all its slots are within it) and added
    in slot order, so the sums are those of one slot a step.  The
    packed numerator accumulates transposed, one row per plane row, in a
    (plane_rows, block_m) VMEM scratch, and is written out as
    (block_m, plane_rows) once, at the last step."""
    nr, per = _ROW_REFS[gemm], _SLOT_REFS[gemm]

    def kernel(cnt_ref, tmap_ref, *refs):
        del tmap_ref  # consumed by the BlockSpec index maps
        rows, cols = refs[:nr], refs[nr:nr + per * slots]
        inv2h2_ref, out_ref, *scratch = refs[nr + per * slots:]
        acc_ref = scratch[0] if gemm == "packed" else out_ref
        i, k = pl.program_id(0), pl.program_id(1)

        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def accumulate(j):
            acc_ref[...] += _score_slot(gemm, rows,
                                        cols[j * per:(j + 1) * per],
                                        inv2h2_ref)

        # a step whose slots all lie within the count runs them under one
        # branch, where the compiler can overlap them: a branch per slot
        # cost 15% of the kernel on a v5e (PERF.md)
        full = slots * k + slots <= cnt_ref[i]

        @pl.when(full)
        def _all():
            for j in range(slots):
                accumulate(j)

        if slots > 1:
            @pl.when(jnp.logical_not(full))
            def _some():
                for j in range(slots):
                    pl.when(slots * k + j < cnt_ref[i])(
                        functools.partial(accumulate, j))

        if gemm == "packed":
            @pl.when(k == pl.num_programs(1) - 1)
            def _write():
                out_ref[...] = acc_ref[...].T

    return kernel


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
    with it, the rows are a contiguous range of the column layout.  Each
    grid step runs :func:`score_slots` visit slots; the tile map is padded
    to a multiple of them with each row's fill tile, which the counts
    mask."""
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
    slots = score_slots(max_visits, block_m=block_m, block_n=block_n, d=d,
                        itemsize=2 if gemm == "dot" else 4)
    pad = -max_visits % slots
    if pad:
        tile_map = jnp.concatenate(
            [tile_map, jnp.broadcast_to(tile_map[:, :1], (mt, pad))], axis=1)
    # the packed numerator accumulates one column per plane row
    out_w = xt.shape[0] if packed else d + 1

    row = pl.BlockSpec((block_m, width), lambda i, k, cnt, tm: (i, 0))
    nrm_row = pl.BlockSpec((block_m, 1), lambda i, k, cnt, tm: (i, 0))
    scalar = pl.BlockSpec((1, 1), lambda i, k, cnt, tm: (0, 0))
    out = pl.BlockSpec((block_m, out_w), lambda i, k, cnt, tm: (i, 0))
    nrm_bcast = jnp.broadcast_to(nrm.reshape(1, -1), (1, n)) \
        if nrm_cols is None else nrm_cols

    def slot_specs(j):
        """Sub-slot j's column specs: visit slot ``slots·k + j``."""
        def at(i, k, cnt, tm):
            return tm[i, slots * k + j]
        col = pl.BlockSpec((xt.shape[0], block_n),
                           lambda i, k, cnt, tm: (0, at(i, k, cnt, tm)))
        aug = pl.BlockSpec((block_n, d + 1),
                           lambda i, k, cnt, tm: (at(i, k, cnt, tm), 0))
        nrm_col = pl.BlockSpec((1, block_n),
                               lambda i, k, cnt, tm: (0, at(i, k, cnt, tm)))
        if gemm == "packed":
            return [col, nrm_col], [xt, nrm_bcast]
        if gemm == "dot":
            return [col, aug, nrm_col], [xt, xaug, nrm_bcast]
        return ([col, col, aug, aug, nrm_col],
                [xt, xt_lo, xaug, xaug_lo, nrm_bcast])

    col_specs, col_args = [], []
    for j in range(slots):
        specs, args = slot_specs(j)
        col_specs += specs
        col_args += args
    kernel = _score_kernel(gemm, slots)

    def launch(cnt, tm, x, nrm, x_lo):
        if packed:
            _note_packed("flash_score_pallas_pruned", "gram", "numerator")
        rows_specs, rows = ([row, nrm_row], [x, nrm]) if x_lo is None \
            else ([row, row, nrm_row], [x, x_lo, nrm])
        scratch = [pltpu.VMEM((out_w, block_m), jnp.float32)] if packed \
            else []
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(tm.shape[0], tm.shape[1] // slots),
                in_specs=rows_specs + col_specs + [scalar],
                out_specs=out, scratch_shapes=scratch),
            out_shape=jax.ShapeDtypeStruct((x.shape[0], out_w), jnp.float32),
            interpret=launch_interpret(interpret),
            compiler_params=compiler_params(),
        )(cnt, tm, *rows, *col_args, inv2h2)

    acc = _by_row_groups(launch, counts, tile_map, (x, nrm, x_lo), block_m,
                         scan_as="flash_score_pallas_pruned")
    return prec.reduce_planes(acc, d) if packed else acc


__all__ = ["flash_kde_pallas_pruned", "flash_score_pallas_pruned",
           "score_slots"]
