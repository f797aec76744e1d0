"""Public wrappers around the Flash-SD-KDE Pallas kernels.

Responsibilities: pad point sets to tile multiples (with far-away sentinel
points whose kernel weight underflows to exactly 0.0, so padding never
changes a result), precompute squared norms and transposed layouts (lane
axis = the streamed column dimension, which is what the TPU wants), budget
VMEM, launch the kernels, slice off padding and normalize.

Three launch knobs thread through every wrapper here:

  * ``precision`` — the GEMM-operand tier (``"f32"`` / ``"bf16"`` /
    ``"bf16x2"``, kernels/precision.py).  Norms, distances, exponentials and
    accumulators stay f32 at every tier; only the MXU operands shrink.
  * ``block_m`` / ``block_n`` — the launch tile, either explicit ints or
    ``"auto"`` (the default), which consults the model-guided autotuner
    (kernels/autotune.py): cost-model shortlist on the padded problem,
    optional on-device timing, memoized winners.
  * ``prune`` — cluster pruning (kernels/spatial.py): ``"off"`` streams
    every tile pair (dense), a float ``epsilon ≥ 0`` reorders the train set
    spatially and skips column tiles whose certified per-point contribution
    is ≤ epsilon (``0.0`` = only tiles whose every term underflows to
    exactly 0.0 in f32 — the dense result, cheaper), and ``"auto"`` (the
    default) applies exact (epsilon=0) pruning once the streamed set is
    large enough to pay for the bounds prepass.

Every function here has a pure-jnp oracle in ``ref.py`` and an allclose
sweep in ``tests/``.
"""

from __future__ import annotations

import functools
import math
import threading
import weakref
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.bandwidth import gaussian_norm_const
from repro.kernels import autotune, flash_pruned, spatial
from repro.kernels import precision as prec
from repro.obs import state as obs_state
from repro.kernels.flash_kde import flash_kde_pallas
from repro.kernels.flash_laplace import flash_laplace_pallas, sq_moment_pallas
from repro.kernels.flash_score import flash_score_pallas

PAD_VALUE = 1.0e6
# VMEM is ~16 MiB/core on v5e; leave headroom for double buffering.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

_STATIC = ("precision", "block_m", "block_n", "interpret")

PruneArg = Union[str, float]  # "auto" | "off" | epsilon ≥ 0

#: ``prune="auto"`` enables exact pruning only past these sizes — below
#: them the bounds prepass and visit-list compaction cost more
#: than the skipped tiles were worth.
PRUNE_AUTO_MIN_COLS = 16384
PRUNE_AUTO_MIN_TILES = 4


def resolve_prune(prune: PruneArg, cols: int, block_n: int) -> Optional[float]:
    """The per-point epsilon a prune argument means; None = dense."""
    if prune is None or prune is False or prune == "off":
        return None
    if prune == "auto":
        if (cols >= PRUNE_AUTO_MIN_COLS
                and cols >= PRUNE_AUTO_MIN_TILES * block_n):
            return 0.0
        return None
    if isinstance(prune, str):
        raise ValueError(
            f"bad prune argument {prune!r} (choose 'auto', 'off', or a "
            "float epsilon >= 0)"
        )
    eps = float(prune)
    if not eps >= 0.0:
        raise ValueError(f"prune epsilon must be >= 0, got {eps}")
    return eps


def _apply_plan(plan, n: int, m: int, d: int, *,
                precision, block_m, block_n, prune):
    """Fill wrapper knobs still at their defaults from an execution plan.

    ``plan`` is None (no-op), ``"auto"`` (resolve one via
    ``repro.plan.plan_for`` for this call's shape), or a resolved
    ``repro.plan.ExecutionPlan``.  Override precedence matches the serve
    layer: a knob passed away from its wrapper default always wins; a knob
    left at its default ("f32" / "auto") is filled from the plan.
    """
    if plan is None:
        return precision, block_m, block_n, prune
    if plan == "auto":
        from repro.plan import plan_for

        plan = plan_for(n, d, q=m, backend="pallas")
    if precision == "f32":
        precision = plan.precision
    if block_m == "auto" and plan.block_m is not None:
        block_m = plan.block_m
    if block_n == "auto" and plan.block_n is not None:
        block_n = plan.block_n
    if prune == "auto":
        prune = plan.prune
    return precision, block_m, block_n, prune


def _traced(*arrays) -> bool:
    """True when any argument is an abstract tracer (jit/vmap/grad).

    The pruned path host-syncs (visit-list extents, layout shapes), so
    under tracing the public wrappers silently fall back to dense — the
    pre-pruning behavior, and the only one that can stay a single jaxpr.
    """
    return not all(jax.core.is_concrete(a) for a in arrays)


# One-shot wrappers amortize the spatial prep across repeated calls on the
# SAME train array (e.g. core.estimator evaluate loops): keyed by array
# identity, guarded by a weakref so a recycled id can never alias, holding
# at most a handful of live entries.
_COLUMNS_CACHE: dict = {}
_COLUMNS_LOCK = threading.Lock()


def _cached_columns(x, *, block_n: int, precision: str,
                    seed: int) -> "TrainColumns":
    key = (id(x), int(block_n), precision, seed)
    with _COLUMNS_LOCK:
        hit = _COLUMNS_CACHE.get(key)
        cols = hit[1] if hit is not None and hit[0]() is x else None
    with obs.span("kernels.prune.columns",
                  cache="miss" if cols is None else "hit"):
        if cols is not None:
            return cols
        cols = prepare_train_columns(x, block_n=block_n, precision=precision,
                                     clustered=True, seed=seed)
    try:
        ref = weakref.ref(x)
    except TypeError:            # not weakref-able: skip caching
        return cols
    with _COLUMNS_LOCK:
        for k in [k for k, (r, _) in _COLUMNS_CACHE.items() if r() is None]:
            del _COLUMNS_CACHE[k]
        _COLUMNS_CACHE[key] = (ref, cols)
    return cols


def _pad_to(x: jnp.ndarray, mult: int, value: float = PAD_VALUE) -> jnp.ndarray:
    n = x.shape[0]
    rem = (-n) % mult
    if rem == 0:
        return x
    return jnp.pad(x, [(0, rem)] + [(0, 0)] * (x.ndim - 1),
                   constant_values=value)


@jax.jit
def _norms(x: jnp.ndarray) -> jnp.ndarray:
    # Rounded squares summed left to right, so every program gets the same
    # bits: a reduce may be reordered, and a square may be contracted into
    # an FMA (the max() blocks that), depending on what XLA fuses around
    # it.  The eager pruned prep and the jitted dense programs must agree
    # for eps=0 pruning to reproduce dense sums.
    x32 = x.astype(jnp.float32)
    sq = jnp.maximum(x32 * x32, 0.0)
    acc = sq[:, 0]
    for k in range(1, sq.shape[-1]):
        acc = acc + sq[:, k]
    return acc[:, None]


def _tier_norms(hi: jnp.ndarray, lo: Optional[jnp.ndarray]) -> jnp.ndarray:
    """f32 squared norms of the points the tier-cast operands represent.

    Computing norms from the *cast* operands (not the f32 originals) keeps
    ``sq = ‖ŷ‖² + ‖x̂‖² − 2·ŷ·x̂`` an exact nonnegative squared distance of
    slightly perturbed points, so reduced precision acts as a data
    perturbation rather than cancellation noise in the exponent (see
    kernels/precision.py).
    """
    return _norms(prec.reconstruct(hi, lo))


def _inv2h2(h) -> jnp.ndarray:
    h = jnp.asarray(h, jnp.float32)
    return (1.0 / (2.0 * h * h)).reshape(1, 1)


def vmem_tile_bytes(block_m: int, block_n: int, d: int,
                    itemsize: int = 4, out_width: Optional[int] = None) -> int:
    """Per-step VMEM working set (inputs + φ tile + output accumulator).

    ``itemsize`` is the GEMM-operand byte width (4 f32, 2 bf16, 4 for the
    two-plane bf16x2 split — ``precision.operand_bytes``); norms, the φ
    tile, and the accumulator are always f32.  ``out_width`` is the
    accumulator width: the (block_n, d+1) xaug operand tile exists only on
    the score path (out_width = d+1); the KDE/Laplace paths (out_width = 1)
    carry neither it nor a (d+1)-wide accumulator.  None keeps the legacy
    conservative budget (score-shaped).
    """
    ow = out_width if out_width is not None else d + 1
    operand_elems = (
        block_m * d            # row tile
        + d * block_n          # xt column tile
        + (block_n * (d + 1) if ow > 1 else 0)   # xaug column tile (score)
    )
    f32_elems = (
        block_m                # row norms
        + block_n              # column norms
        + block_m * block_n    # φ tile (registers/VMEM intermediate)
        + block_m * ow         # accumulator
    )
    return operand_elems * itemsize + f32_elems * 4


def _check_vmem(block_m: int, block_n: int, d: int,
                itemsize: int = 4, out_width: Optional[int] = None) -> None:
    b = vmem_tile_bytes(block_m, block_n, d, itemsize, out_width)
    if b > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"tile working set {b/2**20:.1f} MiB exceeds VMEM budget "
            f"({VMEM_BUDGET_BYTES/2**20:.0f} MiB): block_m={block_m} "
            f"block_n={block_n} d={d} itemsize={itemsize}"
        )


def _resolve(block_m, block_n, rows, cols, d, *, out_width, precision,
             interpret, row_multiple=None, col_multiple=None, pruned=False):
    """Shared "auto"-tile resolution + dtype-aware VMEM gate."""
    block_m, block_n = autotune.resolve_blocks(
        block_m, block_n, rows, cols, d, out_width=out_width,
        precision=precision, row_multiple=row_multiple,
        col_multiple=col_multiple,
        measure=False if interpret else None,
        pruned=pruned,
    )
    _check_vmem(block_m, block_n, d, prec.operand_bytes(precision),
                out_width=out_width)
    return block_m, block_n


# ---------------------------------------------------------------------------
# Score statistics / SD-KDE shift.
# ---------------------------------------------------------------------------


def _score_operands(xp: jnp.ndarray, precision: str, packed: bool = False):
    """(x_ops, xt_ops, xaug_ops, nrm, xrec) for a padded train set.

    ``packed`` (the pruned path at the f32 tier, ``prec.packs``): the
    rows are ``prec.pack_rows``, the columns ``prec.column_planes``, which
    are the [X | 1] weights too (``xaug_ops`` is (None, None)); norms stay
    the f32 points'."""
    if packed:
        x32 = xp.astype(jnp.float32)
        return ((prec.pack_rows(x32), None),
                (prec.column_planes(x32), None), (None, None),
                _norms(x32), x32)
    npad = xp.shape[0]
    xaug = jnp.concatenate([xp, jnp.ones((npad, 1), xp.dtype)], axis=1)
    if precision == "f32":
        x_ops = (xp, None)
        xt_ops = (xp.astype(jnp.float32).T.astype(xp.dtype), None)
        xaug_ops = (xaug, None)
        xrec = xp.astype(jnp.float32)
    else:
        x_ops = prec.cast_operand(xp.astype(jnp.float32), precision)
        xt_ops = (x_ops[0].T, None if x_ops[1] is None else x_ops[1].T)
        xaug_ops = prec.cast_operand(xaug.astype(jnp.float32), precision)
        xrec = prec.reconstruct(*x_ops)
    return x_ops, xt_ops, xaug_ops, _norms(xrec), xrec


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_score_stats_dense(
    x: jnp.ndarray,
    h,
    *,
    precision: str = "f32",
    block_m=128,
    block_n=512,
    interpret: Optional[bool] = None,
):
    n, d = x.shape
    mult = math.lcm(block_m, block_n)
    xp = _pad_to(x, mult)
    x_ops, xt_ops, xaug_ops, nrm, _ = _score_operands(xp, precision)
    s1aug = flash_score_pallas(
        x_ops[0], nrm, xt_ops[0], xaug_ops[0], _inv2h2(h),
        x_ops[1], xt_ops[1], xaug_ops[1],
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    s0 = s1aug[:n, d]
    s1 = s1aug[:n, :d]
    return s0, s1


@functools.partial(jax.jit, static_argnames=_STATIC + ("rows",))
def dense_score_rows(
    xp: jnp.ndarray,
    h,
    *,
    rows: Tuple[int, int],
    precision: str = "f32",
    block_m=128,
    block_n=512,
    interpret: Optional[bool] = None,
):
    """Dense S1aug (r1 - r0, d+1) of the rows ``rows = (r0, r1)`` of a
    padded train set against all of its columns (a row range of
    ``_flash_score_stats_dense``)."""
    r0, r1 = rows
    x_ops, xt_ops, xaug_ops, nrm, _ = _score_operands(xp, precision)
    lo = None if x_ops[1] is None else x_ops[1][r0:r1]
    return flash_score_pallas(
        x_ops[0][r0:r1], nrm[r0:r1], xt_ops[0], xaug_ops[0], _inv2h2(h),
        lo, xt_ops[1], xaug_ops[1], nrm_cols=nrm.reshape(1, -1),
        block_m=block_m, block_n=block_n, interpret=interpret,
    )


def _record_occupancy_profile(rows, col_counts, d, launch_occ, block_n,
                              yrec, meta_fine, inv2h2, epsilon, block_m,
                              kind):
    """Feed the tuner's occupancy profile after one bounds prepass.

    The launch-width occupancy is recorded under every column-count key a
    later resolve may use (true train count and padded layout length).
    The fine-width probe — a second bounds pass at FINE_PROBE_BLOCK,
    ~block_n/128× the prepass cost — runs only until the profile has a
    fine record for this regime: after that the EMA has nothing new to
    learn and the hot query path skips it.
    """
    fine = autotune.FINE_PROBE_BLOCK
    for n_key in col_counts:
        autotune.record_occupancy(rows, n_key, d, launch_occ,
                                  block_n=block_n)
    if meta_fine is None or all(
        autotune.has_occupancy(rows, k, d, fine) for k in col_counts
    ):
        return
    fine_tm = spatial.tile_map(yrec, meta_fine, inv2h2, epsilon,
                               block_m=block_m, kind=kind)
    fine_occ = float(spatial.to_host(jnp.mean(fine_tm.keep)))
    for n_key in col_counts:
        autotune.record_occupancy(rows, n_key, d, fine_occ, block_n=fine)


class ScorePrep(NamedTuple):
    """A pruned score pass's operands on one device: rows of a padded
    cluster-aligned layout (all of them, or a contiguous range on one chip)
    and every column of it, at the tier (``_score_operands``)."""

    x_ops: tuple                 # row operands (hi, lo)
    nrm: jnp.ndarray             # (R, 1) f32 squared norms of the rows
    xrec: jnp.ndarray            # (R, d) f32 rows the kernels see
    xt_ops: tuple                # column operands (hi, lo)
    xaug_ops: tuple              # [X | 1] weights (hi, lo); None packed
    nrm_cols: Optional[jnp.ndarray]  # (1, N); None: the rows are all N
    meta: spatial.TileMeta       # column tiles at the launch width
    meta_fine: Optional[spatial.TileMeta]  # at the tuner's probe width,
    #                            # until its profile holds this regime


def score_prep(xp: jnp.ndarray, real: jnp.ndarray, *, n: int,
               precision: str, block_n: int) -> ScorePrep:
    """Operands and column metadata of a padded cluster layout of ``n``
    points, every row against every column, in the
    ``kernels.prune.operands`` span."""
    d = xp.shape[1]
    fine = autotune.FINE_PROBE_BLOCK
    with obs.span("kernels.prune.operands"):
        x_ops, xt_ops, xaug_ops, nrm, xrec = _score_operands(
            xp, precision, packed=prec.packs(precision))
        meta = spatial.tile_metadata(xrec, real, block=block_n)
        meta_fine = None
        if block_n > fine and xp.shape[0] % fine == 0 \
                and not autotune.has_occupancy(n, n, d, fine):
            meta_fine = spatial.tile_metadata(xrec, real, block=fine)
    return ScorePrep(x_ops, nrm, xrec, xt_ops, xaug_ops, None, meta,
                     meta_fine)


def score_tile_map(prep: ScorePrep, h, epsilon: float, *,
                   block_m: int) -> spatial.TileMap:
    """The bounds prepass of the prepared rows against every column tile,
    in the ``kernels.prune.tile_map`` span."""
    with obs.span("kernels.prune.tile_map"):
        return spatial.tile_map(prep.xrec, prep.meta, _inv2h2(h), epsilon,
                                block_m=block_m, kind="score")


def score_launch(prep: ScorePrep, tm: spatial.TileMap, h, epsilon: float, *,
                 n: int, real_rows: int, precision: str, block_m: int,
                 block_n: int, interpret: Optional[bool],
                 chip: int = 0) -> jnp.ndarray:
    """Visit lists, tuner record and launch of one pruned score pass:
    S1aug (rows, d+1) of the prepared rows against every column.  ``n``
    is the train set's point count, ``real_rows`` the non-sentinel rows;
    ``chip`` names the launch's device in its span."""
    d = prep.xrec.shape[1]
    with obs.span("kernels.prune.visit_lists"):
        vl = spatial.visit_lists(tm.keep)
    with obs.span("kernels.prune.profile"):
        _record_occupancy_profile(n, {n}, d, vl.occupancy, block_n,
                                  prep.xrec, prep.meta_fine, _inv2h2(h),
                                  epsilon, block_m, "score")
        _note_pruned_launch("score", vl, tm)
    slots = flash_pruned.score_slots(
        vl.max_visits, block_m=block_m, block_n=block_n, d=d,
        itemsize=prec.operand_bytes(precision))
    with obs.span("kernels.pruned_score", rows=real_rows,
                  occupancy=round(vl.occupancy, 4), chip=chip,
                  slots_per_step=slots,
                  grid_steps=len(vl.counts) * -(-vl.max_visits // slots)):
        return flash_pruned.flash_score_pallas_pruned(
            vl.counts, vl.tile_map, prep.x_ops[0], prep.nrm,
            prep.xt_ops[0], prep.xaug_ops[0], _inv2h2(h), prep.x_ops[1],
            prep.xt_ops[1], prep.xaug_ops[1], nrm_cols=prep.nrm_cols,
            block_m=block_m, block_n=block_n, max_visits=vl.max_visits,
            interpret=interpret, packed=prec.packs(precision),
        )


def _score_stats_pruned(
    x: jnp.ndarray,
    h,
    epsilon: float,
    index: spatial.SpatialIndex,
    *,
    precision: str,
    block_m: int,
    block_n: int,
    interpret: Optional[bool],
):
    """Pruned score pass; returns (S0, S1) in ``x``'s original row order.

    The score pass is train×train, so the cluster-aligned layout serves
    both axes: row tiles and column tiles of the same padded scatter, and
    the output rows come straight back through the layout's slot map.  The
    certificate uses the score kind — per-point bound exp(-arg)·max(1,
    max|x|) — because the accumulator weights are the [X | 1] columns.
    Each host step runs in its own ``kernels.prune.*`` span.
    """
    n, d = x.shape
    with obs.span("kernels.prune.layout"):
        layout = spatial.cluster_layout(
            jnp.asarray(x, jnp.float32), index.labels, block_n,
            total_multiple=math.lcm(block_m, block_n),
        )
    prep = score_prep(layout.points, layout.real, n=n, precision=precision,
                      block_n=block_n)
    tm = score_tile_map(prep, h, epsilon, block_m=block_m)
    s1aug = score_launch(prep, tm, h, epsilon, n=n, real_rows=n,
                         precision=precision, block_m=block_m,
                         block_n=block_n, interpret=interpret)
    with obs.span("kernels.prune.gather"):
        rows = s1aug[layout.slots]
        return rows[:, d], rows[:, :d]


def _note_pruned_launch(kind: str, vl: spatial.VisitLists,
                        tm: spatial.TileMap) -> None:
    """Record one pruned pass: visit fraction (= 1 − skip rate) and the
    certified error budget actually spent, so serving telemetry can show
    how sparse traffic really is and how close certificates run to their
    epsilon.  The max-reduction over the (tiny) per-row-tile err_bound
    vector host-syncs, so the whole helper is skipped when metrics are
    off — this already sits on the pruned path's host-sync boundary."""
    if not obs_state.metrics_on:
        return
    obs.counter("kernels.prune.launches", labels={"kind": kind}).inc()
    obs.histogram("kernels.prune.visit_fraction",
                  "column tiles visited / total per pruned pass",
                  lo=1e-3, hi=1.0).observe(vl.occupancy)
    err = float(spatial.to_host(jnp.max(tm.err_bound))) \
        if tm.err_bound.size else 0.0
    obs.histogram("kernels.prune.cert_budget",
                  "max certified abs error of the unnormalized "
                  "accumulator per pruned pass",
                  lo=1e-30, hi=1.0, per_decade=1).observe(err)


def _prune_pass(kind: str, rows: int, cols: int):
    """The span around one whole pruned pass, train-side prep included;
    its steps are the ``kernels.prune.*`` spans opened inside it."""
    return obs.span("kernels.prune.pass", kind=kind, rows=rows, cols=cols)


def _score_stats(x, h, epsilon: Optional[float], *, precision: str,
                 block_m: int, block_n: int, interpret: Optional[bool],
                 seed: int):
    """(S0, S1, index): the dense score pass (index None) when
    ``epsilon`` is None, else the pruned one and the clustering it built."""
    n = x.shape[0]
    if epsilon is None:
        with obs.span("kernels.dense_score", rows=n, cols=n):
            s0, s1 = _flash_score_stats_dense(
                x, h, precision=precision, block_m=block_m,
                block_n=block_n, interpret=interpret,
            )
        return s0, s1, None
    with _prune_pass("score", n, n):
        with obs.span("kernels.prune.index"):
            index = spatial.build_index(x, seed=seed)
        s0, s1 = _score_stats_pruned(
            x, h, epsilon, index, precision=precision, block_m=block_m,
            block_n=block_n, interpret=interpret,
        )
    return s0, s1, index


def flash_score_stats(
    x: jnp.ndarray,
    h,
    *,
    precision: str = "f32",
    block_m="auto",
    block_n="auto",
    interpret: Optional[bool] = None,
    prune: PruneArg = "auto",
    seed: int = 0,
    plan=None,
):
    """(S0, S1) score statistics over the train set via the fused kernel."""
    prec.validate(precision)
    n, d = x.shape
    precision, block_m, block_n, prune = _apply_plan(
        plan, n, n, d, precision=precision, block_m=block_m,
        block_n=block_n, prune=prune,
    )
    if _traced(x):
        prune = "off"            # pruning host-syncs; stay traceable
    block_m, block_n = _resolve(
        block_m, block_n, n, n, d, out_width=d + 1, precision=precision,
        interpret=interpret, pruned=prune != "off",
    )
    s0, s1, _ = _score_stats(
        x, h, resolve_prune(prune, n, block_n), precision=precision,
        block_m=block_m, block_n=block_n, interpret=interpret, seed=seed)
    return s0, s1


def _apply_score_shift(x32: jnp.ndarray, s0, s1, h, sh) -> jnp.ndarray:
    """x^SD = x + (h²/2)·ŝ(x) from the fused statistics (rows aligned)."""
    sh = jnp.asarray(sh, jnp.float32)
    h = jnp.asarray(h, jnp.float32)
    score = (s1 - x32 * s0[:, None]) / (sh * sh * s0[:, None])
    return x32 + 0.5 * h * h * score


def flash_sdkde_shift(
    x: jnp.ndarray,
    h,
    *,
    score_h=None,
    precision: str = "f32",
    block_m="auto",
    block_n="auto",
    interpret: Optional[bool] = None,
    prune: PruneArg = "auto",
    seed: int = 0,
    plan=None,
) -> jnp.ndarray:
    """Debiased samples x^SD = x + (h²/2)·ŝ(x), score via the flash kernel."""
    sh = h if score_h is None else score_h
    s0, s1 = flash_score_stats(
        x, sh, precision=precision,
        block_m=block_m, block_n=block_n, interpret=interpret,
        prune=prune, seed=seed, plan=plan,
    )
    return _apply_score_shift(x.astype(jnp.float32), s0, s1, h, sh)


# ---------------------------------------------------------------------------
# KDE / Laplace-KDE evaluation.
# ---------------------------------------------------------------------------


def _prep_eval(x, y, block_m, block_n, precision):
    """Pad, transpose, norm and tier-cast one (train, queries) pair."""
    yp = _pad_to(y, block_m)
    xp = _pad_to(x, block_n)
    if precision == "f32":
        y_ops = (yp, None)
        xt_ops = (xp.astype(jnp.float32).T.astype(xp.dtype), None)
        nrm_y, nrm_x = _norms(yp), _norms(xp).reshape(1, -1)
    else:
        y_ops = prec.cast_operand(yp.astype(jnp.float32), precision)
        x_ops = prec.cast_operand(xp.astype(jnp.float32), precision)
        # cast commutes with transpose: the lane-major column planes are
        # the row-layout planes transposed, and the column norms come from
        # the same cast values the kernel will stream.
        xt_ops = (x_ops[0].T, None if x_ops[1] is None else x_ops[1].T)
        nrm_y = _tier_norms(*y_ops)
        nrm_x = _tier_norms(*x_ops).reshape(1, -1)
    return y_ops, xt_ops, nrm_y, nrm_x


@functools.partial(jax.jit, static_argnames=_STATIC + ("laplace",))
def _flash_eval_dense(
    x: jnp.ndarray,
    y: jnp.ndarray,
    h,
    *,
    precision: str = "f32",
    block_m=128,
    block_n=512,
    interpret: Optional[bool] = None,
    laplace: bool = False,
) -> jnp.ndarray:
    """Dense KDE / fused-Laplace evaluation (normalized densities)."""
    n, d = x.shape
    m = y.shape[0]
    y_ops, xt_ops, nrm_y, nrm_x = _prep_eval(x, y, block_m, block_n,
                                             precision)
    kernel = flash_laplace_pallas if laplace else flash_kde_pallas
    sums = kernel(
        y_ops[0], nrm_y, xt_ops[0], nrm_x, _inv2h2(h), y_ops[1], xt_ops[1],
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    h = jnp.asarray(h, jnp.float32)
    return sums[:m, 0] / (n * gaussian_norm_const(d, 1.0) * h**d)


def _flash_eval(x, y, h, *, precision, block_m, block_n, interpret, prune,
                seed, plan, laplace: bool) -> jnp.ndarray:
    """Normalized KDE (or fused Laplace-KDE) densities at ``y``."""
    prec.validate(precision)
    n, d = x.shape
    m = y.shape[0]
    precision, block_m, block_n, prune = _apply_plan(
        plan, n, m, d, precision=precision, block_m=block_m,
        block_n=block_n, prune=prune,
    )
    if _traced(x, y):
        prune = "off"            # pruning host-syncs; stay traceable
    block_m, block_n = _resolve(
        block_m, block_n, m, n, d, out_width=1, precision=precision,
        interpret=interpret, pruned=prune != "off",
    )
    eps = resolve_prune(prune, n, block_n)
    kind = "laplace" if laplace else "kde"
    if eps is None:
        with obs.span("kernels.dense_eval", rows=m, cols=n, kind=kind):
            return _flash_eval_dense(
                x, y, h, precision=precision, block_m=block_m,
                block_n=block_n, interpret=interpret, laplace=laplace,
            )
    with _prune_pass(kind, m, n):
        cols = _cached_columns(x, block_n=block_n, precision=precision,
                               seed=seed)
        sums = _pruned_eval_sums(
            y, cols, h, eps, precision=precision, block_m=block_m,
            block_n=block_n, interpret=interpret, laplace=laplace,
        )
    h = jnp.asarray(h, jnp.float32)
    return sums / (n * gaussian_norm_const(d, 1.0) * h**d)


def flash_kde(
    x: jnp.ndarray,
    y: jnp.ndarray,
    h,
    *,
    precision: str = "f32",
    block_m="auto",
    block_n="auto",
    interpret: Optional[bool] = None,
    prune: PruneArg = "auto",
    seed: int = 0,
    plan=None,
) -> jnp.ndarray:
    """Normalized Gaussian KDE densities at ``y`` (train set ``x``)."""
    return _flash_eval(x, y, h, precision=precision, block_m=block_m,
                       block_n=block_n, interpret=interpret, prune=prune,
                       seed=seed, plan=plan, laplace=False)


def flash_laplace_kde(
    x: jnp.ndarray,
    y: jnp.ndarray,
    h,
    *,
    precision: str = "f32",
    block_m="auto",
    block_n="auto",
    interpret: Optional[bool] = None,
    prune: PruneArg = "auto",
    seed: int = 0,
    plan=None,
) -> jnp.ndarray:
    """Fused Flash-Laplace-KDE densities at ``y`` — single quadratic pass."""
    return _flash_eval(x, y, h, precision=precision, block_m=block_m,
                       block_n=block_n, interpret=interpret, prune=prune,
                       seed=seed, plan=plan, laplace=True)


@functools.partial(jax.jit, static_argnames=_STATIC)
def laplace_kde_nonfused(
    x: jnp.ndarray,
    y: jnp.ndarray,
    h,
    *,
    precision: str = "f32",
    block_m="auto",
    block_n="auto",
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Non-fused Laplace baseline: two quadratic kernel launches (Fig. 4).

    Stays dense on purpose — it exists as the measured baseline for the
    fusion (and now pruning) speedups.
    """
    prec.validate(precision)
    n, d = x.shape
    m = y.shape[0]
    block_m, block_n = _resolve(
        block_m, block_n, m, n, d, out_width=1, precision=precision,
        interpret=interpret,
    )
    y_ops, xt_ops, nrm_y, nrm_x = _prep_eval(x, y, block_m, block_n,
                                             precision)
    kde_sums = flash_kde_pallas(
        y_ops[0], nrm_y, xt_ops[0], nrm_x, _inv2h2(h), y_ops[1], xt_ops[1],
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    sq_mom = sq_moment_pallas(
        y_ops[0], nrm_y, xt_ops[0], nrm_x, _inv2h2(h), y_ops[1], xt_ops[1],
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    h = jnp.asarray(h, jnp.float32)
    combined = (1.0 + d / 2.0) * kde_sums - sq_mom / (2.0 * h * h)
    return combined[:m, 0] / (n * gaussian_norm_const(d, 1.0) * h**d)


# ---------------------------------------------------------------------------
# Prepared fast path (serving).
# ---------------------------------------------------------------------------


class TrainColumns(NamedTuple):
    """Fit-time prepared train tensors for one precision tier."""

    xt: jnp.ndarray                 # (d, n_padded) tier-cast hi plane
    xt_lo: Optional[jnp.ndarray]    # (d, n_padded) bf16 lo plane (bf16x2)
    nrm_x: jnp.ndarray              # (1, n_padded) f32 column norms
    # Cluster-pruning state (None on non-spatial prepares): per-column-tile
    # geometry certified against the tier-cast points, and the spatial
    # index whose centroids order incoming query batches.  ``meta_fine``
    # is the same geometry at the tuner's fine probe width — the pruned
    # wrappers measure occupancy there too, so the autotuner can
    # extrapolate skip rates to tile widths it has never launched.
    meta: Optional[spatial.TileMeta] = None
    index: Optional[spatial.SpatialIndex] = None
    meta_fine: Optional[spatial.TileMeta] = None
    block_n: int = 0                # prepare-time column-tile width
    # (plane_rows(d), n_padded) bf16 ``prec.column_planes``: what the pruned
    # kernels stream at the f32 tier (``prec.packs``); None elsewhere
    planes: Optional[jnp.ndarray] = None


def prepare_train_columns(
    x: jnp.ndarray,
    *,
    block_n: "int | str" = 512,
    precision: str = "f32",
    clustered: bool = False,
    index: Optional[spatial.SpatialIndex] = None,
    seed: int = 0,
) -> TrainColumns:
    """One-time train-side prep for repeated evaluation against the same set.

    Pads the (debiased) train set to a ``block_n`` multiple with sentinel
    points, builds the transposed (d, n) layout the kernels stream as lane-
    major column tiles (cast to the requested precision tier — for bf16x2
    both hi and lo planes), and precomputes the f32 column squared norms.
    ``block_n`` may be ``"auto"`` (autotuned for a serving-scale row count).

    ``clustered=True`` instead scatters the points into the cluster-aligned
    sentinel-padded layout (k-means by default; pass ``index`` to reuse an
    existing clustering — its per-row labels apply directly when fitted on
    a row-aligned set, e.g. the pre-shift points) and attaches the per-tile
    metadata the pruned kernels' bounds prepass consumes.  The serving
    registry caches the result per tier so none of this work is repeated
    per query batch.
    """
    prec.validate(precision)
    if block_n == "auto":
        _, block_n = autotune.resolve_blocks(
            128, "auto", rows=4096, cols=x.shape[0], d=x.shape[-1],
            precision=precision, measure=False,
        )
    if not clustered:
        return columns_from_layout(_pad_to(x, block_n), None, None,
                                   block_n=block_n, precision=precision)
    if index is None:
        with obs.span("kernels.prune.index"):
            index = spatial.build_index(x, seed=seed)
    with obs.span("kernels.prune.layout"):
        labels = index.labels if (
            index.labels is not None
            and index.labels.shape[0] == x.shape[0]
        ) else spatial.assign(x, index)
        layout = spatial.cluster_layout(jnp.asarray(x), labels, block_n)
    with obs.span("kernels.prune.operands"):
        return columns_from_layout(layout.points, layout.real, index,
                                   block_n=block_n, precision=precision)


def columns_from_layout(
    xp: jnp.ndarray,
    real: Optional[jnp.ndarray],
    index: Optional[spatial.SpatialIndex],
    *,
    block_n: int,
    precision: str = "f32",
) -> TrainColumns:
    """TrainColumns from an already-scattered padded layout.

    The streaming layer owns its layout (slack slots, in-place refreshes)
    and calls this to (re)build the per-tier cast planes + norms + tile
    metadata; ``prepare_train_columns`` routes through here too, so both
    paths share one casting/metadata recipe.  ``real=None`` means a plain
    tail-padded (non-clustered) layout: no metadata is attached, and no
    packed planes (only the pruned kernels read them).
    """
    prec.validate(precision)
    if precision == "f32":
        xt, xt_lo = xp.astype(jnp.float32).T.astype(xp.dtype), None
        xrec = xp.astype(jnp.float32)
        nrm_x = _norms(xp).reshape(1, -1)
    else:
        x_hi, x_lo = prec.cast_operand(xp.astype(jnp.float32), precision)
        xt, xt_lo = x_hi.T, None if x_lo is None else x_lo.T
        xrec = prec.reconstruct(x_hi, x_lo)
        nrm_x = _norms(xrec).reshape(1, -1)
    meta = meta_fine = planes = None
    if real is not None:
        meta = spatial.tile_metadata(xrec, real, block=block_n)
        fine = autotune.FINE_PROBE_BLOCK
        if block_n > fine and xp.shape[0] % fine == 0:
            meta_fine = spatial.tile_metadata(xrec, real, block=fine)
        if prec.packs(precision):
            planes = prec.column_planes(xrec)
    return TrainColumns(xt, xt_lo, nrm_x, meta, index, meta_fine, block_n,
                        planes)


def update_train_columns(
    cols: TrainColumns,
    xp: jnp.ndarray,
    real: jnp.ndarray,
    tiles,
    *,
    precision: str = "f32",
) -> TrainColumns:
    """Refresh prepared columns for only the listed column tiles.

    The streaming delta path: after appends/evictions/shift drift touch a
    subset of tiles, re-cast those tiles' operand columns, recompute their
    norms and tile metadata, and carry every untouched column over
    bit-for-bit.  The *compute* saved is the per-tile cast/split, norm
    and metadata reductions — the functional ``.at[].set`` updates still
    copy the full (d, n) planes, so a flush remains Θ(n·d) in memory
    traffic; what this buys is skipping the reduction work and keeping
    clean tiles' certificates byte-identical.  ``tiles`` may contain
    repeats (pow2-padded index buffers keep retraces bounded); each write
    is recomputed from the current layout, so repeated writes are
    idempotent.
    """
    prec.validate(precision)
    block = cols.block_n
    tiles_np = np.asarray(tiles, np.int64).reshape(-1)
    if tiles_np.size == 0:
        return cols
    rows_np = tiles_np[:, None] * block + np.arange(block)[None, :]
    rows = jnp.asarray(rows_np.reshape(-1), jnp.int32)
    sub = jnp.asarray(xp, jnp.float32)[rows]             # (k·block, d)
    if precision == "f32":
        hi, lo = sub.astype(cols.xt.dtype), None
        rec = sub
    else:
        hi, lo = prec.cast_operand(sub, precision)
        rec = prec.reconstruct(hi, lo)
    xt = cols.xt.at[:, rows].set(hi.T)
    xt_lo = cols.xt_lo if cols.xt_lo is None else (
        cols.xt_lo.at[:, rows].set(lo.T)
    )
    nrm_x = cols.nrm_x.at[0, rows].set(_norms(rec)[:, 0])
    planes = cols.planes if cols.planes is None else (
        cols.planes.at[:, rows].set(prec.column_planes(rec))
    )
    meta, meta_fine = cols.meta, cols.meta_fine
    if meta is not None:
        mask = jnp.asarray(real)[rows]
        meta = spatial.merge_tile_meta(
            meta, tiles_np,
            spatial.tile_meta_from_rows(
                rec.reshape(tiles_np.size, block, -1),
                mask.reshape(tiles_np.size, block),
            ),
        )
        if meta_fine is not None:
            fine = autotune.FINE_PROBE_BLOCK
            ratio = block // fine
            ftiles = (tiles_np[:, None] * ratio
                      + np.arange(ratio)[None, :]).reshape(-1)
            meta_fine = spatial.merge_tile_meta(
                meta_fine, ftiles,
                spatial.tile_meta_from_rows(
                    rec.reshape(ftiles.size, fine, -1),
                    mask.reshape(ftiles.size, fine),
                ),
            )
    return cols._replace(xt=xt, xt_lo=xt_lo, nrm_x=nrm_x, meta=meta,
                         meta_fine=meta_fine, planes=planes)


def _cast_queries(yp: jnp.ndarray, precision: str, packed: bool = False):
    """(y_hi, y_lo, nrm_y, yrec) for a padded query block at one tier;
    ``packed``: y_hi is ``prec.pack_rows`` of the f32 queries."""
    if packed:
        yrec = yp.astype(jnp.float32)
        return prec.pack_rows(yrec), None, _norms(yrec), yrec
    if precision == "f32":
        y_hi, y_lo = yp, None
        yrec = yp.astype(jnp.float32)
    else:
        y_hi, y_lo = prec.cast_operand(yp.astype(jnp.float32), precision)
        yrec = prec.reconstruct(y_hi, y_lo)
    return y_hi, y_lo, _norms(yrec), yrec


def _check_pruned_columns(cols: TrainColumns, precision: str,
                         block_n: int) -> None:
    """Raise unless ``cols`` can serve a pruned launch at ``precision``
    and ``block_n``."""
    if cols.meta is None:
        raise ValueError(
            "pruned evaluation needs spatially prepared train columns "
            "(prepare_train_columns(..., clustered=True))"
        )
    if cols.block_n != block_n:
        raise ValueError(
            "pruned launch block_n must match the width the columns were "
            f"prepared at: launch {block_n} vs prepared {cols.block_n} — "
            "the tile metadata and visit lists address tiles of that width"
        )
    packed = prec.packs(precision)
    if (cols.planes is not None) != packed:
        raise ValueError(
            f"pruned evaluation at precision {precision!r} needs columns "
            f"prepared at that tier ({'with' if packed else 'without'} the "
            "packed f32 planes)"
        )


class EvalRows(NamedTuple):
    """Padded, cluster-ordered query rows cast to a tier
    (``_cast_queries``)."""

    y_hi: jnp.ndarray
    y_lo: Optional[jnp.ndarray]
    nrm_y: jnp.ndarray
    yrec: jnp.ndarray


def eval_rows(yp: jnp.ndarray, precision: str) -> EvalRows:
    """The tier casts of padded query rows, in the
    ``kernels.prune.operands`` span."""
    with obs.span("kernels.prune.operands"):
        return EvalRows(*_cast_queries(yp, precision,
                                       packed=prec.packs(precision)))


def eval_tile_map(q: EvalRows, cols: TrainColumns, h, epsilon: float, *,
                  block_m: int, kind: str) -> spatial.TileMap:
    """The bounds prepass of query rows against prepared columns, in the
    ``kernels.prune.tile_map`` span."""
    with obs.span("kernels.prune.tile_map"):
        return spatial.tile_map(q.yrec, cols.meta, _inv2h2(h), epsilon,
                                block_m=block_m, kind=kind)


def eval_launch(q: EvalRows, tm: spatial.TileMap, cols: TrainColumns, h,
                epsilon: float, *, rows_key: int, real_rows: int,
                precision: str, block_m: int, block_n: int,
                interpret: Optional[bool], laplace: bool,
                chip: int = 0) -> jnp.ndarray:
    """Visit lists, tuner record and launch of one pruned density pass:
    the (rows, 1) kernel sums of the query rows.  ``rows_key`` is the
    query count the tuner's profile is recorded under, ``real_rows`` the
    non-sentinel rows; ``chip`` names the launch's device in its span."""
    d = q.yrec.shape[1]
    kind = "laplace" if laplace else "kde"
    packed = prec.packs(precision)
    with obs.span("kernels.prune.visit_lists"):
        vl = spatial.visit_lists(tm.keep)
    with obs.span("kernels.prune.profile"):
        # record under BOTH column counts a later resolve may key on: the
        # true train count (flash_kde / flash_sdkde resolve pre-padding)
        # and the padded layout length (the prepared serving path)
        n_true = int(spatial.to_host(cols.meta.counts.sum()))
        _record_occupancy_profile(rows_key, {n_true, cols.xt.shape[1]}, d,
                                  vl.occupancy, block_n, q.yrec,
                                  cols.meta_fine, _inv2h2(h), epsilon,
                                  block_m, kind)
        _note_pruned_launch(kind, vl, tm)
    with obs.span("kernels.pruned_eval", rows=real_rows, kind=kind,
                  occupancy=round(vl.occupancy, 4),
                  max_visits=vl.max_visits, chip=chip):
        return flash_pruned.flash_kde_pallas_pruned(
            vl.counts, vl.tile_map, q.y_hi, q.nrm_y,
            cols.planes if packed else cols.xt, cols.nrm_x,
            _inv2h2(h), q.y_lo, cols.xt_lo,
            block_m=block_m, block_n=block_n, max_visits=vl.max_visits,
            interpret=interpret, laplace=laplace, packed=packed,
        )


def _pruned_eval_sums(
    y: jnp.ndarray,
    cols: TrainColumns,
    h,
    epsilon: float,
    *,
    precision: str,
    block_m: int,
    block_n: int,
    interpret: Optional[bool],
    laplace: bool,
    n_real: Optional[int] = None,
) -> jnp.ndarray:
    """Pruned kernel sums (len(y),) for queries against prepared columns.

    ``y`` may carry sentinel padding rows past ``n_real`` (the serving
    path); only real rows enter the query layout.  This is the pruned
    path's one host-sync orchestration: assign queries to the train
    clusters → scatter into a cluster-aligned layout → bounds prepass →
    compact visit lists → launch → gather back to request order,
    each step in its own ``kernels.prune.*`` span.  Callers open the
    ``kernels.prune.pass`` span around it and the train-side prep.
    """
    _check_pruned_columns(cols, precision, block_n)
    if cols.index is None:
        raise ValueError(
            "pruned evaluation needs spatially prepared train columns "
            "(prepare_train_columns(..., clustered=True))"
        )
    y = jnp.asarray(y)
    m_in, d = y.shape
    nr = m_in if n_real is None else min(n_real, m_in)
    # scatter the real queries into their own cluster-aligned layout
    # (assigned against the train centroids) so row tiles stay coherent
    with obs.span("kernels.prune.layout"):
        labels = spatial.assign(y[:nr], cols.index)
        qlayout = spatial.cluster_layout(
            jnp.asarray(y[:nr], jnp.float32), labels, block_m,
            bucket_rows=True,
        )
    q = eval_rows(qlayout.points, precision)
    tm = eval_tile_map(q, cols, h, epsilon, block_m=block_m,
                       kind="laplace" if laplace else "kde")
    sums = eval_launch(q, tm, cols, h, epsilon, rows_key=m_in, real_rows=nr,
                       precision=precision, block_m=block_m,
                       block_n=block_n, interpret=interpret,
                       laplace=laplace)
    with obs.span("kernels.prune.gather"):
        out = sums[qlayout.slots, 0]             # back to request order
        if nr < m_in:                            # caller's sentinel tail
            out = jnp.concatenate([out, jnp.zeros((m_in - nr,), out.dtype)])
        return out


@functools.partial(jax.jit, static_argnames=_STATIC + ("laplace",))
def _flash_kde_prepared_dense(
    yp: jnp.ndarray,
    xt: jnp.ndarray,
    nrm_x: jnp.ndarray,
    h,
    xt_lo: jnp.ndarray | None = None,
    *,
    precision: str = "f32",
    block_m=128,
    block_n=512,
    interpret: Optional[bool] = None,
    laplace: bool = False,
) -> jnp.ndarray:
    y_hi, y_lo, nrm_y, _ = _cast_queries(yp, precision)
    kernel = flash_laplace_pallas if laplace else flash_kde_pallas
    sums = kernel(
        y_hi, nrm_y, xt, nrm_x, _inv2h2(h), y_lo, xt_lo,
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    return sums[:, 0]


def flash_kde_prepared(
    yp: jnp.ndarray,       # (m, d) queries, ALREADY padded to block_m multiple
    xt: jnp.ndarray,       # (d, n) from prepare_train_columns (tier-cast)
    nrm_x: jnp.ndarray,    # (1, n) from prepare_train_columns
    h,
    xt_lo: jnp.ndarray | None = None,  # (d, n) lo plane (bf16x2 tier)
    *,
    precision: str = "f32",
    block_m="auto",
    block_n="auto",
    interpret: Optional[bool] = None,
    laplace: bool = False,
    prune: PruneArg = "off",
    columns: Optional[TrainColumns] = None,
    n_real: Optional[int] = None,
) -> jnp.ndarray:
    """No-reassert fast path: unnormalized kernel sums for pre-padded queries.

    Skips the per-call padding, transposition and norm precomputation that
    ``flash_kde`` does — the serving layer pads queries to shape-bucket
    multiples of ``block_m`` up front and reuses the prepared train tensors
    (cached per precision tier) across every batch.  Returns raw sums (m,);
    the caller divides by ``n_true · (2π)^{d/2} h^d`` (padding rows give ~0
    and are sliced off by the caller).

    ``prune`` ≠ "off" takes the cluster-pruned path: pass the full
    ``columns`` (prepared with ``clustered=True``, so the tile metadata and
    spatial index are fit-time state) and ``n_real`` = the true query count
    so sentinel padding rows stay out of the row-tile geometry.  The dense
    path stays jit-traceable; the pruned path host-syncs once per batch for
    its visit lists' extent.
    """
    prec.validate(precision)
    if _traced(yp):
        prune = "off"            # pruning host-syncs; stay traceable
    if (precision == "bf16x2") != (xt_lo is not None):
        raise ValueError(
            "bf16x2 needs prepared lo planes (and other tiers must not "
            f"pass them): precision={precision} xt_lo={xt_lo is not None}"
        )
    m, d = yp.shape
    n = xt.shape[1]
    if prune != "off" and columns is not None and block_n == "auto":
        # the visit lists index tiles of the prepare-time width — an
        # autotuned width that differs would silently misaddress them
        block_n = columns.block_n
    block_m, block_n = _resolve(
        block_m, block_n, m, n, d, out_width=1, precision=precision,
        interpret=interpret, row_multiple=m, col_multiple=n,
        pruned=prune != "off",
    )
    eps = resolve_prune(prune, n, block_n)
    kind = "laplace" if laplace else "kde"
    if eps is None:
        with obs.span("kernels.dense_eval", rows=m, cols=n, kind=kind):
            return _flash_kde_prepared_dense(
                yp, xt, nrm_x, h, xt_lo, precision=precision,
                block_m=block_m, block_n=block_n, interpret=interpret,
                laplace=laplace,
            )
    if columns is None:
        raise ValueError(
            "flash_kde_prepared(prune=...) needs columns= (the clustered "
            "TrainColumns) for the tile metadata"
        )
    with _prune_pass(kind, m if n_real is None else min(n_real, m), n):
        return _pruned_eval_sums(
            yp, columns, h, eps, precision=precision, block_m=block_m,
            block_n=block_n, interpret=interpret, laplace=laplace,
            n_real=n_real,
        )


# ---------------------------------------------------------------------------
# Full pipeline.
# ---------------------------------------------------------------------------


def flash_sdkde(
    x: jnp.ndarray,
    y: jnp.ndarray,
    h,
    *,
    score_h=None,
    precision: str = "f32",
    block_m="auto",
    block_n="auto",
    interpret: Optional[bool] = None,
    prune: PruneArg = "auto",
    seed: int = 0,
    plan=None,
) -> jnp.ndarray:
    """Full Flash-SD-KDE: score pass → shift → KDE at queries (normalized).

    The pipeline shares one train-side prep: the spatial clustering is
    computed once on ``x`` and its layout is reused for the score pass
    (train×train) *and* the KDE eval on the shifted set — the debias shift
    is O(h²), so the ordering stays tight — and the shifted set flows
    through ``prepare_train_columns`` (no second pad/transpose).
    """
    prec.validate(precision)
    n, d = x.shape
    m = y.shape[0]
    precision, block_m, block_n, prune = _apply_plan(
        plan, n, m, d, precision=precision, block_m=block_m,
        block_n=block_n, prune=prune,
    )
    if _traced(x, y):
        prune = "off"            # pruning host-syncs; stay traceable
    sh = h if score_h is None else score_h
    s_bm, s_bn = _resolve(
        block_m, block_n, n, n, d, out_width=d + 1, precision=precision,
        interpret=interpret, pruned=prune != "off",
    )
    k_bm, k_bn = _resolve(
        block_m, block_n, m, n, d, out_width=1, precision=precision,
        interpret=interpret, pruned=prune != "off",
    )
    s_eps = resolve_prune(prune, n, s_bn)
    k_eps = resolve_prune(prune, n, k_bn)

    x32 = jnp.asarray(x, jnp.float32)
    s0, s1, index = _score_stats(
        x32, sh, s_eps, precision=precision, block_m=s_bm, block_n=s_bn,
        interpret=interpret, seed=seed)
    x_sd = _apply_score_shift(x32, s0, s1, h, sh)

    if k_eps is None:
        cols = prepare_train_columns(x_sd, block_n=k_bn, precision=precision)
        yp = _pad_to(jnp.asarray(y), k_bm)
        with obs.span("kernels.dense_eval", rows=m, cols=n, kind="kde"):
            sums = _flash_kde_prepared_dense(
                yp, cols.xt, cols.nrm_x, h, cols.xt_lo, precision=precision,
                block_m=k_bm, block_n=k_bn, interpret=interpret,
                laplace=False,
            )[:m]
    else:
        with _prune_pass("kde", m, n):
            # one shared eval-side prep, reusing the clustering: the
            # labels fitted on x stay valid row-for-row for the
            # O(h²)-shifted x_sd
            with obs.span("kernels.prune.columns"):
                if index is None:
                    with obs.span("kernels.prune.index"):
                        index = spatial.build_index(x32, seed=seed)
                cols = prepare_train_columns(
                    x_sd, block_n=k_bn, precision=precision, clustered=True,
                    index=index,
                )
            sums = _pruned_eval_sums(
                y, cols, h, k_eps, precision=precision, block_m=k_bm,
                block_n=k_bn, interpret=interpret, laplace=False,
            )
    h = jnp.asarray(h, jnp.float32)
    return sums / (n * gaussian_norm_const(d, 1.0) * h**d)
