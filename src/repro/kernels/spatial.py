"""Spatial tile reordering + certified tile skipping for the flash kernels.

Every dense flash kernel streams all ``m/block_m × n/block_n`` tile pairs
even though ``exp(-‖y−x‖²/2h²)`` underflows to exactly 0.0 for the vast
majority of tiles at paper-scale problems.  This module supplies the three
pieces a *pruned* pass needs (DEANN-style distance-aware pruning, with the
error budgets certified per tile):

  1. **Clustered layout** — k-means (default) or Morton grouping of the
     (debiased) train set, laid out so every streamed ``block_n`` column
     tile holds points of ONE cluster: each cluster's points are
     contiguous and sentinel-padded up to a tile multiple.  Without the
     per-cluster padding, the tiles at cluster boundaries straddle two
     far-apart clusters, inherit a covering radius the size of their
     separation, and can never be skipped — with tile size comparable to
     cluster size that is *every* tile.  Queries go through the same
     layout per batch (assigned to the train centroids), which keeps row
     tiles spatially coherent so their visit lists stay short.
  2. **Tile metadata** — per column tile: centroid, covering radius, real
     (non-sentinel) point count, and max |coordinate| (the score kernel's
     accumulator weight bound).  Sentinel rows are masked out, so
     all-padding tiles carry ``count == 0`` and are skipped for free.
  3. **Tile maps** — the bounds prepass.  For every *query row* the
     distance to every column-tile centroid is one cheap
     ``(m × n/block_n)`` GEMM; min-reducing it over each ``block_m`` row
     tile gives

         dmin_ij = max(0, min_{r ∈ tile i} ‖y_r − c_j‖ − radius_j)
         arg_ij  = margin · dmin_ij² / (2h²)

     a certified lower bound on every pairwise exponent of the (i, j)
     tile (``margin < 1`` absorbs f32 round-off here and in the kernels'
     norm-trick ``sq``).  Using the per-row min — rather than a row-tile
     centroid+radius — keeps the bound tight even when a row tile spans
     several clusters.  The per-point contribution of tile ``j`` to any
     row of tile ``i`` is then at most

         kde:      exp(-arg)
         laplace:  exp(-arg) · (1 + d/2 + arg)      (decreasing in arg)
         score:    exp(-arg) · max(1, max|x| in j)  (the φ@[X|1] weights)

     A tile is skipped iff that bound is ≤ the caller's per-point
     ``epsilon``, or iff ``arg`` clears the f32 exp-underflow threshold —
     in which case the dense kernel would have accumulated *exactly 0.0*
     for every pair, so ``epsilon=0`` pruning reproduces the dense result
     bit-for-bit up to summation order.  The summed bound over skipped
     tiles is returned as a per-row-tile error certificate (tests assert
     the float64 dropped mass never exceeds it).

The kept tiles are compacted, on the device, into per-row-tile visit lists
(``tile_map[i, k]`` = k-th column tile row block ``i`` must stream), which
the pruned kernels consume via scalar prefetch — the grid shrinks from
``m_tiles × n_tiles`` to ``m_tiles × max_visits``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from repro import obs

PAD_VALUE = 1.0e6   # matches ops.PAD_VALUE — kernel weight underflows to 0

# f32 exp(-x) is exactly 0.0 for x > 150·ln2 ≈ 103.97 (subnormal rounding).
# 105 adds a hair of slack; MARGIN then demands ~11% more headroom before a
# tile may be skipped under the exact (epsilon=0) rule.
UNDERFLOW_ARG = 105.0
#: Conservative shrink on the certified exponent lower bound: covers f32
#: rounding in the bounds prepass and the kernels' norms-minus-Gram ``sq``.
MARGIN = 0.9

KINDS = ("kde", "laplace", "score")

#: Histogram of the pruned orchestration's device-to-host fetches: its
#: count is the number of syncs, its sum the bytes moved.
HOST_SYNC_BYTES = "kernels.prune.host_sync_bytes"


class SpatialIndex(NamedTuple):
    """A clustering of one point set: assignment state for layouts."""

    labels: Optional[jnp.ndarray]      # (n,) int32 cluster of each point
    centroids: Optional[jnp.ndarray]   # (k, d) f32 k-means centroids
    method: str = "kmeans"


class ClusterLayout(NamedTuple):
    """A cluster-aligned padded layout of one point set.

    ``points[slots[i]] == x[i]``; every other row is a sentinel.  Cluster
    c occupies a contiguous, ``block``-aligned slab, so no ``block`` tile
    ever holds two clusters.  ``real`` marks non-sentinel rows.
    """

    points: jnp.ndarray   # (total, d) padded layout
    real: jnp.ndarray     # (total,) bool
    slots: jnp.ndarray    # (n,) int32 — row of original point i
    block: int


class TileMeta(NamedTuple):
    """Per-column-tile geometry of a cluster-aligned layout."""

    centroids: jnp.ndarray   # (t, d) f32 centroid of the tile's real points
    radii: jnp.ndarray       # (t,)   f32 max ‖x − centroid‖ over real points
    counts: jnp.ndarray      # (t,)   int32 real (non-sentinel) points
    max_abs: jnp.ndarray     # (t,)   f32 max |coordinate| over real points


class TileMap(NamedTuple):
    """Bounds-prepass output: which tiles each row block must visit."""

    keep: jnp.ndarray        # (mt, t) bool
    err_bound: jnp.ndarray   # (mt,)  f32 certified max abs error per row of
    #                        # the unnormalized accumulator (worst component)


class VisitLists(NamedTuple):
    """Compacted tile map in the layout the pruned kernels prefetch, on the
    keep matrix's device."""

    counts: jnp.ndarray      # (mt,) int32 visits per row tile
    tile_map: jnp.ndarray    # (mt, max_visits) int32 column-tile indices
    max_visits: int          # static grid extent (pow2-bucketed)
    occupancy: float         # mean(counts) / n_tiles — the skip-rate stat


def to_host(a) -> np.ndarray:
    """``a`` as a host array.  Fetching a device array waits for it: each
    fetch observes its bytes in :data:`HOST_SYNC_BYTES` and adds them to
    the enclosing span's ``sync_bytes``.  A host array passes through."""
    out = np.asarray(a)
    if isinstance(a, jax.Array):
        obs.histogram(HOST_SYNC_BYTES, "bytes of one device-to-host fetch "
                      "of the pruned orchestration", lo=1,
                      hi=1e10).observe(out.nbytes)
        obs.current_span().add(sync_bytes=out.nbytes)
    return out


# ---------------------------------------------------------------------------
# Clustering.
# ---------------------------------------------------------------------------


def _sqdist(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    an = jnp.sum(a * a, axis=-1)[:, None]
    bn = jnp.sum(b * b, axis=-1)[None, :]
    g = jnp.matmul(a, b.T, precision=lax.Precision.HIGHEST)
    return jnp.maximum(an + bn - 2.0 * g, 0.0)


def default_n_clusters(n: int) -> int:
    """sqrt-law cluster count: ~128 at 256k points, floor 2, cap 1024.

    Erring toward MORE clusters than the data has is safe: pruning bounds
    only tighten as clusters shrink, while the assignment/bounds GEMMs
    stay O(n·k·d) — negligible next to the O(n·m·d) quadratic pass.
    """
    return max(2, min(1024, int(math.sqrt(max(n, 1) / 16.0))))


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def _kmeans_fit(x: jnp.ndarray, key: jnp.ndarray, *, k: int,
                iters: int) -> jnp.ndarray:
    """Lloyd iterations on (a subsample of) x; returns (k, d) centroids."""
    n = x.shape[0]
    c = x[jax.random.choice(key, n, (k,), replace=n < k)]
    for _ in range(iters):
        lab = jnp.argmin(_sqdist(x, c), axis=1)
        one = jax.nn.one_hot(lab, k, dtype=jnp.float32)     # (n, k)
        cnt = jnp.sum(one, axis=0)[:, None]                 # (k, 1)
        sums = jnp.matmul(one.T, x,                         # (k, d)
                          precision=lax.Precision.HIGHEST)
        c = jnp.where(cnt > 0, sums / jnp.maximum(cnt, 1.0), c)
    return c


def _morton_codes(x: jnp.ndarray) -> jnp.ndarray:
    """Interleaved-bit codes; coords quantized to the data range."""
    n, d = x.shape
    bits = max(1, 31 // d)
    lo = jnp.min(x, axis=0, keepdims=True)
    hi = jnp.max(x, axis=0, keepdims=True)
    q = ((x - lo) / jnp.maximum(hi - lo, 1e-30) * (2**bits - 1)).astype(
        jnp.int32
    )
    code = jnp.zeros((n,), jnp.int32)
    for b in range(bits - 1, -1, -1):
        for j in range(d):
            code = (code << 1) | ((q[:, j] >> b) & 1)
    return code


def _morton_labels(x32: jnp.ndarray, group: int = 64) -> jnp.ndarray:
    """Bucketed morton-rank labels: ~``group`` spatial neighbors per label."""
    n = x32.shape[0]
    order = jnp.argsort(_morton_codes(x32))
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32)
    )
    return rank // group


def build_index(
    x: jnp.ndarray,
    *,
    method: str = "kmeans",
    n_clusters: Optional[int] = None,
    iters: int = 8,
    fit_sample: int = 16384,
    seed: int = 0,
) -> SpatialIndex:
    """Cluster a point set; O(n·k·d) — amortized at prep/fit time.

    k-means fits Lloyd on a ≤``fit_sample`` subsample then assigns every
    point in one pass.  Morton labels points by their interleaved-bit
    code bucketed into ~64-point groups (grouping, not exact clustering —
    a fallback for data k-means fits poorly).
    """
    x32 = jnp.asarray(x, jnp.float32)
    n = x32.shape[0]
    if method == "morton":
        return SpatialIndex(_morton_labels(x32), None, "morton")
    if method != "kmeans":
        raise ValueError(f"unknown spatial ordering {method!r}")
    k = n_clusters or default_n_clusters(n)
    key = jax.random.PRNGKey(seed)
    fit = x32 if n <= fit_sample else x32[
        jax.random.choice(key, n, (fit_sample,), replace=False)
    ]
    c = _kmeans_fit(fit, jax.random.fold_in(key, 1), k=k, iters=iters)
    labels = jnp.argmin(_sqdist(x32, c), axis=1).astype(jnp.int32)
    return SpatialIndex(labels, c, "kmeans")


def assign(y: jnp.ndarray, index: SpatialIndex) -> jnp.ndarray:
    """Cluster labels for a NEW point set (queries) under a train index."""
    y32 = jnp.asarray(y, jnp.float32)
    if index.centroids is not None:
        return jnp.argmin(_sqdist(y32, index.centroids), axis=1).astype(
            jnp.int32
        )
    # morton / centroid-free indexes: group by the queries' own codes
    return _morton_labels(y32)


# ---------------------------------------------------------------------------
# Cluster-aligned layouts.
# ---------------------------------------------------------------------------


def cluster_capacities(labels, block: int, *, slack: float = 0.0,
                       n_clusters: Optional[int] = None):
    """Per-cluster slab geometry ``(starts, caps)`` in padded-row units.

    ``slack > 0`` reserves headroom beyond what the points need —
    ``ceil(size · slack)`` extra rows per cluster, and at least one full
    block even for an empty cluster — before rounding each slab up to a
    ``block`` multiple.  The headroom rows are ordinary sentinel rows
    until a streaming append claims them, so the padded layout's *shape*
    survives appends: new points land in free slots instead of forcing a
    re-scatter.  ``slack == 0`` reproduces the static layout exactly
    (empty clusters get zero rows).
    """
    lab = np.asarray(labels)
    k = n_clusters if n_clusters is not None else (
        int(lab.max()) + 1 if lab.size else 1
    )
    sizes = np.bincount(lab, minlength=k)
    if slack > 0.0:
        want = sizes + np.ceil(sizes * slack).astype(np.int64)
        want = np.maximum(want, 1)                        # empty → 1 block
    else:
        want = sizes
    caps = ((want + block - 1) // block) * block
    starts = np.concatenate([[0], np.cumsum(caps)[:-1]])
    return starts.astype(np.int64), caps.astype(np.int64)


def cluster_slots(labels, block: int, *, slack: float = 0.0) -> np.ndarray:
    """Padded slot of each point: clusters contiguous, ``block``-multiples.

    Host-side (the layout shape must be static for the launch anyway).
    """
    lab = np.asarray(labels)
    n = lab.shape[0]
    k = int(lab.max()) + 1 if n else 1
    starts, _ = cluster_capacities(lab, block, slack=slack, n_clusters=k)
    sizes = np.bincount(lab, minlength=k)
    order = np.argsort(lab, kind="stable")
    within = np.empty(n, np.int64)
    within[order] = np.arange(n) - np.repeat(
        np.concatenate([[0], np.cumsum(sizes)[:-1]]), sizes
    )
    return (starts[lab] + within).astype(np.int32)


def place_points(real, labels_new, starts, caps) -> Optional[np.ndarray]:
    """Free slots for appended points, respecting the cluster slabs.

    ``real`` marks occupied rows of the existing layout; each new point
    (cluster ``labels_new[i]``) takes the first free sentinel slot inside
    its cluster's ``[starts[c], starts[c] + caps[c])`` slab, so the
    cluster-alignment invariant (no tile straddles clusters) is preserved
    without touching any existing row.  Returns the claimed slots, or
    ``None`` when some cluster's slab is full — slack overflow, the
    caller's signal to rebuild the layout.
    """
    occ = np.asarray(real).copy()
    lab = np.asarray(labels_new)
    slots = np.empty(lab.shape[0], np.int32)
    for i, c in enumerate(lab):
        s, e = int(starts[c]), int(starts[c] + caps[c])
        free = np.flatnonzero(~occ[s:e])
        if free.size == 0:
            return None
        slots[i] = s + free[0]
        occ[slots[i]] = True
    return slots


def cluster_layout(x: jnp.ndarray, labels, block: int, *,
                   total_multiple: Optional[int] = None,
                   bucket_rows: bool = False,
                   slack: float = 0.0) -> ClusterLayout:
    """Scatter a point set into its cluster-aligned sentinel-padded layout.

    ``total_multiple`` additionally pads the layout's total length up to a
    multiple (the score pass needs lcm(block_m, block_n); single-sided
    passes just need ``block``, which holds by construction).
    ``bucket_rows`` rounds the tile count up to a power of two — per-batch
    query layouts vary with the label mix, and bucketing keeps ragged
    traffic on a bounded set of compiled shapes (extra tiles are all
    sentinel: zero count, never visited).  ``slack`` reserves per-cluster
    append headroom (see ``cluster_capacities``).
    """
    x = jnp.asarray(x)
    n, d = x.shape
    lab = to_host(labels)
    slots = cluster_slots(lab, block, slack=slack)
    _, caps = cluster_capacities(lab, block, slack=slack)
    total = int(caps.sum())
    total = max(total, block)
    if bucket_rows:
        tiles = -(-total // block)
        total = block * (1 << max(0, math.ceil(math.log2(tiles))))
    if total_multiple is not None:
        total = -(-total // total_multiple) * total_multiple
    slots_j = jnp.asarray(slots)
    points = jnp.full((total, d), PAD_VALUE, x.dtype).at[slots_j].set(x)
    real = jnp.zeros((total,), bool).at[slots_j].set(True)
    return ClusterLayout(points, real, slots_j, block)


# ---------------------------------------------------------------------------
# Tile metadata.
# ---------------------------------------------------------------------------


@jax.jit
def tile_meta_from_rows(x3: jnp.ndarray, mask: jnp.ndarray) -> TileMeta:
    """TileMeta of pre-gathered tile rows: (t, block, d) points, (t, block)
    real-mask.  The shared reduction behind full and partial builds."""
    x3 = jnp.asarray(x3, jnp.float32)
    cnt = jnp.sum(mask, axis=1).astype(jnp.int32)
    denom = jnp.maximum(cnt, 1).astype(jnp.float32)[:, None]
    cen = jnp.sum(jnp.where(mask[..., None], x3, 0.0), axis=1) / denom
    sq = jnp.sum((x3 - cen[:, None, :]) ** 2, axis=-1)       # (t, block)
    radii = jnp.sqrt(jnp.max(jnp.where(mask, sq, 0.0), axis=1))
    max_abs = jnp.max(
        jnp.where(mask[..., None], jnp.abs(x3), 0.0), axis=(1, 2)
    )
    return TileMeta(cen, radii, cnt, max_abs)


@functools.partial(jax.jit, static_argnames=("block",))
def tile_metadata(xp: jnp.ndarray, real: jnp.ndarray, *,
                  block: int) -> TileMeta:
    """Geometry of each ``block``-row tile of a cluster-aligned layout.

    ``real`` masks sentinel rows out of every statistic.  ``xp`` must be
    the f32 points the kernel *actually* computes distances between — at
    reduced precision tiers, the tier-cast reconstruction — so the bounds
    certify the perturbed-operand distances, not the originals.
    """
    npad, d = xp.shape
    t = npad // block
    x3 = jnp.asarray(xp, jnp.float32).reshape(t, block, d)
    mask = jnp.asarray(real).reshape(t, block)
    return tile_meta_from_rows(x3, mask)


def merge_tile_meta(meta: TileMeta, tiles, sub: TileMeta) -> TileMeta:
    """Write ``sub``'s rows over ``meta`` at the listed tile indices.

    ``tiles`` may contain repeats (pow2-padded index buffers): each row of
    ``sub`` is the freshly recomputed geometry of its tile, so repeated
    writes are idempotent.
    """
    tiles = jnp.asarray(np.asarray(tiles, np.int32))
    if tiles.size == 0:
        return meta
    return TileMeta(
        meta.centroids.at[tiles].set(sub.centroids),
        meta.radii.at[tiles].set(sub.radii),
        meta.counts.at[tiles].set(sub.counts),
        meta.max_abs.at[tiles].set(sub.max_abs),
    )


def tile_metadata_update(meta: TileMeta, xp: jnp.ndarray, real: jnp.ndarray,
                         tiles, *, block: int) -> TileMeta:
    """Refresh the metadata of only the listed tiles, in place.

    The streaming layer calls this after an append/evict/delta-shift pass
    with the set of tiles whose points actually changed — every other
    tile's geometry is carried over bit-for-bit, so certificates derived
    from it stay exactly as valid as at the last full build.
    """
    tiles_np = np.asarray(tiles, np.int64)
    if tiles_np.size == 0:
        return meta
    rows = jnp.asarray(
        (tiles_np[:, None] * block + np.arange(block)[None, :]), jnp.int32
    )
    sub = tile_meta_from_rows(jnp.asarray(xp, jnp.float32)[rows],
                              jnp.asarray(real)[rows])
    return merge_tile_meta(meta, tiles_np, sub)


# ---------------------------------------------------------------------------
# The bounds prepass.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_m", "kind"))
def tile_map(
    yp: jnp.ndarray,          # (m_pad, d) f32 padded query rows
    col_meta: TileMeta,
    inv2h2: jnp.ndarray,
    epsilon,
    *,
    block_m: int,
    kind: str = "kde",
) -> TileMap:
    """Certified keep/skip decision for every (row tile, column tile) pair.

    The bound starts from each *query row's* exact distance to each column
    tile centroid (one (m × t) GEMM), min-reduced over the row tile —
    sentinel query rows sit at distance ~PAD_VALUE·√d and never win the
    min, so row tiles need no metadata of their own and stay tight even
    when they span clusters.

    ``epsilon`` is the per-train-point contribution threshold: a skipped
    tile's certified per-point bound (see module docstring) is ≤ epsilon,
    so the absolute error on any row of the unnormalized accumulator is at
    most ``Σ_skipped count_j · bound_ij`` — returned as ``err_bound`` (and,
    loosely, ≤ n·epsilon).  ``epsilon=0`` only skips tiles whose every
    pairwise term underflows to exactly 0.0 in f32.
    """
    assert kind in KINDS, kind
    eps = jnp.asarray(epsilon, jnp.float32)
    m_pad, d = yp.shape
    mt = m_pad // block_m

    def row_tile_min(y_tile):                    # (block_m, d) -> (t,)
        dist = jnp.sqrt(_sqdist(y_tile, col_meta.centroids))
        return jnp.min(dist, axis=0)

    dmin_c = jax.lax.map(
        row_tile_min, jnp.asarray(yp, jnp.float32).reshape(mt, block_m, d)
    )                                            # (mt, t) min row→centroid
    dmin = jnp.maximum(dmin_c - col_meta.radii[None, :], 0.0)
    arg = MARGIN * dmin * dmin * inv2h2.reshape(())
    if kind == "laplace":
        w = 1.0 + d / 2.0 + arg
    elif kind == "score":
        w = jnp.maximum(1.0, col_meta.max_abs)[None, :]
    else:
        w = 1.0
    # per-point, per (i, j).  XLA flushes a subnormal exp to 0.0, but a pair
    # below the underflow threshold can still carry subnormal mass: floor
    # the bound at the smallest normal f32 there so the certificate holds.
    bound = jnp.maximum(
        w * jnp.exp(-arg),
        jnp.where(arg < UNDERFLOW_ARG, jnp.finfo(jnp.float32).tiny, 0.0))
    skip = (arg >= UNDERFLOW_ARG) | (col_meta.counts == 0)[None, :]
    skip = skip | ((eps > 0.0) & (bound <= eps))
    keep = ~skip
    err = jnp.sum(
        jnp.where(skip, col_meta.counts[None, :].astype(jnp.float32) * bound,
                  0.0),
        axis=1,
    )
    return TileMap(keep, err)


@jax.jit
def _row_counts(keep: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(keep, axis=1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("max_visits",))
def _compact(keep: jnp.ndarray, counts: jnp.ndarray, *,
             max_visits: int) -> jnp.ndarray:
    """Each row's kept column tiles in ascending order, then its first kept
    tile (0 for an empty row) in the slots past its count.  Kept tile j
    sorts by key j, a skipped one by j + t: the keys are unique, so the sort
    is the stable sort of the skip flags."""
    mt, t = keep.shape
    j = lax.broadcasted_iota(jnp.int32, (mt, t), 1)
    order = lax.sort(jnp.where(keep, j, j + t), dimension=1)[:, :max_visits]
    fill = jnp.where(counts > 0, order[:, 0], 0)
    pad = lax.broadcasted_iota(jnp.int32, order.shape, 1) >= counts[:, None]
    return jnp.where(pad, fill[:, None], order)


def visit_lists(keep, *, bucket_visits: bool = True) -> VisitLists:
    """Compact a keep matrix into the prefetched visit-list layout, on the
    device that holds it.

    This is the one host-sync point of the pruned path: the grid's static
    ``max_visits`` extent must be a Python int, so the per-row-tile counts
    (4 bytes a row tile) come to the host; the keep matrix stays on its
    device.  ``bucket_visits`` rounds the extent up to a power of two
    (capped at n_tiles) so ragged traffic reuses at most log2(n_tiles)
    compiled grid shapes per launch config; slots past a row's count are
    masked out in-kernel (they replay the row's first kept tile, keeping
    the DMA stream warm and valid).
    """
    counts = _row_counts(keep)
    c = to_host(counts)
    t = keep.shape[1]
    kmax = max(int(c.max(initial=0)), 1)
    if bucket_visits and kmax < t:
        kmax = min(t, 1 << max(0, math.ceil(math.log2(kmax))))
    occ = float(c.mean() / t) if t else 1.0
    return VisitLists(counts, _compact(keep, counts, max_visits=kmax),
                      kmax, occ)


def partition_clusters(labels, n_shards: int) -> np.ndarray:
    """Balanced assignment of whole clusters to shards.

    Greedy longest-processing-time: clusters (by point count, descending)
    go to the currently-lightest shard, ties broken by lowest shard id so
    the partition is deterministic.  Keeping clusters whole means every
    shard is a self-contained cluster-aligned tile set — its own layout,
    its own ``TileMeta``, its own certified bounds — which is exactly what
    the resilience layer's per-shard error certificates need.

    Returns ``(k,)`` int32: the shard of each cluster.  Requires
    ``n_shards <= k`` so no shard ends up empty.
    """
    lab = np.asarray(labels)
    k = int(lab.max()) + 1 if lab.size else 1
    if not (1 <= n_shards <= k):
        raise ValueError(
            f"n_shards={n_shards} must be in [1, n_clusters={k}]"
        )
    sizes = np.bincount(lab, minlength=k)
    shard_of = np.zeros(k, np.int32)
    load = np.zeros(n_shards, np.int64)
    filled = 0
    for c in np.argsort(-sizes, kind="stable"):
        # until every shard holds a cluster, seed the empty ones in order
        s = filled if filled < n_shards else int(np.argmin(load))
        shard_of[c] = s
        load[s] += sizes[c]
        filled += 1
    return shard_of


@functools.partial(jax.jit, static_argnames=("kind",))
def point_mass_bound(y: jnp.ndarray, meta: TileMeta, inv2h2,
                     *, kind: str = "kde") -> jnp.ndarray:
    """Per-query upper bound on the unnormalized kernel mass of an
    *entire absent point set* summarized by ``meta``.

    Same certified geometry as ``tile_map``, applied per query row instead
    of per row tile: each tile of the absent set contributes at most
    ``count · w(arg) · exp(-arg)`` with ``arg = MARGIN·max(0, ‖y−c‖−r)²/
    (2h²)`` — so summing over tiles bounds what a missing shard *would
    have added* to the accumulator.  The resilience layer turns this into
    the certified relative-error bound attached to degraded (partial-
    shard) answers.  For ``laplace`` the bound also caps the magnitude of
    *negative* missing contributions (|1 + d/2 − sq/2h²| ≤ 1 + d/2 + arg
    on the tile), so it is a two-sided envelope.
    """
    assert kind in KINDS, kind
    y32 = jnp.asarray(y, jnp.float32)
    d = y32.shape[-1]
    dist = jnp.sqrt(_sqdist(y32, meta.centroids))             # (m, t)
    dmin = jnp.maximum(dist - meta.radii[None, :], 0.0)
    arg = MARGIN * dmin * dmin * jnp.asarray(inv2h2, jnp.float32).reshape(())
    if kind == "laplace":
        w = 1.0 + d / 2.0 + arg
    elif kind == "score":
        w = jnp.maximum(1.0, meta.max_abs)[None, :]
    else:
        w = 1.0
    per = meta.counts[None, :].astype(jnp.float32) * w * jnp.exp(-arg)
    return jnp.sum(per, axis=1)                               # (m,)


def epsilon_for_density_error(abs_err: float, d: int, h: float) -> float:
    """Per-point epsilon giving |Δdensity| ≤ abs_err (normalization undone).

    density = sums / (n·(2π)^{d/2}·h^d) and the dropped unnormalized mass
    is ≤ n·epsilon, so epsilon = abs_err · (2π)^{d/2} · h^d.
    """
    return float(abs_err * (2.0 * math.pi) ** (d / 2.0) * h**d)


__all__ = [
    "PAD_VALUE", "UNDERFLOW_ARG", "MARGIN", "KINDS", "HOST_SYNC_BYTES",
    "to_host", "SpatialIndex",
    "ClusterLayout", "TileMeta", "TileMap", "VisitLists",
    "default_n_clusters", "build_index", "assign", "cluster_capacities",
    "cluster_slots", "place_points", "cluster_layout", "tile_metadata",
    "tile_meta_from_rows", "merge_tile_meta", "tile_metadata_update",
    "tile_map", "visit_lists", "partition_clusters", "point_mass_bound",
    "epsilon_for_density_error",
]
