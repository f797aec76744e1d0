"""Cluster-pruning correctness: exact mode, certificates, integration.

Three layers of guarantees, each tested directly:

  1. **Exact mode** (``epsilon=0``): the pruned kernels are allclose (rtol
     1e-6 — f32 accumulation-order noise only) to the dense kernels for
     KDE, score stats and Laplace, across every precision tier.
  2. **Certificates** (``epsilon>0``): the per-row-tile error bound emitted
     by the bounds prepass dominates the *true* dropped mass, computed in
     float64 against the same padded layouts — including adversarial
     cluster geometries (huge common offsets, duplicated points, lone
     outliers, off-manifold queries).
  3. **Integration**: the prune knob threads through ops wrappers, the
     serving engine, and the occupancy-aware autotuner.
"""

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.kernels import autotune, ops, spatial
from repro.kernels import precision as prec


def _q(eng, key, y, **kw):
    from repro.serve import QueryRequest
    return eng.query(QueryRequest(key=key, points=y, **kw)).value

TIERS = ("f32", "bf16", "bf16x2")


def _clustered(n, d, k=8, spread=8.0, sigma=0.05, seed=0, offset=0.0):
    key = jax.random.PRNGKey(seed)
    kc, kl, kn = jax.random.split(key, 3)
    centers = jax.random.uniform(kc, (k, d), minval=0.0, maxval=spread)
    lab = jax.random.randint(kl, (n,), 0, k)
    x = centers[lab] + sigma * jax.random.normal(kn, (n, d))
    return x + offset


# ---------------------------------------------------------------------------
# Spatial building blocks.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["kmeans", "morton"])
def test_cluster_layout_roundtrip(method):
    n, d, block = 500, 4, 64
    x = _clustered(n, d)
    idx = spatial.build_index(x, method=method)
    assert np.asarray(idx.labels).shape == (n,)
    lay = spatial.cluster_layout(jnp.asarray(x, jnp.float32), idx.labels,
                                 block)
    assert lay.points.shape[0] % block == 0
    assert int(jnp.sum(lay.real)) == n
    # scatter/gather roundtrip: every point lands in its slot
    np.testing.assert_array_equal(np.asarray(lay.points[lay.slots]),
                                  np.asarray(x, np.float32))
    # cluster alignment: every tile holds at most one label
    labels = np.full(lay.points.shape[0], -1)
    labels[np.asarray(lay.slots)] = np.asarray(idx.labels)
    for i in range(lay.points.shape[0] // block):
        tl = labels[i * block:(i + 1) * block]
        assert len(set(tl[tl >= 0])) <= 1


def test_tile_metadata_masks_sentinels():
    n, d, block = 300, 4, 128
    x = _clustered(n, d)
    idx = spatial.build_index(x)
    lay = spatial.cluster_layout(jnp.asarray(x, jnp.float32), idx.labels,
                                 block)
    meta = spatial.tile_metadata(lay.points, lay.real, block=block)
    t = lay.points.shape[0] // block
    assert meta.centroids.shape == (t, d)
    counts = np.asarray(meta.counts)
    assert counts.sum() == n
    # radius covers every real point of its tile
    x3 = np.asarray(lay.points).reshape(t, block, d)
    mask = np.asarray(lay.real).reshape(t, block)
    for i in range(t):
        if counts[i] == 0:
            continue
        dist = np.linalg.norm(
            x3[i][mask[i]] - np.asarray(meta.centroids)[i], axis=1)
        assert dist.max() <= np.asarray(meta.radii)[i] * (1 + 1e-5) + 1e-6
    # sentinel coordinates never leak into max_abs
    assert np.asarray(meta.max_abs).max() < ops.PAD_VALUE / 2


def test_visit_lists_layout():
    keep = jnp.asarray([[True, False, True, False],
                        [False, False, False, False],
                        [True, True, True, True]])
    vl = spatial.visit_lists(keep)
    counts = np.asarray(vl.counts)
    np.testing.assert_array_equal(counts, [2, 0, 4])
    assert vl.max_visits == 4                      # pow2-bucketed max
    tmap = np.asarray(vl.tile_map)
    np.testing.assert_array_equal(tmap[0, :2], [0, 2])
    np.testing.assert_array_equal(tmap[0, 2:], [0, 0])   # fill = first kept
    np.testing.assert_array_equal(tmap[2], [0, 1, 2, 3])
    assert vl.occupancy == pytest.approx(6 / 12)


# ---------------------------------------------------------------------------
# Certificates vs float64 ground truth (adversarial geometries included).
# ---------------------------------------------------------------------------

GEOMETRIES = {
    "clustered": lambda: (_clustered(900, 6, seed=1),
                          _clustered(250, 6, seed=2)),
    "huge_offset": lambda: (_clustered(900, 6, seed=3, offset=1000.0),
                            _clustered(250, 6, seed=4, offset=1000.0)),
    "duplicates": lambda: (jnp.tile(_clustered(90, 6, seed=5), (10, 1)),
                           _clustered(250, 6, seed=6)),
    "outlier": lambda: (
        jnp.concatenate([_clustered(899, 6, seed=7),
                         jnp.full((1, 6), 250.0)]),
        _clustered(250, 6, seed=8),
    ),
    "far_queries": lambda: (_clustered(900, 6, seed=9),
                            _clustered(250, 6, seed=10) + 500.0),
}


# f32 exp(-x) is exactly 0.0 for x > 150*ln2 — the f64 oracles below model
# the f32 kernel's arithmetic, so mass the kernel NEVER accumulates (it
# underflows to an exact zero) is not "dropped" by pruning.
F32_EXP_UNDERFLOW = 103.97


def _prepass(x, y, h, eps, kind, bm=64, bn=128):
    """Replicate the pruned wrappers' prepass; return f64 layouts + map."""
    index = spatial.build_index(x, seed=0)
    xlay = spatial.cluster_layout(jnp.asarray(x, jnp.float32), index.labels,
                                  bn)
    col_meta = spatial.tile_metadata(xlay.points, xlay.real, block=bn)
    labels_q = spatial.assign(y, index)
    qlay = spatial.cluster_layout(jnp.asarray(y, jnp.float32), labels_q, bm)
    inv2h2 = jnp.asarray(1.0 / (2 * h * h), jnp.float32).reshape(1, 1)
    tm = spatial.tile_map(qlay.points, col_meta, inv2h2, eps, block_m=bm,
                          kind=kind)
    return (np.asarray(xlay.points, np.float64), np.asarray(xlay.real),
            np.asarray(qlay.points, np.float64),
            np.asarray(tm.keep), np.asarray(tm.err_bound), bm, bn)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("kind", ["kde", "laplace"])
def test_certificate_dominates_true_dropped_mass(geometry, kind):
    x, y = GEOMETRIES[geometry]()
    h, eps = 0.4, 1e-7
    xp, xreal, yp, keep, err, bm, bn = _prepass(x, y, h, eps, kind)
    d = xp.shape[1]
    sq = ((yp[:, None, :] - xp[None, :, :]) ** 2).sum(-1)
    scaled = sq / (2 * h * h)
    phi = np.where(scaled > F32_EXP_UNDERFLOW, 0.0, np.exp(-scaled))
    contrib = np.abs(phi * (1 + d / 2 - scaled)) if kind == "laplace" else phi
    contrib[:, ~xreal] = 0.0    # sentinel columns carry no mass
    mt, t = keep.shape
    for i in range(mt):
        rows = contrib[i * bm:(i + 1) * bm]
        dropped = np.zeros(rows.shape[0])
        for j in range(t):
            if not keep[i, j]:
                dropped += rows[:, j * bn:(j + 1) * bn].sum(axis=1)
        assert dropped.max() <= err[i] * (1 + 1e-5) + 1e-300, (geometry, i)


def test_score_certificate_dominates_s1aug_error():
    x = _clustered(600, 5, seed=11)
    h, eps, bm, bn = 0.4, 1e-7, 64, 128
    index = spatial.build_index(x, seed=0)
    lay = spatial.cluster_layout(jnp.asarray(x, jnp.float32), index.labels,
                                 bn, total_multiple=math.lcm(bm, bn))
    col_meta = spatial.tile_metadata(lay.points, lay.real, block=bn)
    inv2h2 = jnp.asarray(1.0 / (2 * h * h), jnp.float32).reshape(1, 1)
    tm = spatial.tile_map(lay.points, col_meta, inv2h2, eps, block_m=bm,
                          kind="score")
    keep, err = np.asarray(tm.keep), np.asarray(tm.err_bound)
    x64 = np.asarray(lay.points, np.float64)
    real = np.asarray(lay.real)
    scaled = ((x64[:, None] - x64[None]) ** 2).sum(-1) / (2 * h * h)
    phi = np.where(scaled > F32_EXP_UNDERFLOW, 0.0, np.exp(-scaled))
    phi[:, ~real] = 0.0
    aug = np.concatenate([x64, np.ones((x64.shape[0], 1))], axis=1)
    w = np.abs(aug)     # per-point |weight| of each S1aug component
    mt, t = keep.shape
    for i in range(mt):
        rows = phi[i * bm:(i + 1) * bm]
        dropped = np.zeros(bm)
        for j in range(t):
            if not keep[i, j]:
                sl = slice(j * bn, (j + 1) * bn)
                dropped = np.maximum(
                    dropped, (rows[:, sl] @ w[sl]).max(axis=1)
                )
        assert dropped.max() <= err[i] * (1 + 1e-5) + 1e-300, i


# ---------------------------------------------------------------------------
# Exact mode (epsilon=0) == dense, across kernels and precision tiers.
# ---------------------------------------------------------------------------


def _tol(tier):
    # pruned-vs-dense at the SAME tier differs only by f32 accumulation
    # order; the atol floor covers deep-tail sums near the underflow edge
    return dict(rtol=1e-6, atol=1e-20)


def _exact_reference(tier, run, dense, visit_every_tile):
    """What eps=0 pruned sums must equal to the bar of ``_tol``: the dense
    kernels' sums where the tier shares their GEMM arithmetic, else (the
    f32 tier, whose pruned kernels pack their GEMMs, ``prec.packs``) the
    same pruned kernels' sums over every column tile.  ``run()`` runs
    the pruned path."""
    if not prec.packs(tier):
        return dense
    with visit_every_tile():
        return run()


@pytest.mark.parametrize("tier", TIERS)
def test_exact_mode_kde_matches_dense(tier, visit_every_tile, f64,
                                      assert_as_accurate):
    x, y = _clustered(900, 6, seed=20), _clustered(300, 6, seed=21)
    kw = dict(precision=tier, block_m=32, block_n=128, interpret=True)
    dense = ops.flash_kde(x, y, 0.35, prune="off", **kw)
    run = lambda: ops.flash_kde(x, y, 0.35, prune=0.0, **kw)  # noqa: E731
    pruned = run()
    ref = _exact_reference(tier, run, dense, visit_every_tile)
    np.testing.assert_allclose(np.asarray(pruned), np.asarray(ref),
                               **_tol(tier))
    if ref is not dense:
        assert_as_accurate(pruned, dense, f64.kde(x, y, 0.35))


@pytest.mark.parametrize("tier", TIERS)
def test_exact_mode_laplace_matches_dense(tier, visit_every_tile, f64,
                                          assert_as_accurate):
    x, y = _clustered(900, 6, seed=22), _clustered(300, 6, seed=23)
    kw = dict(precision=tier, block_m=32, block_n=128, interpret=True)
    dense = ops.flash_laplace_kde(x, y, 0.35, prune="off", **kw)
    run = lambda: ops.flash_laplace_kde(  # noqa: E731
        x, y, 0.35, prune=0.0, **kw)
    pruned = run()
    ref = _exact_reference(tier, run, dense, visit_every_tile)
    # Laplace sums cross zero; bound the deviation against the row scale
    scale = float(np.max(np.abs(np.asarray(ref)))) + 1e-30
    np.testing.assert_allclose(np.asarray(pruned) / scale,
                               np.asarray(ref) / scale,
                               rtol=0, atol=2e-6)
    if ref is not dense:
        assert_as_accurate(pruned, dense, f64.laplace(x, y, 0.35))


@pytest.mark.parametrize("tier", TIERS)
def test_exact_mode_score_stats_match_dense(tier, visit_every_tile, f64,
                                            assert_as_accurate):
    x = _clustered(700, 5, seed=24)
    kw = dict(precision=tier, block_m=32, block_n=128, interpret=True)
    s0d, s1d = ops.flash_score_stats(x, 0.5, prune="off", **kw)
    run = lambda: ops.flash_score_stats(x, 0.5, prune=0.0, **kw)  # noqa
    s0p, s1p = run()
    s0r, s1r = _exact_reference(tier, run, (s0d, s1d), visit_every_tile)
    np.testing.assert_allclose(np.asarray(s0p), np.asarray(s0r), rtol=1e-6,
                               atol=1e-20)
    scale = float(np.max(np.abs(np.asarray(s1r)))) + 1e-30
    np.testing.assert_allclose(np.asarray(s1p) / scale,
                               np.asarray(s1r) / scale, rtol=0, atol=2e-6)
    if prec.packs(tier):
        w0, w1 = f64.score(x, 0.5)
        assert_as_accurate(s0p, s0d, w0)
        assert_as_accurate(s1p, s1d, w1)


def test_exact_mode_far_queries_underflow_consistent():
    """Queries whose true density is exactly 0 in f32: both paths say 0."""
    x = _clustered(600, 4, seed=25)
    y = _clustered(100, 4, seed=26) + 500.0
    kw = dict(block_m=32, block_n=128, interpret=True)
    dense = np.asarray(ops.flash_kde(x, y, 0.3, prune="off", **kw))
    pruned = np.asarray(ops.flash_kde(x, y, 0.3, prune=0.0, **kw))
    np.testing.assert_array_equal(dense, 0.0)
    np.testing.assert_array_equal(pruned, 0.0)


@pytest.mark.parametrize("d", [4, 16])
@pytest.mark.parametrize("kind", ["kde", "score"])
@pytest.mark.parametrize("tier", ["f32", "bf16x2"])
def test_row_group_launches_match_one_launch(monkeypatch, kind, tier, d):
    """A tile map too wide for SMEM runs as one launch per group of row
    tiles (the score kernel's in a scan over one launch); every group's
    rows must land where one launch puts them (at the f32 tier, the packed
    kernels' rows and planes too)."""
    from repro import obs
    from repro.kernels import flash_pruned

    x, y = _clustered(640, d, seed=38), _clustered(200, d, seed=39)
    kw = dict(precision=tier, block_m=32, block_n=128, interpret=True,
              prune=0.0)
    kernel = (flash_pruned.flash_kde_pallas_pruned if kind == "kde"
              else flash_pruned.flash_score_pallas_pruned)

    def run():
        kernel.clear_cache()      # the group size is read at trace time
        if kind == "kde":
            return [np.asarray(ops.flash_kde(x, y, 0.4, **kw))]
        return [np.asarray(a) for a in ops.flash_score_stats(x, 0.4, **kw)]

    traces = obs.counter("kernels.pallas_traces",
                         labels={"mode": "interpret"})
    before = traces.value
    whole = run()
    one = traces.value - before
    groups = []

    def scan(f, init, xs):
        groups.append(len(xs))
        return lax.scan(f, init, xs)

    monkeypatch.setattr(flash_pruned, "SMEM_TILE_MAP_BYTES", 1)
    monkeypatch.setattr(flash_pruned, "lax", SimpleNamespace(
        scan=scan, dynamic_slice_in_dim=lax.dynamic_slice_in_dim,
        optimization_barrier=lax.optimization_barrier))
    before = traces.value
    grouped = run()
    assert one == 1
    if kind == "kde":                    # one launch per row tile
        assert traces.value - before > 2 and not groups
    else:       # in a loop over a launch traced once
        assert traces.value - before == 1
        assert groups and min(groups) > 2
    for a, b in zip(grouped, whole):
        np.testing.assert_array_equal(a, b)


#: Visits per row tile of the slot tests: all-sentinel rows (0), a count
#: that ends mid-step at V = 2 and 4 (5, 3), the whole extent (7, which no
#: V > 1 divides) and one slot (1).
_SLOT_COUNTS = (0, 5, 7, 3, 0, 1, 7, 6, 2, 0, 4, 7, 5, 1, 3, 0)
_SLOT_VISITS = 7
_ONE_SLOT = {}


def _slot_launch(tier, d, slots, monkeypatch):
    """The score kernel over a hand-made visit list, at ``slots`` visit
    slots per grid step."""
    from repro.kernels import flash_pruned

    bm, bn, n = 16, 128, 1024
    x = _clustered(n, d, seed=70 + d)
    packed = prec.packs(tier)
    x_ops, xt_ops, xaug_ops, nrm, _ = ops._score_operands(x, tier,
                                                         packed=packed)
    counts = np.array(_SLOT_COUNTS * (n // bm // len(_SLOT_COUNTS)),
                      np.int32)
    rng = np.random.default_rng(d)
    tiles = np.stack([rng.permutation(n // bn)[:_SLOT_VISITS]
                      for _ in counts]).astype(np.int32)
    fill = np.where(counts > 0, tiles[:, 0], 0)
    tiles = np.where(np.arange(_SLOT_VISITS)[None] < counts[:, None], tiles,
                     fill[:, None])
    monkeypatch.setattr(flash_pruned, "SCORE_SLOTS_MAX", slots)
    assert flash_pruned.score_slots(
        _SLOT_VISITS, block_m=bm, block_n=bn, d=d,
        itemsize=prec.operand_bytes(tier)) == slots
    flash_pruned.flash_score_pallas_pruned.clear_cache()  # V: trace time
    return np.asarray(flash_pruned.flash_score_pallas_pruned(
        jnp.asarray(counts), jnp.asarray(tiles), x_ops[0], nrm, xt_ops[0],
        xaug_ops[0], jnp.full((1, 1), 0.4, jnp.float32), x_ops[1], xt_ops[1],
        xaug_ops[1], block_m=bm, block_n=bn, max_visits=_SLOT_VISITS,
        interpret=True, packed=packed))


@pytest.mark.parametrize("grouped", [False, True],
                         ids=["one_launch", "row_groups"])
@pytest.mark.parametrize("slots", [1, 2, 4])
@pytest.mark.parametrize("tier,d", [("f32", 2), ("f32", 16), ("bf16x2", 4)])
def test_score_slots_per_step_match_one_slot(monkeypatch, tier, d, slots,
                                             grouped):
    """Each grid step of the score kernel runs V visit slots, masked by the
    row's count (a step within it under one branch) and added in slot
    order: S1aug is bit for bit that of one
    slot a step, for counts of 0, counts that end mid-step and an extent
    that V does not divide, in one launch and in the scanned row groups."""
    from repro.kernels import flash_pruned

    if (tier, d) not in _ONE_SLOT:
        _ONE_SLOT[tier, d] = _slot_launch(tier, d, 1, monkeypatch)
    groups = []
    if grouped:
        def scan(f, init, xs):
            groups.append(len(xs))
            return lax.scan(f, init, xs)

        # three of the 64 row tiles a group at the padded extent (8
        # slots): 21 groups in the scan and a last launch of one
        monkeypatch.setattr(flash_pruned, "SMEM_TILE_MAP_BYTES", 4 * 8 * 3)
        monkeypatch.setattr(flash_pruned, "lax", SimpleNamespace(
            scan=scan, dynamic_slice_in_dim=lax.dynamic_slice_in_dim,
            optimization_barrier=lax.optimization_barrier))
    got = _slot_launch(tier, d, slots, monkeypatch)
    assert groups == ([21] if grouped else [])
    assert np.abs(got).max() > 0
    np.testing.assert_array_equal(got, _ONE_SLOT[tier, d])


@pytest.mark.parametrize("d", [2, 5, 16, 22])
@pytest.mark.parametrize("kind", ["kde", "laplace", "score"])
def test_packed_pruned_kernels_match_dense_highest(kind, d, f64,
                                                   assert_as_accurate):
    """At the f32 tier the pruned kernels run their GEMMs packed and the
    dense kernels at HIGHEST: eps=0 pruned sums are as accurate, against
    float64, as the dense ones."""
    from repro import obs
    from repro.kernels import flash_pruned

    assert prec.packs("f32")
    x, y = _clustered(600, d, seed=50 + d), _clustered(160, d, seed=60 + d)
    h = 0.35
    kw = dict(precision="f32", block_m=32, block_n=128, interpret=True)
    kernel = (flash_pruned.flash_score_pallas_pruned if kind == "score"
              else flash_pruned.flash_kde_pallas_pruned)
    packed = obs.counter("kernels.f32_gemm_path", labels={
        "kernel": kernel.__name__, "gemm": "gram", "path": "packed"})
    before = packed.value
    kernel.clear_cache()          # the path is counted at trace time
    if kind == "score":
        s0d, s1d = ops.flash_score_stats(x, h, prune="off", **kw)
        s0p, s1p = ops.flash_score_stats(x, h, prune=0.0, **kw)
        w0, w1 = f64.score(x, h)
        assert_as_accurate(s0p, s0d, w0)
        assert_as_accurate(s1p, s1d, w1)
    else:
        fn = ops.flash_kde if kind == "kde" else ops.flash_laplace_kde
        dense = fn(x, y, h, prune="off", **kw)
        pruned = fn(x, y, h, prune=0.0, **kw)
        want = (f64.kde if kind == "kde" else f64.laplace)(x, y, h)
        assert_as_accurate(pruned, dense, want)
    assert packed.value > before


def test_packed_planes_follow_a_streaming_append(f64, assert_as_accurate):
    """A streaming append re-casts the touched tiles' columns: the f32
    tier's packed planes stay in step, and the pruned sums over the new
    live set are as accurate as the dense HIGHEST ones."""
    d, h = 4, 0.35
    x, xa = _clustered(700, d, seed=70), _clustered(24, d, seed=71)
    y = _clustered(96, d, seed=72)
    cols = ops.prepare_train_columns(x, block_n=128, precision="f32",
                                     clustered=True)
    assert cols.planes is not None
    layout = spatial.cluster_layout(jnp.asarray(x),
                                    np.asarray(cols.index.labels), 128)
    xp = np.asarray(layout.points).copy()
    real = np.asarray(layout.real).copy()
    slack = np.flatnonzero(~real)[:len(xa)]       # free slots take xa
    xp[slack], real[slack] = xa, True
    tiles = np.unique(slack // 128)
    upd = ops.update_train_columns(cols, jnp.asarray(xp), jnp.asarray(real),
                                   tiles, precision="f32")
    np.testing.assert_array_equal(
        np.asarray(upd.planes),
        np.asarray(prec.column_planes(jnp.asarray(xp))))
    live = xp[real]
    yp = ops._pad_to(jnp.asarray(y, jnp.float32), 32)
    kw = dict(precision="f32", block_m=32, block_n=128, interpret=True)
    pruned = ops.flash_kde_prepared(yp, upd.xt, upd.nrm_x, h, prune=0.0,
                                    columns=upd, n_real=len(y), **kw)
    norm = len(live) * (2 * math.pi) ** (d / 2) * h ** d
    dense = ops.flash_kde(live, y, h, prune="off", **kw)
    assert_as_accurate(np.asarray(pruned)[:len(y)] / norm, dense,
                       f64.kde(live, y, h))


@pytest.mark.parametrize("prepared,asked", [("f32", "bf16"), ("bf16", "f32")])
def test_pruned_eval_refuses_columns_of_another_tier(prepared, asked):
    """The f32 tier's pruned kernels read the packed planes, which only
    f32 columns carry: columns prepared at another tier are refused."""
    x, y = _clustered(512, 4, seed=73), _clustered(64, 4, seed=74)
    cols = ops.prepare_train_columns(x, block_n=128, precision=prepared,
                                     clustered=True)
    assert (cols.planes is not None) == prec.packs(prepared)
    yp = ops._pad_to(jnp.asarray(y, jnp.float32), 32)
    with pytest.raises(ValueError, match="columns prepared at that tier"):
        ops.flash_kde_prepared(yp, cols.xt, cols.nrm_x, 0.35, cols.xt_lo,
                               prune=0.0, columns=cols, n_real=len(y),
                               precision=asked, block_m=32, block_n=128,
                               interpret=True)


def test_epsilon_error_within_loose_budget():
    """|pruned − dense| ≤ the documented n·epsilon mass bound + f32 noise."""
    x, y = _clustered(1200, 6, seed=27), _clustered(400, 6, seed=28)
    n, d, h = x.shape[0], x.shape[1], 0.35
    kw = dict(block_m=32, block_n=128, interpret=True)
    dense = np.asarray(ops.flash_kde(x, y, h, prune="off", **kw))
    for eps in (1e-12, 1e-8, 1e-5):
        pruned = np.asarray(ops.flash_kde(x, y, h, prune=eps, **kw))
        budget = eps * n / (n * (2 * math.pi) ** (d / 2) * h**d)
        slack = 1e-5 * np.abs(dense) + 1e-20
        assert np.all(np.abs(pruned - dense) <= budget + slack), eps


def test_sdkde_pipeline_pruned_matches_dense():
    x, y = _clustered(800, 5, seed=29), _clustered(200, 5, seed=30)
    kw = dict(block_m=32, block_n=128, interpret=True)
    dense = ops.flash_sdkde(x, y, 0.4, prune="off", **kw)
    pruned = ops.flash_sdkde(x, y, 0.4, prune=0.0, **kw)
    # exact-mode score noise is amplified through the shift's exponentials
    np.testing.assert_allclose(np.asarray(pruned), np.asarray(dense),
                               rtol=2e-4, atol=1e-12)


# ---------------------------------------------------------------------------
# Property test: random geometry, certificate + exact mode (hypothesis).
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:  # the property test degrades to a fixed-seed sweep
    _HAVE_HYPOTHESIS = False


def _certificate_case(seed, k, spread, sigma, h, eps):
    x = _clustered(260, 3, k=k, spread=spread, sigma=sigma, seed=seed)
    y = _clustered(70, 3, k=k, spread=spread, sigma=sigma, seed=seed + 1)
    xp, xreal, yp, keep, err, bm, bn = _prepass(
        x, y, h, eps, "kde", bm=32, bn=64
    )
    scaled = ((yp[:, None] - xp[None]) ** 2).sum(-1) / (2 * h * h)
    phi = np.where(scaled > F32_EXP_UNDERFLOW, 0.0, np.exp(-scaled))
    phi[:, ~xreal] = 0.0
    mt, t = keep.shape
    for i in range(mt):
        rows = phi[i * bm:(i + 1) * bm]
        dropped = np.zeros(bm)
        for j in range(t):
            if not keep[i, j]:
                dropped += rows[:, j * bn:(j + 1) * bn].sum(axis=1)
        assert dropped.max() <= err[i] * (1 + 1e-5) + 1e-300


if _HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(
        seed=st.integers(0, 10_000),
        k=st.integers(1, 6),
        spread=st.floats(0.5, 50.0),
        sigma=st.floats(0.01, 1.0),
        h=st.floats(0.05, 1.0),
        eps=st.sampled_from([0.0, 1e-10, 1e-6, 1e-3]),
    )
    def test_certificate_property(seed, k, spread, sigma, h, eps):
        _certificate_case(seed, k, spread, sigma, h, eps)

else:

    @pytest.mark.parametrize("seed,k,spread,sigma,h,eps", [
        (0, 1, 0.5, 1.0, 0.05, 0.0),
        (1, 4, 20.0, 0.05, 0.3, 1e-10),
        (2, 6, 50.0, 0.5, 1.0, 1e-6),
        (3, 3, 5.0, 0.01, 0.1, 1e-3),
        (4, 2, 2.0, 0.2, 0.5, 1e-6),
    ])
    def test_certificate_property(seed, k, spread, sigma, h, eps):
        _certificate_case(seed, k, spread, sigma, h, eps)


# ---------------------------------------------------------------------------
# Dispatch policy, autotuner occupancy, VMEM widths.
# ---------------------------------------------------------------------------


def test_resolve_prune_policy():
    assert ops.resolve_prune("off", 10**6, 512) is None
    assert ops.resolve_prune("auto", 1024, 512) is None       # too small
    assert ops.resolve_prune("auto", 10**6, 512) == 0.0
    assert ops.resolve_prune(1e-8, 64, 512) == 1e-8           # explicit: on
    assert ops.resolve_prune(0.0, 64, 512) == 0.0
    with pytest.raises(ValueError):
        ops.resolve_prune(-1.0, 10**6, 512)
    with pytest.raises(ValueError):
        ops.resolve_prune("both", 10**6, 512)


def test_occupancy_learning_feeds_the_tuner():
    autotune.clear_cache()
    try:
        assert autotune.expected_occupancy(4096, 10**6, 16) == 1.0
        autotune.record_occupancy(4096, 10**6, 16, 0.1, block_n=128)
        assert autotune.expected_occupancy(
            4096, 10**6, 16, block_n=128) == pytest.approx(0.1)
        autotune.record_occupancy(4096, 10**6, 16, 0.3, block_n=128)  # EMA
        assert autotune.expected_occupancy(
            4096, 10**6, 16, block_n=128) == pytest.approx(0.2)
        # tile-width extrapolation: wider tiles prune worse, linearly
        assert autotune.expected_occupancy(
            4096, 10**6, 16, block_n=512) == pytest.approx(0.8)
        assert autotune.expected_occupancy(
            4096, 10**6, 16, block_n=4096) == 1.0        # capped
        dense = autotune.modeled_cost(4096, 10**6, 16, block_m=128,
                                      block_n=512)
        sparse = autotune.modeled_cost(4096, 10**6, 16, block_m=128,
                                       block_n=512, occupancy=0.2)
        assert sparse.step_time < dense.step_time / 2
    finally:
        autotune.clear_cache()


def test_pruned_wrappers_record_occupancy():
    autotune.clear_cache()
    try:
        x, y = _clustered(1024, 4, seed=31), _clustered(128, 4, seed=32)
        ops.flash_kde(x, y, 0.2, block_m=32, block_n=128, interpret=True,
                      prune=0.0)
        assert autotune.expected_occupancy(128, 1024, 4, block_n=128) < 1.0
        # and the next auto-resolve for this regime consults the record
        bm, bn = autotune.resolve_blocks("auto", "auto", 128, 1024, 4,
                                         measure=False, pruned=True)
        assert bn in autotune.DEFAULT_BLOCK_NS
    finally:
        autotune.clear_cache()


def test_vmem_is_out_width_aware():
    d = 256
    score_b = ops.vmem_tile_bytes(128, 1024, d, out_width=d + 1)
    kde_b = ops.vmem_tile_bytes(128, 1024, d, out_width=1)
    legacy = ops.vmem_tile_bytes(128, 1024, d)        # None = conservative
    assert kde_b < score_b == legacy
    # exactly the xaug operand tile + the accumulator width difference
    assert score_b - kde_b == 4 * (1024 * (d + 1)) + 4 * 128 * d
    # a tile the score budget rejects fits on the KDE path
    bm, bn, dd = 128, 2048, 700
    with pytest.raises(ValueError, match="VMEM"):
        ops._check_vmem(bm, bn, dd, out_width=dd + 1)
    ops._check_vmem(bm, bn, dd, out_width=1)


def test_prepare_train_columns_auto_block_and_annotation():
    x = _clustered(600, 4, seed=33)
    cols = ops.prepare_train_columns(x, block_n="auto", precision="f32")
    assert cols.xt.shape[0] == 4
    assert cols.xt.shape[1] % 128 == 0    # padded to a real resolved tile
    assert cols.meta is None and cols.index is None
    spatialized = ops.prepare_train_columns(x, block_n=128, clustered=True)
    assert spatialized.meta is not None and spatialized.index is not None
    assert np.asarray(spatialized.meta.counts).sum() == 600


# ---------------------------------------------------------------------------
# Serving integration.
# ---------------------------------------------------------------------------


def test_serve_pruned_matches_reference():
    from repro.core import kde as refkde
    from repro.serve import ServeConfig, ServeEngine

    x = _clustered(2048, 6, seed=34)
    y = _clustered(300, 6, seed=35)
    cfg = ServeConfig(backend="pallas", method="sdkde", interpret=True,
                      block_m=32, block_n=256, prune=0.0,
                      min_batch=64, max_batch=512)
    eng = ServeEngine(cfg)
    prep = eng.register("clustered", x, h=0.4)
    got = np.asarray(_q(eng, "clustered", y))
    want = np.asarray(refkde.sdkde_eval(x, y, 0.4, block=1024))
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-6 * float(np.max(np.abs(want))))
    # the clustered columns are fit-time state, shared across tiers
    cols_f32 = prep.columns_for("f32")
    cols_bf16 = prep.columns_for("bf16")
    assert cols_f32.meta is not None and cols_bf16.meta is not None
    assert cols_bf16.index is cols_f32.index


def test_serve_prune_off_unchanged(visit_every_tile, f64,
                                   assert_as_accurate):
    from repro.serve import ServeConfig, ServeEngine

    x = _clustered(512, 4, seed=36)
    y = _clustered(64, 4, seed=37)
    on = ServeEngine(ServeConfig(backend="pallas", method="kde",
                                 interpret=True, block_m=32, block_n=128,
                                 prune=0.0, min_batch=32, max_batch=128))
    off = ServeEngine(ServeConfig(backend="pallas", method="kde",
                                  interpret=True, block_m=32, block_n=128,
                                  prune="off", min_batch=32, max_batch=128))
    on.register("k", x, h=0.3)
    off.register("k", x, h=0.3)
    got, dense = np.asarray(_q(on, "k", y)), np.asarray(_q(off, "k", y))
    # f32 at d=4: the pruned kernels pack their GEMMs, the dense ones not
    assert prec.packs(on.config.precision)
    with visit_every_tile():
        every = np.asarray(_q(on, "k", y))
    np.testing.assert_allclose(got, every, rtol=1e-6, atol=1e-20)
    assert_as_accurate(got, dense, f64.kde(x, y, 0.3))


def test_serve_config_validates_prune():
    from repro.serve import ServeConfig

    with pytest.raises(ValueError, match="prune"):
        ServeConfig(prune="sometimes")
    with pytest.raises(ValueError, match="prune"):
        ServeConfig(prune=-0.5)
    ServeConfig(prune=1e-9)
    ServeConfig(prune="off")


def test_public_wrappers_stay_jittable():
    """Under jit tracing the wrappers fall back to dense (the pruned path
    host-syncs) instead of crashing with a tracer-conversion error."""
    x, y = _clustered(600, 4, seed=50), _clustered(80, 4, seed=51)
    kw = dict(block_m=32, block_n=128, interpret=True)
    jitted = jax.jit(lambda a, b: ops.flash_kde(a, b, 0.3, prune=0.0, **kw))
    dense = ops.flash_kde(x, y, 0.3, prune="off", **kw)
    np.testing.assert_allclose(np.asarray(jitted(x, y)), np.asarray(dense),
                               rtol=1e-6, atol=1e-20)


def test_one_shot_columns_cache_amortizes_prep():
    """Repeated evaluation on the SAME train array reuses one spatial prep."""
    x = _clustered(700, 4, seed=52)
    c1 = ops._cached_columns(x, block_n=128, precision="f32", seed=0)
    c2 = ops._cached_columns(x, block_n=128, precision="f32", seed=0)
    assert c1 is c2
    # different array identity -> fresh prep
    x2 = x + 0.0
    c3 = ops._cached_columns(x2, block_n=128, precision="f32", seed=0)
    assert c3 is not c1


def test_prepared_prune_rejects_mismatched_block_n():
    """Visit lists address prepare-width tiles; a different launch width
    must be rejected, and "auto" must resolve to the prepared width."""
    x = _clustered(900, 5, seed=40)
    y = _clustered(64, 5, seed=41)
    cols = ops.prepare_train_columns(x, block_n=128, clustered=True)
    assert cols.block_n == 128
    yp = ops._pad_to(jnp.asarray(y, jnp.float32), 32)
    with pytest.raises(ValueError, match="block_n"):
        ops.flash_kde_prepared(yp, cols.xt, cols.nrm_x, 0.35,
                               prune=0.0, columns=cols, n_real=64,
                               block_m=32, block_n=64, interpret=True)
    # "auto" snaps to the prepared width instead of misaddressing tiles
    ops.flash_kde_prepared(yp, cols.xt, cols.nrm_x, 0.35,
                           prune=0.0, columns=cols, n_real=64,
                           block_m=32, block_n="auto", interpret=True)


@pytest.mark.parametrize("tier", TIERS)
def test_prepared_prune_tiers(tier):
    """flash_kde_prepared's pruned path across tiers, with sentinel rows."""
    x = _clustered(900, 5, seed=38)
    y = _clustered(100, 5, seed=39)
    cols = ops.prepare_train_columns(x, block_n=128, precision=tier,
                                     clustered=True)
    yp = ops._pad_to(jnp.asarray(y, jnp.float32), 64)
    kw = dict(precision=tier, block_m=64, block_n=128, interpret=True)
    dense = ops.flash_kde_prepared(yp, cols.xt, cols.nrm_x, 0.35,
                                   cols.xt_lo, **kw)
    pruned = ops.flash_kde_prepared(yp, cols.xt, cols.nrm_x, 0.35,
                                    cols.xt_lo, prune=0.0, columns=cols,
                                    n_real=100, **kw)
    np.testing.assert_allclose(np.asarray(pruned)[:100],
                               np.asarray(dense)[:100], rtol=1e-6,
                               atol=1e-20)
