"""repro.stream: incremental fit, generations, staleness, rebuild policy.

The flagship invariant (acceptance criterion): after ANY interleaving of
appends and evictions, served densities match a from-scratch refit over
the surviving live set to ≤1e-5 relative (f32, exact eps=0 pruning), and
the same interleaving at reduced precision tiers meets each tier's
documented accuracy bar.  Everything runs at small sizes with tiny
interpret-mode tiles, like the rest of the tier-1 suite.
"""

import threading

import jax
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import kde as ref
from repro.core.estimator import SDKDE, EstimatorConfig
from repro.kernels import ops, spatial
from repro.serve import QueryRequest, ServeConfig, ServeEngine
from repro.stream import StreamConfig, StreamingSDKDE, delta


def _q(eng, key, y, **kw):
    """One typed query, densities out."""
    return eng.query(QueryRequest(key=key, points=y, **kw)).value

D, H = 4, 0.5


@pytest.fixture(scope="module")
def data():
    kx, ka, ky = jax.random.split(jax.random.PRNGKey(0), 3)
    return (np.asarray(jax.random.normal(kx, (512, D)), np.float32),
            np.asarray(jax.random.normal(ka, (64, D)), np.float32),
            np.asarray(jax.random.normal(ky, (128, D)), np.float32))


def _serve_cfg(**kw):
    base = dict(backend="pallas", method="sdkde", interpret=True,
                block_m=8, block_n=64, min_batch=16, max_batch=128,
                stream=True, staleness_budget=0)
    base.update(kw)
    return ServeConfig(**base)


def _refit_eval(x_live, y, method="sdkde"):
    fn = {"kde": ref.kde_eval, "sdkde": ref.sdkde_eval,
          "laplace": ref.laplace_kde_eval}[method]
    return np.asarray(fn(jnp.asarray(x_live), jnp.asarray(y), H, block=256))


# ---------------------------------------------------------------------------
# The delta score pass (stream.delta).
# ---------------------------------------------------------------------------


def test_cross_stats_matches_reference_score_pass(data):
    x, _, _ = data
    s0, s1 = delta.initial_stats(x, H, block=100)   # odd block: remainders
    r0, r1 = ref.score_stats(jnp.asarray(x), jnp.asarray(x), H, block=128)
    np.testing.assert_allclose(s0, np.asarray(r0), rtol=1e-5)
    np.testing.assert_allclose(s1, np.asarray(r1), rtol=1e-5, atol=1e-5)


def test_append_then_evict_roundtrips_stats(data):
    x, xa, _ = data
    s0, s1 = delta.initial_stats(x, H)
    ds0, ds1, _, _ = delta.append_delta(x, xa, H)
    es0, es1 = delta.evict_delta(x, xa, H)
    # f64 accumulation: the += / -= cancel to f64 rounding, not f32 drift
    np.testing.assert_allclose(s0 + ds0 - es0, s0, rtol=1e-12)
    np.testing.assert_allclose(s1 + ds1 - es1, s1, rtol=1e-12, atol=1e-12)


def test_append_delta_includes_within_batch_terms(data):
    """Grown-set stats == old stats + append deltas, including the new
    points' within-batch and self (φ=1) terms."""
    x, xa, _ = data
    want0, want1 = delta.initial_stats(np.concatenate([x, xa]), H)
    base0, base1 = delta.initial_stats(x, H)
    ds0, ds1, s0n, s1n = delta.append_delta(x, xa, H)
    np.testing.assert_allclose(np.concatenate([base0 + ds0, s0n]),
                               want0, rtol=1e-10)
    np.testing.assert_allclose(np.concatenate([base1 + ds1, s1n]),
                               want1, rtol=1e-10, atol=1e-12)


# ---------------------------------------------------------------------------
# Slack layouts + incremental placement (kernels.spatial).
# ---------------------------------------------------------------------------


def test_cluster_capacities_reserve_slack():
    labels = np.array([0] * 10 + [1] * 100 + [2] * 3)
    starts, caps = spatial.cluster_capacities(labels, 16, slack=0.5)
    sizes = np.array([10, 100, 3])
    assert (caps >= sizes + np.ceil(sizes * 0.5)).all()
    assert (caps % 16 == 0).all()
    assert (np.diff(starts) == caps[:-1]).all() and starts[0] == 0
    # slack=0 reproduces the legacy geometry (empty cluster -> 0 rows)
    _, caps0 = spatial.cluster_capacities(np.array([0, 2, 2]), 8, slack=0.0,
                                          n_clusters=4)
    assert caps0.tolist() == [8, 0, 8, 0]


def test_slack_layout_roundtrip_and_placement(data):
    x, xa, _ = data
    index = spatial.build_index(jnp.asarray(x), n_clusters=4, seed=0)
    labels = np.asarray(index.labels)
    layout = spatial.cluster_layout(jnp.asarray(x), labels, 16, slack=0.5)
    np.testing.assert_array_equal(
        np.asarray(layout.points)[np.asarray(layout.slots)], x)
    starts, caps = spatial.cluster_capacities(labels, 16, slack=0.5)
    real = np.asarray(layout.real).copy()
    lab_new = np.asarray(spatial.assign(jnp.asarray(xa), index))
    slots = spatial.place_points(real, lab_new, starts, caps)
    assert slots is not None
    assert not real[slots].any()                      # claimed free slots only
    for s, c in zip(slots, lab_new):                  # inside the right slab
        assert starts[c] <= s < starts[c] + caps[c]
    assert len(np.unique(slots)) == len(slots)
    # exhaust one cluster's slab -> overflow signal
    tight_real = np.ones_like(real)
    assert spatial.place_points(tight_real, lab_new[:1], starts, caps) is None


def test_tile_metadata_update_matches_full_rebuild(data):
    x, xa, _ = data
    index = spatial.build_index(jnp.asarray(x), n_clusters=4, seed=0)
    labels = np.asarray(index.labels)
    layout = spatial.cluster_layout(jnp.asarray(x), labels, 16, slack=0.5)
    xp = np.asarray(layout.points).copy()
    real = np.asarray(layout.real).copy()
    meta = spatial.tile_metadata(jnp.asarray(xp), jnp.asarray(real), block=16)
    # mutate two tiles' worth of rows, update just those tiles
    xp[:16] = xa[:16]
    real[:16] = True
    xp[32:40] = xa[16:24]
    real[32:40] = True
    upd = spatial.tile_metadata_update(meta, jnp.asarray(xp),
                                       jnp.asarray(real), [0, 2], block=16)
    full = spatial.tile_metadata(jnp.asarray(xp), jnp.asarray(real), block=16)
    for f in spatial.TileMeta._fields:
        np.testing.assert_array_equal(np.asarray(getattr(upd, f)),
                                      np.asarray(getattr(full, f)))
    # untouched tiles carried over bit-for-bit
    for f in spatial.TileMeta._fields:
        np.testing.assert_array_equal(np.asarray(getattr(upd, f))[1],
                                      np.asarray(getattr(meta, f))[1])


def test_update_train_columns_matches_fresh_prepare(data):
    x, xa, _ = data
    for tier in ("f32", "bf16x2"):
        cols = ops.prepare_train_columns(jnp.asarray(x), block_n=64,
                                         precision=tier, clustered=True)
        xp = np.full((cols.xt.shape[1], D), ops.PAD_VALUE, np.float32)
        # reconstruct the layout's points from the prepared planes is
        # lossy at reduced tiers; rebuild the layout directly instead
        labels = np.asarray(cols.index.labels)
        layout = spatial.cluster_layout(jnp.asarray(x), labels, 64)
        xp = np.asarray(layout.points).copy()
        real = np.asarray(layout.real).copy()
        # swap some rows of tile 0 and refresh it
        xp[:8] = xa[:8]
        real[:8] = True
        upd = ops.update_train_columns(cols, jnp.asarray(xp),
                                       jnp.asarray(real), [0, 0],
                                       precision=tier)   # repeats are ok
        fresh = ops.columns_from_layout(jnp.asarray(xp), jnp.asarray(real),
                                        cols.index, block_n=64,
                                        precision=tier)
        np.testing.assert_array_equal(np.asarray(upd.xt),
                                      np.asarray(fresh.xt))
        if tier == "bf16x2":
            np.testing.assert_array_equal(np.asarray(upd.xt_lo),
                                          np.asarray(fresh.xt_lo))
        else:                   # the f32 tier's packed planes, in step
            assert upd.planes is not None
            np.testing.assert_array_equal(np.asarray(upd.planes),
                                          np.asarray(fresh.planes))
        np.testing.assert_array_equal(np.asarray(upd.nrm_x),
                                      np.asarray(fresh.nrm_x))
        for f in spatial.TileMeta._fields:
            np.testing.assert_array_equal(np.asarray(getattr(upd.meta, f)),
                                          np.asarray(getattr(fresh.meta, f)))


# ---------------------------------------------------------------------------
# StreamingSDKDE: the acceptance-criterion interleavings.
# ---------------------------------------------------------------------------


def test_interleaved_updates_match_refit_exact_pruning(data):
    """Appends/evictions in every order vs from-scratch refit, f32 eps=0."""
    x, xa, y = data
    cfg = _serve_cfg(prune=0.0)          # exact pruning on every dispatch
    eng = ServeEngine(cfg)
    eng.register("ds", x, h=H)
    ids0 = eng.registry.append("ds", xa[:32])
    eng.registry.evict_ids("ds", ids0[:8])
    eng.registry.append("ds", xa[32:])
    eng.registry.evict_ids("ds", np.arange(16))       # oldest originals
    eng.registry.append("ds", xa[:4])                 # duplicates are fine
    got = np.asarray(_q(eng, "ds", y))
    live = np.concatenate([x[16:], xa[8:32], xa[32:], xa[:4]])
    want = _refit_eval(live, y)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-6 * float(want.max()))
    st = eng.registry.get("ds").stream
    assert st.n_live == live.shape[0]
    snap = st.snapshot()
    assert snap.affected_tiles <= snap.total_tiles


@pytest.mark.parametrize("tier,rtol,atol_frac", [
    ("f32", 1e-5, 1e-6), ("bf16", 5e-2, 5e-3), ("bf16x2", 5e-4, 1e-5),
])
def test_streaming_matches_refit_across_precision_tiers(data, tier, rtol,
                                                        atol_frac):
    x, xa, y = data
    eng = ServeEngine(_serve_cfg(precision=tier))
    eng.register("ds", x, h=H)
    ids = eng.registry.append("ds", xa)
    eng.registry.evict_ids("ds", ids[::2])
    got = np.asarray(_q(eng, "ds", y))
    live = np.concatenate([x, xa[1::2]])
    want = _refit_eval(live, y)
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=atol_frac * float(want.max()))


@pytest.mark.parametrize("method", ["kde", "laplace"])
def test_streaming_methods_without_stats(data, method):
    x, xa, y = data
    eng = ServeEngine(_serve_cfg(method=method))
    eng.register("ds", x, h=H)
    eng.registry.slide("ds", xa)          # sliding window: append + evict
    got = np.asarray(_q(eng, "ds", y))
    live = np.concatenate([x[len(xa):], xa])
    want = _refit_eval(live, y, method)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-6 * float(np.abs(want).max()))


def test_staleness_budget_serves_stale_then_flushes(data):
    x, xa, y = data
    eng = ServeEngine(_serve_cfg(staleness_budget=2))
    eng.register("ds", x, h=H)
    q0 = np.asarray(_q(eng, "ds", y))
    eng.registry.append("ds", xa[:16])                 # gen 1
    q1 = np.asarray(_q(eng, "ds", y))                # within budget
    np.testing.assert_array_equal(q0, q1)              # stale gen served
    eng.registry.append("ds", xa[16:32])               # gen 2
    eng.registry.append("ds", xa[32:])                 # gen 3 > budget
    q2 = np.asarray(_q(eng, "ds", y))                # must flush
    want = _refit_eval(np.concatenate([x, xa]), y)
    np.testing.assert_allclose(q2, want, rtol=1e-5,
                               atol=1e-6 * float(want.max()))
    s = eng.staleness_summary()
    assert s["max"] >= 1 and s["count"] == 3


def test_value_generations_reuse_executables_rebuild_invalidates(data):
    """Appends that keep the layout shape must NOT rebuild executables;
    only a layout rebuild (epoch bump) builds new ones."""
    x, xa, y = data
    eng = ServeEngine(_serve_cfg())
    eng.register("ds", x, h=H)
    _q(eng, "ds", y[:16])
    misses0 = eng.cache.misses
    eng.registry.append("ds", xa[:8])     # slack absorbs it: same epoch
    _q(eng, "ds", y[:16])
    assert eng.cache.misses == misses0    # same compiled executable served
    st = eng.registry.get("ds").stream
    epoch0 = st.snapshot().layout_epoch
    # force a rebuild through the policy and confirm new executables
    eng.registry.append("ds", np.repeat(xa, 20, axis=0))   # > append budget
    _q(eng, "ds", y[:16])
    assert st.snapshot().layout_epoch > epoch0
    assert eng.cache.misses > misses0


def test_slack_overflow_triggers_rebuild_and_stays_correct(data):
    x, xa, y = data
    eng = ServeEngine(_serve_cfg(method="kde", stream_slack=0.05,
                                 staleness_budget=0))
    eng.register("ds", x[:128], h=H)
    big = np.concatenate([x[128:], xa])
    eng.registry.append("ds", big)                    # overflows the slack
    got = np.asarray(_q(eng, "ds", y))
    st = eng.registry.get("ds").stream
    assert st.rebuilds >= 1
    assert st.last_rebuild_reason == "slack-overflow"
    want = _refit_eval(np.concatenate([x[:128], big]), y, "kde")
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-6 * float(want.max()))


def test_clean_tiles_carry_over_bitwise(data):
    """A far-away append leaves every unaffected tile's metadata and
    operand columns bit-for-bit unchanged (the in-place update is real)."""
    x, _, _ = data
    far = x + np.float32(100.0)           # separate cluster, zero overlap
    both = np.concatenate([x, far])
    st = StreamingSDKDE(both, H, method="sdkde", backend="pallas",
                        block_n=64, config=StreamConfig(slack=0.5))
    snap0 = st.snapshot()
    cols0 = st.columns_for("f32", snap0)
    # append next to the far cluster: φ against the near cluster is 0.0
    st.append(far[:8] + np.float32(0.1))
    snap1 = st.ensure(0)
    assert snap1.layout_epoch == snap0.layout_epoch   # no rebuild
    assert 0 < snap1.affected_tiles < snap1.total_tiles
    cols1 = st.columns_for("f32", snap1)
    # identify tiles of the near cluster via the f64 stats: unaffected
    changed = np.zeros(snap1.total_tiles, bool)
    xt0 = np.asarray(cols0.xt)
    xt1 = np.asarray(cols1.xt)
    for t in range(snap0.total_tiles):
        sl = slice(t * 64, (t + 1) * 64)
        if not np.array_equal(xt0[:, sl], xt1[:, sl]):
            changed[t] = True
    assert changed.sum() == snap1.affected_tiles or changed.sum() <= \
        snap1.affected_tiles                       # pads may rewrite equal
    clean = ~changed
    for f in spatial.TileMeta._fields:
        a0 = np.asarray(getattr(cols0.meta, f))
        a1 = np.asarray(getattr(cols1.meta, f))
        np.testing.assert_array_equal(a0[clean], a1[clean])


def test_append_into_trailing_empty_cluster(data, monkeypatch):
    """k-means can leave a trailing centroid with zero train points; the
    layout must still reserve that cluster's slab so a later append
    assigned to it has somewhere to land (regression: IndexError)."""
    x, _, _ = data
    cents = np.zeros((3, D), np.float32)
    cents[0] -= 1.0
    cents[1] += 1.0
    cents[2] = 50.0                       # no train point lands here

    def fake_index(pts, **kw):
        idx = spatial.SpatialIndex(None, jnp.asarray(cents))
        return spatial.SpatialIndex(spatial.assign(pts, idx),
                                    jnp.asarray(cents))

    monkeypatch.setattr(spatial, "build_index", fake_index)
    st = StreamingSDKDE(x[:64], H, method="kde", backend="pallas",
                        block_n=16)
    assert st._caps.shape[0] == 3         # slab reserved for the empty one
    far = np.full((3, D), 50.0, np.float32)
    ids = st.append(far)                  # must place, not IndexError
    assert (st._slots[-3:] >= 0).all()
    snap = st.ensure(0)
    assert snap.n_live == 67
    cols = st.columns_for("f32", snap)
    assert int(np.asarray(cols.meta.counts).sum()) == 67
    st.evict(ids)
    assert st.ensure(0).n_live == 64


def test_jnp_stream_bounds_executable_shapes(data):
    """Net appends on the jnp backend reuse the padded pow2 row bucket —
    the published layout shape changes only when the bucket overflows."""
    x, xa, _ = data
    st = StreamingSDKDE(x[:200], H, method="kde", backend="jnp")
    shape0 = st.snapshot().xp.shape
    st.append(xa[:8])
    assert st.ensure(0).xp.shape == shape0      # same bucket, no retrace
    st.append(np.repeat(xa, 2, axis=0))         # past the pow2 bucket
    snap = st.ensure(0)
    assert snap.xp.shape[0] >= snap.n_live
    assert snap.xp.shape != shape0


def test_background_flush_serves_stale_then_catches_up(data):
    x, xa, y = data
    st = StreamingSDKDE(x, H, method="kde", backend="jnp",
                        config=StreamConfig(background=True,
                                            staleness_budget=0))
    gen0 = st.snapshot().gen
    st.append(xa)                          # kicks a worker build
    snap = st.ensure(0)                    # joins the worker
    assert snap.gen == st.gen and snap.gen > gen0
    got = np.asarray(ref.kde_eval(snap.points, jnp.asarray(y), H, block=256))
    want = _refit_eval(np.concatenate([x, xa]), y, "kde")
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-6 * float(want.max()))


def test_stream_rejects_bad_usage(data):
    x, xa, _ = data
    st = StreamingSDKDE(x[:64], H, method="kde", backend="jnp")
    with pytest.raises(KeyError):
        st.evict([999999])
    with pytest.raises(ValueError):
        st.evict(st.ids)                   # cannot evict everything
    with pytest.raises(ValueError):
        st.append(xa[:, :2])               # dimension mismatch
    with pytest.raises(ValueError):
        StreamingSDKDE(x[:64], H, backend="ring")
    with pytest.raises(ValueError):
        ServeConfig(backend="ring", stream=True)
    eng = ServeEngine(_serve_cfg(stream=False))
    eng.register("static", x[:64], h=H)
    with pytest.raises(ValueError):
        eng.registry.append("static", xa)


# ---------------------------------------------------------------------------
# core.estimator.SDKDE incremental API.
# ---------------------------------------------------------------------------


def test_sdkde_append_evict_matches_refit(data):
    x, xa, y = data
    est = SDKDE(H, EstimatorConfig(backend="jnp", block=128)).fit(
        jnp.asarray(x))
    est.append(xa).evict(np.arange(32))
    got = np.asarray(est.evaluate(jnp.asarray(y)))
    want = _refit_eval(np.concatenate([x[32:], xa]), y)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-6 * float(want.max()))
    with pytest.raises(ValueError):
        est.evict(np.arange(est.x_train.shape[0]))


def test_sdkde_refit_resets_streaming_stats(data):
    """fit() must drop lazily-seeded stats — an append after a refit on a
    different dataset reseeds instead of mixing old statistics in."""
    x, xa, y = data
    est = SDKDE(H, EstimatorConfig(backend="jnp", block=128)).fit(
        jnp.asarray(x))
    est.append(xa)                       # seeds f64 stats for x + xa
    est.fit(jnp.asarray(x[:256]))        # refit: different dataset
    est.append(xa[:16])
    got = np.asarray(est.evaluate(jnp.asarray(y)))
    want = _refit_eval(np.concatenate([x[:256], xa[:16]]), y)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-6 * float(want.max()))


# ---------------------------------------------------------------------------
# Registry/engine update races (the satellite's three scenarios).
# ---------------------------------------------------------------------------


def test_registry_evict_during_inflight_queries(data):
    """Thread A queries while thread B evicts the key and re-registers:
    every answer is either a valid density vector from some published
    generation or a clean KeyError — never corruption."""
    x, _, y = data
    eng = ServeEngine(_serve_cfg(method="kde", backend="jnp"))
    eng.register("ds", x, h=H)
    want_a = _refit_eval(x, y[:16], "kde")
    want_b = _refit_eval(2.0 + x, y[:16], "kde")
    errors, results = [], []

    def worker():
        for _ in range(20):
            try:
                results.append(np.asarray(_q(eng, "ds", y[:16])))
            except KeyError:
                pass
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    t = threading.Thread(target=worker)
    t.start()
    for _ in range(5):
        eng.registry.evict("ds")
        eng.register("ds", 2.0 + x, h=H)
        eng.registry.evict("ds")
        eng.register("ds", x, h=H)
    t.join()
    assert not errors, errors
    assert results
    for r in results:
        assert np.isfinite(r).all()
        ok_a = np.allclose(r, want_a, rtol=1e-5,
                           atol=1e-6 * float(want_a.max()))
        ok_b = np.allclose(r, want_b, rtol=1e-5,
                           atol=1e-6 * float(want_b.max()))
        assert ok_a or ok_b


def test_point_evict_during_pinned_snapshot_is_consistent(data):
    """An in-flight dispatch pinned to snapshot g keeps serving g's
    tensors even while evictions publish g+1 (snapshots are immutable)."""
    x, xa, y = data
    eng = ServeEngine(_serve_cfg())
    eng.register("ds", x, h=H)
    st = eng.registry.get("ds").stream
    pinned = st.ensure(0)
    cols_before = st.columns_for("f32", pinned)
    ids = eng.registry.append("ds", xa)
    eng.registry.evict_ids("ds", ids)                # live set moved on
    st.ensure(0)                                     # publish the new gen
    cols_after = st.columns_for("f32", pinned)       # pinned view unchanged
    np.testing.assert_array_equal(np.asarray(cols_before.xt),
                                  np.asarray(cols_after.xt))
    assert pinned.n_live == x.shape[0]
    # and the live snapshot reflects the round-trip back to x
    want = _refit_eval(x, y)
    got = np.asarray(_q(eng, "ds", y))
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-6 * float(want.max()))


def test_stream_refit_bumps_generation_and_invalidates(data):
    """refit=True on a streaming key rebuilds the stream and can never
    serve executables of the replaced one."""
    x, xa, y = data
    eng = ServeEngine(_serve_cfg(method="kde"))
    eng.register("ds", x, h=H)
    stale = np.asarray(_q(eng, "ds", y[:16]))
    gen0 = eng.registry.get("ds").generation
    eng.register("ds", 2.0 + x, h=H, refit=True)
    assert eng.registry.get("ds").generation != gen0
    fresh = np.asarray(_q(eng, "ds", y[:16]))
    want = _refit_eval(2.0 + x, y[:16], "kde")
    np.testing.assert_allclose(fresh, want, rtol=1e-5,
                               atol=1e-6 * float(want.max()))
    assert not np.allclose(fresh, stale)


# ---------------------------------------------------------------------------
# Execution planning (repro.plan): planned streaming == explicit knobs,
# across a generation flip.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tier", ["f32", "bf16", "bf16x2"])
def test_planned_stream_matches_explicit_across_generation_flip(data, tier):
    """A plan-resolved streaming estimator serves the same densities as a
    hand-pinned one, before AND after an append bumps the generation."""
    x, xa, y = data
    planned = ServeConfig(
        backend="pallas", method="sdkde", interpret=True, plan="auto",
        precision=tier,                   # explicit: wins over the plan
        stream=True, min_batch=16, max_batch=128,
    )
    ep = ServeEngine(planned)
    prep = ep.register("ds", x, h=H)
    assert prep.plan is not None
    # default accuracy target is f32-grade -> the plan pins freshness
    assert prep.config.staleness_budget == 0
    explicit = ServeConfig(
        backend="pallas", method="sdkde", interpret=True,
        precision=tier, prune=prep.config.prune,
        block_m=prep.block_m, block_n=prep.block_n,
        stream=True, staleness_budget=0,
        min_batch=16, max_batch=128,
    )
    ee = ServeEngine(explicit)
    ee.register("ds", x, h=H)

    before_p = np.asarray(_q(ep, "ds", y[:64]))
    before_e = np.asarray(_q(ee, "ds", y[:64]))
    np.testing.assert_allclose(before_p, before_e, rtol=1e-5,
                               atol=1e-8 * float(np.max(before_e)))

    ep.registry.append("ds", xa)          # generation flip on both
    ee.registry.append("ds", xa)
    after_p = np.asarray(_q(ep, "ds", y[:64]))
    after_e = np.asarray(_q(ee, "ds", y[:64]))
    np.testing.assert_allclose(after_p, after_e, rtol=1e-5,
                               atol=1e-8 * float(np.max(after_e)))
    assert not np.allclose(before_p, after_p)   # the flip actually served


def test_planned_stream_loose_accuracy_gets_staleness_budget(data):
    x, _, _ = data
    eng = ServeEngine(ServeConfig(
        backend="pallas", method="sdkde", interpret=True, plan="auto",
        accuracy_target=5e-2, stream=True, min_batch=16, max_batch=128,
    ))
    prep = eng.register("ds", x, h=H)
    assert prep.config.staleness_budget == 2
    assert prep.config.stream_background
