"""The f32 tier's packed GEMMs (kernels/precision.py) and where they run.

The helpers are held against float64 and against ``dot_f32`` at
``Precision.HIGHEST`` on clustered near pairs far from the origin, where
``sq = ‖y‖² + ‖x‖² − 2g`` cancels: the packed errors stay within 2× of
HIGHEST's, at every dimension: those whose six split products fit the
MXU's depth in one pass (d ≤ 21), odd ones (5, 21) and those that take
two (22, 32).  The trace-time counter ``kernels.f32_gemm_path`` and the
kernel bodies' dot products say that the pruned kernels pack at the f32
tier, at every d, and that nothing changed below it or in the dense
kernels.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import flash_pruned, ops
from repro.kernels import precision as prec
from repro.kernels.flash_kde import flash_kde_pallas
from repro.kernels.flash_score import flash_score_pallas

DIMS = (2, 5, 16, 21, 22, 32)


def _near_pairs(d, m=256, n=512, seed=0):
    """Queries and train points in tight clusters ~4 from the origin."""
    rng = np.random.default_rng(seed + d)
    c = rng.uniform(-4.0, 4.0, size=(8, d))
    y = c[rng.integers(0, 8, m)] + 0.01 * rng.normal(size=(m, d))
    x = c[rng.integers(0, 8, n)] + 0.01 * rng.normal(size=(n, d))
    return y.astype(np.float32), x.astype(np.float32)


def _err(got, want):
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("d", DIMS)
def test_packed_layout(d):
    assert prec.packs("f32")
    assert not prec.packs("bf16") and not prec.packs("bf16x2")
    rows_n = prec.plane_rows(d)
    assert rows_n % 16 == 0 and 3 * d + 1 <= rows_n < 3 * d + 17
    y, x = _near_pairs(d, m=32, n=64)
    rows = np.asarray(prec.pack_rows(jnp.asarray(y)), np.float64)
    planes = np.asarray(prec.column_planes(jnp.asarray(x)), np.float64)
    assert rows.shape == (32, 6 * d) and planes.shape == (rows_n, 64)
    # [a3, a1, a2, a2, a1, a1]: the three distinct planes sum to the rows
    np.testing.assert_array_equal(
        rows[:, :d] + rows[:, d:2 * d] + rows[:, 2 * d:3 * d], y)
    np.testing.assert_array_equal(
        planes[:d] + planes[d:2 * d] + planes[2 * d:3 * d], x.T)
    np.testing.assert_array_equal(planes[3 * d], 1.0)
    np.testing.assert_array_equal(planes[3 * d + 1:], 0.0)


@pytest.mark.parametrize("d", DIMS)
def test_split3_is_exact(d):
    y, _ = _near_pairs(d)
    # magnitudes over twelve decades, so every exponent of the planes moves
    y = y * (10.0 ** np.random.default_rng(d).uniform(-6, 6, y.shape)
             ).astype(np.float32)
    planes = [np.asarray(p, np.float64) for p in prec.split3(jnp.asarray(y))]
    np.testing.assert_array_equal(sum(planes), y.astype(np.float64))


@pytest.mark.parametrize("d", DIMS)
def test_packed_gram_as_accurate_as_highest(d):
    y, x = _near_pairs(d)
    y64, x64 = y.astype(np.float64), x.astype(np.float64)
    g64 = y64 @ x64.T
    g_hi = prec.dot_f32(jnp.asarray(y), jnp.asarray(x).T)
    g_pk = prec.gram_packed(prec.pack_rows(jnp.asarray(y)),
                            prec.column_planes(jnp.asarray(x)))
    assert g_pk.dtype == jnp.float32 and g_pk.shape == g64.shape
    e_hi, e_pk = _err(g_hi, g64), _err(g_pk, g64)
    assert e_pk <= 2 * e_hi, (e_pk, e_hi)
    assert e_pk < 1e-6
    # the squared distances the kernels form from it, f32 norms of the
    # points: the near pairs cancel to ~1e-3 of the norms
    ny, nx = ops._norms(jnp.asarray(y)), ops._norms(jnp.asarray(x)).T
    sq64 = ((y64[:, None] - x64[None]) ** 2).sum(-1)
    sq_hi = np.asarray(ny + nx - 2.0 * g_hi, np.float64)
    sq_pk = np.asarray(ny + nx - 2.0 * g_pk, np.float64)
    a_hi = np.max(np.abs(sq_hi - sq64))
    a_pk = np.max(np.abs(sq_pk - sq64))
    assert a_pk <= 2 * a_hi, (a_pk, a_hi)


@pytest.mark.parametrize("d", DIMS)
def test_packed_numerator_as_accurate_as_highest(d):
    y, x = _near_pairs(d)
    x64 = x.astype(np.float64)
    sq64 = ((y.astype(np.float64)[:, None] - x64[None]) ** 2).sum(-1)
    phi = np.exp(-sq64 / (2 * 0.05 ** 2)).astype(np.float32)
    w64 = np.concatenate([x64, np.ones((x.shape[0], 1))], axis=1)
    want = phi.astype(np.float64) @ w64
    hi = prec.weighted_accum(jnp.asarray(phi),
                             jnp.asarray(w64.astype(np.float32)))
    acc = prec.weighted_accum_packed(jnp.asarray(phi),
                                     prec.column_planes(jnp.asarray(x)))
    assert acc.shape == (prec.plane_rows(d), y.shape[0])
    pk = prec.reduce_planes(acc.T, d)
    assert pk.shape == want.shape
    e_hi, e_pk = _err(hi, want), _err(pk, want)
    assert e_pk <= 2 * e_hi, (e_pk, e_hi)
    assert e_pk < 1e-6


# ---------------------------------------------------------------------------
# Which path the pruned kernels take.
# ---------------------------------------------------------------------------


_KERNEL_GEMMS = (("flash_score_pallas_pruned", "gram"),
                 ("flash_score_pallas_pruned", "numerator"),
                 ("flash_kde_pallas_pruned", "gram"))


def _path_counts():
    """{(kernel, gemm): count} of ``kernels.f32_gemm_path``."""
    return {(k, g): obs.counter("kernels.f32_gemm_path", labels={
        "kernel": k, "gemm": g, "path": "packed"}).value
        for k, g in _KERNEL_GEMMS}


def _delta(before, after):
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


@pytest.mark.parametrize("tier", ["f32", "bf16", "bf16x2"])
@pytest.mark.parametrize("d", DIMS)
def test_pruned_kernels_count_their_f32_gemm_path(d, tier):
    rng = np.random.default_rng(d)
    x = rng.normal(size=(256, d)).astype(np.float32)
    y = rng.normal(size=(64, d)).astype(np.float32)
    kw = dict(precision=tier, block_m=32, block_n=128, interpret=True,
              prune=0.0)
    flash_pruned.flash_score_pallas_pruned.clear_cache()
    flash_pruned.flash_kde_pallas_pruned.clear_cache()
    before = _path_counts()
    ops.flash_score_stats(x, 1.5, **kw)
    ops.flash_kde(x, y, 1.5, **kw)
    got = _delta(before, _path_counts())
    if tier != "f32":
        assert got == {}             # nothing below the f32 tier
        return
    assert set(got) == set(_KERNEL_GEMMS), got


def _kernel_dots(fn, *args, **kw):
    """(operand dtypes, precision) of every dot product in the Pallas
    kernel bodies ``fn`` traces."""
    dots, in_kernel = [], [False]

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "dot_general" and in_kernel[0]:
                dots.append((tuple(str(v.aval.dtype) for v in eqn.invars),
                             eqn.params["precision"]))
            inside = in_kernel[0]
            in_kernel[0] = inside or name == "pallas_call"
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)
            in_kernel[0] = inside

    walk(jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args).jaxpr)
    return dots


HIGHEST = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)


def _pruned_args(kind, tier, d, n=256, bm=32, bn=128):
    """A pruned launch's operands as ops builds them for a tier, and
    whether they are packed."""
    x = jnp.asarray(np.random.default_rng(d).normal(size=(n, d)),
                    jnp.float32)
    mt = n // bm
    counts = jnp.full((mt,), n // bn, jnp.int32)
    tmap = jnp.tile(jnp.arange(n // bn, dtype=jnp.int32)[None], (mt, 1))
    inv = ops._inv2h2(1.0)
    packed = prec.packs(tier)
    if kind == "score":
        xo, xto, augo, nrm, _ = ops._score_operands(x, tier, packed=packed)
        return (counts, tmap, xo[0], nrm, xto[0], augo[0], inv, xo[1],
                xto[1], augo[1]), packed
    cols = ops.columns_from_layout(x, jnp.ones((n,), bool), None,
                                   block_n=bn, precision=tier)
    yh, yl, nrm_y, _ = ops._cast_queries(x, tier, packed=packed)
    return (counts, tmap, yh, nrm_y, cols.planes if packed else cols.xt,
            cols.nrm_x, inv, yl, cols.xt_lo), packed


def _pruned_kernel(kind):
    return (flash_pruned.flash_kde_pallas_pruned if kind == "kde"
            else flash_pruned.flash_score_pallas_pruned)


@pytest.mark.parametrize("kind", ["kde", "score"])
@pytest.mark.parametrize("tier,d", [("f32", 16), ("f32", 2), ("f32", 22),
                                    ("f32", 32), ("bf16", 16),
                                    ("bf16x2", 16)])
def test_kernel_bodies_pack_only_at_the_f32_tier(kind, tier, d):
    args, packed = _pruned_args(kind, tier, d)
    dots = _kernel_dots(_pruned_kernel(kind), *args, block_m=32,
                        block_n=128, max_visits=2, interpret=True,
                        packed=packed)
    gemms = 1 if kind == "kde" else 2
    if kind == "score":    # per visit slot of a step, in the branch of
        slots = flash_pruned.score_slots(      # full steps and the masked one
            2, block_m=32, block_n=128, d=d,
            itemsize=prec.operand_bytes(tier))
        gemms *= slots * (2 if slots > 1 else 1)
    if tier == "bf16x2":               # four products per GEMM
        gemms *= 4
    # packed or bf16: one bf16 pass per GEMM, nothing at HIGHEST
    assert dots == [(("bfloat16", "bfloat16"), None)] * gemms


@pytest.mark.parametrize("kind", ["kde", "score"])
def test_pruned_kernels_refuse_unpacked_f32_operands(kind):
    """The f32 tier has one pruned path: f32 operands without
    ``packed`` do not fall back to HIGHEST."""
    args, _ = _pruned_args(kind, "f32", 16)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(256, 16)),
                    jnp.float32)
    nrm = ops._norms(x)
    if kind == "score":
        xaug = jnp.concatenate([x, jnp.ones((256, 1))], axis=1)
        args = args[:2] + (x, nrm, x.T, xaug, args[6])
    else:
        args = args[:2] + (x, nrm, x.T, nrm.reshape(1, -1), args[6])
    with pytest.raises(AssertionError, match="run packed"):
        _pruned_kernel(kind)(*args, block_m=32, block_n=128, max_visits=2,
                             interpret=True)


@pytest.mark.parametrize("d", [2, 16])
def test_dense_kernels_keep_highest(d):
    n = 256
    x = jnp.asarray(np.random.default_rng(d).normal(size=(n, d)),
                    jnp.float32)
    xo, xto, augo, nrm, _ = ops._score_operands(x, "f32")
    kw = dict(block_m=32, block_n=128, interpret=True)
    inv = ops._inv2h2(1.0)
    assert _kernel_dots(flash_score_pallas, xo[0], nrm, xto[0], augo[0],
                        inv, **kw) == [(("float32", "float32"),
                                        HIGHEST)] * 2
    assert _kernel_dots(flash_kde_pallas, xo[0], nrm, xto[0],
                        nrm.reshape(1, -1), inv, **kw) == [
        (("float32", "float32"), HIGHEST)]
