"""Pallas-kernel allclose sweeps against the pure-jnp oracles (ref.py).

Every kernel × a sweep of shapes (including non-tile-multiple row counts,
which exercise the sentinel padding) × dtypes, in interpret mode (CPU
executes the kernel body in Python — the brief's validation mode).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [
    (64, 16, 8),        # n, m, d  — tiny
    (300, 50, 16),      # non-multiples: padding path
    (513, 129, 16),     # prime-ish
    (1024, 128, 4),     # d not 16
    (256, 256, 32),     # larger d
    (128, 64, 1),       # 1-D (the appendix setting)
]

BLOCKS = [(32, 64), (128, 128)]


def _data(n, m, d, dtype=jnp.float32, seed=0):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (n, d), jnp.float32).astype(dtype)
    y = jax.random.normal(ky, (m, d), jnp.float32).astype(dtype) * 1.2
    return x, y


@pytest.mark.parametrize("n,m,d", SHAPES)
@pytest.mark.parametrize("bm,bn", BLOCKS)
def test_flash_kde_matches_ref(n, m, d, bm, bn):
    x, y = _data(n, m, d)
    h = 0.7
    got = ops.flash_kde(x, y, h, block_m=bm, block_n=bn, interpret=True)
    # normalize the oracle the same way
    from repro.core.bandwidth import gaussian_norm_const

    want = ref.ref_kde_sums(x, y, h) / (n * gaussian_norm_const(d, 1.0) * h**d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=1e-9)


@pytest.mark.parametrize("n,m,d", SHAPES)
def test_flash_laplace_matches_ref(n, m, d):
    x, y = _data(n, m, d, seed=1)
    h = 0.9
    from repro.core.bandwidth import gaussian_norm_const

    norm = n * gaussian_norm_const(d, 1.0) * h**d
    got = ops.flash_laplace_kde(x, y, h, block_m=32, block_n=64,
                                interpret=True)
    want = ref.ref_laplace_sums(x, y, h) / norm
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=1e-7)


@pytest.mark.parametrize("n,m,d", SHAPES)
def test_nonfused_laplace_matches_fused(n, m, d):
    """Fusion is an implementation detail, not an estimator change (§5)."""
    x, y = _data(n, m, d, seed=2)
    h = 0.8
    fused = ops.flash_laplace_kde(x, y, h, block_m=32, block_n=64,
                                  interpret=True)
    nonfused = ops.laplace_kde_nonfused(x, y, h, block_m=32, block_n=64,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(nonfused),
                               rtol=2e-4, atol=1e-7)


@pytest.mark.parametrize("n,d", [(64, 8), (300, 16), (513, 16), (128, 1)])
@pytest.mark.parametrize("bm,bn", BLOCKS)
def test_flash_score_stats_matches_ref(n, d, bm, bn):
    x, _ = _data(n, 1, d, seed=3)
    h = 0.6
    s0, s1 = ops.flash_score_stats(x, h, block_m=bm, block_n=bn,
                                   interpret=True)
    r0, r1 = ref.ref_score_stats(x, h)
    np.testing.assert_allclose(np.asarray(s0), np.asarray(r0), rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(r1),
                               rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("n,d", [(128, 16), (300, 8)])
def test_flash_sdkde_shift_matches_ref(n, d):
    x, _ = _data(n, 1, d, seed=4)
    h = 0.5
    got = ops.flash_sdkde_shift(x, h, block_m=32, block_n=64, interpret=True)
    want = ref.ref_sdkde_shift(x, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kde_dtypes(dtype):
    """bf16 inputs, f32 MXU accumulation — the mixed-precision path."""
    x, y = _data(256, 64, 16, dtype=dtype, seed=5)
    h = 0.8
    got = ops.flash_kde(x, y, h, block_m=32, block_n=64, interpret=True)
    x32, y32 = x.astype(jnp.float32), y.astype(jnp.float32)
    from repro.core.bandwidth import gaussian_norm_const

    want = ref.ref_kde_sums(x32, y32, h) / (
        256 * gaussian_norm_const(16, 1.0) * h**16
    )
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol)


def test_full_pipeline_matches_reference_path():
    """flash_sdkde (pallas) == core.kde.sdkde_eval (streaming jnp GEMM)."""
    from repro.core import kde

    x, y = _data(300, 77, 16, seed=6)
    h = 0.6
    got = ops.flash_sdkde(x, y, h, block_m=32, block_n=64, interpret=True)
    want = kde.sdkde_eval(x, y, h, block=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-4)


def test_vmem_budget_rejects_oversized_tiles():
    with pytest.raises(ValueError, match="VMEM"):
        ops.flash_kde(jnp.zeros((1024, 16)), jnp.zeros((64, 16)), 1.0,
                      block_m=2048, block_n=2048, interpret=True)


def test_interpret_mode_derived_from_platform():
    """``interpret=None`` (every config's default) interprets off a TPU and
    compiles on one; each traced kernel program is counted under its mode."""
    from repro import obs
    from repro.kernels import interpret_mode

    assert interpret_mode() == (jax.default_backend() != "tpu")
    assert interpret_mode(True) is True and interpret_mode(False) is False
    mode = "interpret" if interpret_mode() else "compiled"
    traces = obs.counter("kernels.pallas_traces", labels={"mode": mode})
    before = traces.value
    x, y = _data(72, 24, 3, seed=7)       # a shape no other test traces
    ops.flash_kde(x, y, 0.9, block_m=8, block_n=128, prune="off")
    assert traces.value > before
