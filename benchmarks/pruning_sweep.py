"""Cluster-pruning sweep: occupancy, certified error, speedup vs dense.

Three kinds of cells:

  * ``pruning_smoke`` — the pruned kernels actually run (interpret mode on
    CPU) against the dense kernels at a small clustered problem: max
    relative deviation at epsilon=0 (must be f32-noise-level) and the
    measured occupancy.  This is the CI gate.
  * ``pruning`` — the epsilon sweep at the acceptance scale: per epsilon,
    the measured tile-map occupancy (real bounds prepass on the real
    clustered data), the certified per-row error bound, the measured
    relative density error of the *actual pruned kernel* on a query
    subsample vs the streaming-jnp dense reference, and the modeled
    dense/pruned runtimes (kernels/autotune.py cost model with the
    occupancy term — the same model PR 3's acceptance cell used; on TPU
    hardware the smoke cells above become the measured counterpart).
  * ``pruning_acceptance`` — the issue's gate: a clustered 256k-sample
    16-d problem, the largest modeled speedup among epsilons whose
    measured relative error is ≤ 1e-6, target ≥ 5×.

    PYTHONPATH=src python -m benchmarks.pruning_sweep
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.core.mixtures import GaussianMixture
from repro.kernels import autotune, interpret_mode, ops, spatial


def clustered_mixture(d: int = 16, k: int = 64, spread: float = 4.0,
                      sigma: float = 0.05, seed: int = 0) -> GaussianMixture:
    """k tight, well-separated isotropic clusters in [0, spread]^d.

    The regime DEANN-style pruning targets: bandwidths that resolve the
    cluster structure make almost every cross-cluster tile's kernel weight
    underflow, so certified skipping removes ~(1 − 1/k) of the work.
    """
    rng = np.random.default_rng(seed)
    means = rng.uniform(0.0, spread, size=(k, d))
    return GaussianMixture(
        means=means,
        stds=np.full((k,), sigma),
        weights=np.full((k,), 1.0 / k),
    )


def _modeled_times(m, n, d, dense_blocks, pruned_blocks, occ,
                   precision="f32"):
    """(dense_s, pruned_s) modeled step times, each at ITS OWN tuned tiles;
    pruned includes the per-batch bounds prepass (query row-tile stats +
    the (m/bm × n/bn) centroid-distance GEMM)."""
    dense = autotune.modeled_cost(m, n, d, block_m=dense_blocks[0],
                                  block_n=dense_blocks[1],
                                  precision=precision)
    bm, bn = pruned_blocks
    pruned = autotune.modeled_cost(m, n, d, block_m=bm, block_n=bn,
                                   precision=precision, occupancy=occ)
    from repro.kernels import tuning

    mt, nt = -(-m // bm), -(-n // bn)
    prepass_flops = 2.0 * mt * nt * d + 6.0 * m * d      # bounds GEMM + stats
    prepass_s = prepass_flops / tuning.VPU_OPS
    return dense.step_time, pruned.step_time + prepass_s


def smoke_cells(n: int = 8192, m: int = 1024, d: int = 8, h: float = 0.25,
                seed: int = 0):
    """Pruned kernels really run and match dense at epsilon=0."""
    mix = clustered_mixture(d=d, k=16, spread=6.0, sigma=0.05, seed=seed)
    key = jax.random.PRNGKey(seed)
    x = mix.sample(key, n)
    y = mix.sample(jax.random.fold_in(key, 1), m)
    bm, bn = 64, 256
    kw = dict(block_m=bm, block_n=bn)
    t_dense = timeit(lambda: ops.flash_kde(x, y, h, prune="off", **kw))
    t_pruned = timeit(lambda: ops.flash_kde(x, y, h, prune=0.0, **kw))
    dense = np.asarray(ops.flash_kde(x, y, h, prune="off", **kw))
    pruned = np.asarray(ops.flash_kde(x, y, h, prune=0.0, **kw))
    rel = float(np.max(np.abs(pruned - dense) / (np.abs(dense) + 1e-30)))
    occ = autotune.expected_occupancy(m, n, d)
    emit("pruning_smoke", n=n, m=m, d=d, h=h, block_m=bm, block_n=bn,
         max_rel_err_eps0=f"{rel:.2e}", occupancy=round(occ, 4),
         wall_dense_ms=round(t_dense * 1e3, 1),
         wall_pruned_ms=round(t_pruned * 1e3, 1),
         interpret=interpret_mode())
    assert rel < 1e-5, f"epsilon=0 pruning deviated from dense: {rel}"
    return rel, occ


def acceptance_cells(n: int = 262144, m: int = 32768, d: int = 16,
                     k_clusters: int = 64, h: float = 0.2, seed: int = 0,
                     n_err_queries: int = 512,
                     epsilons=(0.0, 1e-12, 1e-9, 1e-6)):
    """The 256k×16-d clustered acceptance sweep (modeled runtimes).

    Error accounting: ``epsilon=0`` pruning is bitwise-identical to
    visiting every tile in the clustered layout (a skipped tile's every
    f32 term underflows to exactly 0.0), so the error *attributable to
    pruning* at epsilon>0 is measured against the epsilon=0 run.  The
    residual deviation between the epsilon=0 run and the dense kernel is
    pure f32 accumulation-order noise (the same magnitude as the dense
    kernel's own deviation from a float64 oracle) and is emitted
    separately as ``reorder_noise``.
    """
    mix = clustered_mixture(d=d, k=k_clusters, spread=4.0, sigma=0.05,
                            seed=seed)
    key = jax.random.PRNGKey(seed)
    x = mix.sample(key, n)
    y = mix.sample(jax.random.fold_in(key, 1), m)
    yq = y[:n_err_queries]

    # what a dense pass would launch at this shape
    dense_blocks = autotune.resolve_blocks("auto", "auto", m, n, d,
                                           precision="f32", measure=False)
    # warm-up pruned call on the REAL traffic shape: records occupancy at
    # the launch AND fine-probe widths under the (rows, cols, d) bucket the
    # re-resolve below will consult — which then *learns* that smaller
    # column tiles skip more (the autotuner's expected-occupancy term)
    ops.flash_kde(x, y, h, block_m=dense_blocks[0], block_n=dense_blocks[1],
                  prune=0.0, seed=seed)
    bm, bn = autotune.resolve_blocks("auto", "auto", m, n, d,
                                     precision="f32", measure=False,
                                     pruned=True)
    emit("pruning_tiles", n=n, m=m, d=d,
         dense_block_m=dense_blocks[0], dense_block_n=dense_blocks[1],
         pruned_block_m=bm, pruned_block_n=bn,
         learned_occ_fine=round(autotune.expected_occupancy(
             m, n, d, autotune.FINE_PROBE_BLOCK), 4))

    # fit-time spatial prep at the tuned tiles (what the serve registry
    # caches per tier), plus the full-traffic bounds prepass for occupancy
    index = spatial.build_index(x, n_clusters=k_clusters, seed=seed)
    xlay = spatial.cluster_layout(jnp.asarray(x, jnp.float32), index.labels,
                                  bn)
    col_meta = spatial.tile_metadata(xlay.points, xlay.real, block=bn)
    qlay = spatial.cluster_layout(jnp.asarray(y, jnp.float32),
                                  spatial.assign(y, index), bm)
    inv2h2 = jnp.asarray(1.0 / (2.0 * h * h), jnp.float32).reshape(1, 1)

    # anchors: dense kernel (at its own tiles) and the exact-mode run
    dense_out = np.asarray(ops.flash_kde(
        x, yq, h, block_m=dense_blocks[0], block_n=dense_blocks[1],
        prune="off"))
    base = np.asarray(ops.flash_kde(x, yq, h, block_m=bm, block_n=bn,
                                    prune=0.0, seed=seed))
    noise = float(np.max(np.abs(base - dense_out)
                         / (np.abs(dense_out) + 1e-30)))

    best = None
    for eps in epsilons:
        tm = spatial.tile_map(qlay.points, col_meta, inv2h2, eps,
                              block_m=bm, kind="kde")
        vl = spatial.visit_lists(tm.keep)
        occ = vl.occupancy
        cert = float(jnp.max(tm.err_bound))
        got = np.asarray(ops.flash_kde(x, yq, h, block_m=bm, block_n=bn,
                                       prune=eps, seed=seed))
        rel_err = float(np.max(np.abs(got - base) / (np.abs(base) + 1e-30)))
        dense_s, pruned_s = _modeled_times(m, n, d, dense_blocks, (bm, bn),
                                           occ)
        speedup = dense_s / pruned_s
        emit("pruning", n=n, m=m, d=d, h=h, epsilon=eps,
             block_m=bm, block_n=bn,
             occupancy=round(occ, 4),
             cert_max_abs=f"{cert:.2e}",
             prune_rel_err=f"{rel_err:.2e}",
             reorder_noise=f"{noise:.2e}",
             dense_model_ms=round(dense_s * 1e3, 3),
             pruned_model_ms=round(pruned_s * 1e3, 3),
             modeled_speedup=round(speedup, 2),
             err_queries=n_err_queries)
        if rel_err <= 1e-6 and (best is None or speedup > best[0]):
            best = (speedup, eps, occ, rel_err)

    assert best is not None, "no epsilon met the 1e-6 relative-error bar"
    speedup, eps, occ, rel_err = best
    emit("pruning_acceptance", n=n, m=m, d=d, h=h,
         epsilon=eps, occupancy=round(occ, 4),
         rel_err=f"{rel_err:.2e}", modeled_speedup=round(speedup, 2),
         target_speedup=5.0, meets_target=bool(speedup >= 5.0))
    return speedup


def main(smoke_n: int = 8192, smoke_m: int = 1024,
         acceptance: bool = True, acceptance_n: int = 262144,
         acceptance_m: int = 32768):
    smoke_cells(n=smoke_n, m=smoke_m)
    if acceptance:
        acceptance_cells(n=acceptance_n, m=acceptance_m)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=1)
    ap.add_argument("--no-acceptance", action="store_true",
                    help="smoke cells only (fast CI lane)")
    a = ap.parse_args()
    main(smoke_n=8192 * a.scale, smoke_m=1024 * a.scale,
         acceptance=not a.no_acceptance,
         acceptance_n=262144 * a.scale, acceptance_m=32768 * a.scale)
