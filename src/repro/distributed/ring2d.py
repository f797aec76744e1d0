"""2-D block-partitioned SD-KDE: the production-mesh decomposition.

``ring.py`` parallelizes over point rows only — on a (data, model) mesh the
model axis would sit idle for the KDE workload.  This module partitions the
PAIR space over the full mesh:

  * EVAL/QUERY rows shard over the ``model`` axis (16-way),
  * TRAIN columns shard over (pod, data) (16/32-way),
  * device (i, j) accumulates the partial statistics of
    (row-shard j × column-shard i) — n²/chips pairs, no redundancy —
  * one small ``lax.psum`` over (pod, data) completes the column reduction
    (payload = the (rows_loc × (d+1)) accumulator, NOT anything quadratic).

Within a device, column blocks stream through a ``lax.scan`` in ``chunk``-
sized sub-blocks so the (rows × cols) φ tile never materializes at full
width (the paper's streaming accumulation; the Pallas kernels push the same
idea into VMEM on real TPU).

History (EXPERIMENTS.md §Perf, flash_sdkde_1m iteration 2): the first
version of this module rotated the column shards around a (pod, data)
ppermute ring — correct, but every ring member consumed EVERY column shard,
duplicating all work ``data×pod``-fold.  The roofline table's
MODEL_FLOPS/HLO_FLOPs column sat at 0.07 ≈ 1/16 for the SD-KDE cells, which
is exactly how the bug was found.  A ppermute ring is the right tool when
rows and columns shard over the SAME axis (ring.py); with distinct axes the
block partition + psum is strictly better.

``check_vma=False``: the accumulators are psum'd to replicated across the
column axes, which the variance tracker cannot prove through the scan.
Agreement with the single-device reference path: tests/test_ring2d.py.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.bandwidth import gaussian_norm_const
from repro.core.kde import PAD_VALUE, sqdist
from repro.distributed.ring import chunked_consume as _chunked_consume


def _phi(sq, h):
    return jnp.exp(-sq / (2.0 * h * h))


def _axes(mesh: Mesh):
    pod = "pod" if "pod" in mesh.axis_names else None
    ring = (("pod", "data") if pod else ("data",))
    return pod, ring


def ring2d_score_stats(
    x_rows: jnp.ndarray,       # row-sharded view (model axis)
    x_cols: jnp.ndarray,       # column-sharded view (pod, data)
    h,
    *,
    mesh: Mesh,
    chunk: int = 2048,
):
    """(S0, S1) over the train set; rows over ``model``, cols over the rest."""
    pod, col_axes = _axes(mesh)

    def local(rows, cols):
        def body(acc, r, blk):
            s0, s1 = acc
            phi = _phi(sqdist(r, blk), h)
            return s0 + jnp.sum(phi, axis=1), s1 + jnp.matmul(
                phi, blk, precision=lax.Precision.HIGHEST)

        init = (
            jnp.zeros(rows.shape[0], jnp.float32),
            jnp.zeros(rows.shape, jnp.float32),
        )
        s0, s1 = _chunked_consume(rows, cols, chunk, body, init)
        return lax.psum(s0, col_axes), lax.psum(s1, col_axes)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", None), P(col_axes, None)),
        out_specs=(P("model"), P("model", None)),
        check_vma=False,
    )(x_rows, x_cols)


def ring2d_kde_sums(
    y_rows: jnp.ndarray,
    x_cols: jnp.ndarray,
    h,
    *,
    mesh: Mesh,
    chunk: int = 2048,
    laplace: bool = False,
):
    """Unnormalized (Laplace-)KDE sums at model-sharded queries."""
    pod, col_axes = _axes(mesh)
    d = x_cols.shape[-1]

    def local(rows, cols):
        def body(acc, r, blk):
            sq = sqdist(r, blk)
            phi = _phi(sq, h)
            if laplace:
                phi = phi * (1.0 + d / 2.0 - sq / (2.0 * h * h))
            return acc + jnp.sum(phi, axis=1)

        init = jnp.zeros(rows.shape[0], jnp.float32)
        acc = _chunked_consume(rows, cols, chunk, body, init)
        return lax.psum(acc, col_axes)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", None), P(col_axes, None)),
        out_specs=P("model"),
        check_vma=False,
    )(y_rows, x_cols)


def ring2d_sdkde(
    x: jnp.ndarray,            # (n, d) train points
    y: jnp.ndarray,            # (m, d) queries
    h,
    *,
    score_h=None,
    n_true: int | None = None,
    mesh: Mesh,
    chunk: int = 2048,
    laplace_final: bool = False,
    eps: float = 1e-30,
) -> jnp.ndarray:
    """Full SD-KDE on the production mesh; returns densities at ``y``.

    Program structure:
      1. score pass: rows of X over ``model``, X columns over (pod, data)
      2. shift (elementwise, stays model-row-sharded)
      3. KDE pass: rows of Y over ``model``, shifted X columns over
         (pod, data)
    GSPMD inserts the reshard between (2) and (3) — an all-to-all moving the
    debiased samples from row sharding to column sharding, O(n·d) bytes.
    """
    n, d = x.shape
    n_true = n if n_true is None else n_true
    sh = h if score_h is None else score_h

    s0, s1 = ring2d_score_stats(x, x, sh, mesh=mesh, chunk=chunk)
    score = (s1 - x * s0[:, None]) / (sh * sh * s0[:, None] + eps)
    x_sd = x + 0.5 * h * h * score

    sums = ring2d_kde_sums(
        y, x_sd, h, mesh=mesh, chunk=chunk, laplace=laplace_final
    )
    h = jnp.asarray(h, jnp.float32)
    return sums / (n_true * gaussian_norm_const(d, 1.0) * h**d)


def pad_for_mesh(x: jnp.ndarray, mesh: Mesh) -> jnp.ndarray:
    """Pad rows so both the column shards and the model-row shards divide."""
    import math

    pod, col_axes = _axes(mesh)
    cols = 1
    for a in col_axes:
        cols *= mesh.shape[a]
    mult = math.lcm(cols, mesh.shape["model"])
    rem = (-x.shape[0]) % mult
    if rem:
        x = jnp.pad(x, [(0, rem), (0, 0)], constant_values=PAD_VALUE)
    return x
