"""Ring-sharded SD-KDE: the paper's streaming accumulation, at mesh scale.

The single-chip Flash kernels stream column tiles HBM→VMEM; this module
applies the same idea one level up the hierarchy: point-set *shards* are
streamed device→device around a ring with ``lax.ppermute`` while each device
consumes the block it currently holds.  Per-device collective traffic is
O(n·d / R) per step — linear in n, never quadratic — and the permute of the
next block is independent of the GEMMs on the current block, so XLA's
latency-hiding scheduler overlaps communication with compute.

Multi-pod meshes use a *hierarchical* two-level ring: an inner ring over the
``data`` axis (fast intra-pod ICI) and an outer rotation over the ``pod``
axis (slow inter-pod links).  Cross-pod transfers happen once per full inner
ring, so each inter-pod permute has an entire pod's worth of compute to hide
behind — the key to scaling past one pod.

All functions are shard_map'd over a mesh and agree with the single-device
reference path to float tolerance (tested in tests/test_distributed_kde.py).
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.bandwidth import gaussian_norm_const
from repro.core.kde import PAD_VALUE, sqdist


def default_mesh(data_axis: str = "data") -> Mesh:
    """One-axis ring over every local device (1 device → a trivial ring).

    Serving and the estimator's ``ring`` backend use this when no mesh is
    passed, so the same code path runs unchanged from a CPU laptop to a pod.
    """
    import numpy as np

    return Mesh(np.asarray(jax.devices()), (data_axis,))


#: Column sub-block a device consumes at once: the (rows × CHUNK) φ tile
#: is the largest intermediate, so a shard of 256k rows stays at 1 GiB
#: whatever the shard's column count.
CHUNK = 1024


def chunked_consume(rows, cols, chunk: int, body, acc):
    """Stream ``cols`` in ``chunk`` blocks: acc = body(acc, rows, col_blk)."""
    n = cols.shape[0]
    if n <= chunk:
        return body(acc, rows, cols)
    nb = n // chunk
    main, tail = cols[: nb * chunk], cols[nb * chunk:]
    blocks = main.reshape(nb, chunk, cols.shape[-1])

    def step(a, blk):
        return body(a, rows, blk), None

    acc, _ = lax.scan(step, acc, blocks)
    if tail.shape[0]:
        acc = body(acc, rows, tail)
    return acc


def _ring_perm(size: int):
    return [(i, (i + 1) % size) for i in range(size)]


def _pvary(tree, axes: tuple):
    """Mark zero-init carries as varying over the ring axes (shard_map vma)."""
    return jax.tree.map(lambda a: lax.pcast(a, axes, to="varying"), tree)


def _ring_scan(
    cols0: jnp.ndarray,
    init_acc,
    consume: Callable,
    mesh: Mesh,
    data_axis: str,
    pod_axis: str | None,
):
    """Hierarchical ring fold: acc = consume(acc, block) over all blocks.

    ``cols0`` is this device's resident column block.  Inner ring rotates
    over ``data_axis``; if ``pod_axis`` is given, an outer rotation over pods
    runs a full inner ring per pod step.
    """
    n_data = mesh.shape[data_axis]
    n_pod = mesh.shape[pod_axis] if pod_axis else 1
    vary_axes = (data_axis,) + ((pod_axis,) if pod_axis else ())
    init_acc = _pvary(init_acc, vary_axes)

    def inner_ring(carry_cols, acc):
        def body(i, state):
            acc, cols = state
            # The permute is independent of the consume — XLA overlaps them.
            nxt = (
                lax.ppermute(cols, data_axis, _ring_perm(n_data))
                if n_data > 1
                else cols
            )
            acc = consume(acc, cols)
            return acc, nxt

        acc, cols = lax.fori_loop(0, n_data, body, (acc, carry_cols))
        return cols, acc

    def outer_body(p, state):
        acc, cols = state
        cols, acc = inner_ring(cols, acc)
        if pod_axis and n_pod > 1:
            cols = lax.ppermute(cols, pod_axis, _ring_perm(n_pod))
        return acc, cols

    acc, _ = lax.fori_loop(0, n_pod, outer_body, (init_acc, cols0))
    return acc


def _row_axes(mesh: Mesh, data_axis: str, pod_axis: str | None):
    return (pod_axis, data_axis) if pod_axis else (data_axis,)


def _phi(sq, h):
    return jnp.exp(-sq / (2.0 * h * h))


# ---------------------------------------------------------------------------
# Ring score statistics (train × train).
# ---------------------------------------------------------------------------


def ring_score_stats(
    x: jnp.ndarray,
    h,
    *,
    mesh: Mesh | None = None,
    data_axis: str = "data",
    pod_axis: str | None = None,
):
    """(S0, S1) with rows and streamed columns sharded over the ring.

    ``x`` must be evenly shardable over the ring axes (pad with
    ``repro.core.kde.pad_rows`` first — sentinel rows contribute exactly 0).
    """
    mesh = default_mesh(data_axis) if mesh is None else mesh
    axes = _row_axes(mesh, data_axis, pod_axis)
    spec = P(axes, None)

    def local(x_rows):
        def body(acc, rows, cols):
            s0, s1 = acc
            phi = _phi(sqdist(rows, cols), h)
            return s0 + jnp.sum(phi, axis=1), s1 + jnp.matmul(
                phi, cols, precision=lax.Precision.HIGHEST)

        def consume(acc, cols):
            return chunked_consume(x_rows, cols, CHUNK, body, acc)

        init = (
            jnp.zeros(x_rows.shape[0], jnp.float32),
            jnp.zeros(x_rows.shape, jnp.float32),
        )
        return _ring_scan(x_rows, init, consume, mesh, data_axis, pod_axis)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec,), out_specs=(P(axes), spec)
    )(x)


def ring_sdkde_shift(
    x: jnp.ndarray,
    h,
    *,
    score_h=None,
    mesh: Mesh | None = None,
    data_axis: str = "data",
    pod_axis: str | None = None,
    eps: float = 1e-30,
) -> jnp.ndarray:
    """Debiased samples, rows staying sharded over the ring axes."""
    mesh = default_mesh(data_axis) if mesh is None else mesh
    sh = h if score_h is None else score_h
    s0, s1 = ring_score_stats(
        x, sh, mesh=mesh, data_axis=data_axis, pod_axis=pod_axis
    )
    score = (s1 - x * s0[:, None]) / (sh * sh * s0[:, None] + eps)
    return x + 0.5 * h * h * score


# ---------------------------------------------------------------------------
# Ring KDE / Laplace evaluation (train × query).
# ---------------------------------------------------------------------------


def _ring_eval(
    x: jnp.ndarray,
    y: jnp.ndarray,
    h,
    weight_fn,
    *,
    n_true: int,
    mesh: Mesh | None,
    data_axis: str,
    pod_axis: str | None,
):
    mesh = default_mesh(data_axis) if mesh is None else mesh
    axes = _row_axes(mesh, data_axis, pod_axis)
    spec = P(axes, None)
    d = x.shape[-1]

    def local(y_rows, x_cols):
        def body(acc, rows, cols):
            return acc + jnp.sum(weight_fn(sqdist(rows, cols), h, d), axis=1)

        def consume(acc, cols):
            return chunked_consume(y_rows, cols, CHUNK, body, acc)

        init = jnp.zeros(y_rows.shape[0], jnp.float32)
        return _ring_scan(x_cols, init, consume, mesh, data_axis, pod_axis)

    sums = jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec), out_specs=P(axes)
    )(y, x)
    h = jnp.asarray(h, jnp.float32)
    return sums / (n_true * gaussian_norm_const(d, 1.0) * h**d)


def ring_kde(
    x: jnp.ndarray,
    y: jnp.ndarray,
    h,
    *,
    n_true: int | None = None,
    mesh: Mesh | None = None,
    data_axis: str = "data",
    pod_axis: str | None = None,
) -> jnp.ndarray:
    """Gaussian KDE at sharded queries; train shards rotate around the ring."""
    n_true = int(x.shape[0]) if n_true is None else n_true
    return _ring_eval(
        x, y, h, lambda sq, h_, d_: _phi(sq, h_),
        n_true=n_true, mesh=mesh, data_axis=data_axis, pod_axis=pod_axis,
    )


def ring_laplace_kde(
    x: jnp.ndarray,
    y: jnp.ndarray,
    h,
    *,
    n_true: int | None = None,
    mesh: Mesh | None = None,
    data_axis: str = "data",
    pod_axis: str | None = None,
) -> jnp.ndarray:
    """Fused Laplace-corrected KDE on the ring."""
    n_true = int(x.shape[0]) if n_true is None else n_true

    def w(sq, h_, d_):
        scaled = sq / (2.0 * h_ * h_)
        return _phi(sq, h_) * (1.0 + d_ / 2.0 - scaled)

    return _ring_eval(
        x, y, h, w,
        n_true=n_true, mesh=mesh, data_axis=data_axis, pod_axis=pod_axis,
    )


def ring_sdkde(
    x: jnp.ndarray,
    y: jnp.ndarray,
    h,
    *,
    score_h=None,
    n_true: int | None = None,
    mesh: Mesh | None = None,
    data_axis: str = "data",
    pod_axis: str | None = None,
) -> jnp.ndarray:
    """Full distributed SD-KDE: ring score pass → local shift → ring KDE.

    This is the compiled program behind the ``flash_sdkde_*`` dry-run cells:
    the paper's 1M-point workload sharded over a (pod, data, model) mesh.
    """
    n_true = int(x.shape[0]) if n_true is None else n_true
    x_sd = ring_sdkde_shift(
        x, h, score_h=score_h, mesh=mesh,
        data_axis=data_axis, pod_axis=pod_axis,
    )
    return ring_kde(
        x_sd, y, h, n_true=n_true, mesh=mesh,
        data_axis=data_axis, pod_axis=pod_axis,
    )


# ---------------------------------------------------------------------------
# Host-level helpers.
# ---------------------------------------------------------------------------


def shard_points(
    x: jnp.ndarray, mesh: Mesh, axes: Sequence[str]
) -> jnp.ndarray:
    """Pad rows to the ring size and place with a row sharding."""
    ring = 1
    for a in axes:
        ring *= mesh.shape[a]
    n = x.shape[0]
    rem = (-n) % ring
    if rem:
        x = jnp.pad(x, [(0, rem), (0, 0)], constant_values=PAD_VALUE)
    return jax.device_put(x, NamedSharding(mesh, P(tuple(axes), None)))
