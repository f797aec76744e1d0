"""Row-sharded SD-KDE on the chips of one host, through the Flash kernels.

A train set fits every chip many times over (2M × 16 f32 is 128 MB against
a v5e's 16 GB): what more chips buy is time on the O(n²·d) score pass.  So
the rows are sharded, every chip holds every column, and nothing rotates.

  score pass  the first chip builds the cluster index, layout and operands
              of all n points once, as the one-chip path does; each chip
              then gets one of ``chips`` equal contiguous ranges of the
              cluster-ordered rows and every column, and runs the bounds
              prepass and the pruned score kernel for its range.
  exchange    the score statistics come back to the first chip, which
              shifts the points (x_sd) and prepares them for evaluation as
              the one-chip path's evaluate does.
  evaluate    every chip gets all the debiased columns and one equal
              contiguous range of the queries' cluster layout, whose length
              depends on the query count alone (``_query_rows``: no draw
              compiles new programs; not bucketed to a power of two, which
              would leave the last chips only sentinel tiles); the sums
              come back in request order.

The pruned passes compact their visit lists on the host, per chip, with
data-dependent extents, so each chip gets its own launches from the host,
dispatched asynchronously.  Everything a chip's programs read is put on
that chip before any kernel runs, and what the host makes for a launch
(visit lists, scalars) is made there (``jax.default_device``): a copy from
the first chip would wait behind the first chip's kernel, and the chips
would run one after another.  Each row's answer comes from the same row
tile, visit list and column order as on one chip, so it is the one-chip
``pallas`` path's answer, bit for bit; with one device this is that path,
launch for launch but for the query layout's length.  The dense passes
(``prune="off"``, or below the ``"auto"`` threshold) shard the same way
without the layout.

Spans and counters (``repro.obs``):
  ``distributed.shard.pass {kind, chips, rows, cols}``  one sharded pass;
  ``distributed.shard.dispatch {chip, rows}``  one chip's visit lists and
      launch (``rows``: its real rows);
  ``distributed.shard.gather {bytes}``  an exchange between chips, waited
      for: every chip's part to it, or every chip's results to the first;
  histogram ``distributed.shard.transfer_bytes``  one observation per
      array moved between chips.
The host steps keep their ``kernels.prune.*`` spans, under a
``kernels.prune.pass`` span that closes once the last chip's launch is
dispatched (the wait for the chips is the gather's), and each launch span
(``kernels.pruned_*``, ``kernels.dense_*``) names its ``chip``.

The chips are every local device, ``jax.devices()``: a process that should
use fewer has to see fewer.
"""

from __future__ import annotations

import math
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.bandwidth import gaussian_norm_const
from repro.kernels import ops, spatial
from repro.kernels import precision as prec

#: Histogram of the bytes of each array moved between chips.
TRANSFER_BYTES = "distributed.shard.transfer_bytes"


def _real_rows(real: jnp.ndarray, chips: int, total: int) -> List[int]:
    """Non-sentinel rows in each chip's equal range (one host fetch)."""
    if chips == 1:
        return [total]
    per = spatial.to_host(jnp.sum(real.reshape(chips, -1), axis=1))
    return [int(c) for c in per]


def _on(a, dev):
    """``a`` on ``dev``; a move between chips is observed in
    :data:`TRANSFER_BYTES`.  Anything but a device array passes."""
    if not isinstance(a, jax.Array) or a.devices() == {dev}:
        return a
    obs.histogram(TRANSFER_BYTES, "bytes of one array moved between chips "
                  "by the sharded passes", lo=1, hi=1e10).observe(a.nbytes)
    return jax.device_put(a, dev)


def _moved_bytes(tree, dev) -> int:
    return sum(a.nbytes for a in jax.tree.leaves(tree)
               if isinstance(a, jax.Array) and a.devices() != {dev})


def _place(parts, devs) -> list:
    """Each chip's part (a pytree of arrays, made on the first chip) on its
    chip, waited for: the first chip is still idle, so nothing queues the
    copies behind its kernels."""
    nbytes = sum(_moved_bytes(p, d) for p, d in zip(parts, devs))
    with obs.span("distributed.shard.gather", bytes=nbytes):
        return jax.block_until_ready(
            [jax.tree.map(lambda a, d=dev: _on(a, d), p)
             for p, dev in zip(parts, devs)])


def _collect(parts, dev) -> jnp.ndarray:
    """Every chip's part on ``dev``, concatenated in chip order, waited
    for."""
    nbytes = sum(_moved_bytes(p, dev) for p in parts)
    with obs.span("distributed.shard.gather", bytes=nbytes):
        moved = [_on(p, dev) for p in parts]
        out = moved[0] if len(moved) == 1 else jnp.concatenate(moved)
        return out.block_until_ready()


def _split(tree, chips: int) -> list:
    """``chips`` equal row ranges of every array of ``tree`` (None stays)."""
    leaves, treedef = jax.tree.flatten(tree)
    parts = [jnp.split(a, chips) for a in leaves]
    return [jax.tree.unflatten(treedef, [p[k] for p in parts])
            for k in range(chips)]


# ---------------------------------------------------------------------------
# Score pass.
# ---------------------------------------------------------------------------


def _pruned_score(x32, h, eps, devs, *, precision, block_m, block_n,
                  interpret, seed):
    n, d = x32.shape
    chips = len(devs)
    with ops._prune_pass("score", n, n):
        with obs.span("kernels.prune.index"):
            index = spatial.build_index(x32, seed=seed)
        with obs.span("kernels.prune.layout"):
            layout = spatial.cluster_layout(
                x32, index.labels, block_n,
                total_multiple=math.lcm(block_m * chips, block_n))
            real = _real_rows(layout.real, chips, n)
        prep = ops.score_prep(layout.points, layout.real, n=n,
                              precision=precision, block_n=block_n)
        if chips > 1:
            # each chip: its range of the rows against every column
            rows = _split((prep.x_ops, prep.nrm, prep.xrec), chips)
            prep = prep._replace(nrm_cols=prep.nrm.reshape(1, -1))
            preps = _place([prep._replace(x_ops=x_ops, nrm=nrm, xrec=xrec)
                            for x_ops, nrm, xrec in rows], devs)
        else:
            preps = [prep]
        tms = []
        for prep, dev in zip(preps, devs):
            with jax.default_device(dev):
                tms.append(ops.score_tile_map(prep, h, eps,
                                              block_m=block_m))
        parts = []
        for k, (prep, tm, dev) in enumerate(zip(preps, tms, devs)):
            with obs.span("distributed.shard.dispatch", chip=k,
                          rows=real[k]), jax.default_device(dev):
                parts.append(ops.score_launch(
                    prep, tm, h, eps, n=n, real_rows=real[k],
                    precision=precision, block_m=block_m, block_n=block_n,
                    interpret=interpret, chip=k))
    s1aug = _collect(parts, devs[0])
    with obs.span("kernels.prune.gather"):
        rows = s1aug[layout.slots]
    return rows[:, d], rows[:, :d]


def _dense_score(x32, h, devs, *, precision, block_m, block_n, interpret):
    n, d = x32.shape
    chips = len(devs)
    xp = ops._pad_to(x32, math.lcm(block_m * chips, block_n))
    per = xp.shape[0] // chips
    parts = []
    for k, (xk,) in enumerate(_place([(xp,)] * chips, devs)):
        real = min(max(n - k * per, 0), per)
        with obs.span("distributed.shard.dispatch", chip=k, rows=real), \
                obs.span("kernels.dense_score", rows=real, cols=n, chip=k), \
                jax.default_device(devs[k]):
            parts.append(ops.dense_score_rows(
                xk, h, rows=(k * per, (k + 1) * per), precision=precision,
                block_m=block_m, block_n=block_n, interpret=interpret))
    s1aug = _collect(parts, devs[0])[:n]
    return s1aug[:, d], s1aug[:, :d]


def flash_sdkde_shift(
    x: jnp.ndarray,
    h,
    *,
    score_h=None,
    precision: str = "f32",
    block_m="auto",
    block_n="auto",
    interpret: Optional[bool] = None,
    prune: ops.PruneArg = "auto",
    seed: int = 0,
) -> jnp.ndarray:
    """Debiased samples x^SD = x + (h²/2)·ŝ(x), the score pass's rows
    sharded over every local device; the answer lies on the first.
    Tiles, tier and pruning resolve as ``ops.flash_sdkde_shift`` resolves
    them, which serves a traced call."""
    if ops._traced(x):
        return ops.flash_sdkde_shift(
            x, h, score_h=score_h, precision=precision, block_m=block_m,
            block_n=block_n, interpret=interpret, prune=prune, seed=seed)
    prec.validate(precision)
    devs = jax.devices()
    n, d = x.shape
    block_m, block_n = ops._resolve(
        block_m, block_n, n, n, d, out_width=d + 1, precision=precision,
        interpret=interpret, pruned=prune != "off")
    eps = ops.resolve_prune(prune, n, block_n)
    h = float(h)
    sh = h if score_h is None else float(score_h)
    x32 = jax.device_put(jnp.asarray(x, jnp.float32), devs[0])
    knobs = dict(precision=precision, block_m=block_m, block_n=block_n,
                 interpret=interpret)
    with obs.span("distributed.shard.pass", kind="score", chips=len(devs),
                  rows=n, cols=n):
        if eps is None:
            s0, s1 = _dense_score(x32, sh, devs, **knobs)
        else:
            s0, s1 = _pruned_score(x32, sh, eps, devs, seed=seed, **knobs)
    return ops._apply_score_shift(x32, s0, s1, h, sh)


# ---------------------------------------------------------------------------
# Density pass.
# ---------------------------------------------------------------------------


def _query_rows(m: int, k: int, block_m: int, chips: int) -> int:
    """Rows of the query layout: ``m`` queries in ``k`` clusters, each
    cluster's rows padded to whole tiles, take at most m + k·(block_m − 1)
    rows, whatever the draw, so every draw of ``m`` queries compiles the
    same programs; rounded up to equal ranges of whole tiles per chip."""
    rows = m + min(k, m) * (block_m - 1)
    return -(-rows // (block_m * chips)) * block_m * chips


def _pruned_kde(x32, y32, h, eps, devs, *, precision, block_m, block_n,
                interpret, seed):
    m = y32.shape[0]
    chips = len(devs)
    with ops._prune_pass("kde", m, x32.shape[0]):
        with obs.span("kernels.prune.columns"):
            cols = ops.prepare_train_columns(
                x32, block_n=block_n, precision=precision, clustered=True,
                seed=seed)
        with obs.span("kernels.prune.layout"):
            labels = spatial.assign(y32, cols.index)
            qlayout = spatial.cluster_layout(
                y32, labels, block_m, total_multiple=_query_rows(
                    m, cols.index.centroids.shape[0], block_m, chips))
            real = _real_rows(qlayout.real, chips, m)
        q = ops.eval_rows(qlayout.points, precision)
        if chips > 1:
            # every chip: all the debiased columns and its range of the
            # queries
            placed = _place([(cols._replace(index=None), qk)
                             for qk in _split(q, chips)], devs)
        else:
            placed = [(cols, q)]
        tms = []
        for (ck, qk), dev in zip(placed, devs):
            with jax.default_device(dev):
                tms.append(ops.eval_tile_map(qk, ck, h, eps,
                                             block_m=block_m, kind="kde"))
        parts = []
        for k, ((ck, qk), tm, dev) in enumerate(zip(placed, tms, devs)):
            with obs.span("distributed.shard.dispatch", chip=k,
                          rows=real[k]), jax.default_device(dev):
                parts.append(ops.eval_launch(
                    qk, tm, ck, h, eps, rows_key=m, real_rows=real[k],
                    precision=precision, block_m=block_m, block_n=block_n,
                    interpret=interpret, laplace=False, chip=k))
    sums = _collect(parts, devs[0])
    with obs.span("kernels.prune.gather"):
        return sums[qlayout.slots, 0]


def _dense_kde(x32, y32, h, devs, *, precision, block_m, block_n,
               interpret):
    n = x32.shape[0]
    m = y32.shape[0]
    chips = len(devs)
    yp = ops._pad_to(y32, chips)
    per = yp.shape[0] // chips
    parts = []
    for k, (xk, yk) in enumerate(_place(
            [(x32, yr) for yr in _split(yp, chips)], devs)):
        real = min(max(m - k * per, 0), per)
        with obs.span("distributed.shard.dispatch", chip=k, rows=real), \
                obs.span("kernels.dense_eval", rows=real, cols=n, kind="kde",
                         chip=k), \
                jax.default_device(devs[k]):
            parts.append(ops._flash_eval_dense(
                xk, yk, h, precision=precision, block_m=block_m,
                block_n=block_n, interpret=interpret))
    return _collect(parts, devs[0])[:m]


def flash_kde(
    x: jnp.ndarray,
    y: jnp.ndarray,
    h,
    *,
    precision: str = "f32",
    block_m="auto",
    block_n="auto",
    interpret: Optional[bool] = None,
    prune: ops.PruneArg = "auto",
    seed: int = 0,
) -> jnp.ndarray:
    """Normalized Gaussian KDE densities at ``y`` (train set ``x``), the
    query rows sharded over every local device and every chip holding all
    of ``x``; the answer lies on the first.  Tiles, tier and pruning
    resolve as ``ops.flash_kde`` resolves them, which serves a traced
    call."""
    if ops._traced(x, y):
        return ops.flash_kde(x, y, h, precision=precision, block_m=block_m,
                             block_n=block_n, interpret=interpret,
                             prune=prune, seed=seed)
    prec.validate(precision)
    devs = jax.devices()
    n, d = x.shape
    m = y.shape[0]
    block_m, block_n = ops._resolve(
        block_m, block_n, m, n, d, out_width=1, precision=precision,
        interpret=interpret, pruned=prune != "off")
    eps = ops.resolve_prune(prune, n, block_n)
    h = float(h)
    x32 = jax.device_put(jnp.asarray(x, jnp.float32), devs[0])
    y32 = jax.device_put(jnp.asarray(y, jnp.float32), devs[0])
    knobs = dict(precision=precision, block_m=block_m, block_n=block_n,
                 interpret=interpret)
    with obs.span("distributed.shard.pass", kind="kde", chips=len(devs),
                  rows=m, cols=n):
        if eps is None:
            return _dense_kde(x32, y32, h, devs, **knobs)
        sums = _pruned_kde(x32, y32, h, eps, devs, seed=seed, **knobs)
    h = jnp.asarray(h, jnp.float32)
    return sums / (n * gaussian_norm_const(d, 1.0) * h**d)


__all__ = ["TRANSFER_BYTES", "flash_sdkde_shift", "flash_kde"]
