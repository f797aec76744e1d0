"""The main-path Pallas kernels compile for a TPU v5e at the paper's widths.

Nothing here runs: each kernel is lowered and compiled by the TPU compiler
for a *described* v5e chip (no chip attached), from ShapeDtypeStructs.
That catches what interpret mode cannot — misaligned tiles, VMEM overuse,
unsupported Mosaic ops — before any chip time is spent.

Shapes: the paper's Table 1 deployment (32768 x 16 train, 4096 queries)
and its §7 workload (1048576 x 16 train, 131072 queries), at the tiles the
autotuner's cost model picks for them (``measure=False``) and, at 1M, the
estimator's default 128 x 512 launch.  Every precision tier.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels.flash_kde import flash_kde_pallas
from repro.kernels.flash_pruned import (flash_kde_pallas_pruned,
                                        flash_score_pallas_pruned)
from repro.kernels.flash_score import flash_score_pallas

D = 16
SIZES = {"32k": (32768, 4096), "1m": (1048576, 131072)}
CASES = [("32k", "auto"), ("1m", "auto"), ("1m", (128, 512))]
TIERS = ("f32", "bf16", "bf16x2")
KERNELS = ("kde", "score", "kde_pruned", "score_pruned")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _tiles(kernel, size, tiles):
    n, m = SIZES[size]
    if tiles != "auto":
        return tiles
    score = kernel.startswith("score")
    return autotune.resolve_blocks(
        "auto", "auto", rows=n if score else m, cols=n, d=D,
        out_width=D + 1 if score else 1, measure=False, pruned=True)


def _visits(n, bn):
    """A wide visit extent (every tile up to 128): at 1M its tile map
    outgrows SMEM, so these launches also compile the row-group split."""
    return min(n // bn, 128)


def _args(kernel, tier, size, bm, bn, sharding):
    """ShapeDtypeStructs for one launch, in the launcher's positional order."""
    n, m = SIZES[size]
    op = jnp.float32 if tier == "f32" else jnp.bfloat16
    lo = tier == "bf16x2"

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    score = kernel.startswith("score")
    rows = n if score else m
    args = [s((rows, D), op), s((rows, 1)), s((D, n), op)]
    if score:
        args.append(s((n, D + 1), op))
    else:
        args.append(s((1, n)))
    args.append(s((1, 1)))
    if score:
        args += [s((n, D), op), s((D, n), op), s((n, D + 1), op)] if lo \
            else [None, None, None]
    else:
        args += [s((m, D), op), s((D, n), op)] if lo else [None, None]
    if kernel.endswith("pruned"):
        visits = _visits(n, bn)
        args = [s((rows // bm,), jnp.int32),
                s((rows // bm, visits), jnp.int32)] + args
    return args


@pytest.mark.parametrize("size,tiles", CASES, ids=lambda c: str(c))
@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(kernel, tier, size, tiles, one_chip):
    bm, bn = _tiles(kernel, size, tiles)
    args = _args(kernel, tier, size, bm, bn, one_chip)
    kw = dict(block_m=bm, block_n=bn, interpret=False)
    if kernel.endswith("pruned"):
        kw["max_visits"] = _visits(SIZES[size][0], bn)
    fn = {"kde": flash_kde_pallas, "score": flash_score_pallas,
          "kde_pruned": flash_kde_pallas_pruned,
          "score_pruned": flash_score_pallas_pruned}[kernel]
    compiled = fn.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
