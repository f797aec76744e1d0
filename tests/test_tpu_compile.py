"""The main-path Pallas kernels compile for a TPU v5e at the paper's widths.

Nothing here runs: each kernel is lowered and compiled by the TPU compiler
for a *described* v5e chip (no chip attached), from ShapeDtypeStructs.
That catches what interpret mode cannot — misaligned tiles, VMEM overuse,
unsupported Mosaic ops — before any chip time is spent.

Shapes: the paper's Table 1 deployment (32768 x 16 train, 4096 queries)
and its §7 workload (1048576 x 16 train, 131072 queries), at the tiles the
autotuner's cost model picks for them (``measure=False``) and, at 1M, the
estimator's default 128 x 512 launch.  Every precision tier; the pruned
kernels at the f32 tier take the packed operands ops builds for them
(``precision.pack_rows`` / ``column_planes``), and the
``kernels.f32_gemm_path`` counter shows they lowered the packed GEMMs —
at D=16 and, at the Table 1 size, at dimensions whose planes do not sit
on whole bf16 sublane tiles (2, 5) or take two MXU passes (22, 32).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.kernels import autotune
from repro.kernels import precision as prec
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


def _packed(kernel, tier):
    """The f32 tier's pruned kernels run their GEMMs packed."""
    return kernel.endswith("pruned") and prec.packs(tier)


def _args(kernel, tier, size, bm, bn, sharding, d=D):
    """ShapeDtypeStructs for one launch, in the launcher's positional order."""
    n, m = SIZES[size]
    op = jnp.float32 if tier == "f32" else jnp.bfloat16
    lo = tier == "bf16x2"

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    score = kernel.startswith("score")
    rows = n if score else m
    if _packed(kernel, tier):
        bf = jnp.bfloat16
        args = [s((rows, 6 * d), bf), s((rows, 1)),
                s((prec.plane_rows(d), n), bf)]
    else:
        args = [s((rows, d), op), s((rows, 1)), s((d, n), op)]
    if score:
        args.append(None if _packed(kernel, tier) else s((n, d + 1), op))
    else:
        args.append(s((1, n)))
    args.append(s((1, 1)))
    if score:
        args += [s((n, d), op), s((d, n), op), s((n, d + 1), op)] if lo \
            else [None, None, None]
    else:
        args += [s((m, d), op), s((d, n), op)] if lo else [None, None]
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
    if kernel.endswith("pruned"):
        kw["packed"] = _packed(kernel, tier)
    _compiles_packed_as_counted(fn, args, kw, _packed(kernel, tier))


def _compiles_packed_as_counted(fn, args, kw, packed):
    def packed_traces():
        return obs.counter("kernels.f32_gemm_path", labels={
            "kernel": fn.__name__, "gemm": "gram", "path": "packed"}).value

    before = packed_traces()
    compiled = fn.lower(*args, **kw).compile()
    assert _kernel_ops(compiled.as_text()) == {fn.__name__}
    assert (packed_traces() > before) == packed


def _kernel_ops(hlo: str) -> set:
    """Names of the compiled program's Mosaic kernel operations, without
    their ``.N`` suffix: the device trace names each launch by them, and
    the benchmark's kernel readers find a kernel by that name, also where
    its row groups run in a loop."""
    return {line.split(" = ", 1)[0].strip().lstrip("%").rsplit(".", 1)[0]
            for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line}


@pytest.mark.parametrize("d", [2, 5, 22, 32])
@pytest.mark.parametrize("kernel", ["kde_pruned", "score_pruned"])
def test_packed_kernels_compile_for_v5e_at_any_d(kernel, d, one_chip):
    bm, bn = 128, 512
    args = _args(kernel, "f32", "32k", bm, bn, one_chip, d=d)
    fn = {"kde_pruned": flash_kde_pallas_pruned,
          "score_pruned": flash_score_pallas_pruned}[kernel]
    kw = dict(block_m=bm, block_n=bn, interpret=False, packed=True,
              max_visits=_visits(SIZES["32k"][0], bn))
    _compiles_packed_as_counted(fn, args, kw, True)


#: One chip's share of the four-chip cell: 2^19 rows of a 2^21-point
#: layout against all of its columns, and a quarter of 131072 queries.
SHARD_ROWS, SHARD_COLS, SHARD_QUERIES = 1 << 19, 1 << 21, 1 << 15


@pytest.mark.parametrize("kernel", ["kde_pruned", "score_pruned"])
def test_pruned_kernels_compile_for_one_chips_row_range(kernel, one_chip):
    """The ``ring`` backend's launches: a row range against a whole column
    layout, the score kernel taking the columns' norms apart from the
    rows'."""
    bm, bn, d = 128, 512, D
    score = kernel == "score_pruned"
    rows = SHARD_ROWS if score else SHARD_QUERIES
    visits = _visits(SHARD_COLS, bn)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf = jnp.bfloat16
    args = [s((rows // bm,), jnp.int32), s((rows // bm, visits), jnp.int32),
            s((rows, 6 * d), bf), s((rows, 1)),
            s((prec.plane_rows(d), SHARD_COLS), bf)]
    kw = dict(block_m=bm, block_n=bn, interpret=False, packed=True,
              max_visits=visits)
    if score:
        args += [None, s((1, 1))]
        kw["nrm_cols"] = s((1, SHARD_COLS))
        fn = flash_score_pallas_pruned
    else:
        args += [s((1, SHARD_COLS)), s((1, 1))]
        fn = flash_kde_pallas_pruned
    _compiles_packed_as_counted(fn, args, kw, True)


#: The 1M cell's score launch: every column tile of its layout (2165 at
#: 128 x 512), padded to a multiple of the slots, as row groups of 30
#: row tiles.
CELL_VISITS = 2165


@pytest.mark.parametrize("tier,tiles,visits,slots", [
    ("f32", (128, 512), CELL_VISITS, "most"),
    ("bf16x2", (512, 4096), 128, 1),
], ids=["f32-128x512", "bf16x2-512x4096"])
def test_score_kernel_compiles_at_its_slots_per_step(tier, tiles, visits,
                                                     slots, one_chip):
    """The score kernel runs the visit slots per grid step that its launch
    shape gives (``flash_pruned.score_slots``): the most at the 1M cell's
    128 x 512 packed launch, one at bf16x2's 512 x 4096 tiles."""
    from repro.kernels import flash_pruned

    bm, bn = tiles
    got = flash_pruned.score_slots(visits, block_m=bm, block_n=bn, d=D,
                                   itemsize=prec.operand_bytes(tier))
    assert got == (flash_pruned.SCORE_SLOTS_MAX if slots == "most"
                   else slots)
    packed = _packed("score_pruned", tier)
    args = _args("score_pruned", tier, "1m", bm, bn, one_chip)
    n = SIZES["1m"][0]
    args[1] = jax.ShapeDtypeStruct((n // bm, visits), jnp.int32,
                                   sharding=one_chip)
    kw = dict(block_m=bm, block_n=bn, interpret=False, packed=packed,
              max_visits=visits)
    _compiles_packed_as_counted(flash_score_pallas_pruned, args, kw, packed)
