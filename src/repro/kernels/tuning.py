"""Flash-kernel performance model + block-size hillclimb (paper §4.1/§6.2 on TPU).

On the XLA-GEMM path the φ matrix spills to HBM between the Gram dot, the
exponential, and the S1 GEMM.  The Pallas kernels keep φ in VMEM — their
HBM traffic is the PAPER'S tile model (§4.1), which this module evaluates
per (block_m, block_n) under the v5e VMEM budget, exactly the
launch-parameter hillclimb of §6.2 with TPU constraints instead of
warps/stages.

Compute is a TWO-resource model — the TPU analogue of the paper's
SFU-budget accounting (1 exp = 8 FP32 flops on the A6000's 128:16 ratio):

    t_mxu = GEMM flops / 197 TFLOP/s        (systolic array)
    t_vpu = (exp ops × EXP_VPU_OPS + scalar flops) / VPU throughput
    t_hbm = tile-model bytes / 819 GB/s

    step  ≥ max(t_mxu, t_vpu, t_hbm)

Validated against the paper's own coefficients in tests/test_analysis.py
(FLOPs 81.5 k², bytes 1.13 k² at the paper's blocks).
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Optional, Tuple

# v5e per-chip constants
MXU_FLOPS = 197e12
HBM_BW = 819e9
VMEM_BYTES = 16 * 2**20
VMEM_BUDGET = 12 * 2**20           # headroom for double buffering
# VPU: 8 sublanes × 128 lanes × 2 issue × ~940 MHz  ≈ 1.9e12 elementwise op/s
VPU_OPS = 1.9e12
EXP_VPU_OPS = 10                   # ~ops per transcendental on the VPU


@dataclasses.dataclass(frozen=True)
class KernelCost:
    block_m: int
    block_n: int
    hbm_bytes: float
    mxu_flops: float
    exp_count: float
    vpu_flops: float               # non-exp elementwise work
    vmem_bytes: int

    @property
    def t_hbm(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_mxu(self) -> float:
        return self.mxu_flops / MXU_FLOPS

    @property
    def t_vpu(self) -> float:
        return (self.exp_count * EXP_VPU_OPS + self.vpu_flops) / VPU_OPS

    @property
    def step_time(self) -> float:
        return max(self.t_hbm, self.t_mxu, self.t_vpu)

    @property
    def bound(self) -> str:
        terms = {"hbm": self.t_hbm, "mxu": self.t_mxu, "vpu": self.t_vpu}
        return max(terms, key=terms.get)


def pair_pass_cost(
    rows: int, cols: int, d: int, *, block_m: int, block_n: int,
    out_width: Optional[int] = None, itemsize: int = 4,
) -> KernelCost:
    """One streaming pairwise pass (score OR kde OR laplace kernel).

    ``rows`` — resident row tile set (queries / eval points, per device);
    ``cols`` — streamed column points (per device, over the full ring);
    ``out_width`` — accumulator width (d+1 for score S1aug, 1 for KDE sums);
    ``itemsize`` — bytes/element of the GEMM *operands* (4 for f32, 2 for
    bf16, 4 for the two-plane bf16x2 split — kernels/precision.py).  Norms,
    the φ tile, and the accumulator are f32 at every tier.

    HBM per (row-tile × col-tile), the paper's §4.1 ledger: row tile loaded
    once per row block (amortized over the column sweep), column tile
    streamed per tile, partial output written once per row block.
    """
    ow = out_width if out_width is not None else 1
    m_tiles = -(-rows // block_m)
    n_tiles = -(-cols // block_n)
    per_tile = (itemsize * block_n * d               # streamed cols
                + 4 * block_n)                       # + f32 norms
    per_row_block = (itemsize * block_m * d          # row tile
                     + 4 * block_m                   # + f32 norms
                     + 4 * block_m * ow)             # accumulator writeback
    hbm = m_tiles * n_tiles * per_tile + m_tiles * per_row_block

    pairs = float(rows) * cols
    gram = 2.0 * d * pairs                           # MXU
    accum = 2.0 * ow * pairs if ow > 1 else 0.0      # φ @ [X|1] MXU GEMM
    exps = pairs
    scalar = 4.0 * pairs + (2.0 * pairs if ow == 1 else 0.0)

    # VMEM working set: matches ops.vmem_tile_bytes (operands at itemsize,
    # f32 norms / φ tile / accumulator at 4 bytes).  The xaug column tile
    # exists only on the score path (ow > 1); KDE/Laplace accumulate a
    # single column, so budgeting the (block_n, d+1) tile and a (d+1)-wide
    # accumulator for them would shrink the feasible tile space for no
    # reason.
    vmem = itemsize * (
        block_m * d + d * block_n + (block_n * (d + 1) if ow > 1 else 0)
    ) + 4 * (
        block_m + block_n + block_m * block_n + block_m * ow
    )
    return KernelCost(block_m, block_n, hbm, gram + accum, exps, scalar, vmem)


def sdkde_device_cost(
    n: int, m: int, d: int, *, chips: int = 256, model_shards: int = 16,
    block_m: int = 1024, block_n: int = 2048,
) -> Tuple[KernelCost, KernelCost]:
    """(score pass, kde pass) per-device costs under the block-partitioned
    2-D decomposition (distributed/ring2d.py): eval rows over ``model``
    (n/16, m/16), train columns over the remaining chips/16 shards —
    n²/chips pairs per device, no redundancy."""
    col_shards = max(chips // model_shards, 1)
    score = pair_pass_cost(n // model_shards, n // col_shards, d,
                           block_m=block_m, block_n=block_n, out_width=d + 1)
    kde = pair_pass_cost(m // model_shards, n // col_shards, d,
                         block_m=block_m, block_n=block_n, out_width=1)
    return score, kde


def sweep_blocks(
    rows: int, cols: int, d: int, *,
    block_ms: Iterable[int] = (64, 128, 256, 512, 1024, 2048, 4096),
    block_ns: Iterable[int] = (256, 512, 1024, 2048, 4096),
    out_width: Optional[int] = None, itemsize: int = 4,
):
    """The §6.2 hillclimb: every launch config under the VMEM budget,
    sorted by modeled step time.  (kernels/autotune.py layers padding-aware,
    precision-derated costs and a winner cache on top of this sweep.)"""
    rows_aligned = []
    for bm in block_ms:
        for bn in block_ns:
            c = pair_pass_cost(rows, cols, d, block_m=bm, block_n=bn,
                               out_width=out_width, itemsize=itemsize)
            if c.vmem_bytes <= VMEM_BUDGET:
                rows_aligned.append(c)
    return sorted(rows_aligned, key=lambda c: c.step_time)


def best_blocks(rows: int, cols: int, d: int, **kw) -> KernelCost:
    return sweep_blocks(rows, cols, d, **kw)[0]
