"""Operations and bytes of the pairwise kernels, and their roofline share.

A launch evaluates ``pairs`` (query row, train column) pairs.  Its least
time on the chip is the largest of three terms, and the share names the
term that bounds it:

  mxu  2*d flops per pair for the distance Gram, plus 2*(d+1) per pair for
       the score numerator phi @ [X | 1], times the bf16 passes one GEMM of
       the precision tier costs, over the published bf16 peak;
  hbm  bytes of the paper's section 4.1 tile model, over the published HBM
       bandwidth;
  exp  one exponential per pair, over an exp rate measured in the same
       traced run (no exp rate of the chip is published).

The tile model is a copy of ``kernels/tuning.pair_pass_cost``, extended by
the [X | 1] tile the score kernel also streams, and counted over the tiles
a pruned launch visits.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Optional

from kdebench.spec import BENCH_DIR


#: bf16 MXU passes one GEMM costs at each precision tier, on a chip whose
#: MXU multiplies bf16: the f32 tier asks for ``Precision.HIGHEST``, which
#: is lowered as six bf16 passes (XLA's BF16_6X); bf16x2 is the program's
#: four-product sum of hi and lo planes; bf16 is one pass.
MXU_PASSES = {"f32": 6, "bf16x2": 4, "bf16": 1}


def peaks(device_kind: str, path: Path = BENCH_DIR / "peaks.json") -> dict:
    """Published peaks of one device kind; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}; add them with their source")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class LaunchWork:
    """What one kernel launch computed: pairs and the tiles they came in."""

    pairs: float          # pairs evaluated (row x column)
    rows: float           # query rows of the launch
    d: int
    block_m: int
    block_n: int
    score: bool           # score kernel (phi @ [X | 1]) or density kernel
    itemsize: int = 4     # GEMM operand bytes (f32)
    passes: int = 1       # bf16 MXU passes per GEMM (``MXU_PASSES``)

    @property
    def flops(self) -> float:
        """The GEMMs' flops, each counted once whatever the tier."""
        per_pair = 2.0 * self.d + (2.0 * (self.d + 1) if self.score else 0.0)
        return per_pair * self.pairs

    @property
    def mxu_flops(self) -> float:
        """The bf16 flops the MXU runs for them: ``flops`` times the
        passes of the tier."""
        return self.passes * self.flops

    @property
    def hbm_bytes(self) -> float:
        tiles = self.pairs / (self.block_m * self.block_n)
        per_tile = self.itemsize * self.block_n * self.d + 4 * self.block_n
        if self.score:
            per_tile += self.itemsize * self.block_n * (self.d + 1)
        ow = self.d + 1 if self.score else 1
        row_tiles = self.rows / self.block_m
        per_row_block = (self.itemsize * self.block_m * self.d
                         + 4 * self.block_m + 4 * self.block_m * ow)
        return tiles * per_tile + row_tiles * per_row_block


def add(works):
    """Sum of several launches of one kernel (same tiles and kind)."""
    works = list(works)
    if not works:
        return None
    w0 = works[0]
    return dataclasses.replace(w0, pairs=sum(w.pairs for w in works),
                               rows=sum(w.rows for w in works))


def share(work: Optional[LaunchWork], device_s: float, peak: dict,
          exp_per_s: Optional[float]):
    """(percent, bound) of the least time over the kernel's device time;
    None where there is no work or no time to compare."""
    if work is None or not device_s or device_s <= 0 or not work.pairs:
        return None
    terms = {"mxu": work.mxu_flops / peak["mxu_flops_per_s"],
             "hbm": work.hbm_bytes / peak["hbm_bytes_per_s"]}
    if exp_per_s:
        terms["exp"] = work.pairs / exp_per_s
    bound = max(terms, key=terms.get)
    return 100.0 * terms[bound] / device_s, bound
