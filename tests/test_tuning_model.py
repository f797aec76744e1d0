"""Kernel performance model (kernels/tuning.py) sanity + paper anchors."""

import pytest

from repro.kernels.tuning import (
    VMEM_BUDGET,
    best_blocks,
    pair_pass_cost,
    sdkde_device_cost,
    sweep_blocks,
)


def test_byte_model_anchors_paper_coefficient():
    """§4.1: Bytes₁₆(k) ≈ 1.13 k² at the paper's (64, 1024) blocks.

    Our ledger amortizes row-tile loads over the column sweep (the paper
    re-counts them per tile), so we anchor slightly below: ~1.07 k².
    """
    c = pair_pass_cost(32768, 32768, 16, block_m=64, block_n=1024,
                       out_width=17)
    coef = c.hbm_bytes / 32768**2
    assert 1.0 < coef < 1.2, coef


def test_flops_match_paper_model():
    from repro.analysis.flops import sdkde_flops

    n, m, d = 32768, 4096, 16
    s = pair_pass_cost(n, n, d, block_m=64, block_n=1024, out_width=d + 1)
    k = pair_pass_cost(m, n, d, block_m=64, block_n=1024, out_width=1)
    total = (s.mxu_flops + s.exp_count * 8 + s.vpu_flops
             + k.mxu_flops + k.exp_count * 8 + k.vpu_flops)
    # within 15% of the paper's aggregate (scalar-op bookkeeping differs)
    paper = sdkde_flops(n, d, n_test=m)
    assert abs(total - paper) / paper < 0.15, (total, paper)


def test_sweep_respects_vmem_budget():
    for c in sweep_blocks(65536, 65536, 16, out_width=17):
        assert c.vmem_bytes <= VMEM_BUDGET


def test_bigger_row_blocks_cut_hbm():
    small = pair_pass_cost(65536, 65536, 16, block_m=64, block_n=1024)
    big = pair_pass_cost(65536, 65536, 16, block_m=1024, block_n=1024)
    assert big.hbm_bytes < small.hbm_bytes / 4


def test_device_cost_uses_block_partition():
    """Per-device pairs must be n²/chips (the §Perf iteration-2 fix)."""
    s, k = sdkde_device_cost(1048576, 131072, 16, chips=256)
    assert s.exp_count == pytest.approx(1048576**2 / 256)
    assert k.exp_count == pytest.approx(131072 * 1048576 / 256)


def test_kernel_path_is_vpu_bound_at_1m():
    """The §Perf conclusion: on v5e the flash kernel is exp-bound."""
    s, k = sdkde_device_cost(1048576, 131072, 16, chips=256,
                             block_m=1024, block_n=2048)
    assert s.bound == "vpu"
    assert s.t_vpu > 3 * s.t_hbm


def test_best_blocks_returns_feasible_minimum():
    best = best_blocks(65536, 65536, 16, out_width=17)
    assert best.vmem_bytes <= VMEM_BUDGET
    assert best.step_time <= sweep_blocks(65536, 65536, 16,
                                          out_width=17)[-1].step_time
