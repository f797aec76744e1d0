"""Roofline arithmetic on hand-computed shapes."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from kdebench import roofline  # noqa: E402

PEAK = {"mxu_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_peaks_come_from_the_table_and_an_unknown_device_is_an_error():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["mxu_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_density_kernel_work_on_one_dense_tile_grid():
    # 256 rows x 1024 columns, d=16, tiles 128 x 512: 2 x 2 tiles
    w = roofline.LaunchWork(pairs=256 * 1024, rows=256, d=16, block_m=128,
                            block_n=512, score=False)
    assert w.flops == 2 * 16 * 256 * 1024
    per_tile = 4 * 512 * 16 + 4 * 512                  # columns + norms
    per_row_block = 4 * 128 * 16 + 4 * 128 + 4 * 128   # rows, norms, out
    assert w.hbm_bytes == 4 * per_tile + 2 * per_row_block


def test_score_kernel_also_streams_the_augmented_tile():
    w = roofline.LaunchWork(pairs=128 * 512, rows=128, d=16, block_m=128,
                            block_n=512, score=True)
    assert w.flops == (2 * 16 + 2 * 17) * 128 * 512
    per_tile = 4 * 512 * 16 + 4 * 512 + 4 * 512 * 17
    per_row_block = 4 * 128 * 16 + 4 * 128 + 4 * 128 * 17
    assert w.hbm_bytes == per_tile + per_row_block


def test_share_names_the_bound_that_sets_the_least_time():
    w = roofline.LaunchWork(pairs=1e12, rows=1e6, d=16, block_m=128,
                            block_n=512, score=True)
    t_mxu = 66e12 / 197e12                            # 0.34 s
    tiles = 1e12 / (128 * 512)
    t_hbm = (tiles * (4 * 512 * 16 + 4 * 512 + 4 * 512 * 17)
             + 1e6 / 128 * (4 * 128 * 16 + 4 * 128 + 4 * 128 * 17)) / 819e9
    assert t_hbm > t_mxu                              # 1.30 s
    # no exp rate: the tiles' bytes bound it
    pct, bound = roofline.share(w, 10.0, PEAK, None)
    assert bound == "hbm" and pct == pytest.approx(100 * t_hbm / 10.0)
    # an exp rate of 5e11/s: 2 s of exponentials bound it
    pct, bound = roofline.share(w, 10.0, PEAK, 5e11)
    assert bound == "exp" and pct == pytest.approx(20.0)
    # a kernel at the bound reads 100%
    pct, _ = roofline.share(w, 2.0, PEAK, 5e11)
    assert pct == pytest.approx(100.0)


def test_nothing_to_read_is_none_not_zero():
    w = roofline.LaunchWork(pairs=0, rows=0, d=16, block_m=128,
                            block_n=512, score=False)
    assert roofline.share(w, 1.0, PEAK, 1e12) is None
    assert roofline.share(None, 1.0, PEAK, 1e12) is None
    w1 = roofline.LaunchWork(pairs=10, rows=1, d=16, block_m=128,
                             block_n=512, score=False)
    assert roofline.share(w1, 0.0, PEAK, 1e12) is None
    assert roofline.add([]) is None


def test_launches_add_up():
    a = roofline.LaunchWork(pairs=100, rows=10, d=16, block_m=128,
                            block_n=512, score=False)
    b = roofline.LaunchWork(pairs=50, rows=5, d=16, block_m=128,
                            block_n=512, score=False)
    s = roofline.add([a, b])
    assert s.pairs == 150 and s.rows == 15
    assert s.hbm_bytes == pytest.approx(a.hbm_bytes + b.hbm_bytes)


def test_the_f32_tier_costs_six_mxu_passes():
    w = roofline.LaunchWork(pairs=1e12, rows=1e6, d=16, block_m=128,
                            block_n=512, score=True,
                            passes=roofline.MXU_PASSES["f32"])
    assert w.flops == 66e12                            # counted once
    assert w.mxu_flops == 6 * 66e12
    # 6 x 66e12 / 197e12 = 2.01 s of MXU passes outlast 2 s of exponentials
    pct, bound = roofline.share(w, 10.0, PEAK, 5e11)
    assert bound == "mxu"
    assert pct == pytest.approx(100 * 6 * 66e12 / 197e12 / 10.0)
    assert roofline.MXU_PASSES["bf16"] == 1
