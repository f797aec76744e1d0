"""Trace reduction: busy union, idle share, kernel time by name, gaps.

``bench/testdata/sdkde_32k_trace.xplane.pb`` was recorded on a TPU v5e
with the harness's profiler options: a 32768-point SD-KDE fit and a
4096-query evaluation inside ``bench.window``, then three launches of the
exp probe."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from kdebench import probe, xtrace  # noqa: E402

TRACE = BENCH / "testdata" / "sdkde_32k_trace.xplane.pb"


def test_op_and_module_names():
    assert xtrace.op_name("%flash_kde_pallas_pruned.1 = f32[8192,1]{1,0} "
                          "custom-call(s32[64] %a)") == \
        "flash_kde_pallas_pruned"
    assert xtrace.op_name("%fusion.13 = f32[90] fusion(...)") == "fusion"
    assert xtrace.op_name("%sort = (u32[8]) sort(...)") == "sort"
    assert xtrace.module_name("jit_tile_map(14588659804861294645)") == \
        "jit_tile_map"


def test_union_of_overlapping_intervals():
    assert xtrace.union_length([]) == 0.0
    assert xtrace.union_length([(0, 10), (5, 15), (20, 30)]) == 25.0
    assert xtrace.union_length([(0, 10), (2, 3), (10, 12)]) == 12.0
    assert xtrace.merged([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]


def _hand_trace():
    # window 0..100 ns; a kernel 10..40, an op 30..50 (overlapping), a
    # kernel 80..90 and one op outside the window
    dev = [[("flash_score_pallas_pruned", "jit_score", 10, 40),
            ("fusion", "jit_x", 30, 50),
            ("flash_kde_pallas_pruned", "jit_kde", 80, 90),
            ("fusion", "jit_x", 150, 160)]]
    host = [("python", "bench.window", 0, 100),
            ("python", "bench.fit", 0, 60),
            ("python", "bench.evaluate", 60, 100),
            ("python", "flash_kde_pruned", 62, 75)]
    return xtrace.Trace(dev, host)


def test_busy_idle_and_kernel_time_by_name():
    tr = _hand_trace()
    assert tr.window_s == pytest.approx(100e-9)
    assert tr.busy_s == pytest.approx(50e-9)        # 10..50 and 80..90
    assert tr.idle_share == pytest.approx(0.5)
    assert tr.kernel_s(["flash_score"]) == pytest.approx(30e-9)
    assert tr.kernel_s(["flash_kde"]) == pytest.approx(10e-9)
    assert tr.launches("fusion") == [pytest.approx(20e-9),
                                     pytest.approx(10e-9)]


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    gaps = dict(map(tuple, _hand_trace().idle_gaps()))
    # three gaps, each named at its midpoint: 0..10 under bench.fit;
    # 50..80 (midpoint 65) inside the program's flash_kde_pruned
    # annotation in bench.evaluate; 90..100 in bench.evaluate
    assert gaps["bench.fit"] == pytest.approx(10e-9)
    assert gaps["bench.evaluate/flash_kde_pruned"] == pytest.approx(30e-9)
    assert gaps["bench.evaluate"] == pytest.approx(10e-9)
    b = _hand_trace().breakdown()
    assert b["device_ops"][0] == ["jit_score/flash_score_pallas_pruned",
                                  pytest.approx(30e-9)]


def test_a_trace_without_a_window_is_refused():
    with pytest.raises(ValueError):
        xtrace.Trace([[]], [("python", "bench.fit", 0, 1)])


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(TRACE.read_bytes())
    return xtrace.load(d.parents[2])


def test_recorded_chip_trace_reduces(chip_trace):
    tr = chip_trace
    assert len(tr.devices) == 1 and tr.devices[0]
    assert 0.0 < tr.busy_s < tr.window_s
    assert 0.0 < tr.idle_share < 1.0
    score = tr.kernel_s(["flash_score_pallas_pruned"])
    kde = tr.kernel_s(["flash_kde_pallas_pruned"])
    assert score > kde > 0.0
    assert score + kde <= tr.busy_s
    probes = tr.launches(probe.NAME)
    assert len(probes) == 3 and all(t > 0 for t in probes)
    # the probe runs after the window: at most the edge of one launch falls
    # inside it, where the device's clock and the host's differ by ~1 ms
    assert tr.kernel_s([probe.NAME]) < min(probes)
    b = tr.breakdown()
    assert b["device_ops"][0][0].endswith("flash_score_pallas_pruned")
    assert all(name.startswith("bench.") for name, _ in b["idle_gaps"])
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
