"""The pruning layer's readers: idle time under the pruned passes' host
steps, host time inside them, and their device-to-host fetches, per job."""

import io
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from kdebench import harness, rehearse, spans, xtrace  # noqa: E402

TRACE = BENCH / "testdata" / "sdkde_32k_trace.xplane.pb"
READERS = {"pruning.idle_ms.offline": spans.idle_ms,
           "pruning.host_ms.offline": spans.host_ms,
           "pruning.host_syncs.offline": spans.host_syncs}


def _hand_trace():
    # window 0..1000 ns holding two jobs.  Job 1 fits under a pruned pass
    # (50..250) whose visit-list step (100..150) and launch (200..240)
    # each cover one device gap; job 2 evaluates with no program span
    # over its gap (600..700).
    dev = [[("fusion", "jit_a", 0, 100), ("fusion", "jit_a", 150, 205),
            ("flash_score_pallas_pruned", "jit_s", 215, 600),
            ("fusion", "jit_a", 700, 1000)]]
    host = [("python", "bench.window", 0, 1000),
            ("python", "bench.job", 0, 500),
            ("python", "bench.job", 500, 1000),
            ("python", "bench.fit", 0, 300),
            ("python", "kernels.prune.pass", 50, 250),
            ("python", "kernels.prune.visit_lists", 100, 150),
            ("python", "kernels.pruned_score", 200, 240),
            ("python", "bench.evaluate", 300, 1000)]
    return xtrace.Trace(dev, host)


def _ctx(trace, hist=None):
    return SimpleNamespace(trace=trace, hist_delta=lambda name: hist)


def test_idle_under_a_pruned_step_counts_and_bench_alone_does_not():
    # 100..150 lies under kernels.prune.visit_lists: 50 ns over two jobs;
    # 205..215 lies under the launch and 600..700 under bench.evaluate
    assert spans.idle_ms(_ctx(_hand_trace())) == pytest.approx(25e-6)


def test_host_time_leaves_out_the_launches_inside_a_pass():
    # the pass's 200 ns less its 40 ns launch, over two jobs
    assert spans.host_ms(_ctx(_hand_trace())) == pytest.approx(80e-6)


def test_host_syncs_are_per_job_with_their_bytes():
    got = spans.host_syncs(_ctx(_hand_trace(), hist=(16, 1000.0)))
    assert got == (8.0, {"bytes": 500.0})
    assert spans.host_syncs(_ctx(_hand_trace())) is None


def test_a_trace_without_a_job_reads_nothing():
    tr = _hand_trace()
    tr.host = [h for h in tr.host if h[1] != "bench.job"]
    for read in READERS.values():
        assert read(_ctx(tr, hist=(16, 1000.0))) is None


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(TRACE.read_bytes())
    return xtrace.load(d.parents[2])


def test_a_program_without_the_spans_reads_nothing(chip_trace):
    # recorded before the program had its kernels.prune.* spans, and
    # before the harness wrapped each job: give the window one job, so
    # that only the missing spans can make the readers read nothing
    job = ("python", "bench.job", chip_trace.t0, chip_trace.t1)
    tr = xtrace.Trace(chip_trace.devices, chip_trace.host + [job])
    assert tr.devices and spans.jobs(tr) == 1
    for read in READERS.values():
        assert read(_ctx(tr)) is None


def test_traced_rehearsal_reads_host_time_and_syncs_but_no_idle(tmp_path):
    root = rehearse.make_root(tmp_path)
    buf = io.StringIO()
    assert harness.run("sdkde_1m_d16.offline_job", 2 ** 33 + 5, 0.3, True,
                       root=root, require_chip=False, compile_cache=False,
                       out=buf) == 0
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    got = line["metrics"]
    assert line["correct"] is True
    # no device plane off the chip, so no device-idle time to read
    assert "pruning.idle_ms.offline" not in got
    assert got["pruning.host_ms.offline"]["value"] > 0
    assert got["pruning.host_ms.offline"]["unit"] == "ms"
    syncs = got["pruning.host_syncs.offline"]
    assert syncs["unit"] == "count" and syncs["value"] >= 1
    assert syncs["bytes"] > 0
