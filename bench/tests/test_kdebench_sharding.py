"""The four-chip cell: its sharding layer's readers, and the cell rehearsed
off the chip.

The readers are checked on a hand-made four-chip trace.  The rehearsal runs
the whole harness for ``sdkde_2m_d16_4chip.offline_job`` at a tiny size in
a child process that sees four forced host devices (the test process keeps
one), with the Pallas kernels interpreted.  A CPU profile has no device
plane, so for the traced run the child lays each pruned launch span of a
sharded pass on its chip's timeline (the k-th launch of a pass ran on chip
k) for the device-trace readers to read.
"""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from kdebench import sharding, xtrace  # noqa: E402

CELL = "sdkde_2m_d16_4chip.offline_job"


def _four_chip_trace():
    # window 0..1000 ns holding one job and one sharded pass (0..900).
    # Chips 0-2 run their pruned kernel 100..300, chip 3 250..650, after
    # its dispatch's visit-list step (100..250): the slowest chip's 400 ns
    # over the mean 250 is 60% over.  Every chip copies 650..700 and then
    # waits in the gather (700..900).
    dev = []
    for chip in range(4):
        start, end = (250, 650) if chip == 3 else (100, 300)
        dev.append(([] if chip == 3 else [("fusion", "jit_a", 0, 100)])
                   + [("flash_score_pallas_pruned", "jit_s", start, end),
                      ("copy", "jit_c", 650, 700),
                      ("fusion", "jit_b", 900, 1000)])
    host = [("python", "bench.window", 0, 1000),
            ("python", "bench.job", 0, 1000),
            ("python", "distributed.shard.pass", 0, 900),
            ("python", "distributed.shard.dispatch", 100, 600),
            ("python", "kernels.prune.visit_lists", 100, 250),
            ("python", "distributed.shard.gather", 700, 900)]
    return xtrace.Trace(dev, host)


def _ctx(trace):
    return SimpleNamespace(trace=trace)


def test_imbalance_is_the_slowest_chip_over_the_mean():
    assert sharding.imbalance(_ctx(_four_chip_trace())) == pytest.approx(60.0)


def test_imbalance_averages_the_passes_of_the_window():
    tr = _four_chip_trace()
    for chip in tr.devices:          # a second pass, even on every chip
        chip.append(("flash_kde_pallas_pruned", "jit_e", 920, 960))
    tr.host.append(("python", "distributed.shard.pass", 910, 990))
    assert sharding.imbalance(_ctx(tr)) == pytest.approx(30.0)


def test_idle_under_the_exchange_and_dispatch_counts_per_chip():
    # each gap counts by its midpoint's innermost span: chips 0-2 idle
    # 300..650 under the dispatch and 700..900 under the gather (550 ns
    # each); chip 3 idles 0..250, its midpoint under the visit-list step
    # (the pruning layer's, not counted), and 700..900 (200 ns)
    got = sharding.idle_ms(_ctx(_four_chip_trace()))
    assert got == pytest.approx((3 * 550 + 200) / 4 / 1e6)


def test_a_trace_without_the_spans_or_with_one_chip_reads_nothing():
    tr = _four_chip_trace()
    tr.host = [h for h in tr.host if not h[1].startswith("distributed.")]
    assert sharding.imbalance(_ctx(tr)) is None
    assert sharding.idle_ms(_ctx(tr)) is None
    one = _four_chip_trace()
    one.devices = one.devices[:1]
    assert sharding.imbalance(_ctx(one)) is None
    assert sharding.idle_ms(_ctx(one)) is None


_CHILD = r"""
import io, json, sys, tempfile
from pathlib import Path
sys.path[:0] = [sys.argv[1], str(Path(sys.argv[1]).parent / "src")]
import jax.numpy as jnp
from kdebench import harness, rehearse, xtrace
from repro.distributed import shard

CELL = sys.argv[2]
root = rehearse.make_root(Path(tempfile.mkdtemp()))
PRUNED = {"kernels.pruned_score": "flash_score_pallas_pruned",
          "kernels.pruned_eval": "flash_kde_pallas_pruned"}
real_load = xtrace.load


def load_with_chips(trace_dir, chips=1):
    tr = real_load(trace_dir, chips=chips)
    if not tr.devices:
        tr.devices = [[] for _ in range(chips)]
        for _, name, s, e in tr.host:
            if name != "distributed.shard.pass":
                continue
            launches = sorted((ls, le, PRUNED[n]) for _, n, ls, le in tr.host
                              if n in PRUNED and s <= ls < e)
            for k, (ls, le, op) in enumerate(launches):
                tr.devices[k].append((op, "jit_" + op, ls, le))
    return tr


def run(**kw):
    buf = io.StringIO()
    rc = harness.run(CELL, 2 ** 32 + 17, 0.3, kw.pop("trace", False),
                     root=root, require_chip=False, compile_cache=False,
                     out=buf, **kw)
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    return {"rc": rc, "correct": line["correct"],
            "count": line["device"]["count"], "metrics": sorted(line["metrics"])}


out = {"sound": run(), "control": run(tier="bf16x2")}
xtrace.load = load_with_chips
out["traced"] = run(trace=True)
xtrace.load = real_load
collect = shard._collect


def leave_out_chip_1(parts, dev):
    return collect([jnp.zeros_like(p) if k == 1 else p
                    for k, p in enumerate(parts)], dev)


shard._collect = leave_out_chip_1
out["fault"] = run()
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _CHILD, str(BENCH), CELL],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT ")]
    assert lines, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_a_sound_run_on_four_devices_is_correct(rehearsal):
    got = rehearsal["sound"]
    assert got["rc"] == 0 and got["count"] == 4 and got["correct"] is True
    assert got["metrics"] == ["job_s", "setup_s"]


def test_the_control_comes_out_not_correct(rehearsal):
    assert rehearsal["control"]["correct"] is False


def test_one_chips_rows_left_out_of_the_gather_is_caught(rehearsal):
    assert rehearsal["fault"]["correct"] is False


def test_a_traced_run_reports_the_sharding_layer(rehearsal):
    got = rehearsal["traced"]
    assert got["correct"] is True
    assert {"shard.imbalance.offline", "shard.idle_ms.offline",
            "spatial.visit_fraction", "pruning.idle_ms.offline",
            "pruning.host_ms.offline"} <= set(got["metrics"])
