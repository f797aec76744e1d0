"""The offline-job cell rehearsed off the chip, sound and broken.

Each run drives the whole harness (set-up, window, reference, comparison)
at a tiny size with the Pallas kernels interpreted, skipping only the look
for a chip.  A fault planted in the program under the timed path, or the
control's lower precision, has to turn ``correct`` false."""

import io
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from kdebench import harness, rehearse  # noqa: E402

CELL = "sdkde_1m_d16.offline_job"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.make_root(tmp_path_factory.mktemp("offline_root"))


def run(root, seed=2 ** 32 + 17, **kw):
    buf = io.StringIO()
    rc = harness.run(CELL, seed, 0.3, kw.pop("trace", False), root=root,
                     require_chip=False, compile_cache=False, out=buf, **kw)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct_and_keeps_to_the_line_format(root):
    line = run(root)
    assert line["correct"] is True
    assert list(line)[-1] == "check"
    assert set(line["metrics"]) == {"job_s", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["metrics"]["job_s"]["unit"] == "s"
    assert set(line["check"]) == {"max_rel_err", "med_rel_err",
                                  "failed_jobs"}
    for v in line["check"].values():
        assert v["value"] <= v["limit"]


def test_traced_run_reports_per_layer_metrics(root):
    line = run(root, trace=True)
    assert line["correct"] is True
    assert "job_s" not in line["metrics"]
    assert 0 < line["metrics"]["spatial.visit_fraction"]["value"] <= 100
    assert line["metrics"]["compiles_in_window.offline"]["unit"] == "count"
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_control_comes_out_not_correct(root):
    line = run(root, tier="bf16x2")
    assert line["correct"] is False


def test_a_fit_that_leaves_the_points_unchanged_is_caught(root, monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "flash_sdkde_shift",
                        lambda x, h, **kw: x.astype(np.float32))
    assert run(root)["correct"] is False


def test_half_the_train_set_left_out_is_caught(root, monkeypatch):
    from repro.core import estimator

    full = estimator.SDKDE._train_points
    monkeypatch.setattr(estimator.SDKDE, "_train_points",
                        lambda self: full(self)[: full(self).shape[0] // 2])
    assert run(root)["correct"] is False


def test_an_answer_altered_where_it_is_produced_is_caught(root, monkeypatch):
    from repro.kernels import ops

    real = ops.flash_kde

    def altered(*a, **kw):
        out = real(*a, **kw)
        return out.at[0].multiply(1.001)

    monkeypatch.setattr(ops, "flash_kde", altered)
    line = run(root)
    assert line["correct"] is False
    assert line["check"]["max_rel_err"]["value"] > 5e-4
