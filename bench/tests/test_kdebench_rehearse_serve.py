"""The open-loop serving cell rehearsed off the chip, sound and broken.

The cell is kept as a draft (``serve_ragged_cell.json``) until the program
can serve ragged requests on the chip; here it is added to a tiny copy of
the benchmark by entries and a traffic file alone.  Each run drives the
whole harness (fit, bucket prewarm, warm traffic, the open-loop window
through ``AsyncFrontend``, reference, comparison) with the Pallas kernels
interpreted, skipping only the look for a chip.  A fault planted in the
program under the timed path, or the control's lower precision, has to
turn ``correct`` false."""

import io
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from kdebench import harness, rehearse  # noqa: E402

DRAFT = json.loads((Path(__file__).parent / "serve_ragged_cell.json")
                   .read_text())
CELL = DRAFT["workload"]["name"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.make_root(tmp_path_factory.mktemp("serve_root"),
                              drafts=[DRAFT])


def run(root, seed=2 ** 31 + 3, **kw):
    buf = io.StringIO()
    rc = harness.run(CELL, seed, 1.0, kw.pop("trace", False), root=root,
                     require_chip=False, compile_cache=False, out=buf, **kw)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct_and_reports_tails(root):
    line = run(root)
    assert line["correct"] is True
    assert list(line)[-1] == "check"
    m = line["metrics"]
    assert set(m) == {"latency_p50_ms", "latency_p95_ms", "setup_s"}
    assert m["latency_p95_ms"]["value"] >= m["latency_p50_ms"]["value"]
    assert m["latency_p50_ms"]["value"] > 0
    assert line["attempted"] == 5 and line["failed"] == 0
    assert line["check"]["unresolved"]["value"] == 0


def test_traced_run_reads_the_engine_and_front_end(root):
    line = run(root, trace=True)
    assert line["correct"] is True
    m = line["metrics"]
    assert m["engine.pad_ratio"]["value"] >= 1.0
    assert m["frontend.queue_wait_ms"]["value"] >= 0.0
    assert "latency_p50_ms" not in m


def test_the_control_comes_out_not_correct(root):
    assert run(root, tier="bf16x2")["correct"] is False


def test_a_fit_that_leaves_the_points_unchanged_is_caught(root, monkeypatch):
    from repro.serve import registry

    monkeypatch.setattr(registry.EstimatorRegistry, "_debias",
                        lambda self, x, h, cfg: x)
    assert run(root)["correct"] is False


def test_half_the_train_set_left_out_is_caught(root, monkeypatch):
    from repro.serve import registry

    real = registry.EstimatorRegistry._prepare
    monkeypatch.setattr(
        registry.EstimatorRegistry, "_prepare",
        lambda self, key, x, h, cfg: real(self, key, x[: x.shape[0] // 2],
                                          h, cfg))
    assert run(root)["correct"] is False


def test_an_answer_altered_where_it_is_produced_is_caught(root, monkeypatch):
    from repro.serve import engine

    real = engine.ServeEngine._dispatch

    def altered(self, prep, y, precision=None):
        return real(self, prep, y, precision).at[0].multiply(1.001)

    monkeypatch.setattr(engine.ServeEngine, "_dispatch", altered)
    line = run(root)
    assert line["correct"] is False
    assert line["check"]["max_rel_err"]["value"] > 5e-4
