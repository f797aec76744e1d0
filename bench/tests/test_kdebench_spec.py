"""BENCHMARK.json keeps to its contract, and everything resolves by name."""

import json
import math
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from kdebench import rehearse, spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["paths"] == ["bench"]
    assert all(not w.startswith("/") and ".." not in w
               for w in bench["command"])
    assert len((spec.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_and_units_fit_the_allowed_characters(bench):
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    names += [w["config"] for w in bench["workloads"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [e["name"] for e in bench[k]]
        assert len(ns) == len(set(ns)), k
    for e in bench["configs"] + bench["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]


def test_entry_keys_and_arrows(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in SOURCES_E2E
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")


def test_every_cell_reports_setup_another_e2e_and_a_layer(bench):
    for w in bench["workloads"]:
        cell = spec.resolve_cell(bench, w["name"])
        names = {m.name for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_every_workload_resolves_its_files_by_name(bench):
    for w in bench["workloads"]:
        cell = spec.resolve_cell(bench, w["name"])
        assert cell.config["precision"] == "f32"
        assert cell.config["limits"]
        assert cell.traffic["kind"] in ("jobs", "open_loop")
        readers = spec.readers(cell)
        assert set(readers) == {m.name for m in cell.per_layer}
        assert all(callable(r) for r in readers.values())


def test_a_full_check_fits_its_time(bench):
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (bench["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200


def test_a_mix_added_in_a_new_file_is_found(tmp_path):
    """A later PR adds a cell by adding files and entries only."""
    root = rehearse.make_root(tmp_path)
    (root / "bench" / "traffic" / "serve_small.json").write_text(
        json.dumps({"kind": "open_loop",
                    "arrivals": {"kind": "poisson", "rate": 40.0},
                    "rows_min": 1, "rows_max": 256, "pool_rows": 1024,
                    "warm_seconds": 0.5}))
    (root / "bench" / "layer_metrics" / "frontend.rejects.py").write_text(
        "def read(ctx):\n    return 0.0\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "sdkde_32k_d16",
                         "source": "https://arxiv.org/abs/2602.10378",
                         "file": "bench/configs/sdkde_32k_d16.json",
                         "reduced": [], "why": "Table 1"})
    b["workloads"].append({"name": "sdkde_32k_d16.serve_small",
                           "config": "sdkde_32k_d16",
                           "traffic": "serve_small", "chips": 1,
                           "why": "small requests"})
    b["end_to_end"].append({"name": "latency_p50_ms", "unit": "ms",
                            "better": "lower", "bound": 0.25,
                            "source": "host_clock",
                            "workloads": ["sdkde_32k_d16.serve_small"]})
    b["per_layer"].append({"name": "frontend.rejects", "unit": "count",
                           "better": "lower", "source": "program_counter",
                           "layer": "front end", "moves": "latency_p50_ms",
                           "workloads": ["sdkde_32k_d16.serve_small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.resolve_cell(spec.load_benchmark(root),
                             "sdkde_32k_d16.serve_small", root)
    assert cell.traffic["arrivals"]["rate"] == 40.0
    assert {m.name for m in cell.end_to_end} == {"latency_p50_ms", "setup_s"}
    readers = spec.readers(cell, root / "bench")
    assert readers["frontend.rejects"](None) == 0.0
    assert "score_kernel_roofline" not in readers  # listed for another cell


def test_unknown_workload_is_refused(bench):
    with pytest.raises(KeyError):
        spec.resolve_cell(bench, "no_such.cell")


def test_configs_state_their_sizes_and_nothing_reduced(bench):
    for c in bench["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] == []
        assert cfg["data"]["d"] == 16
        assert len(cfg["source"]) <= 200
        mix = cfg["data"]["mixture"]
        assert math.isclose(sum(mix["weights"]), 1.0)
        assert all(len(m) == cfg["data"]["d"] for m in mix["means"])
