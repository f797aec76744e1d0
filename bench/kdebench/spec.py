"""``BENCHMARK.json`` and the files it names, found by name.

A cell (workload) names a configuration and a traffic mix; each per-layer
metric is a reader module named after the metric.  Everything resolves from
the benchmark's own directory, so a cell, a configuration, a mix or a
metric is added by adding a file and an entry, never by editing one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Callable, Dict, List, Optional

#: The benchmark's directory (``bench/``) and the checkout's root.
BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: Optional[str] = None          # per-layer only
    layer: Optional[str] = None          # per-layer only
    bound: Optional[float] = None        # end-to-end only
    workloads: Optional[tuple] = None    # cells it is read in; None = all


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload entry with everything it names, loaded."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def traffic_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "traffic" / f"{name}.json"


def reader_path(metric: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "layer_metrics" / f"{metric}.py"


def _metric(entry: dict) -> Metric:
    wl = entry.get("workloads")
    return Metric(
        name=entry["name"], unit=entry["unit"], better=entry["better"],
        source=entry["source"], moves=entry.get("moves"),
        layer=entry.get("layer"), bound=entry.get("bound"),
        workloads=None if wl is None else tuple(wl))


def _applies(m: Metric, cell: str, e2e_names) -> bool:
    if m.workloads is not None:
        return cell in m.workloads
    # a per-layer metric without a list is read wherever its end-to-end
    # metric is reported
    return m.moves is None or m.moves in e2e_names


def resolve_cell(bench: dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` with its configuration, mix and metrics
    loaded from their own files; raises ``KeyError`` for an unknown name."""
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(traffic_path(entry["traffic"], root / "bench")) as f:
        traffic = json.load(f)
    e2e = [_metric(m) for m in bench["end_to_end"]]
    e2e = [m for m in e2e if _applies(m, workload, ())]
    names = {m.name for m in e2e}
    per_layer = [m for m in map(_metric, bench["per_layer"])
                 if _applies(m, workload, names)]
    return Cell(workload, int(entry["chips"]), entry["config"], config,
                entry["traffic"], traffic, e2e, per_layer)


def load_reader(metric: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read(ctx)`` function of a per-layer metric's reader file."""
    path = reader_path(metric, bench_dir)
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def readers(cell: Cell, bench_dir: Path = BENCH_DIR) -> Dict[str, Callable]:
    return {m.name: load_reader(m.name, bench_dir) for m in cell.per_layer}
