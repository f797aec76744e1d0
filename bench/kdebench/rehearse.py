"""A tiny copy of the benchmark, to rehearse a cell off the chip.

``make_root`` copies ``BENCHMARK.json`` and every file under ``bench/``
into a scratch root, adds any cell kept as a draft, and shrinks each
configuration and mix, so the whole path (set-up, window, readers,
reference, comparison) runs in seconds on the CPU with the Pallas kernels
interpreted (``bench/tests/`` drives it).
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

from kdebench import spec

#: Tiny sizes: n is past neither pruning threshold, so the configs also
#: ask for pruning at epsilon 0 explicitly to rehearse the pruned path.
TINY_DATA = {"n": 2048, "queries": 256}
TINY_TRAFFIC = {"pool_rows": 1024, "warm_seconds": 1.0}
TINY_ROWS = 200
TINY_RATE = 5.0


def add_cell(root: Path, draft: dict) -> None:
    """Add a cell kept as a draft to the benchmark at ``root`` the way a
    later change adds one: entries in ``BENCHMARK.json`` (its workload,
    configuration and metrics, where not there yet) and its traffic file."""
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    entries = {"workloads": [draft["workload"]], "configs": [draft["config"]],
               "end_to_end": draft["end_to_end"],
               "per_layer": draft["per_layer"]}
    for key, new in entries.items():
        have = {e["name"] for e in bench[key]}
        bench[key] += [e for e in new if e["name"] not in have]
    path.write_text(json.dumps(bench))
    spec.traffic_path(draft["workload"]["traffic"], root / "bench") \
        .write_text(json.dumps(draft["traffic"]))


def make_root(dest: Path, src: Path = spec.ROOT, drafts=()) -> Path:
    dest = Path(dest)
    shutil.copy(src / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(src / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".out",
                                                  "__pycache__"),
                    dirs_exist_ok=True)
    for draft in drafts:
        add_cell(dest, draft)
    for path in (dest / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["data"].update({k: v for k, v in TINY_DATA.items()
                            if k in cfg["data"]})
        for knobs in (cfg["system"].get("estimator"),
                      cfg["system"].get("serve")):
            if knobs is not None:
                knobs["prune"] = 0.0
        if "serve" in cfg["system"]:
            # a short bucket ladder: every tiny request fits 256 rows
            cfg["system"]["serve"]["max_batch"] = 256
        if "frontend" in cfg["system"]:
            # interpreted kernels and first-time compiles are slow: give
            # every request time to be answered and compared
            cfg["system"]["frontend"]["default_deadline_ms"] = 120000.0
        path.write_text(json.dumps(cfg))
    for path in (dest / "bench" / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        if t["kind"] == "open_loop":
            t.update(TINY_TRAFFIC)
            # one request size: off the chip every new size compiles a few
            # dozen small programs, which only slows the rehearsal
            t["rows_min"] = t["rows_max"] = min(int(t["rows_max"]), TINY_ROWS)
            t["arrivals"] = {"kind": "poisson", "rate": TINY_RATE}
        path.write_text(json.dumps(t))
    return dest

