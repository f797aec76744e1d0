"""Benchmark aggregator: one harness per paper figure/table.

``python -m benchmarks.run`` runs every harness at CPU-scaled sizes,
prints ``name,key=value,...`` CSV, and dumps the whole suite as
machine-readable JSON to ``BENCH_flash.json`` (per-cell runtime, config,
precision tier, tuned launch tiles) so the perf trajectory is tracked
across PRs.  Individual harnesses accept flags for the paper's full sizes
on real hardware.

A harness that raises does NOT abort the suite — the remaining harnesses
still run and the JSON artifact is still written (with the failure
recorded in its cells and meta) — but the process exits nonzero, so CI
can never upload a partial BENCH_flash.json as if it were healthy.
"""

from __future__ import annotations

import sys
import time
import traceback

from benchmarks import (
    chaos_soak,
    common,
    fig1_runtime,
    fig2_oracle_16d,
    fig3_oracle_1d,
    fig4_fusion,
    fig5_utilization,
    obs_overhead,
    overload_soak,
    planner_cells,
    precision_sweep,
    pruning_sweep,
    rff_cascade,
    serve_throughput,
    streaming_throughput,
    table1_methods,
)
from repro import obs
from repro.launch import enable_compile_cache

BENCH_JSON = "BENCH_flash.json"

#: Harnesses whose run raised, in order (nonzero exit + JSON meta).
FAILURES: list = []


def _run(name: str, desc: str, fn, *args, **kw) -> None:
    print(f"# {name}: {desc}")
    t0 = time.time()
    try:
        fn(*args, **kw)
        ok = True
    except Exception as e:  # noqa: BLE001 - record and keep the suite going
        ok = False
        FAILURES.append(name)
        traceback.print_exc()
        common.emit("harness_error", harness=name,
                    error=f"{type(e).__name__}: {e}")
    common.emit("harness", harness=name, wall_s=round(time.time() - t0, 2),
                ok=ok)


def main() -> None:
    enable_compile_cache()
    t0 = time.time()
    print("# Flash-SD-KDE benchmark suite (CPU-scaled; see EXPERIMENTS.md)")
    _run("fig1", "16-D runtime, naive vs GEMM vs flash (paper Fig. 1)",
         fig1_runtime.main, ns=(1024, 2048, 4096))
    _run("fig2", "16-D oracle MISE/MIAE (paper Fig. 2)",
         fig2_oracle_16d.main, ns=(512, 1024, 2048), seeds=(0, 1),
         n_mc=2048)
    _run("fig3", "1-D oracle MISE/MIAE (paper Fig. 3)",
         fig3_oracle_1d.main, ns=(512, 1024, 2048, 4096), seeds=(0, 1))
    _run("fig4", "Laplace fusion speedup (paper Fig. 4)",
         fig4_fusion.main, ns=(4096, 8192, 16384))
    _run("fig5", "utilization / roofline terms (paper Fig. 5/7)",
         fig5_utilization.main, ns=(1024, 2048, 4096))
    _run("table1", "method comparison at fixed size (paper Table 1)",
         table1_methods.main, n=8192)
    _run("precision", "f32/bf16/bf16x2 accuracy-vs-runtime + autotuner "
         "acceptance cell (kernels/precision.py, kernels/autotune.py)",
         precision_sweep.main, ns=(1024,))
    _run("serve", "query-serving qps / tail latency (repro.serve)",
         serve_throughput.main,
         n=1024, d=8, backends=("jnp", "pallas"),
         batch_sizes=(8, 32), n_requests=8)
    _run("pruning", "cluster-pruned vs dense: occupancy, certified error, "
         "and the 256k×16d acceptance cell (kernels/spatial.py)",
         pruning_sweep.main, smoke_n=8192, smoke_m=1024, acceptance=True)
    _run("streaming", "incremental append/evict serving: appends/sec, "
         "staleness, and the 256k×16d amortized-vs-refit cell "
         "(repro.stream)",
         streaming_throughput.main, smoke_n=2048, smoke_d=8,
         run_acceptance=True)
    _run("rff_cascade", "RFF fast tier + accuracy cascade: mixed-traffic "
         "hit fraction, certified bands, and the 256k modeled "
         "cascade-vs-exact acceptance cell (kernels/flash_rff.py, "
         "serve/cascade.py)",
         rff_cascade.main, smoke_n=8192, smoke_d=2, run_acceptance=True)
    _run("planner", "execution-planner decisions per committed gated cell: "
         "plan cost vs the default serve path + golden-fixture cross-check "
         "(repro.plan, benchmarks/planner_cells.py)",
         planner_cells.main)
    _run("obs_overhead", "serve p50 with telemetry off vs fully on "
         "(repro.obs; informational, not a speedup cell)",
         obs_overhead.main)
    _run("chaos", "resilient serving soak: injected shard kill + recovery "
         "under sustained traffic, plus certified degraded answers — "
         "HARD-FAILS on any dropped query or a lying error certificate "
         "(serve/resilience.py, fault_injection.py)",
         chaos_soak.main, n=2048, d=4, requests=48)
    _run("overload", "admission frontend soak: open-loop steady -> 4x "
         "burst -> recovery through the continuous batcher — HARD-FAILS "
         "on any silent drop, an uncapped tail, or collapsed goodput "
         "(serve/frontend.py, benchmarks/overload_soak.py)",
         overload_soak.main, n=2048, d=4, phase_s=0.6)
    total = time.time() - t0
    # embed the process-wide metrics snapshot the suite itself produced —
    # cache hit rates, prune occupancies, tuner decisions — so the perf
    # artifact carries its own telemetry alongside the timing cells
    common.write_bench_json(BENCH_JSON, suite="cpu-scaled",
                            total_s=round(total, 1),
                            failed_harnesses=",".join(FAILURES) or None,
                            metrics=obs.metrics_snapshot())
    print(f"# total {total:.1f}s  → {BENCH_JSON}")
    if FAILURES:
        print(f"# FAILED harnesses: {', '.join(FAILURES)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
