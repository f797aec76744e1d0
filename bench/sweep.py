#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: step the offered rate.

The cell has to be in ``BENCHMARK.json``; ``bench/tests/serve_ragged_cell.json``
holds the entries that add the serving cell.

    python3 bench/sweep.py --workload sdkde_32k_d16.serve_ragged --seed 1 \\
        --seconds 10 --rates 5,10,20,40,80

One process sets the cell up once (fit, bucket prewarm, warm traffic), then
offers each rate in turn for ``--seconds``, with the mix's row sizes, from
its own stream of the seed.  A rate is sustained when no request is shed,
expires or answers late, and the queue does not grow: the median latency
of the last quarter of its requests stays under 1.5 times that of the
first quarter.  The knee is the highest sustained rate below the first
that is not; the cell's rate is set at 4/5 of it, by hand, in its
traffic file.
Prints one row per rate and writes them to ``bench/.out/sweep.json``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent

#: A queue grows when the last quarter's median latency exceeds the first
#: quarter's by this factor.
GROWTH = 1.5

sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests/s, ascending")
    args = ap.parse_args(argv)

    from kdebench import data, device, drive, spec, window

    cell = spec.resolve_cell(spec.load_benchmark(), args.workload)
    try:
        devices = device.require_tpu(cell.chips)
    except device.NoChip as e:
        print(f"sweep: {e}; nothing run", file=sys.stderr)
        return 2
    device.enable_compile_cache()
    compiles = device.CompileCounter()
    print(json.dumps(device.describe(devices)), flush=True)
    drv = drive.driver(cell.config, cell.traffic, args.seed, args.seconds)
    if cell.traffic["kind"] != "open_loop":
        print("sweep: only open-loop mixes have a knee", file=sys.stderr)
        return 2
    drv.setup()
    print(f"setup {time.perf_counter() - T_START:.1f}s", flush=True)
    rows, knee = [], None
    for step, rate in enumerate(float(r) for r in args.rates.split(",")):
        drv.traffic = dict(cell.traffic,
                           arrivals={"kind": "poisson", "rate": rate})
        c0 = compiles.n
        drv.run_schedule(data.WINDOW + 100 + step, args.seconds,
                         record=True)
        lat = drv.latencies()
        q = max(1, len(lat) // 4)
        first = window.percentile(lat[:q], 50)
        last = window.percentile(lat[-q:], 50)
        failed = drv.failed
        ok = failed == 0 and last < GROWTH * first
        row = {"rate": rate, "requests": drv.attempted, "failed": failed,
               "p50_ms": 1e3 * window.percentile(lat, 50),
               "p95_ms": 1e3 * window.percentile(lat, 95),
               "first_quarter_p50_ms": 1e3 * first,
               "last_quarter_p50_ms": 1e3 * last,
               "lateness_max_ms": drv.generator_lateness()["max_ms"],
               "compiles": compiles.n - c0, "failed_by": drv.failures(),
               "sustained": ok}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if not ok:
            break
        knee = rate
    drv.free()
    out = {"workload": args.workload, "seed": args.seed,
           "seconds": args.seconds, "rows": rows, "knee": knee,
           "rate_at_four_fifths": None if knee is None else 0.8 * knee}
    dest = HERE / ".out" / "sweep.json"
    dest.parent.mkdir(exist_ok=True)
    dest.write_text(json.dumps(out, indent=1))
    print("| rate /s | requests | failed | p50 ms | p95 ms | first-quarter "
          "p50 ms | last-quarter p50 ms | sustained |")
    print("|---|---|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['rate']:g} | {r['requests']} | {r['failed']} | "
              f"{r['p50_ms']:.1f} | {r['p95_ms']:.1f} | "
              f"{r['first_quarter_p50_ms']:.1f} | "
              f"{r['last_quarter_p50_ms']:.1f} | {r['sustained']} |")
    print(f"knee {knee} req/s; cell rate at 4/5: {out['rate_at_four_fifths']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
