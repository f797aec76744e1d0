#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program, and its control.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \\
        [--tier bf16x2] [--seconds 0]

Runs the cell once per seed in one process, exactly as ``bench/run.py``
does, and prints each run's result line.  ``--tier`` substitutes a lower
precision for the one the configuration states: the configuration's
``control_precision`` is the control, the program's own path one step
below its f32 tier, which has to come out not correct against the limits.
Without ``--tier`` the runs give the program's own readings.  The
benchmark's runs never run the control.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--tier", default=None)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    from kdebench import harness

    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        print(f"control: workload={args.workload} seed={seed} "
              f"tier={args.tier or 'configuration'}", flush=True)
        rc |= harness.run(args.workload, seed, args.seconds, False,
                          t_start=time.perf_counter(), tier=args.tier)
    return rc


if __name__ == "__main__":
    sys.exit(main())
