#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Finds the cell in ``BENCHMARK.json``, loads its configuration, traffic mix
and per-layer metric readers by name, and drives the system on the chip it
is started on.  Exits nonzero, printing no result, when JAX finds no TPU
or fewer chips than the cell asks for.  The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last the compared
numbers beside their limits (``check``).
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from kdebench import harness

    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
