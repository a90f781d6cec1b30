"""``python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one cell, one process, one line.

Fails, with no result line, when JAX finds no TPU, fewer chips than the
cell asks for, or a ``device_kind`` without recorded peaks. ``BENCH_RUN``
is not read.
"""

from __future__ import annotations

import time

_T_PROCESS = time.time()  # before any heavy import: set-up starts here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmark import harness

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           _T_PROCESS)
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
