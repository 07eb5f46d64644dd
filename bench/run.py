#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload dna-seed-open --seed 7 --seconds 30 --trace 0

Set-up builds the cell's index or texts from ``--seed``, warms every
program the window uses, then measures for ``--seconds``.  With
``--trace 0`` the result line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace.  The last
line of standard output is the result as one JSON object; the numbers the
correctness check compared, each with its limit, are the last lines of
standard error.  Without a TPU the command exits non-zero and prints no
result.

``--fault`` plants a named control or fault (``bench/faults.py``) in the
timed path; it exists for the correctness check's own tests and readings.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_process=T_PROCESS,
                       fault=args.fault)


if __name__ == "__main__":
    sys.exit(main())
