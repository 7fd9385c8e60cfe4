"""Run one benchmark cell once and print its result as the last line of stdout.

    python3 bench/run.py --workload secstr.vdt.steady --seed 7 --seconds 10 --trace 0

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  ``--trace 1`` prints the cell's per-layer metrics
from a profiler trace of the window instead of its end-to-end metrics.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    devices = harness.require_chip(cell.chips)
    # any whole number is a seed; the generators take it as 64 unsigned bits
    seed = args.seed % (1 << 64)
    return harness.run(cell, seed, args.seconds, bool(args.trace), devices, T0)


if __name__ == "__main__":
    sys.exit(main())
