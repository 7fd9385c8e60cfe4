"""Find a cell's knee: offer rising open-loop rates to one set-up server.

    python3 bench/sweep.py --workload secstr.vdt.steady [--seed 5] [--rates 10,20,30] [--seconds 10]

Sets up once, then runs one window per rate with the cell's traffic mix at
that rate, and prints one line per rate: requests, p50 and p95 latency
from due time, completed requests per second, and the ratio of the median
latency of the window's last third of requests to its first third.  The
rates default to 0.5 to 1.6 times the cell's own.  The knee is the highest
rate whose backlog does not grow over the window (that ratio near 1).
Exits non-zero off a TPU, like the benchmark.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def sweep(cell, seed: int, rates, seconds: float) -> list[dict]:
    import numpy as np

    from bench import harness
    from bench.traffic import schedule

    server = harness.setup(cell, seed)
    rows = []
    for i, rate in enumerate(rates):
        traffic = copy.deepcopy(cell.traffic)
        traffic["arrivals"]["rate_per_s"] = float(rate)
        reqs = schedule(traffic, seed + i, seconds)
        win = harness.run_window(server, reqs, seed + i, seconds)
        e2e = harness.end_to_end(win, 0.0)
        lat = (win.done - win.due) * 1e3
        third = max(1, len(reqs) // 3)
        growth = float(np.nanmedian(lat[-third:]) / np.nanmedian(lat[:third]))
        rows.append({"rate_per_s": float(rate), "requests": len(reqs),
                     "failed": int(win.failed.sum()), "p50_ms": e2e["p50_ms"],
                     "p95_ms": e2e["p95_ms"], "req_per_s": e2e["req_per_s"],
                     "late_over_early": growth,
                     "occupancy": win.counters["batched_requests"]
                     / max(1, win.counters["dispatches"])})
        print(json.dumps(rows[-1]), flush=True)
        time.sleep(1.0)
    server.engine.shutdown()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from bench import harness
    from repro.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    harness.require_chip(cell.chips)
    enable_compile_cache()
    base = cell.traffic["arrivals"]["rate_per_s"]
    rates = ([float(r) for r in args.rates.split(",")] if args.rates
             else [round(base * f, 2) for f in (0.5, 0.8, 1.0, 1.12, 1.25, 1.4, 1.6)])
    sweep(cell, args.seed, rates, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
