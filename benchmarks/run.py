"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Set BENCH_FAST=1 to run the
reduced sweep (CI); BENCH_LARGE_N scales the Table-2 surrogate.
"""
from __future__ import annotations

import os
import sys
import traceback


def main() -> None:
    fast = os.environ.get("BENCH_FAST", "0") == "1"
    if fast:
        os.environ.setdefault("BENCH_LARGE_N", "20000")

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (ccr, construction, kernels_bench, large_scale,
                            matvec, refinement, roofline_table, serving)

    suites = [
        ("fig2a-construction", construction.run),
        ("fig2b-matvec", matvec.run),
        ("fig2c-ccr", ccr.run),
        ("fig2d-k-refinement", refinement.run),
        ("table2-large-scale", large_scale.run),
        ("kernels", kernels_bench.run),
        ("roofline", roofline_table.run),
        ("serving-engine", serving.run),
    ]
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites:
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
