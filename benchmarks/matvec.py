"""Paper Fig. 2B: transition-matrix matvec time vs N (exact vs kNN vs VDT),
plus the fused Pallas exact-matvec kernel (beyond paper) and the batched
multi-RHS engine (one dispatch vs a loop of single-RHS calls).

Set BENCH_TINY=1 for a seconds-long CI smoke run (small N, batched section
only at the single size).  Writes ``BENCH_matvec.json`` with the
batched-vs-loop speedups per size — the figures the CI bench-gate compares
against ``benchmarks/baselines.json``."""
from __future__ import annotations

import os

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit, timeit, write_json
from repro.core.baselines import (build_knn_graph, exact_transition_matrix,
                                  knn_matvec, streaming_exact_matvec)
from repro.core.sigma import sigma_init
from repro.core.vdt import VariationalDualTree
from repro.data.synthetic import secstr_like

TINY = bool(os.environ.get("BENCH_TINY"))
SIZES = (256,) if TINY else (1000, 4000, 16000)
C = 2
BATCH = 8       # multi-RHS stack size for the batched engine section
LP_ITERS = 5 if TINY else 50


def _bench_batched(vdt, n: int) -> dict:
    """Batched (BATCH, N, C) engine vs BATCH looped single-RHS calls."""
    r = np.random.RandomState(0)
    ys = jnp.asarray(r.randn(BATCH, n, C).astype(np.float32))

    def loop(stack):
        return [vdt.matvec(stack[i]) for i in range(BATCH)]

    us_loop = timeit(loop, ys)
    us_bat = timeit(vdt.matvec_batched, ys)
    emit(f"batched/matvec/loop/n={n}/b={BATCH}", us_loop, "")
    emit(f"batched/matvec/batched/n={n}/b={BATCH}", us_bat,
         f"speedup={us_loop / us_bat:.2f}x")

    y0 = jnp.asarray((r.rand(BATCH, n, C) > 0.9).astype(np.float32))

    def lp_loop(stack):
        return [vdt.label_propagate(stack[i], n_iters=LP_ITERS)
                for i in range(BATCH)]

    def lp_bat(stack):
        return vdt.label_propagate(stack, n_iters=LP_ITERS)

    us_l = timeit(lp_loop, y0)
    us_b = timeit(lp_bat, y0)
    emit(f"batched/lp{LP_ITERS}/loop/n={n}/b={BATCH}", us_l, "")
    emit(f"batched/lp{LP_ITERS}/batched/n={n}/b={BATCH}", us_b,
         f"speedup={us_l / us_b:.2f}x")
    return {
        "n": n, "batch": BATCH, "lp_iters": LP_ITERS,
        "matvec_loop_us": us_loop, "matvec_batched_us": us_bat,
        "matvec_speedup": us_loop / us_bat,
        "lp_loop_us": us_l, "lp_batched_us": us_b,
        "lp_speedup": us_l / us_b,
    }


def run():
    results = []
    data = secstr_like(n=max(SIZES), d=64 if TINY else 315)
    for n in SIZES:
        x = jnp.asarray(data.x[:n])
        y = jnp.asarray(data.x[:n, :C]).astype(jnp.float32)
        sig = sigma_init(x)

        vdt = VariationalDualTree.fit(x, sigma=float(sig), learn_sigma=False)
        us = timeit(vdt.matvec, y)
        emit(f"fig2b/matvec/vdt/n={n}", us, f"blocks={vdt.n_blocks}")

        results.append(_bench_batched(vdt, n))

        g = build_knn_graph(x, 2, sig)
        us = timeit(lambda yy: knn_matvec(g, yy), y)
        emit(f"fig2b/matvec/knn2/n={n}", us, "")

        if n <= 4000:
            p = exact_transition_matrix(x, sig)
            us = timeit(lambda yy: p @ yy, y)
            emit(f"fig2b/matvec/exact/n={n}", us, "")

        us = timeit(lambda yy: streaming_exact_matvec(x, yy, sig), y)
        emit(f"fig2b/matvec/exact_streaming/n={n}", us,
             "fused flash form, O(N*blk) mem")

    write_json("matvec", {
        "sizes": results,
        # gate figures: worst case over sizes, so a regression at any N trips
        "matvec_speedup": min(r["matvec_speedup"] for r in results),
        "lp_speedup": min(r["lp_speedup"] for r in results),
    })


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
