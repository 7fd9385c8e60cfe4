"""GRF walker-estimator benchmark: accuracy-vs-walkers curve + throughput.

The scenario this backend exists for: a natively sparse graph (ring +
random chords, constant out-degree) too large to materialize densely at
production scale.  Two figures feed the CI gate (``BENCH_grf.json``,
bounds under the ``grf`` section of ``benchmarks/baselines.json``):

* ``kernels.grf.rel_err_at_budget`` — relative L2 error of
  ``grf_label_propagate`` at the serving-default walker budget (m = 64)
  against the dense eq.-15 reference on the same matrix.  The CLT makes
  this budget-predictable (the MC noise only touches the series tail,
  total weight ``alpha``), so a cap well above the quiet-runner figure
  still catches a broken importance correction or coefficient schedule.
* ``kernels.grf.speedup_vs_dense`` — jitted streamed-walk LP vs the dense
  reference at the same iteration count.  Per step the walker scan does
  O(N * m) work vs O(N^2) dense, and the ratio tracks that: ~0.1x at the
  tiny N=512 shape, ~0.3x at N=2048 (per-walker threefry PRNG has a large
  constant on CPU while dense rides BLAS; the crossover sits past the
  sizes a CI runner can time).  Like ``serving.fifo.speedup``, the
  committed floor is therefore a catastrophic-degradation floor — it
  trips if the scan stops scaling linearly, not a claim that GRF beats
  dense at CI shapes.

The accuracy curve (m = 8 / 32 / 128) is recorded, not gated: it
documents the ~1/sqrt(m) decay operators size ``rtol`` budgets against.
On the CPU these timings say nothing about a TPU; the algorithmic O(N*m)
vs O(N^2) contrast is what this gate protects.
"""
from __future__ import annotations

import os

import jax
import numpy as np

from benchmarks.common import emit, timeit, write_json
from repro.core.grf import CSRGraph, grf_label_propagate
from repro.kernels.grf.ref import dense_lp_ref

TINY = bool(os.environ.get("BENCH_TINY"))
N = 512 if TINY else 2048
DEG = 8            # constant out-degree: density DEG/N (~1.6% tiny)
C = 4
ALPHA = 0.1
N_ITERS = 10
BUDGET = 64        # the serving default the gated rel-err is measured at
CURVE = (8, 32, 128)


def sparse_ring_graph(rng, n, deg):
    """Ring + random chords: connected, sparse, non-uniform weights."""
    cols = np.empty((n, deg), np.int64)
    cols[:, 0] = (np.arange(n) + 1) % n          # ring edge: connectivity
    cols[:, 1:] = rng.randint(0, n, size=(n, deg - 1))
    indptr = np.arange(n + 1, dtype=np.int64) * deg
    weights = rng.rand(n * deg) + 0.1
    return CSRGraph.from_csr(indptr, cols.reshape(-1), weights)


def rel_err(est, want):
    est, want = np.asarray(est, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(est - want) / np.linalg.norm(want))


def run():
    rng = np.random.RandomState(0)
    graph = sparse_ring_graph(rng, N, DEG)
    y0 = (rng.rand(N, C) > 0.8).astype(np.float32)
    dense = graph.dense_p()
    want = np.asarray(dense_lp_ref(dense, y0, alpha=ALPHA, n_iters=N_ITERS))

    curve = {}
    for m in CURVE:
        est = grf_label_propagate(graph, y0, alpha=ALPHA, n_iters=N_ITERS,
                                  n_walkers=m, seed=1)
        curve[str(m)] = rel_err(est, want)
        emit(f"grf/rel_err/n={N},m={m}", 0.0, f"rel_err={curve[str(m)]:.4f}")

    est_b = grf_label_propagate(graph, y0, alpha=ALPHA, n_iters=N_ITERS,
                                n_walkers=BUDGET, seed=1)
    rel_err_at_budget = rel_err(est_b, want)
    emit(f"grf/rel_err_at_budget/n={N},m={BUDGET}", 0.0,
         f"rel_err={rel_err_at_budget:.4f}")

    grf_fn = jax.jit(lambda y: grf_label_propagate(
        graph, y, alpha=ALPHA, n_iters=N_ITERS, n_walkers=BUDGET, seed=1))
    dense_fn = jax.jit(lambda y: dense_lp_ref(dense, y, alpha=ALPHA,
                                              n_iters=N_ITERS))
    y0j = np.asarray(y0)
    us_grf = timeit(grf_fn, y0j)
    us_dense = timeit(dense_fn, y0j)
    speedup = us_dense / max(us_grf, 1e-9)
    emit(f"grf/lp_streamed/n={N},m={BUDGET},iters={N_ITERS}", us_grf,
         "O(N*m) per step")
    emit(f"grf/lp_dense_ref/n={N},iters={N_ITERS}", us_dense,
         f"O(N^2) per step, speedup={speedup:.2f}x")

    write_json("grf", {
        "n": N, "deg": DEG, "c": C, "alpha": ALPHA, "n_iters": N_ITERS,
        "budget": BUDGET, "density": graph.density,
        "kernels": {
            "grf": {
                "rel_err_at_budget": rel_err_at_budget,
                "rel_err_curve": curve,
                "grf_us": us_grf,
                "dense_us": us_dense,
                "speedup_vs_dense": speedup,
            }
        },
    })


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
