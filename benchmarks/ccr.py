"""Paper Fig. 2C: LP classification accuracy vs problem size, 10% labels,
exact vs kNN vs VariationalDT under identical conditions."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit
from repro.core.baselines import (build_knn_graph, exact_transition_matrix,
                                  knn_matvec)
from repro.core.label_prop import ccr, label_propagate, one_hot_labels
from repro.core.vdt import VariationalDualTree
from repro.data.synthetic import digit1_like

import os
FAST = os.environ.get("BENCH_FAST", "0") == "1"
SIZES = (500, 1500) if FAST else (250, 500, 1000, 1500)
ALPHA, ITERS = 0.01, 200 if FAST else 500


def run():
    data = digit1_like(n=max(SIZES))
    rng = np.random.RandomState(0)
    for n in SIZES:
        x = jnp.asarray(data.x[:n])
        labels = data.labels[:n]
        labeled = np.zeros(n, bool)
        labeled[rng.choice(n, max(n // 10, 2), replace=False)] = True
        y0 = one_hot_labels(labels, labeled, data.n_classes)

        vdt = VariationalDualTree.fit(x, max_blocks=4 * n)
        sig = jnp.asarray(vdt.sigma)
        yf = label_propagate(vdt.matvec, y0, ALPHA, ITERS)
        acc_v = ccr(yf, labels, ~labeled)
        emit(f"fig2c/ccr/vdt/n={n}", 0.0, f"ccr={acc_v:.4f}")

        g = build_knn_graph(x, 4, sig)
        yf = label_propagate(lambda y: knn_matvec(g, y), y0, ALPHA, ITERS)
        emit(f"fig2c/ccr/knn4/n={n}", 0.0,
             f"ccr={ccr(yf, labels, ~labeled):.4f}")

        p = exact_transition_matrix(x, sig)
        yf = label_propagate(lambda y: p @ y, y0, ALPHA, ITERS)
        emit(f"fig2c/ccr/exact/n={n}", 0.0,
             f"ccr={ccr(yf, labels, ~labeled):.4f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
