"""Paper Fig. 2D-K: refinement cost and CCR vs refinement level, on the
Digit1-like and USPS-like surrogates (1500 x 241, 2 classes), for
VariationalDT vs kNN, at 10 and 100 labels."""
from __future__ import annotations

import time

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit
from repro.core.baselines import build_knn_graph, knn_matvec
from repro.core.label_prop import ccr, label_propagate, one_hot_labels
from repro.core.vdt import VariationalDualTree
from repro.data.synthetic import digit1_like, usps_like

import os
FAST = os.environ.get("BENCH_FAST", "0") == "1"
N = 1500
ALPHA, ITERS = 0.01, 200 if FAST else 500
LEVELS = (2, 6) if FAST else (2, 4, 6, 8)   # |B| = k*N <-> kNN k


def run():
    rng = np.random.RandomState(1)
    for ds_name, ds in (("digit1", digit1_like(n=N)),
                        ("usps", usps_like(n=N))):
        x = jnp.asarray(ds.x)
        labels = ds.labels
        vdt = VariationalDualTree.fit(x)  # coarsest; sigma learned
        sig = jnp.asarray(vdt.sigma)

        for n_lab in (10, 100):
            labeled = np.zeros(N, bool)
            labeled[rng.choice(N, n_lab, replace=False)] = True
            y0 = one_hot_labels(labels, labeled, ds.n_classes)

            v = VariationalDualTree.fit(x, sigma=float(sig),
                                        learn_sigma=False)
            for k in LEVELS:
                t0 = time.perf_counter()
                v.refine(max_blocks=k * N)
                us_ref = (time.perf_counter() - t0) * 1e6
                yf = label_propagate(v.matvec, y0, ALPHA, ITERS)
                acc = ccr(yf, labels, ~labeled)
                emit(f"fig2d-k/{ds_name}/vdt/labels={n_lab}/k={k}", us_ref,
                     f"ccr={acc:.4f},blocks={v.n_blocks}")

            for k in LEVELS:
                t0 = time.perf_counter()
                g = build_knn_graph(x, k, sig)
                g.weights.block_until_ready()
                us_ref = (time.perf_counter() - t0) * 1e6
                yf = label_propagate(lambda y: knn_matvec(g, y), y0,
                                     ALPHA, ITERS)
                acc = ccr(yf, labels, ~labeled)
                emit(f"fig2d-k/{ds_name}/knn/labels={n_lab}/k={k}", us_ref,
                     f"ccr={acc:.4f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
