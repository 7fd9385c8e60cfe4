"""Paper Fig. 2A: model construction time vs problem size N.

Compares exact (O(N^2)), kNN (blocked brute force + top_k), and
VariationalDT (O(N log N) tree + O(|B|) q-opt) builds on SecStr-like data,
the paper's first experiment (synthetic surrogate, DESIGN.md §8).

Times are reported WARM (jit caches primed by a same-shape build) — the
deployment regime, and the regime where the paper's serial-CPU comparison is
meaningful; the one-off XLA compile is reported separately as `cold`.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.core.baselines import build_knn_graph, exact_transition_matrix
from repro.core.sigma import sigma_init
from repro.core.vdt import VariationalDualTree
from repro.data.synthetic import secstr_like

FAST = os.environ.get("BENCH_FAST", "0") == "1"
SIZES_EXACT = (500, 1000, 2000, 4000)
SIZES_ALL = (500, 1000, 2000, 4000) if FAST else (500, 1000, 2000, 4000,
                                                  8000, 16000)


def _cold_warm(fn):
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    cold = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    warm = (time.perf_counter() - t0) * 1e6
    return cold, warm


def run():
    data = secstr_like(n=max(SIZES_ALL), d=315)
    for n in SIZES_ALL:
        x = data.x[:n]
        sig = float(sigma_init(jnp.asarray(x)))

        def build_vdt():
            v = VariationalDualTree.fit(x, sigma=sig, learn_sigma=False)
            return v.qstate.log_q

        cold, warm = _cold_warm(build_vdt)
        emit(f"fig2a/construct/vdt/n={n}", warm, f"cold_us={cold:.0f}")
        us_vdt = warm

        xj = jnp.asarray(x)
        cold, warm = _cold_warm(
            lambda: build_knn_graph(xj, 2, jnp.asarray(sig)).weights)
        emit(f"fig2a/construct/knn2/n={n}", warm,
             f"cold_us={cold:.0f},vdt_speedup={warm / max(us_vdt, 1):.2f}x")

        if n in SIZES_EXACT:
            cold, warm = _cold_warm(
                lambda: exact_transition_matrix(xj, jnp.asarray(sig)))
            emit(f"fig2a/construct/exact/n={n}", warm,
                 f"cold_us={cold:.0f},vdt_speedup={warm / max(us_vdt, 1):.2f}x")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
