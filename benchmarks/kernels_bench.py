"""Kernel microbenchmarks: Pallas (interpret on CPU) wrappers vs jnp oracles.

On this CPU container interpret-mode timings measure correctness paths, not
TPU performance — the roofline for the kernels is in EXPERIMENTS.md §Roofline.
The oracle timings still give the paper's exact-vs-streaming memory trade.

The batched-LP section is the exception: interpret mode executes the real
kernel FLOPs, so the distance-reusing layout's ~B-fold cut in
distance/softmax work shows up even on CPU.  Its speedup over the legacy
per-batch-recompute kernel is written to ``BENCH_kernels.json`` as
``fused_lp_reuse_speedup`` and held to the committed floor in
``benchmarks/baselines.json`` by the CI bench gate.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit, timeit, write_json
from repro.core.baselines import exact_transition_matrix, streaming_exact_matvec
from repro.kernels.fused_lp import fused_lp_matvec_batched
from repro.kernels.pairwise import pairwise_sq_dists_ref

# the committed floor for fused_lp_reuse_speedup is DEFINED at this shape,
# so the batched section runs it even under BENCH_TINY/BENCH_FAST (a few
# kernel calls, ~1-2 min in interpret mode) — unlike matvec/serving there
# is no smaller shape that measures the same thing
N, D, C = 4096, 64, 4
BATCH = 8  # the acceptance shape: N=4096, B=8, C<=4


def run():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(N, D), jnp.float32)
    y = jnp.asarray(rng.randn(N, C), jnp.float32)
    sig = jnp.asarray(1.5)

    us = timeit(lambda: pairwise_sq_dists_ref(x[:1024], x[:1024]))
    emit("kernels/pairwise_ref/1024x1024", us, "jnp oracle")

    p = exact_transition_matrix(x, sig)
    us_d = timeit(lambda: p @ y)
    emit(f"kernels/exact_dense_matvec/n={N}", us_d,
         f"mem={N*N*4/1e6:.0f}MB materialized")

    us_s = timeit(lambda: streaming_exact_matvec(x, y, sig, block=512))
    emit(f"kernels/exact_streaming_matvec/n={N}", us_s,
         f"mem={N*512*4/1e6:.0f}MB streaming,ratio={us_s/max(us_d,1):.2f}x")

    # distance-reusing vs per-batch-recompute batched LP kernel: same math,
    # grid (M, N) with the batch folded into channels vs grid (B, M, N)
    ys = jnp.asarray(rng.randn(BATCH, N, C), jnp.float32)
    us_pb = timeit(lambda: fused_lp_matvec_batched(x, ys, 1.5, reuse=False))
    emit(f"kernels/fused_lp_batched_perbatch/n={N},b={BATCH},c={C}", us_pb,
         "grid (B,M,N): distances derived B times")
    us_re = timeit(lambda: fused_lp_matvec_batched(x, ys, 1.5, reuse=True))
    reuse_speedup = us_pb / max(us_re, 1e-9)
    emit(f"kernels/fused_lp_batched_reuse/n={N},b={BATCH},c={C}", us_re,
         f"grid (M,N) folded: speedup={reuse_speedup:.2f}x")

    # per-backend (per-divergence) reuse floors: the distance-reusing win
    # must hold for every divergence kernel the serving engine can dispatch,
    # not just the default sqeuclidean tile.  KL runs a smaller shape (the
    # tile itself is pricier in interpret mode); its floor in baselines.json
    # is proportionally softer.
    backends = {"sqeuclidean": {"n": N, "batch": BATCH, "c": C,
                                "perbatch_us": us_pb, "reuse_us": us_re,
                                "reuse_speedup": reuse_speedup}}
    kn, kb, kc = 1024, 4, 2
    x_pos = jnp.asarray(rng.rand(kn, D) + 0.1, jnp.float32)  # KL domain: > 0
    ys_kl = jnp.asarray(rng.rand(kb, kn, kc), jnp.float32)
    us_pb_kl = timeit(lambda: fused_lp_matvec_batched(
        x_pos, ys_kl, 1.5, reuse=False, divergence="kl"))
    us_re_kl = timeit(lambda: fused_lp_matvec_batched(
        x_pos, ys_kl, 1.5, reuse=True, divergence="kl"))
    kl_speedup = us_pb_kl / max(us_re_kl, 1e-9)
    emit(f"kernels/fused_lp_batched_reuse_kl/n={kn},b={kb},c={kc}", us_re_kl,
         f"speedup={kl_speedup:.2f}x")
    backends["kl"] = {"n": kn, "batch": kb, "c": kc,
                      "perbatch_us": us_pb_kl, "reuse_us": us_re_kl,
                      "reuse_speedup": kl_speedup}

    write_json("kernels", {
        "n": N, "batch": BATCH, "c": C,
        "perbatch_us": us_pb,
        "reuse_us": us_re,
        "fused_lp_reuse_speedup": reuse_speedup,
        "backends": backends,
        # always the full acceptance shape; never mislabeled as tiny
        "tiny": False,
    })


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
