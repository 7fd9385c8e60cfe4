"""Tiled pairwise squared-distance Pallas kernel (TPU).

Computes D2[i, j] = ||x_i - y_j||^2 for x (M, d), y (N, d) as
``xx + yy - 2 x.y^T``: the cross term hits the MXU as a (bm, d) x (d, bn)
matmul per tile; the norm terms are rank-1 VPU adds.  Tiles are MXU-aligned
(128-multiples); the d (contraction) dimension stays whole in VMEM — for the
paper's workloads d <= 1156 so a (256, 1156) f32 tile is ~1.2 MB, well under
the ~16 MB VMEM budget for the 3 live tiles.

This is the build-time hot spot of both baselines (kNN graph construction
and the exact transition matrix) in the paper's §5 comparisons.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["pairwise_sq_dists_kernel"]


def _kernel(x_ref, y_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)      # (bm, d)
    y = y_ref[...].astype(jnp.float32)      # (bn, d)
    xx = jnp.sum(x * x, axis=-1)            # (bm,)
    yy = jnp.sum(y * y, axis=-1)            # (bn,)
    xy = jnp.dot(x, y.T, preferred_element_type=jnp.float32)  # MXU
    d2 = xx[:, None] + yy[None, :] - 2.0 * xy
    o_ref[...] = jnp.maximum(d2, 0.0)


def pairwise_sq_dists_kernel(
    x: jax.Array,
    y: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """(M, d), (N, d) -> (M, N) squared distances via pl.pallas_call."""
    m, d = x.shape
    n = y.shape[0]
    mp = -(-m // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    xp = jnp.pad(x, ((0, mp - m), (0, 0)))
    yp = jnp.pad(y, ((0, np_ - n), (0, 0)))

    out = pl.pallas_call(
        _kernel,
        grid=(mp // block_m, np_ // block_n),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(xp, yp)
    return out[:m, :n]

