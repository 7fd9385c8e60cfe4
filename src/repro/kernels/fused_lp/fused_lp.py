"""Fused "flash" transition matvec Pallas kernel (TPU).

Computes one exact Label-Propagation matvec

    out = row_softmax(-||x_i - x_j||^2 / (2 sigma^2), zero diagonal) @ Y

in a single pass with online max/normalizer (flash-attention style), never
materializing the (N, N) transition matrix P.  This is the beyond-paper TPU
contribution: it turns the paper's O(N^2)-memory "exact" baseline into an
O(N * block) VMEM-resident streaming computation, so the exact model runs at
sizes where P itself could never be stored.

Grid: (M/bm rows, N/bn cols), cols innermost.  VMEM scratch carries the
running max m, normalizer s, and the weighted accumulator acc across column
tiles; the last column tile writes acc / s.

The distance cross-term x @ x_colsᵀ is an MXU matmul; bm/bn are 128-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_lp_matvec_kernel", "stream_tile_update", "NEG_BIG",
           "tile_config", "vmem_params"]

NEG_BIG = -1e30

# tile footprints up to this keep Mosaic's default scoped-VMEM limit
# (16 MiB on v5e); larger ones raise it, to at most _VMEM_MAX of a v5e
# TensorCore's 128 MiB
_VMEM_KEEP_DEFAULT = 12 * 2**20
_VMEM_MAX = 100 * 2**20


def vmem_params(block_m: int, block_n: int, d: int, k: int):
    """Compiler params that fit a streaming tile of label width ``k``.

    The footprint is the double-buffered row/column point tiles and
    ``(block, k)`` value, seed and output tiles plus the f32 accumulator —
    about 29.5 MiB at ``k = 4096``, ``d = 315`` with 256-row tiles, past
    Mosaic's 16 MiB default.  Widths whose footprint stays under 12 MiB
    (every ``k <= 1024`` at ``d <= 500``) keep the default and so compile
    to the same program as before; wider ones get twice their footprint,
    room for Mosaic's own temporaries.
    """
    need = 4 * (2 * (block_m + block_n) * d
                + 2 * (block_n + 2 * block_m) * k + block_m * k)
    if need <= _VMEM_KEEP_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(2 * need, _VMEM_MAX))


def tile_config(divergence):
    """``(tile_fn, pad_value, transform)`` for a divergence spec.

    ``tile_fn=None`` selects the inline squared-Euclidean tile in
    :func:`stream_tile_update` — chosen for the default Gaussian (keeping it
    bit-identical to the pre-Bregman kernels) AND for divergences that are
    squared Euclidean after a point pre-map (e.g. Mahalanobis), whose
    ``transform`` the caller applies to the point array *outside* the Pallas
    body — tile functions must not close over array constants, which Pallas
    kernels reject.  Other divergences (KL, Itakura-Saito) supply their
    traced tile function plus the in-domain value points are padded with.
    """
    from repro.core.divergence import resolve_divergence

    div = resolve_divergence(divergence)
    if div.name == "sqeuclidean":
        return None, 0.0, None  # identity transform: skip the extra op
    if div.euclidean_after_transform:
        return None, div.pad_value, div.transform_points
    return div.tile, div.pad_value, div.transform_points


def stream_tile_update(rows_ref, cols_ref, y_tile, m_ref, s_ref, acc_ref,
                       i, j, *, inv_two_sigma_sq: float, n_valid: int,
                       block_m: int, block_n: int, tile_fn=None,
                       row_base=0):
    """One column-tile step of the online-softmax streaming recurrence.

    Shared body of the single-RHS and batched fused-LP kernels: computes
    the tile's masked logits and folds them into the running max m,
    normalizer s and accumulator acc (acc += p @ y_tile).  ``y_tile`` is
    the already-indexed (block_n, C) value tile.  Callers own scratch init
    (at j == 0) and the finishing epilogue (at the last j).

    ``tile_fn`` generalizes the similarity: given the f32 ``(bm, d)`` row
    and ``(bn, d)`` column point tiles it returns the ``(bm, bn)``
    divergence tile (see ``core.divergence.Divergence.tile``).  ``None``
    keeps the built-in squared-Euclidean tile — the default Gaussian path,
    byte-for-byte the pre-Bregman kernel.

    ``row_base`` shifts the *global* row identity of this grid's row
    blocks: the self-transition mask compares ``row_base + i*block_m +
    local`` against column ids.  A caller whose row operand is a slice of
    the full point set (the sharded engine hands each device its own row
    stripe, so every device's ``i`` restarts at 0) passes the stripe's
    global offset; the default 0 is the classic whole-matrix grid.
    """
    x = rows_ref[...].astype(jnp.float32)          # (bm, d)
    xc = cols_ref[...].astype(jnp.float32)         # (bn, d)
    if tile_fn is None:
        xx = jnp.sum(x * x, axis=-1)
        cc = jnp.sum(xc * xc, axis=-1)
        d2 = xx[:, None] + cc[None, :] - 2.0 * jnp.dot(
            x, xc.T, preferred_element_type=jnp.float32)
    else:
        d2 = tile_fn(x, xc)
    logits = -jnp.maximum(d2, 0.0) * inv_two_sigma_sq

    row_ids = row_base + i * block_m + jax.lax.broadcasted_iota(
        jnp.int32, (block_m, block_n), 0)
    col_ids = j * block_n + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_m, block_n), 1)
    invalid = (row_ids == col_ids) | (col_ids >= n_valid)
    logits = jnp.where(invalid, NEG_BIG, logits)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[:, None])
    s_ref[...] = s_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jnp.dot(p, y_tile.astype(jnp.float32),
                              preferred_element_type=jnp.float32))
    m_ref[...] = m_new


def _kernel(rows_ref, cols_ref, y_ref, o_ref, m_ref, s_ref, acc_ref,
            *, inv_two_sigma_sq: float, n_valid: int, block_m: int,
            block_n: int, tile_fn=None):
    i = pl.program_id(0)
    j = pl.program_id(1)
    ncols = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_BIG)
        s_ref[...] = jnp.zeros_like(s_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    stream_tile_update(rows_ref, cols_ref, y_ref[...], m_ref, s_ref, acc_ref,
                       i, j, inv_two_sigma_sq=inv_two_sigma_sq,
                       n_valid=n_valid, block_m=block_m, block_n=block_n,
                       tile_fn=tile_fn)

    @pl.when(j == ncols - 1)
    def _finish():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(s_ref[...], 1e-38)[:, None]).astype(
                          o_ref.dtype)


def fused_lp_matvec_kernel(
    x: jax.Array,          # (N, d)
    y: jax.Array,          # (N, C)
    sigma: float,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    divergence=None,
) -> jax.Array:
    """P @ Y without materializing P.  O(N^2 d) FLOPs, O(N*block) memory.

    ``divergence`` swaps the tile similarity from ``||a-b||^2`` to any
    registered Bregman divergence; point padding uses the divergence's
    in-domain pad value (masked out of every accumulation by the column
    mask) so KL/IS tiles stay finite on the padded rows/cols.
    """
    tile_fn, pad, transform = tile_config(divergence)
    if transform is not None:
        x = transform(x)
    n, d = x.shape
    c = y.shape[1]
    mp = -(-n // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    xp_rows = jnp.pad(x, ((0, mp - n), (0, 0)), constant_values=pad)
    xp_cols = jnp.pad(x, ((0, np_ - n), (0, 0)), constant_values=pad)
    yp = jnp.pad(y, ((0, np_ - n), (0, 0)))

    kern = functools.partial(
        _kernel,
        inv_two_sigma_sq=float(1.0 / (2.0 * sigma * sigma)),
        n_valid=n, block_m=block_m, block_n=block_n, tile_fn=tile_fn,
    )
    out = pl.pallas_call(
        kern,
        grid=(mp // block_m, np_ // block_n),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, c), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, c), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, c), y.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m,), jnp.float32),
            pltpu.VMEM((block_m,), jnp.float32),
            pltpu.VMEM((block_m, c), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=vmem_params(block_m, block_n, d, c),
    )(xp_rows, xp_cols, yp)
    return out[:n]
