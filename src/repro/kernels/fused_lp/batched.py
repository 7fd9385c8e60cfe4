"""Batched fused Label-Propagation step Pallas kernels (TPU).

One device dispatch computes, for a stack of ``batch`` independent label
matrices over the SAME point set,

    out[b] = alpha * row_softmax(-||x_i - x_j||^2 / (2 sigma^2), zero diag) @ Y[b]
             + (1 - alpha) * Y0[b]

i.e. a full eq.-15 LP update fused with the exact streaming transition
matvec, never materializing the (N, N) matrix P.  This is the multi-user
serving shape: one fitted model, many concurrent propagation problems.

Two batched layouts implement it:

* **per-batch recompute** (``fused_lp_step_batched_kernel``): grid
  ``(B, M/bm, N/bn)`` — every batch element re-derives the same ``(bm, bn)``
  distance tile and its online-softmax normalizer, so the distance/softmax
  work (the dominant term for small label widths) is paid ``B`` times.
  Kept as the A/B baseline the bench gate measures the reuse win against.

* **distance-reusing** (``fused_lp_step_folded_kernel``): the batch is
  folded into the channel axis, ``(B, N, C) -> (N, B*C)`` (the canonical
  :func:`~repro.core.matvec.fold_batch` layout), and the grid drops to
  ``(M/bm, N/bn)``.  Each distance tile and its normalizer is computed
  ONCE and applied to all ``B`` right-hand sides as a single
  ``(bm, bn) @ (bn, B*C)`` MXU matmul — the paper's "one approximated
  transition matrix amortizes across many random walks" claim realized at
  the kernel level.  FLOPs fall from ``B * N^2 * (d + C)`` to
  ``N^2 * (d + B*C)``, ~``B``-fold for ``C << d``.  Alpha rides as a
  *traced* ``(B*C,)`` per-column row (LP is column-independent), so
  heterogeneous per-request alphas share the dispatch and never grow the
  compile cache.

``fused_lp_scan_folded_kernel`` is the multi-iteration form: it pads once,
keeps ``Y`` resident on device in the folded padded layout across all LP
steps under one ``lax.scan`` (no per-step fold/unfold, no host sync), and
slices back at the end — the serving engine's exact-backend hot loop.

VMEM budget: besides the ``(bm, B*C)`` f32 accumulator, Pallas
double-buffers the ``(bn, B*C)`` label tile and the ``(bm, B*C)`` seed and
output tiles, so a step holds about ``7 * 256 * K * 4`` bytes at folded
width ``K = B*C`` with 256-row tiles.  Compiled for a v5e, ``K = 1024``
fits Mosaic's 16 MiB default scoped limit, while ``K = 4096`` (the
engine's widest layout, ``max_batch=32`` x width 128) needs 29.5 MiB at
``d = 315``; :func:`~repro.kernels.fused_lp.fused_lp.vmem_params` raises
the limit for such widths (``tests/test_tpu_compile.py`` pins both).

Grid iteration order: cols innermost; VMEM scratch carries the running max
m, normalizer s and weighted accumulator acc across column tiles; the last
column tile applies the fused axpy epilogue ``alpha * acc / s +
(1 - alpha) * y0`` and writes out.  Scratch is re-initialized at every row
tile since the column axis is the fastest-varying grid dimension.

``alpha=1.0`` degenerates to a plain batched matvec (the ``(1-alpha) * Y0``
term vanishes), which is how ``ops.fused_lp_matvec_batched`` calls it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.matvec import fold_batch, unfold_batch
from repro.kernels.fused_lp.fused_lp import (NEG_BIG, stream_tile_update,
                                             tile_config, vmem_params)

__all__ = [
    "fused_lp_step_batched_kernel",
    "fused_lp_step_folded_kernel",
    "fused_lp_step_batched_reuse_kernel",
    "fused_lp_scan_folded_kernel",
    "fused_lp_scan_folded_resume_kernel",
    "fused_lp_scan_batched_reuse_kernel",
    "fused_lp_scan_batched_resume_kernel",
]


# --------------------------------------------------- per-batch recompute path
def _kernel(rows_ref, cols_ref, y_ref, y0_ref, o_ref, m_ref, s_ref, acc_ref,
            *, inv_two_sigma_sq: float, alpha: float, n_valid: int,
            block_m: int, block_n: int, tile_fn=None):
    i = pl.program_id(1)
    j = pl.program_id(2)
    ncols = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_BIG)
        s_ref[...] = jnp.zeros_like(s_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    stream_tile_update(rows_ref, cols_ref, y_ref[0], m_ref, s_ref, acc_ref,
                       i, j, inv_two_sigma_sq=inv_two_sigma_sq,
                       n_valid=n_valid, block_m=block_m, block_n=block_n,
                       tile_fn=tile_fn)

    @pl.when(j == ncols - 1)
    def _finish():
        py = acc_ref[...] / jnp.maximum(s_ref[...], 1e-38)[:, None]
        out = alpha * py + (1.0 - alpha) * y0_ref[0].astype(jnp.float32)
        o_ref[...] = out[None].astype(o_ref.dtype)


def fused_lp_step_batched_kernel(
    x: jax.Array,          # (N, d)   shared points
    y: jax.Array,          # (B, N, C) stacked current label matrices
    y0: jax.Array,         # (B, N, C) stacked seed label matrices
    sigma: float,
    alpha: float = 1.0,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    divergence=None,
) -> jax.Array:
    """Per-batch-recompute baseline: grid (B, M, N), divergences derived B times.

    Prefer :func:`fused_lp_step_batched_reuse_kernel`; this survives as the
    A/B reference the bench gate holds the reuse kernel's win against.
    """
    tile_fn, pad, transform = tile_config(divergence)
    if transform is not None:
        x = transform(x)
    n, d = x.shape
    batch, _, c = y.shape
    mp = -(-n // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    xp_rows = jnp.pad(x, ((0, mp - n), (0, 0)), constant_values=pad)
    xp_cols = jnp.pad(x, ((0, np_ - n), (0, 0)), constant_values=pad)
    yp = jnp.pad(y, ((0, 0), (0, np_ - n), (0, 0)))
    y0p = jnp.pad(y0, ((0, 0), (0, mp - n), (0, 0)))

    kern = functools.partial(
        _kernel,
        inv_two_sigma_sq=float(1.0 / (2.0 * sigma * sigma)),
        alpha=float(alpha),
        n_valid=n, block_m=block_m, block_n=block_n, tile_fn=tile_fn,
    )
    out = pl.pallas_call(
        kern,
        grid=(batch, mp // block_m, np_ // block_n),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda b, i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda b, i, j: (j, 0)),
            pl.BlockSpec((1, block_n, c), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_m, c), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, c), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, mp, c), y.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m,), jnp.float32),
            pltpu.VMEM((block_m,), jnp.float32),
            pltpu.VMEM((block_m, c), jnp.float32),
        ],
        interpret=interpret,
    )(xp_rows, xp_cols, yp, y0p)
    return out[:, :n]


# ----------------------------------------------------- distance-reusing path
def _folded_body(rows_ref, cols_ref, y_ref, y0_ref, alpha_ref, o_ref,
                 m_ref, s_ref, acc_ref, *, inv_two_sigma_sq: float,
                 n_valid: int, block_m: int, block_n: int, tile_fn=None,
                 row_base=0):
    i = pl.program_id(0)
    j = pl.program_id(1)
    ncols = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_BIG)
        s_ref[...] = jnp.zeros_like(s_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # one divergence tile + normalizer update for ALL folded columns at once
    stream_tile_update(rows_ref, cols_ref, y_ref[...], m_ref, s_ref, acc_ref,
                       i, j, inv_two_sigma_sq=inv_two_sigma_sq,
                       n_valid=n_valid, block_m=block_m, block_n=block_n,
                       tile_fn=tile_fn, row_base=row_base)

    @pl.when(j == ncols - 1)
    def _finish():
        py = acc_ref[...] / jnp.maximum(s_ref[...], 1e-38)[:, None]
        al = alpha_ref[0].astype(jnp.float32)[None, :]   # (1, K) per-column
        out = al * py + (1.0 - al) * y0_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


def _folded_kernel(rows_ref, cols_ref, y_ref, y0_ref, alpha_ref, o_ref,
                   m_ref, s_ref, acc_ref, **kw):
    _folded_body(rows_ref, cols_ref, y_ref, y0_ref, alpha_ref, o_ref,
                 m_ref, s_ref, acc_ref, **kw)


def _folded_kernel_rb(rows_ref, cols_ref, y_ref, y0_ref, alpha_ref, rb_ref,
                      o_ref, m_ref, s_ref, acc_ref, **kw):
    # row_base rides as a (1, 1) int32 operand so it may be traced (the
    # sharded engine derives it from lax.axis_index inside shard_map)
    _folded_body(rows_ref, cols_ref, y_ref, y0_ref, alpha_ref, o_ref,
                 m_ref, s_ref, acc_ref, row_base=rb_ref[0, 0], **kw)


def _folded_call(xp_rows, xp_cols, yp, y0p, alpha_row, *,
                 inv_two_sigma_sq: float, n_valid: int,
                 block_m: int, block_n: int, interpret: bool,
                 tile_fn=None, row_base=None) -> jax.Array:
    """pallas_call on already-padded folded operands; returns padded rows.

    ``row_base`` (optional, traced or concrete int32) is the global row id
    of ``xp_rows``'s first row when the row operand is a stripe of the full
    point set; ``None`` keeps the classic whole-matrix program untouched.
    """
    mp, d = xp_rows.shape
    np_ = xp_cols.shape[0]
    k = yp.shape[1]
    kw = dict(inv_two_sigma_sq=inv_two_sigma_sq, n_valid=n_valid,
              block_m=block_m, block_n=block_n, tile_fn=tile_fn)
    in_specs = [
        pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
        pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        pl.BlockSpec((block_n, k), lambda i, j: (j, 0)),
        pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
        pl.BlockSpec((1, k), lambda i, j: (0, 0)),
    ]
    operands = [xp_rows, xp_cols, yp, y0p, alpha_row]
    if row_base is None:
        kern = functools.partial(_folded_kernel, **kw)
    else:
        kern = functools.partial(_folded_kernel_rb, **kw)
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j: (0, 0)))
        operands.append(jnp.asarray(row_base, jnp.int32).reshape(1, 1))
    return pl.pallas_call(
        kern,
        grid=(mp // block_m, np_ // block_n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, k), yp.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m,), jnp.float32),
            pltpu.VMEM((block_m,), jnp.float32),
            pltpu.VMEM((block_m, k), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=vmem_params(block_m, block_n, d, k),
    )(*operands)


def _alpha_row(alpha, k: int) -> jax.Array:
    """Broadcast scalar / per-column alpha to the (1, K) kernel operand."""
    return jnp.broadcast_to(
        jnp.asarray(alpha, jnp.float32).reshape(-1), (k,))[None]


def fused_lp_step_folded_kernel(
    x: jax.Array,          # (N, d)   shared points
    y: jax.Array,          # (N, K)   folded current labels (K = B*C)
    y0: jax.Array,         # (N, K)   folded seed labels
    sigma: float,
    alpha=1.0,             # traced scalar or (K,) per-column
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    divergence=None,
) -> jax.Array:
    """One eq.-15 step in the folded layout; each divergence tile computed once."""
    tile_fn, pad, transform = tile_config(divergence)
    if transform is not None:
        x = transform(x)
    n, _ = x.shape
    k = y.shape[1]
    mp = -(-n // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    out = _folded_call(
        jnp.pad(x, ((0, mp - n), (0, 0)), constant_values=pad),
        jnp.pad(x, ((0, np_ - n), (0, 0)), constant_values=pad),
        jnp.pad(y, ((0, np_ - n), (0, 0))),
        jnp.pad(y0, ((0, mp - n), (0, 0))),
        _alpha_row(alpha, k),
        inv_two_sigma_sq=float(1.0 / (2.0 * sigma * sigma)),
        n_valid=n, block_m=block_m, block_n=block_n, interpret=interpret,
        tile_fn=tile_fn,
    )
    return out[:n]


def fused_lp_step_batched_reuse_kernel(
    x: jax.Array,          # (N, d)   shared points
    y: jax.Array,          # (B, N, C) stacked current label matrices
    y0: jax.Array,         # (B, N, C) stacked seed label matrices
    sigma: float,
    alpha=1.0,             # traced scalar or (B,) per-request
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    divergence=None,
) -> jax.Array:
    """Distance-reusing batched eq.-15 step: fold, one grid pass, unfold."""
    batch, _, c = y.shape
    alpha = jnp.asarray(alpha, jnp.float32)
    if alpha.ndim == 1:
        # folded column b*C + ch belongs to request b (see fold_batch)
        alpha = jnp.repeat(alpha, c)
    out = fused_lp_step_folded_kernel(
        x, fold_batch(y), fold_batch(y0), sigma, alpha,
        block_m=block_m, block_n=block_n, interpret=interpret,
        divergence=divergence,
    )
    return unfold_batch(out, batch, c)


# ------------------------------------------------------ multi-iteration scan
def fused_lp_scan_folded_resume_kernel(
    x: jax.Array,          # (N, d)
    y: jax.Array,          # (N, K) folded carry: the walk state entering
    y0: jax.Array,         # (N, K) folded seed labels (eq.-15 restart term)
    sigma: float,
    alpha,                 # traced scalar or (K,)
    n_iters,               # TRACED segment length (or concrete int)
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    divergence=None,
) -> jax.Array:
    """``n_iters`` fused eq.-15 steps entered from a mid-walk carry ``y``.

    The segmented-dispatch primitive: eq. 15 is a pure fixed-point
    iteration, so running ``n_iters`` steps from the carry of an earlier
    scan continues the monolithic walk *bit-identically* — the per-step
    body is the same program, only the init differs.  ``n_iters`` is a
    *dynamic* ``fori_loop`` bound, deliberately: a static length-1 tail
    segment would let XLA inline the single trip and fuse its epilogue
    differently (observed 1-ulp drift), and every distinct static segment
    length would compile its own executable.  A dynamic bound keeps one
    while-loop executable per shape whose body is the very program the
    monolithic ``lax.scan`` runs, whatever the segment split.

    Rows past ``n`` hold epilogue garbage mid-scan, but the column mask
    (``col >= n_valid``) keeps padding out of every accumulation, so a
    carry re-padded with zeros between segments changes nothing in the
    valid region; the final slice drops pad rows.
    """
    tile_fn, pad, transform = tile_config(divergence)
    if transform is not None:
        x = transform(x)
    n, _ = x.shape
    k = y0.shape[1]
    tile = math.lcm(block_m, block_n)
    sp = -(-n // tile) * tile
    xp = jnp.pad(x, ((0, sp - n), (0, 0)), constant_values=pad)
    yp = jnp.pad(y, ((0, sp - n), (0, 0)))
    y0p = jnp.pad(y0, ((0, sp - n), (0, 0)))
    al = _alpha_row(alpha, k)
    inv = float(1.0 / (2.0 * sigma * sigma))

    def body(_, yc):
        return _folded_call(xp, xp, yc, y0p, al, inv_two_sigma_sq=inv,
                            n_valid=n, block_m=block_m, block_n=block_n,
                            interpret=interpret, tile_fn=tile_fn)

    yc = jax.lax.fori_loop(0, n_iters, body, yp)
    return yc[:n]


def fused_lp_scan_folded_kernel(
    x: jax.Array,          # (N, d)
    y0: jax.Array,         # (N, K) folded seed labels
    sigma: float,
    alpha,                 # traced scalar or (K,)
    n_iters: int,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    divergence=None,
) -> jax.Array:
    """``n_iters`` fused eq.-15 steps with Y resident across iterations.

    Pads once to a common row/col tile multiple so the step's padded output
    feeds straight back as the next step's padded input — the ``lax.scan``
    carries Y in the folded on-device layout, never re-padding, re-folding,
    or touching the host between steps.  Rows past ``n`` hold epilogue
    garbage mid-scan, but the column mask (``col >= n_valid``) keeps them
    out of every accumulation; the final slice drops them.
    """
    tile_fn, pad, transform = tile_config(divergence)
    if transform is not None:
        x = transform(x)
    n, _ = x.shape
    k = y0.shape[1]
    tile = math.lcm(block_m, block_n)
    sp = -(-n // tile) * tile
    xp = jnp.pad(x, ((0, sp - n), (0, 0)), constant_values=pad)
    y0p = jnp.pad(y0, ((0, sp - n), (0, 0)))
    al = _alpha_row(alpha, k)
    inv = float(1.0 / (2.0 * sigma * sigma))

    def step(y, _):
        y = _folded_call(xp, xp, y, y0p, al, inv_two_sigma_sq=inv,
                         n_valid=n, block_m=block_m, block_n=block_n,
                         interpret=interpret, tile_fn=tile_fn)
        return y, None

    y, _ = jax.lax.scan(step, y0p, None, length=n_iters)
    return y[:n]


def fused_lp_scan_batched_reuse_kernel(
    x: jax.Array,          # (N, d)
    y0: jax.Array,         # (B, N, C) stacked seed labels
    sigma: float,
    alpha,                 # traced scalar or (B,)
    n_iters: int,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    divergence=None,
) -> jax.Array:
    """Whole batched LP run: fold once, scan the reuse step, unfold once."""
    batch, _, c = y0.shape
    alpha = jnp.asarray(alpha, jnp.float32)
    if alpha.ndim == 1:
        alpha = jnp.repeat(alpha, c)
    out = fused_lp_scan_folded_kernel(
        x, fold_batch(y0), sigma, alpha, n_iters,
        block_m=block_m, block_n=block_n, interpret=interpret,
        divergence=divergence,
    )
    return unfold_batch(out, batch, c)


def fused_lp_scan_batched_resume_kernel(
    x: jax.Array,          # (N, d)
    y: jax.Array,          # (B, N, C) stacked mid-walk carries
    y0: jax.Array,         # (B, N, C) stacked seed labels
    sigma: float,
    alpha,                 # traced scalar or (B,)
    n_iters: int,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
    divergence=None,
) -> jax.Array:
    """Batched LP segment from a carry: fold both operands, resume, unfold."""
    batch, _, c = y0.shape
    alpha = jnp.asarray(alpha, jnp.float32)
    if alpha.ndim == 1:
        alpha = jnp.repeat(alpha, c)
    out = fused_lp_scan_folded_resume_kernel(
        x, fold_batch(y), fold_batch(y0), sigma, alpha, n_iters,
        block_m=block_m, block_n=block_n, interpret=interpret,
        divergence=divergence,
    )
    return unfold_batch(out, batch, c)
