"""Label Propagation (Zhou et al., 2003) on any transition-matrix backend.

    Y^{t+1} = alpha * P Y^t + (1 - alpha) * Y^0        (paper eq. 15)

The matvec is pluggable: VDT block matvec (O(|B|)), kNN sparse matvec
(O(kN)), dense exact (O(N^2)), or the streaming/fused kernel.  Iterations run
under ``lax.scan``.

Two entry points:

* :func:`label_propagate` — generic, takes any matvec closure.  Re-traced
  per call (the closure is fresh each time); fine for scripts and tests.
* :func:`lp_scan_leaforder` — the serving hot path.  Jitted once per
  ``(L, n_iters, shape)`` with ``alpha`` as a *traced* scalar-or-per-column
  array, so repeated serving calls hit the compile cache regardless of the
  alpha values, and requests with different alphas can share one dispatch
  (LP is column-independent, so a per-column alpha is exact).  The whole
  scan runs in leaf order: the row<->leaf permutation is applied once
  outside the scan instead of a gather + scatter per iteration.

A third entry point, :func:`lp_scan_fused`, is the **exact** counterpart of
``lp_scan_leaforder``: the same eq.-15 recursion against the exact
transition matrix P (paper eq. 3) instead of the VDT approximation Q,
served by the distance-reusing fused Pallas kernel — O(N * block) memory,
and for a batched ``(B, N, C)`` stack each pairwise-distance tile is
computed once per iteration for all B requests.  It backs
``VariationalDualTree.label_propagate(backend="exact")`` and the serving
engine's ``backend="exact"`` mode (accuracy-validation traffic at sizes
where dense P would not fit).

Segmented scans (preemptible dispatch)
--------------------------------------
Both hot-path scans have ``*_resume`` twins that enter the recursion from a
mid-walk carry instead of the seed, and ``*_segmented`` drivers that split
``n_iters`` into ``segment_iters``-sized checkpointed segments.  Eq. 15 is
a pure fixed-point iteration — ``Y^{t+1}`` depends only on ``(Y^t, Y^0,
alpha)`` — so the split is *exact*: the carry re-enters the next segment
and the composed walk is bit-identical to the monolithic scan.  The serving
engine drives segments itself (re-checking its queue between them) so a
tight-deadline arrival can preempt a long in-flight dispatch at the next
segment boundary instead of waiting out the whole scan.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.matvec import mpt_matvec_leaforder

__all__ = ["one_hot_labels", "label_propagate", "lp_scan_leaforder",
           "lp_scan_leaforder_resume", "lp_scan_leaforder_segmented",
           "lp_scan_fused", "lp_scan_fused_resume", "lp_scan_fused_segmented",
           "route_backend", "AUTO_EXACT_MAX_N", "AUTO_GRF_MAX_DENSITY",
           "AUTO_GRF_MIN_RTOL", "CONCRETE_BACKENDS", "ccr"]

# `backend="auto"` routes to the exact eq.-3 scan at or below this many
# points: one exact LP iteration is O(N^2 d) streamed, which at this scale
# costs about the same as the VDT dispatch overhead, so small problems might
# as well get the ground-truth walk.  Above it, auto traffic rides the
# fitted O(|B|) approximation.  The boundary is INCLUSIVE (n == 1024 is
# exact, n == 1025 is vdt — pinned by tests/test_grf.py), and callers with
# different exact-kernel budgets may override it per call via
# ``route_backend(..., auto_exact_max_n=...)``.
AUTO_EXACT_MAX_N = 1024

# `backend="auto"` considers the GRF walker estimator only when BOTH hold
# (boundaries inclusive):
#   * the graph is sparse enough that walkers beat dense/streamed linear
#     algebra — edge fraction nnz/N^2 at most AUTO_GRF_MAX_DENSITY (the
#     per-step costs cross around deg ~= 0.05 N: one walker step is O(m)
#     per node vs O(deg) per node for a sparse matvec with m ~ 100s);
#   * the request's accuracy target tolerates Monte-Carlo noise — rtol at
#     least AUTO_GRF_MIN_RTOL, since an m-walker mean's relative error is
#     ~1/sqrt(m) (CLT) and rtol below 5% would demand m > 400 walkers,
#     past which exact/vdt wins (see core.grf.walkers_for_rtol).
# Requests that don't state density or rtol never auto-route to grf.
AUTO_GRF_MAX_DENSITY = 0.05
AUTO_GRF_MIN_RTOL = 0.05

# widest column slab one VDT walk materializes.  Each iteration gathers and
# segment-sums (slots, K) f32 arrays over the scan table: compiled for a
# v5e at SecStr size (2^17 leaves, |B| = 4N in 344,064 slots), the scan
# holds 1.07 GB of temporaries at K = 512 and 9.9 GB, most of the chip's
# 16 GB, at the engine's widest K = 4096.  LP is column-independent, so
# wider walks run slab by slab (1.9 GB there).
VDT_MAX_COLS = 512

# the three concrete scan implementations every routing tag resolves to —
# the serving tier's validate/group-key/warmup paths all share this
# vocabulary, so a new backend lands in exactly one place
CONCRETE_BACKENDS = ("vdt", "exact", "grf")


def route_backend(requested, default: str = "vdt", *, n=None,
                  density=None, rtol=None,
                  auto_exact_max_n: int = AUTO_EXACT_MAX_N) -> str:
    """Resolve a per-request backend tag to a concrete scan implementation.

    The single routing decision behind the engine's hybrid serving (and
    ``propagate_many``): every request carries ``backend`` as ``None`` (use
    the caller's ``default``), an explicit concrete tag (``"vdt"`` /
    ``"exact"`` / ``"grf"``), or ``"auto"``.  ``"auto"`` resolves by the
    documented rule, in order:

    1. ``"grf"`` iff the graph is sparse AND the accuracy target tolerates
       Monte-Carlo noise: ``density <= AUTO_GRF_MAX_DENSITY`` and
       ``rtol >= AUTO_GRF_MIN_RTOL`` (both boundaries inclusive; a
       ``None`` density or rtol disqualifies grf — no stated sparsity or
       tolerance means no walker routing);
    2. else ``"exact"`` iff ``n <= auto_exact_max_n`` (inclusive;
       override the cutoff per call for a different exact-kernel budget);
    3. else ``"vdt"``.

    Returns a member of :data:`CONCRETE_BACKENDS`; raises ``ValueError``
    on anything else so bad tags fail at submit time, not at dispatch.
    """
    if requested is None:
        requested = default
    if requested == "auto":
        if (density is not None and rtol is not None
                and float(density) <= AUTO_GRF_MAX_DENSITY
                and float(rtol) >= AUTO_GRF_MIN_RTOL):
            return "grf"
        if n is None:
            raise ValueError("backend='auto' routing needs the problem size n")
        return "exact" if int(n) <= int(auto_exact_max_n) else "vdt"
    if requested not in CONCRETE_BACKENDS:
        raise ValueError(
            f"backend must be one of {CONCRETE_BACKENDS}, 'auto' or None, "
            f"got {requested!r}")
    return requested


def one_hot_labels(
    labels: np.ndarray, labeled_mask: np.ndarray, n_classes: int
) -> jnp.ndarray:
    """Y0: one-hot rows for labeled points, zero rows otherwise."""
    y0 = jax.nn.one_hot(jnp.asarray(labels), n_classes, dtype=jnp.float32)
    return y0 * jnp.asarray(labeled_mask, jnp.float32)[:, None]


def label_propagate(
    matvec: Callable[[jax.Array], jax.Array],
    y0: jax.Array,
    alpha: float = 0.01,
    n_iters: int = 500,
) -> jax.Array:
    """Run eq. 15 for ``n_iters`` steps; returns the final label matrix."""

    def step(y, _):
        y = alpha * matvec(y) + (1.0 - alpha) * y0
        return y, None

    y, _ = jax.lax.scan(step, y0, None, length=n_iters)
    return y


def column_slabs(walk, cols, alpha):
    """``walk(*cols, alpha)`` over slabs of at most :data:`VDT_MAX_COLS`.

    ``cols`` are ``(rows, K)`` label arrays sharing the column axis and
    ``alpha`` a scalar or ``(K,)``.  Narrow walks call ``walk`` unchanged;
    wider ones zero-pad K to whole slabs (alpha 0: the pad columns stay
    zero) and walk one slab after another, writing each into the output in
    place, so at most one slab's temporaries are live.
    """
    k = cols[0].shape[1]
    if k <= VDT_MAX_COLS:
        return walk(*cols, alpha)
    n_slabs = -(-k // VDT_MAX_COLS)
    pad = n_slabs * VDT_MAX_COLS - k
    cols = [jnp.pad(c, ((0, 0), (0, pad))) for c in cols]
    alpha = jnp.pad(jnp.broadcast_to(alpha, (k,)), (0, pad))

    def slab(i, out):
        lo = i * VDT_MAX_COLS
        part = [jax.lax.dynamic_slice_in_dim(c, lo, VDT_MAX_COLS, axis=1)
                for c in cols]
        al = jax.lax.dynamic_slice_in_dim(alpha, lo, VDT_MAX_COLS)
        return jax.lax.dynamic_update_slice_in_dim(out, walk(*part, al), lo,
                                                   axis=1)

    out = jax.lax.fori_loop(0, n_slabs, slab, jnp.zeros_like(cols[0]))
    return out[:, :k]


@functools.partial(jax.jit, static_argnames=("L", "n_iters"))
def lp_scan_leaforder(
    y0_leaf: jax.Array,      # (Np, K) seed labels in leaf order (ghosts 0)
    leaf_mask: jax.Array,    # (Np, 1) 1.0 at real leaves, 0.0 at ghosts
    a: jax.Array,            # (slots,) scan table (matvec.scan_table):
    b: jax.Array,            # (slots,)   row nodes sorted, col nodes,
    q: jax.Array,            # (slots,)   exp(log_q), 0 at the pads
    alpha: jax.Array,        # () or (K,) — traced, NOT part of the jit key
    L: int,
    n_iters: int,
) -> jax.Array:
    """Eq. 15 for ``n_iters`` steps, entirely in leaf order; returns (Np, K).

    Ghost leaves receive meaningless DistributeDown path sums, so the matvec
    term is re-masked every iteration — otherwise ghost garbage would feed
    back into the next CollectUp and corrupt real rows.  ``y0_leaf`` is zero
    at ghosts by construction, so masked rows stay identically zero and the
    caller can gather real rows with ``tree.slot_of`` afterwards.  Walks
    wider than :data:`VDT_MAX_COLS` run in column slabs.
    """

    def walk(y0_leaf, alpha):
        def step(y, _):
            y = leaf_mask * (alpha * mpt_matvec_leaforder(y, a, b, q, L)) \
                + (1.0 - alpha) * y0_leaf
            return y, None

        y, _ = jax.lax.scan(step, y0_leaf, None, length=n_iters)
        return y

    return column_slabs(walk, (y0_leaf,), alpha)


@functools.partial(jax.jit, static_argnames=("L",))
def lp_scan_leaforder_resume(
    y_leaf: jax.Array,       # (Np, K) mid-walk carry in leaf order
    y0_leaf: jax.Array,      # (Np, K) seed labels (the eq.-15 restart term)
    leaf_mask: jax.Array,    # (Np, 1) 1.0 at real leaves, 0.0 at ghosts
    a: jax.Array,
    b: jax.Array,
    q: jax.Array,
    alpha: jax.Array,
    L: int,
    n_iters,
) -> jax.Array:
    """``n_iters`` eq.-15 steps entered from a mid-walk carry ``y_leaf``.

    The segmented-dispatch primitive behind :func:`lp_scan_leaforder`: the
    per-iteration body is identical, only the loop init differs, so
    resuming from the carry of an earlier scan continues the monolithic
    walk bit-identically (``lp_scan_leaforder(y0, ...)`` is the
    ``y_leaf == y0_leaf`` special case).  Ghost rows of the carry are zero
    by the re-masking invariant, so a carry round-tripped through row order
    between segments re-enters unchanged.

    ``n_iters`` is *traced* — a dynamic ``fori_loop`` bound — so all
    segment lengths share ONE compiled executable per ``(shape, L)``: odd
    remainder segments never stall a serving dispatch on a fresh compile,
    and XLA can never constant-fold a short tail into a differently-fused
    inline body (which is what breaks length-1 bit-parity on the fused
    path; see ``kernels/fused_lp/batched.py``).
    """

    def walk(y_leaf, y0_leaf, alpha):
        def body(_, y):
            return leaf_mask * (alpha * mpt_matvec_leaforder(y, a, b, q, L)) \
                + (1.0 - alpha) * y0_leaf

        return jax.lax.fori_loop(0, n_iters, body, y_leaf)

    return column_slabs(walk, (y_leaf, y0_leaf), alpha)


def lp_scan_leaforder_segmented(
    y0_leaf: jax.Array,
    leaf_mask: jax.Array,
    a: jax.Array,
    b: jax.Array,
    q: jax.Array,
    alpha: jax.Array,
    L: int,
    n_iters: int,
    segment_iters: int,
) -> jax.Array:
    """Eq. 15 as ``ceil(n_iters / segment_iters)`` checkpointed segments.

    Bit-identical to ``lp_scan_leaforder(..., n_iters)`` — the carry of
    each segment re-enters the next via :func:`lp_scan_leaforder_resume` —
    while syncing at every segment boundary.  The parity reference for the
    engine's preemptible dispatch (which drives the same resume primitive
    but interleaves queue checks between segments).
    """
    if segment_iters < 1:
        raise ValueError(f"segment_iters must be >= 1, got {segment_iters}")
    if segment_iters >= n_iters:
        # one segment covers the walk: run the monolithic scan directly
        return lp_scan_leaforder(y0_leaf, leaf_mask, a, b, q, alpha, L,
                                 int(n_iters))
    y, done = y0_leaf, 0
    while done < n_iters:
        k = min(int(segment_iters), int(n_iters) - done)
        y = lp_scan_leaforder_resume(y, y0_leaf, leaf_mask, a, b, q, alpha,
                                     L, k)
        done += k
    return y


def lp_scan_fused(
    x: jax.Array,            # (N, d) points
    y0: jax.Array,           # (N,), (N, C) or (batch, N, C) seed labels
    sigma: float,
    alpha=0.01,
    n_iters: int = 500,
    *,
    block_m: int = 256,
    block_n: int = 256,
    divergence=None,
) -> jax.Array:
    """Eq. 15 against the EXACT transition matrix, streamed, never dense.

    The fused-kernel twin of :func:`lp_scan_leaforder`: every iteration is
    one pass of the distance-reusing Pallas kernel (see
    ``kernels/fused_lp/batched.py``), so P is never materialized and a
    batched ``(batch, N, C)`` stack pays the pairwise-distance/softmax work
    once per iteration for the whole batch, not once per request.

    ``alpha`` is traced: a scalar, per-column ``(C,)`` (2-D ``y0``), or
    per-request ``(batch,)`` (3-D ``y0``).  ``sigma``, ``n_iters``,
    ``divergence`` and the block sizes are static; repeated calls with the
    same shapes hit the jit cache — and distinct divergences always compile
    distinct executables (the divergence is part of the jit key), so mixed
    traffic cannot cross-contaminate the cache.  Returns the final labels
    in ``y0``'s shape.
    """
    # deferred so importing core never pulls the Pallas toolchain eagerly
    from repro.core.divergence import resolve_divergence
    from repro.kernels.fused_lp import fused_lp_scan_batched, fused_lp_scan_folded

    # unwrap BoundDivergence (carries tree arrays, not hashable) to the
    # hashable Divergence that rides as the static jit key
    divergence = resolve_divergence(divergence)
    y0 = jnp.asarray(y0)
    if not jnp.issubdtype(y0.dtype, jnp.floating):
        y0 = y0.astype(jnp.float32)
    sigma = float(sigma)
    if y0.ndim == 3:
        batch = y0.shape[0]
        alpha = jnp.asarray(alpha, jnp.float32)
        if alpha.ndim == 1 and alpha.shape[0] != batch:
            raise ValueError(
                f"per-request alpha wants shape ({batch},), got {alpha.shape}")
        return fused_lp_scan_batched(x, y0, sigma, alpha, int(n_iters),
                                     block_m=block_m, block_n=block_n,
                                     divergence=divergence)
    squeeze = y0.ndim == 1
    if squeeze:
        y0 = y0[:, None]
    out = fused_lp_scan_folded(x, y0, sigma, jnp.asarray(alpha, jnp.float32),
                               int(n_iters), block_m=block_m, block_n=block_n,
                               divergence=divergence)
    return out[:, 0] if squeeze else out


def lp_scan_fused_resume(
    x: jax.Array,            # (N, d) points
    y: jax.Array,            # carry, same shape family as ``y0``
    y0: jax.Array,           # (N,), (N, C) or (batch, N, C) seed labels
    sigma: float,
    alpha=0.01,
    n_iters: int = 500,
    *,
    block_m: int = 256,
    block_n: int = 256,
    divergence=None,
) -> jax.Array:
    """``n_iters`` exact eq.-15 steps entered from a mid-walk carry ``y``.

    The exact-backend segmented-dispatch primitive: same shape/alpha/static
    handling as :func:`lp_scan_fused` (which is the ``y == y0`` special
    case), but the streamed scan starts from the carry of an earlier
    segment, continuing the monolithic walk bit-identically.
    """
    from repro.core.divergence import resolve_divergence
    from repro.kernels.fused_lp import (fused_lp_scan_batched_resume,
                                        fused_lp_scan_folded_resume)

    divergence = resolve_divergence(divergence)
    y0 = jnp.asarray(y0)
    if not jnp.issubdtype(y0.dtype, jnp.floating):
        y0 = y0.astype(jnp.float32)
    y = jnp.asarray(y, y0.dtype)
    if y.shape != y0.shape:
        raise ValueError(
            f"carry shape {y.shape} must match seed shape {y0.shape}")
    sigma = float(sigma)
    if y0.ndim == 3:
        batch = y0.shape[0]
        alpha = jnp.asarray(alpha, jnp.float32)
        if alpha.ndim == 1 and alpha.shape[0] != batch:
            raise ValueError(
                f"per-request alpha wants shape ({batch},), got {alpha.shape}")
        return fused_lp_scan_batched_resume(
            x, y, y0, sigma, alpha, int(n_iters),
            block_m=block_m, block_n=block_n, divergence=divergence)
    squeeze = y0.ndim == 1
    if squeeze:
        y, y0 = y[:, None], y0[:, None]
    out = fused_lp_scan_folded_resume(
        x, y, y0, sigma, jnp.asarray(alpha, jnp.float32), int(n_iters),
        block_m=block_m, block_n=block_n, divergence=divergence)
    return out[:, 0] if squeeze else out


def lp_scan_fused_segmented(
    x: jax.Array,
    y0: jax.Array,
    sigma: float,
    alpha=0.01,
    n_iters: int = 500,
    *,
    segment_iters: int,
    block_m: int = 256,
    block_n: int = 256,
    divergence=None,
) -> jax.Array:
    """Exact eq.-15 walk as checkpointed ``segment_iters``-sized segments.

    Bit-identical to ``lp_scan_fused(..., n_iters)``; see
    :func:`lp_scan_leaforder_segmented` for the contract.
    """
    if segment_iters < 1:
        raise ValueError(f"segment_iters must be >= 1, got {segment_iters}")
    if segment_iters >= n_iters:
        # one segment covers the walk: run the monolithic scan directly
        return lp_scan_fused(x, y0, sigma, alpha, int(n_iters),
                             block_m=block_m, block_n=block_n,
                             divergence=divergence)
    y, done = y0, 0
    while done < n_iters:
        k = min(int(segment_iters), int(n_iters) - done)
        y = lp_scan_fused_resume(x, y, y0, sigma, alpha, k,
                                 block_m=block_m, block_n=block_n,
                                 divergence=divergence)
        done += k
    return y


@functools.partial(jax.jit, static_argnames=())
def _argmax(y: jax.Array) -> jax.Array:
    return jnp.argmax(y, axis=-1)


def ccr(y_final: jax.Array, labels: np.ndarray, eval_mask: np.ndarray) -> float:
    """Correct classification rate on ``eval_mask`` rows."""
    pred = np.asarray(_argmax(y_final))
    mask = np.asarray(eval_mask, bool)
    if mask.sum() == 0:
        return float("nan")
    return float((pred[mask] == np.asarray(labels)[mask]).mean())
