"""GRF backend: unbiased Monte-Carlo transition-matrix action by random walks.

The third serving backend (graph random features, arXiv:2305.00156 /
2410.10368).  Where ``"vdt"`` serves the fitted variational approximation Q
and ``"exact"`` streams the dense eq.-3 matrix P, ``"grf"`` never touches
P's rows at all: every node launches ``n_walkers`` terminating random
walks over a sparse CSR neighbor table, and the load-weighted walker mean

    est[i, :] = (1/m) * sum_w load_t[i, w] * Y[pos_t[i, w], :]

is an **unbiased** estimate of ``(P^t @ Y)[i, :]`` (see
``kernels/grf/walkers.py`` for the importance-weighting argument).  Cost
per step is O(N * m) — independent of edge count and of N^2 — which opens
sparse-graph workloads the dual tree cannot touch and gives a per-request
accuracy dial: the relative error of an m-walker mean scales as
``O(1 / sqrt(m))`` (CLT), so ``m ~= 1 / rtol^2`` walkers buy a target
relative tolerance (:func:`walkers_for_rtol`).

Label propagation composes from walk prefixes.  Unrolling eq. 15,

    Y_T = sum_{t<T} (1-a) a^t P^t Y_0  +  a^T P^T Y_0,

so ONE walk set of horizon T estimates every term at once: the step-t
walker population estimates ``P^t Y_0``, weighted by the series
coefficient ``(1-a) a^t`` (or ``a^T`` for the final term).
:func:`grf_label_propagate` streams this: one ``lax.scan`` advances the
walkers and accumulates coefficient-weighted feature products, O(N * m)
memory, never storing walk histories.  Per-column coefficients make
heterogeneous alphas exact in one dispatch (LP is column-independent),
matching the serving tier's coalescing contract.

Graphs come in two ways: natively sparse via :meth:`CSRGraph.from_csr`
(neighbor lists — the workload this backend exists for), or bridged from
the existing point-cloud path via :meth:`CSRGraph.from_points`, which
materializes the dense eq.-3 kernel row-softmax once (O(N^2) — fine at
validation sizes, and what makes GRF differentially testable against the
exact backend).  Positive-domain Bregman divergences (KL, Itakura-Saito)
are rejected: their kernel rows need the dual-tree subtree-stats
machinery at every visited node, which a walker does not carry.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.grf.walkers import sample_walks as _sample_walks
from repro.kernels.grf.walkers import walk_step, walker_mean

__all__ = ["CSRGraph", "DEFAULT_N_WALKERS", "MAX_RTOL_WALKERS",
           "walkers_for_rtol", "sample_walks", "grf_transition_action",
           "grf_label_propagate"]

# serving default walker budget: rel. error ~ 1/sqrt(64) = 12.5% per step
# estimate — the latency-lean end of the dial; requests wanting tighter
# pass n_walkers or rtol explicitly
DEFAULT_N_WALKERS = 64

# cap on rtol-derived budgets: 1/rtol^2 explodes as rtol -> 0, and a
# request wanting that much accuracy should ride "exact"/"vdt" instead
# (route_backend("auto") refuses grf below AUTO_GRF_MIN_RTOL for the same
# reason) — the cap just keeps an explicit backend="grf" + tiny-rtol
# request from allocating an absurd walker population
MAX_RTOL_WALKERS = 4096


def walkers_for_rtol(rtol: float) -> int:
    """Walker budget for a target relative tolerance: ``ceil(1 / rtol^2)``.

    CLT sizing: the m-walker mean's relative standard error is
    ``sigma_rel / sqrt(m)`` with ``sigma_rel = O(1)`` for row-stochastic
    loads, so ``m = 1 / rtol^2`` puts one standard error at ``rtol``.
    Clamped to ``[1, MAX_RTOL_WALKERS]``.
    """
    rtol = float(rtol)
    if not (rtol > 0.0):
        raise ValueError(f"rtol must be > 0, got {rtol}")
    return max(1, min(MAX_RTOL_WALKERS, math.ceil(1.0 / (rtol * rtol))))


def _check_divergence(divergence) -> None:
    from repro.core.divergence import resolve_divergence

    div = resolve_divergence(divergence)
    if not div.euclidean_after_transform:
        raise ValueError(
            f"backend='grf' does not support divergence {div.name!r}: "
            f"positive-domain Bregman kernels (kl, itakura_saito) need the "
            f"dual-tree subtree-stats factorization at every visited node, "
            f"which a random walker does not carry; use backend='vdt' or "
            f"'exact'")


@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """A row-stochastic sparse transition matrix in padded device layout.

    ``nbr[i, k]`` / ``prob[i, k]`` are node i's k-th neighbor and its
    transition probability for ``k < deg[i]`` (padding slots hold
    neighbor 0 with probability 0 — inert under the walkers' load
    weighting).  Rows are normalized to sum to 1 at construction, so the
    dense scatter :meth:`dense_p` is row-stochastic by construction.
    """

    nbr: jax.Array    # (N, max_deg) int32 padded neighbor table
    prob: jax.Array   # (N, max_deg) f32 transition probs, padding 0
    deg: jax.Array    # (N,) int32 true neighbor counts
    n: int
    nnz: int

    @property
    def max_deg(self) -> int:
        return int(self.nbr.shape[1])

    @property
    def density(self) -> float:
        """Edge fraction ``nnz / N^2`` — the :func:`route_backend` signal."""
        return self.nnz / float(self.n * self.n)

    @classmethod
    def from_csr(cls, indptr, indices, weights=None) -> "CSRGraph":
        """Build from CSR neighbor lists; weights default to uniform.

        Validates the structure a random walk needs: monotone ``indptr``,
        in-range ``indices``, every row at least one outgoing edge (a
        dangling node has no transition distribution), and non-negative
        finite ``weights`` with positive row sums.
        """
        indptr = np.asarray(indptr, np.int64)
        indices = np.asarray(indices, np.int64)
        if indptr.ndim != 1 or indptr.size < 2:
            raise ValueError(f"indptr must be (N+1,), got {indptr.shape}")
        n = indptr.size - 1
        deg = np.diff(indptr)
        if indptr[0] != 0 or indptr[-1] != indices.size or (deg < 0).any():
            raise ValueError("indptr must be monotone from 0 to len(indices)")
        if (deg < 1).any():
            rows = np.nonzero(deg < 1)[0][:5].tolist()
            raise ValueError(
                f"every node needs >= 1 outgoing edge for a random walk; "
                f"rows {rows} have none")
        if indices.size and (indices.min() < 0 or indices.max() >= n):
            raise ValueError(f"indices must lie in [0, {n}), got range "
                             f"[{indices.min()}, {indices.max()}]")
        if weights is None:
            weights = np.ones(indices.size, np.float64)
        else:
            weights = np.asarray(weights, np.float64)
            if weights.shape != indices.shape:
                raise ValueError(
                    f"weights shape {weights.shape} != indices "
                    f"shape {indices.shape}")
            if not np.isfinite(weights).all() or (weights < 0).any():
                raise ValueError("weights must be finite and >= 0")
        max_deg = int(deg.max())
        mask = np.arange(max_deg)[None, :] < deg[:, None]   # (N, max_deg)
        nbr = np.zeros((n, max_deg), np.int32)
        nbr[mask] = indices                      # CSR order is row-major
        w = np.zeros((n, max_deg), np.float64)
        w[mask] = weights
        row_sum = w.sum(axis=1)
        if (row_sum <= 0).any():
            rows = np.nonzero(row_sum <= 0)[0][:5].tolist()
            raise ValueError(
                f"rows {rows} have zero total weight — no transition "
                f"distribution to walk")
        prob = (w / row_sum[:, None]).astype(np.float32)
        return cls(nbr=jnp.asarray(nbr), prob=jnp.asarray(prob),
                   deg=jnp.asarray(deg.astype(np.int32)), n=n,
                   nnz=int(deg.sum()))

    @classmethod
    def from_dense(cls, p, atol: float = 0.0) -> "CSRGraph":
        """Sparsify a dense transition matrix (entries ``> atol`` kept)."""
        p = np.asarray(p, np.float64)
        if p.ndim != 2 or p.shape[0] != p.shape[1]:
            raise ValueError(f"p must be square (N, N), got {p.shape}")
        keep = p > atol
        rows, cols = np.nonzero(keep)
        indptr = np.zeros(p.shape[0] + 1, np.int64)
        np.cumsum(np.bincount(rows, minlength=p.shape[0]), out=indptr[1:])
        return cls.from_csr(indptr, cols, p[rows, cols])

    @classmethod
    def from_points(cls, x, sigma, divergence=None) -> "CSRGraph":
        """Bridge from the point-cloud path: the dense eq.-3 kernel graph.

        Materializes the row-softmax transition matrix once (O(N^2) —
        validation/analysis sizes), so GRF estimates converge to exactly
        the matrix the ``"exact"`` backend walks.  Raises ``ValueError``
        for positive-domain divergences (see module docstring).
        """
        from repro.kernels.fused_lp.ref import dense_transition_ref

        _check_divergence(divergence)
        p = np.asarray(dense_transition_ref(x, float(sigma),
                                            divergence=divergence))
        return cls.from_dense(p)

    def dense_p(self) -> np.ndarray:
        """Scatter back to the dense ``(N, N)`` matrix — the test oracle."""
        deg = np.asarray(self.deg)
        mask = np.arange(self.max_deg)[None, :] < deg[:, None]
        p = np.zeros((self.n, self.n), np.float32)
        rows = np.broadcast_to(np.arange(self.n)[:, None], mask.shape)[mask]
        np.add.at(p, (rows, np.asarray(self.nbr)[mask]),
                  np.asarray(self.prob)[mask])
        return p


def sample_walks(graph: CSRGraph, *, n_steps: int, n_walkers: int,
                 seed: int = 0, p_halt: float = 0.0):
    """Walk histories for ``graph``: ``(pos, load)``, ``(N, m, T+1)`` each."""
    key = jax.random.PRNGKey(int(seed))
    return _sample_walks(graph.nbr, graph.prob, graph.deg, key,
                         n_steps=int(n_steps), n_walkers=int(n_walkers),
                         p_halt=float(p_halt))


def grf_transition_action(graph: CSRGraph, y, *, t: int,
                          n_walkers: int = DEFAULT_N_WALKERS, seed: int = 0,
                          p_halt: float = 0.0, return_samples: bool = False):
    """Unbiased MC estimate of ``P^t @ Y`` without materializing P.

    ``y`` is ``(N,)`` or ``(N, C)``; the estimate matches its shape.  With
    ``return_samples=True`` also returns the per-walker contributions
    ``(N, m, C)`` whose walker-axis mean IS the estimate — the statistical
    harness derives its CLT confidence bounds from their spread.
    """
    y = jnp.asarray(y)
    squeeze = y.ndim == 1
    y2 = y[:, None] if squeeze else y
    pos, load = sample_walks(graph, n_steps=int(t), n_walkers=n_walkers,
                             seed=seed, p_halt=p_halt)
    pos_t, load_t = pos[:, :, int(t)], load[:, :, int(t)]
    est = walker_mean(pos_t, load_t, y2.astype(jnp.float32))
    est = est[:, 0] if squeeze else est
    if return_samples:
        samples = (jnp.take(y2.astype(jnp.float32), pos_t, axis=0)
                   * load_t[..., None])
        return est, (samples[:, :, 0] if squeeze else samples)
    return est


def grf_label_propagate(graph: CSRGraph, y0, alpha=0.01, n_iters: int = 500,
                        *, n_walkers: int = DEFAULT_N_WALKERS, seed: int = 0,
                        p_halt: float = 0.0):
    """Eq.-15 label propagation estimated from one streamed walk set.

    ``y0`` is ``(N,)``, ``(N, C)`` or ``(batch, N, C)``; ``alpha`` a
    scalar, per-column ``(C,)`` (2-D), or per-request ``(batch,)`` (3-D) —
    the same shape/alpha contract as ``VariationalDualTree
    .label_propagate``, so the serving tier coalesces GRF groups exactly
    like the other backends (batch folds into the channel axis; walker
    paths are label-independent, so the whole folded stack shares ONE walk
    set).  Deterministic per ``(seed, shapes)``: repeated dispatches are
    bit-identical.
    """
    from repro.core import matvec as matvec_mod

    y0 = jnp.asarray(y0)
    if not jnp.issubdtype(y0.dtype, jnp.floating):
        y0 = y0.astype(jnp.float32)
    if int(n_iters) < 0:
        raise ValueError(f"n_iters must be >= 0, got {n_iters}")
    if y0.ndim == 3:
        batch, _, c = y0.shape
        alpha = jnp.asarray(alpha, jnp.float32)
        if alpha.ndim == 1:
            if alpha.shape[0] != batch:
                raise ValueError(
                    f"per-request alpha wants shape ({batch},), "
                    f"got {alpha.shape}")
            # folded column b*C + ch belongs to request b (see fold_batch)
            alpha = jnp.repeat(alpha, c)
        out = grf_label_propagate(
            graph, matvec_mod.fold_batch(y0), alpha=alpha, n_iters=n_iters,
            n_walkers=n_walkers, seed=seed, p_halt=p_halt)
        return matvec_mod.unfold_batch(out, batch, c)
    squeeze = y0.ndim == 1
    if squeeze:
        y0 = y0[:, None]
    alpha = jnp.asarray(alpha, jnp.float32)
    if alpha.ndim == 1 and alpha.shape[0] != y0.shape[1]:
        raise ValueError(
            f"per-column alpha wants shape ({y0.shape[1]},), "
            f"got {alpha.shape}")
    alpha_cols = jnp.broadcast_to(alpha, (y0.shape[1],))
    out = _lp_streamed(graph.nbr, graph.prob, graph.deg,
                       y0.astype(jnp.float32), alpha_cols,
                       jax.random.PRNGKey(int(seed)), int(n_iters),
                       int(n_walkers), float(p_halt))
    return out[:, 0] if squeeze else out


@functools.partial(jax.jit,
                   static_argnames=("n_iters", "n_walkers", "p_halt"))
def _lp_streamed(nbr, prob, deg, y0, alpha_cols, key, n_iters: int,
                 n_walkers: int, p_halt: float):
    """One scan: advance walkers + accumulate series-weighted features.

    Carry is O(N * m + N * K): walker state plus the running estimate.
    Coefficients follow the eq.-15 unroll — ``(1 - a) a^t`` for ``t <
    n_iters`` and ``a^T`` for the final term — per folded column, so
    heterogeneous alphas are exact.  Step t's randomness is
    ``fold_in(key_w, t)`` with t in 1..T, matching ``sample_walks``
    bit-for-bit (the differential tests lean on this).
    """
    n, k = y0.shape
    t_steps = int(n_iters)
    t_idx = jnp.arange(t_steps + 1, dtype=jnp.float32)[:, None]  # (T+1, 1)
    a = alpha_cols[None, :]                                      # (1, K)
    coeff = a ** t_idx
    coeff = jnp.where(t_idx < t_steps, (1.0 - a) * coeff, coeff)  # (T+1, K)
    acc = coeff[0][None, :] * y0  # t=0 features are exactly y0 (load 1)
    if t_steps == 0:
        return acc
    w = n * n_walkers
    start = jnp.repeat(jnp.arange(n, dtype=jnp.int32), n_walkers)
    wkeys = jax.random.split(key, w)

    def body(carry, t):
        pos, load, alive, acc = carry
        pos, load, alive = walk_step(nbr, prob, deg, pos, load, alive,
                                     wkeys, t, p_halt)
        feat = walker_mean(pos.reshape(n, n_walkers),
                           load.reshape(n, n_walkers), y0)
        acc = acc + coeff[t][None, :] * feat
        return (pos, load, alive, acc), None

    init = (start, jnp.ones((w,), jnp.float32), jnp.ones((w,), bool), acc)
    (_, _, _, acc), _ = jax.lax.scan(
        body, init, jnp.arange(1, t_steps + 1, dtype=jnp.int32))
    return acc
