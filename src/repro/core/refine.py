"""Greedy block refinement (paper §4.4), batched for TPU.

Horizontal refinement of block (A, B) replaces it by {(A, B_l), (A, B_r)}.
The closed-form lower bound on its log-likelihood gain (eq. 19):

    Delta_h(A, B) = W_A W_B q_AB * log( sum_t W_{B_t} e^{G_{A B_t}}
                                        / (W_B e^{G_AB}) )

Gains are >= 0 by Jensen.  *Symmetric refinement*: picking (A, B) also
horizontally refines its mirror (B, A) (the paper's stand-in for vertical
refinement, which has no closed-form gain).

TPU adaptation: the paper pops one block at a time off a priority queue; we
compute all gains vectorized, take the top-k in one shot, apply the union of
picked blocks and their mirrors, then globally re-optimize q (O(|B|)).  k = 1
recovers the paper's schedule exactly.
"""
from __future__ import annotations

import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocks import BlockPartition
from repro.core.qopt import QState, block_log_G, optimize_q
from repro.core.tree import PartitionTree

__all__ = ["refinement_gains", "refine_topk", "refine_to_budget"]


@jax.jit
def _gains_impl(W, log_g, log_gl, log_gr, wb, wbl, wbr, log_q, refinable):
    lse = jnp.logaddexp(
        jnp.where(wbl > 0, jnp.log(jnp.maximum(wbl, 1e-12)) + log_gl, -jnp.inf),
        jnp.where(wbr > 0, jnp.log(jnp.maximum(wbr, 1e-12)) + log_gr, -jnp.inf),
    )
    parent = jnp.log(jnp.maximum(wb, 1e-12)) + log_g
    gain_log = lse - parent
    q = jnp.where(jnp.isfinite(log_q), jnp.exp(log_q), 0.0)
    del W
    gains = jnp.where(
        refinable & jnp.isfinite(gain_log), q * jnp.maximum(gain_log, 0.0), -jnp.inf
    )
    return gains


def refinement_gains(
    tree: PartitionTree,
    a: jax.Array,
    b: jax.Array,
    active: jax.Array,
    log_q: jax.Array,
    sigma: jax.Array,
    divergence=None,
) -> jax.Array:
    """Delta_h * (W_A W_B)^{-1}-free gains for all blocks; −inf if unrefinable.

    Returns the *total* gain W_A W_B q_AB log(...) per block (eq. 19).
    """
    n_leaf_first = tree.n_internal  # first leaf id
    wa, wb = tree.W[a], tree.W[b]
    b_internal = b < n_leaf_first
    bl = jnp.where(b_internal, 2 * b + 1, b)
    br = jnp.where(b_internal, 2 * b + 2, b)
    from repro.core.divergence import bind_divergence
    div = bind_divergence(divergence, tree)  # bind stats once for all 3 calls
    log_g = block_log_G(tree, a, b, active, sigma, divergence=div)
    log_gl = block_log_G(tree, a, bl, active, sigma, divergence=div)
    log_gr = block_log_G(tree, a, br, active, sigma, divergence=div)
    refinable = active & b_internal & (wa > 0) & (wb > 0)
    raw = _gains_impl(tree.W, log_g, log_gl, log_gr,
                      wb, tree.W[bl], tree.W[br], log_q, refinable)
    return jnp.where(refinable, wa * wb * raw, -jnp.inf)


def refine_topk(
    bp: BlockPartition,
    tree: PartitionTree,
    gains: np.ndarray,
    k: int,
    stale: np.ndarray | None = None,
) -> int:
    """Apply symmetric refinement to the top-k blocks by gain (host-side).

    Returns the number of blocks actually refined.  Each refined block is
    deactivated and replaced by its two horizontal children; mirrors of the
    new blocks are wired up when both sides of a symmetric pair refine.

    ``stale`` (optional (>= bp.n,) bool array) marks blocks whose statistics
    were patched by streaming inserts/deletes since the last refinement:
    stale blocks with a finite gain are refined FIRST (gain-ordered among
    themselves), so the block budget is spent where the fitted structure is
    most out of date.  Refined slots have their stale flag cleared in place.
    """
    g = np.asarray(gains[: bp.n], dtype=np.float64)
    g[~bp.active[: bp.n]] = -np.inf
    if stale is not None:
        # stale arrays are sized to the partition they were created for;
        # blocks appended by earlier refinement rounds are implicitly fresh
        s = np.zeros(bp.n, bool)
        m = min(len(stale), bp.n)
        s[:m] = np.asarray(stale[:m], bool)
        # primary key: stale first; secondary: gain descending (lexsort
        # reads keys last-to-first)
        order = np.lexsort((-g, ~s))
    else:
        order = np.argsort(-g)
    picked: list[int] = []
    seen: set[int] = set()
    for idx in order[: 4 * k]:
        if len(picked) >= k or not np.isfinite(g[idx]):
            break
        i = int(idx)
        if i in seen:
            continue
        picked.append(i)
        seen.add(i)
        m = int(bp.mirror[i])
        if m >= 0 and bp.active[m] and m not in seen:
            # symmetric refinement: mirror is refined too (doesn't count
            # against k — it is the paper's vertical-refinement stand-in)
            picked.append(m)
            seen.add(m)
    if not picked:
        return 0

    w = np.asarray(tree.W)
    new_a, new_b = [], []
    for i in picked:
        ai, bi = int(bp.a[i]), int(bp.b[i])
        for bc in (2 * bi + 1, 2 * bi + 2):
            # children whose kernel side is all-ghost cover no real pair;
            # skipping them keeps the fitted block layout (and its log_q
            # bit pattern) independent of ghost headroom.  The streaming
            # layer appends them lazily on its copy-on-write partition
            # (blocks.complete_forest) before any weight-driven coverage
            # math, so no hole survives an insert into a ghost subtree.
            if w[ai] > 0 and w[bc] > 0:
                new_a.append(ai)
                new_b.append(bc)
        bp.active[i] = False
        bp.refined[i] = True
        if stale is not None and i < len(stale):
            stale[i] = False

    # refinement children generally have no mirror in B (the paper's
    # "if it also belongs to B" clause) — only coarsest sibling blocks do.
    bp.append_pairs(
        np.asarray(new_a, np.int32),
        np.asarray(new_b, np.int32),
        np.full(len(new_a), -1, np.int32),
    )
    return len(picked)


def refine_to_budget(
    bp: BlockPartition,
    tree: PartitionTree,
    sigma: jax.Array,
    max_blocks: int,
    batch: int = 64,
    refit_sigma: bool = False,
    divergence=None,
    stale: np.ndarray | None = None,
    *,
    stats=None,
) -> Tuple[QState, jax.Array]:
    """Refine until ``n_active >= max_blocks``; returns final (QState, sigma).

    Re-optimizes q globally after every batched round (the paper re-optimizes
    after every single refinement; batching amortizes this — measured in
    benchmarks/refinement.py).

    ``stale`` (optional bool array over block slots) prioritizes blocks
    whose stats were patched by streaming mutations — see
    :func:`refine_topk`; refined slots are cleared in place so a streaming
    model's staleness bookkeeping drains as the budget is spent.

    Each round records three profiler spans (``jax.profiler``
    ``TraceAnnotation``): ``fit.refine.gains`` (the gains and their copy to
    the host), ``fit.refine.select`` (:func:`refine_topk` on the host) and
    ``fit.refine.qopt`` (re-optimizing q, and sigma with ``refit_sigma``);
    ``docs/ARCHITECTURE.md`` ("Profiling a fit") reads them. ``stats`` (a :class:`~repro.core.vdt.VdtStats`), where given, counts
    the rounds in ``refine_rounds`` and adds the host clock's seconds of
    selection to ``refine_select_s``.
    """
    from repro.core.divergence import bind_divergence
    from repro.core.sigma import sigma_star  # local import to avoid cycle

    div = bind_divergence(divergence, tree)
    qs = optimize_q(tree, jnp.asarray(bp.a), jnp.asarray(bp.b),
                    jnp.asarray(bp.active), sigma, divergence=div)
    while bp.n_active < max_blocks:
        k = min(batch, max(1, (max_blocks - bp.n_active) // 2))
        with jax.profiler.TraceAnnotation("fit.refine.gains"):
            gains = np.asarray(refinement_gains(
                tree, jnp.asarray(bp.a), jnp.asarray(bp.b), jnp.asarray(bp.active),
                qs.log_q, sigma, divergence=div,
            ))
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("fit.refine.select"):
            done = refine_topk(bp, tree, gains, k, stale=stale)
        if stats is not None:
            stats.refine_rounds += 1
            stats.refine_select_s += time.perf_counter() - t0
        if done == 0:
            break
        with jax.profiler.TraceAnnotation("fit.refine.qopt"):
            qs = optimize_q(tree, jnp.asarray(bp.a), jnp.asarray(bp.b),
                            jnp.asarray(bp.active), sigma, divergence=div)
            if refit_sigma:
                sigma = sigma_star(tree, jnp.asarray(bp.a), jnp.asarray(bp.b),
                                   jnp.asarray(bp.active), qs.log_q, divergence=div)
                qs = optimize_q(tree, jnp.asarray(bp.a), jnp.asarray(bp.b),
                                jnp.asarray(bp.active), sigma, divergence=div)
    return qs, sigma
