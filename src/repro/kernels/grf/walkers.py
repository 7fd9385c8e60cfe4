"""Batched terminating random walks over a padded CSR neighbor table.

The sampling half of the GRF backend (graph random features,
arXiv:2305.00156 / 2410.10368): every node launches ``n_walkers``
independent walkers, and each walker carries an importance-sampling *load*
that keeps the estimator unbiased however the walk is proposed:

* the proposal draws the next hop **uniformly** over the current node's
  neighbors (one gather + one multiply per step — no per-row alias tables
  or prefix sums), and the load multiplies by the importance weight
  ``deg(u) * P[u, v]`` so that ``E[load_t * f(pos_t)] = (P^t f)(start)``
  exactly;
* with ``p_halt > 0`` walkers terminate geometrically; survivors divide
  their load by ``(1 - p_halt)`` per step, so termination thins the walk
  population without biasing it (dead walkers keep stepping with load 0 —
  the arrays stay rectangular and the scan stays shape-static).

Randomness is **per-walker**: walker ``w`` owns key ``split(key, W)[w]``
and derives its step-``t`` draws via ``fold_in(key_w, t)``.  Two
consequences the tests pin:

* determinism — the same ``(key, shapes)`` reproduces the same walks
  bit-for-bit, on any backend, in any batch layout;
* the prefix property — walks of horizon ``T`` are exactly the first ``T``
  steps of horizon ``T' > T`` walks, so one walk set serves every
  intermediate power ``P^t`` of a label-propagation series at once.

:func:`walker_mean` reduces one step's walker population to its feature
estimate with an XLA gather — O(N * m * C) per step, which compiles on
every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["walk_step", "sample_walks", "walker_mean"]


def walk_step(nbr, prob, deg, pos, load, alive, wkeys, t, p_halt=0.0):
    """Advance every walker one step; returns ``(pos, load, alive)``.

    ``nbr``/``prob`` are the padded ``(N, max_deg)`` neighbor table and
    transition probabilities, ``deg`` the true ``(N,)`` neighbor counts;
    ``pos``/``load``/``alive`` are the ``(W,)`` walker state and ``wkeys``
    the ``(W, 2)`` per-walker keys.  ``t`` (traced) folds into each
    walker's key so every step draws fresh randomness; ``p_halt`` is a
    static python float.
    """
    u = jax.vmap(
        lambda k: jax.random.uniform(jax.random.fold_in(k, t), (2,)))(wkeys)
    d = deg[pos]                                        # (W,) true degrees
    slot = jnp.minimum((u[:, 0] * d).astype(jnp.int32), d - 1)
    nxt = nbr[pos, slot]
    # uniform proposal over deg(u) neighbors -> importance weight deg * P
    mult = d.astype(jnp.float32) * prob[pos, slot]
    if p_halt > 0.0:
        alive = jnp.logical_and(alive, u[:, 1] >= p_halt)
        mult = mult / (1.0 - p_halt)  # survivor correction: stays unbiased
    load = load * mult * alive.astype(jnp.float32)
    return nxt, load, alive


@functools.partial(jax.jit,
                   static_argnames=("n_steps", "n_walkers", "p_halt"))
def sample_walks(nbr, prob, deg, key, *, n_steps: int, n_walkers: int,
                 p_halt: float = 0.0):
    """Full walk histories: ``(pos, load)``, each ``(N, m, n_steps + 1)``.

    ``pos[i, w, t]`` / ``load[i, w, t]`` are walker ``w``-of-node-``i``'s
    position and load after ``t`` steps (``t = 0`` is the start:
    ``pos = i``, ``load = 1``), so ``mean_w load[:, :, t] * f(pos[:, :, t])``
    estimates ``P^t f`` for EVERY ``t <= n_steps`` from one walk set.
    O(N * m * T) memory — the analysis/test surface; the serving estimator
    (``core.grf.grf_label_propagate``) streams the same :func:`walk_step`
    recurrence without storing histories.
    """
    n = nbr.shape[0]
    w = n * n_walkers
    start = jnp.repeat(jnp.arange(n, dtype=jnp.int32), n_walkers)
    wkeys = jax.random.split(key, w)

    def body(carry, t):
        pos, load, alive = walk_step(nbr, prob, deg, *carry, wkeys, t,
                                     p_halt)
        return (pos, load, alive), (pos, load)

    init = (start, jnp.ones((w,), jnp.float32), jnp.ones((w,), bool))
    # steps are numbered 1..T: step t's randomness is fold_in(key_w, t),
    # identical to the streaming estimator's numbering -> bit-parity and
    # the prefix property both hold across the two drivers
    _, (ps, ls) = jax.lax.scan(body, init,
                               jnp.arange(1, n_steps + 1, dtype=jnp.int32))
    pos = jnp.concatenate([start[None], ps], axis=0)          # (T+1, W)
    load = jnp.concatenate([jnp.ones((1, w), jnp.float32), ls], axis=0)
    pos = jnp.moveaxis(pos, 0, -1).reshape(n, n_walkers, n_steps + 1)
    load = jnp.moveaxis(load, 0, -1).reshape(n, n_walkers, n_steps + 1)
    return pos, load


def walker_mean(pos, load, y):
    """``(1/m) * sum_w load[s, w] * y[pos[s, w], :]``: ``(S, m) x (N, C) -> (S, C)``.

    The load-weighted walker mean that estimates one row block of
    ``P^t @ Y`` from the step-``t`` walker positions and loads.
    """
    y = jnp.asarray(y, jnp.float32)
    gathered = jnp.take(y, jnp.asarray(pos, jnp.int32), axis=0)  # (S, m, C)
    return (gathered * jnp.asarray(load, jnp.float32)[..., None]).mean(axis=1)
