"""Pure-jnp oracles for the GRF estimator — scipy-free, dense, O(N^2).

``dense_power_action_ref`` / ``dense_lp_ref`` iterate the dense transition
matrix directly — the ground truth the statistical harness
(``tests/test_grf.py``) bounds the walker estimators against with
CLT-derived tolerances.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["dense_power_action_ref", "dense_lp_ref"]


def dense_power_action_ref(p, y, t: int):
    """``P^t @ Y`` by ``t`` explicit dense matvecs (no eigendecomposition)."""
    p = jnp.asarray(p, jnp.float32)
    out = jnp.asarray(y, jnp.float32)
    for _ in range(int(t)):
        out = p @ out
    return out


def dense_lp_ref(p, y0, alpha=0.01, n_iters: int = 500):
    """Eq.-15 label propagation against a dense transition matrix.

    ``alpha`` may be a scalar or per-column ``(C,)`` (broadcast against the
    ``(N, C)`` labels) — the same semantics the GRF estimator serves.
    """
    p = jnp.asarray(p, jnp.float32)
    y0 = jnp.asarray(y0, jnp.float32)
    alpha = jnp.asarray(alpha, jnp.float32)
    y = y0
    for _ in range(int(n_iters)):
        y = alpha * (p @ y) + (1.0 - alpha) * y0
    return y
