"""Plain reference for a VDT label-propagation server: eq. 15 over the fitted blocks.

The program under test fits a block partition of the transition matrix
(paper §3-§4) and serves eq.-15 walks over it.  This reference takes from
the fit only its discrete choices and one scalar: which rows sit at which
leaf of the tree, which (A, B) node pairs form the partition, and the
bandwidth sigma.  Everything numeric it recomputes from its own copy of the
data, in float64 on the host:

* node sums W, S1, S2 from the data in leaf order;
* D2_AB = sum_{i in A, j in B} ||x_i - x_j||^2 (paper eq. 9);
* the optimal q of paper eq. 7 under the row constraints (eq. 16), by the
  closed-form recursion of Thiesson & Kim (2012, Alg. 3);

and holds the fit to what the configuration states:

* ``partition_bad_leaves``: the blocks form a partition: no block pairs a
  node with itself or a relative, and each real row's blocks cover all
  N - 1 other rows;
* ``blocks_short``: the partition has the configuration's
  ``blocks_per_point * N`` active blocks, so refinement ran to its budget;
* ``sigma_gap``: sigma is the eq.-12 optimum for the q it gives,
  ``|sigma*(q) / sigma - 1|`` with ``sigma*^2 = sum_B q_AB D2_AB / (d N)``;
* ``bound_gain``: the variational bound (eq. 7's objective at q) of the
  fitted partition over that of the coarsest partition of the same tree at
  the same sigma, in nats per row: how much the refinement's choice of
  blocks bought.

The walk then runs on the device in the precision asked for, as plainly as
it can be written: node sums by levels, a scatter-add over the blocks, and
each leaf summing its ancestors.  Nothing here imports the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["GAP", "take", "prepare", "answers"]

# the name under which the check reports this backend's answer gap
GAP = "walk_gap"

_CHUNK_BLOCKS = 1 << 16
_CHUNK_COLS = 256


def take(vdt) -> dict:
    """The fit's discrete choices, copied to the host: the only program output read."""
    bp = vdt.bp
    return {"a": np.array(bp.a[:bp.n]), "b": np.array(bp.b[:bp.n]),
            "active": np.array(bp.active[:bp.n]),
            "slot_of": np.asarray(vdt.tree.slot_of).copy(),
            "L": int(vdt.tree.L), "sigma": float(vdt.sigma)}


def _node_sums(x: np.ndarray, slot_of: np.ndarray, L: int):
    """Heap-ordered W, S1, S2 (float64) of the points in leaf order."""
    n_leaves = 1 << L
    xl = np.zeros((n_leaves, x.shape[1]), np.float64)
    xl[slot_of] = x
    w = np.zeros(n_leaves, np.float64)
    w[slot_of] = 1.0
    s2 = (xl * xl).sum(1)
    Ws, S1s, S2s = [w], [xl], [s2]
    for _ in range(L):
        Ws.append(Ws[-1].reshape(-1, 2).sum(1))
        S1s.append(S1s[-1].reshape(-1, 2, x.shape[1]).sum(1))
        S2s.append(S2s[-1].reshape(-1, 2).sum(1))
    return (np.concatenate(Ws[::-1]), np.concatenate(S1s[::-1]),
            np.concatenate(S2s[::-1]))


def _leaf_range(node: np.ndarray, L: int):
    level = np.floor(np.log2(node + 1)).astype(np.int64)
    span = np.left_shift(1, L - level)
    lo = (node - (np.left_shift(1, level) - 1)) * span
    return lo, lo + span


def _bad_leaves(a, b, W, L: int, n: int, real: np.ndarray) -> int:
    """Real leaves whose blocks do not cover the other n - 1 rows, plus blocks
    whose two sides overlap (a node paired with itself or a relative)."""
    alo, ahi = _leaf_range(a, L)
    blo, bhi = _leaf_range(b, L)
    overlap = int(((alo < bhi) & (blo < ahi)).sum())
    diff = np.zeros((1 << L) + 1, np.int64)
    wb = np.rint(W[b]).astype(np.int64)
    np.add.at(diff, alo, wb)
    np.add.at(diff, ahi, -wb)
    cover = np.cumsum(diff)[:-1]
    return overlap + int((cover[real] != n - 1).sum())


def _d2(a, b, W, S1, S2) -> np.ndarray:
    """Paper eq. 9: the summed squared distances of each block, float64."""
    dot = np.empty(a.shape[0], np.float64)
    for lo in range(0, a.shape[0], _CHUNK_BLOCKS):
        sl = slice(lo, lo + _CHUNK_BLOCKS)
        dot[sl] = np.einsum("ij,ij->i", S1[a[sl]], S1[b[sl]])
    return np.maximum(W[a] * S2[b] + W[b] * S2[a] - 2.0 * dot, 0.0)


def _log_q(a, b, W, d2, sigma: float, L: int) -> np.ndarray:
    """Paper eq. 7's optimal log q for the blocks (a, b), float64."""
    n_nodes = W.shape[0]
    g = -d2 / (2.0 * sigma * sigma * W[a] * W[b])
    contrib = np.log(W[b]) + g
    # log z_A = logsumexp over A's blocks of log W_B + G_AB
    m = np.full(n_nodes, -np.inf)
    np.maximum.at(m, a, contrib)
    s = np.bincount(a, weights=np.exp(contrib - m[a]), minlength=n_nodes)
    with np.errstate(divide="ignore", invalid="ignore"):
        log_z = np.where(s > 0, np.log(s) + np.where(np.isfinite(m), m, 0.0), -np.inf)
        # bottom-up: Zt_A = z_A + exp(Wbar_A), Wbar_A the W-weighted mean
        # of the children's log Zt (children of no weight count for nothing)
        log_zt = log_z.copy()
        wbar = np.full(n_nodes, -np.inf)
        for lvl in range(L - 1, -1, -1):
            nodes = np.arange((1 << lvl) - 1, (1 << (lvl + 1)) - 1)
            left, right = 2 * nodes + 1, 2 * nodes + 2
            wl, wr, wn = W[left], W[right], W[nodes]
            zl, zr = log_zt[left], log_zt[right]
            num = np.where(wl > 0, wl * zl, 0.0) + np.where(wr > 0, wr * zr, 0.0)
            dead = ((wl > 0) & ~np.isfinite(zl)) | ((wr > 0) & ~np.isfinite(zr))
            wbar[nodes] = np.where((wn > 0) & ~dead, num / np.where(wn > 0, wn, 1.0),
                                   -np.inf)
            log_zt[nodes] = np.logaddexp(log_z[nodes], wbar[nodes])
        # top-down: the root row mass is 1; a node keeps v_A = R_A z_A / Zt_A
        # and hands R_A - v_A = R_A exp(Wbar_A) / Zt_A to each child
        log_r = np.full(n_nodes, -np.inf)
        log_r[0] = 0.0
        for lvl in range(L):
            nodes = np.arange((1 << lvl) - 1, (1 << (lvl + 1)) - 1)
            ok = np.isfinite(log_r[nodes]) & np.isfinite(wbar[nodes])
            rc = np.where(ok, log_r[nodes] + wbar[nodes] - log_zt[nodes], -np.inf)
            log_r[2 * nodes + 1] = rc
            log_r[2 * nodes + 2] = rc
        log_v = np.where(np.isfinite(log_r) & np.isfinite(log_z),
                         log_r + log_z - log_zt, -np.inf)
        return log_v[a] + g - log_z[a]



def _bound(a, b, W, d2, log_q, sigma: float, d: int) -> float:
    """Eq. 7's objective at q: the variational lower bound on log p(X)."""
    n = W[0]
    q = np.exp(log_q)
    ent = np.where(q > 0, W[a] * W[b] * q * np.where(q > 0, log_q, 0.0), 0.0)
    const = -n * (0.5 * d * np.log(2.0 * np.pi * sigma * sigma) + np.log(max(n - 1.0, 1.0)))
    return float(const - (q * d2).sum() / (2.0 * sigma * sigma) - ent.sum())


def _coarsest(W, L: int):
    """Both orderings of every sibling pair whose sides both hold rows."""
    p = np.arange((1 << L) - 1)
    left, right = 2 * p + 1, 2 * p + 2
    keep = (W[left] > 0) & (W[right] > 0)
    left, right = left[keep], right[keep]
    return np.concatenate([left, right]), np.concatenate([right, left])


def prepare(x, fitted: dict, config: dict):
    """Recompute q from the data; returns ``(state, numbers)``.

    ``numbers`` holds the four checks of the fit named in the module's
    docstring; ``config`` is the cell's configuration, which states the
    block budget.
    """
    L, slot_of, sigma = fitted["L"], fitted["slot_of"], fitted["sigma"]
    act = fitted["active"]
    a = fitted["a"][act].astype(np.int64)
    b = fitted["b"][act].astype(np.int64)
    x = np.asarray(x, np.float64)
    n, d = x.shape
    W, S1, S2 = _node_sums(x, slot_of, L)
    real = np.zeros(1 << L, bool)
    real[slot_of] = True
    bad = _bad_leaves(a, b, W, L, n, real)
    d2 = _d2(a, b, W, S1, S2)
    log_q = _log_q(a, b, W, d2, sigma, L)
    q = np.exp(log_q)
    sigma_star = float(np.sqrt((q * d2).sum() / (d * n)))
    ca, cb = _coarsest(W, L)
    cd2 = _d2(ca, cb, W, S1, S2)
    gain = (_bound(a, b, W, d2, log_q, sigma, d)
            - _bound(ca, cb, W, cd2, _log_q(ca, cb, W, cd2, sigma, L), sigma, d)) / n
    need = int(config["fit"]["blocks_per_point"] * n)
    state = {"a": jnp.asarray(a, jnp.int32), "b": jnp.asarray(b, jnp.int32),
             "q": q, "slot_of": jnp.asarray(slot_of, jnp.int32), "L": L,
             "mask": jnp.asarray(real, jnp.float32)[:, None]}
    return state, {"partition_bad_leaves": bad,
                   "blocks_short": max(0, need - int(a.shape[0])),
                   "sigma_gap": abs(sigma_star / sigma - 1.0),
                   "bound_gain": float(gain)}


@functools.partial(jax.jit, static_argnames=("L", "n_iters"))
def _walk(y0, alpha, mask, a, b, q, slot_of, L: int, n_iters: int):
    n_leaves = 1 << L
    dt = q.dtype
    y0_leaf = jnp.zeros((n_leaves, y0.shape[1]), dt).at[slot_of].set(y0.astype(dt))
    alpha = alpha.astype(dt)
    mask = mask.astype(dt)
    leaf = jnp.arange(n_leaves)

    def q_times(y):
        levels = [y]
        for _ in range(L):
            levels.append(levels[-1].reshape(-1, 2, y.shape[1]).sum(1))
        t = jnp.concatenate(levels[::-1])
        c = jnp.zeros_like(t).at[a].add(q[:, None] * t[b])
        out = jnp.zeros_like(y)
        for lvl in range(L + 1):
            out = out + c[(1 << lvl) - 1 + (leaf >> (L - lvl))]
        return out

    def step(_, y):
        return mask * (alpha * q_times(y)) + (1 - alpha) * y0_leaf

    return jax.lax.fori_loop(0, n_iters, step, y0_leaf)[slot_of].astype(jnp.float32)


def answers(state, cases, dtype=jnp.float32) -> list[np.ndarray]:
    """Eq.-15 answers for ``cases``: ``(y0, alpha, n_iters)`` triples.

    ``dtype`` is the precision the whole walk is computed in: float32 for
    the reference, bfloat16 for the control.  Cases sharing ``n_iters``
    walk together, ``_CHUNK_COLS`` label columns at a time.
    """
    q = jnp.asarray(state["q"], dtype)
    out: list = [None] * len(cases)
    by_iters: dict[int, list[int]] = {}
    for k, (_, _, n_iters) in enumerate(cases):
        by_iters.setdefault(int(n_iters), []).append(k)
    for n_iters, ks in sorted(by_iters.items()):
        start = 0
        while start < len(ks):
            group, cols = [], 0
            while start < len(ks) and (not group or
                                       cols + cases[ks[start]][0].shape[1] <= _CHUNK_COLS):
                group.append(ks[start])
                cols += cases[ks[start]][0].shape[1]
                start += 1
            y0 = np.concatenate([cases[k][0] for k in group], axis=1)
            alpha = np.concatenate([np.full(cases[k][0].shape[1], cases[k][1], np.float32)
                                    for k in group])
            res = np.asarray(_walk(jnp.asarray(y0), jnp.asarray(alpha), state["mask"],
                                   state["a"], state["b"], q, state["slot_of"],
                                   L=state["L"], n_iters=n_iters))
            col = 0
            for k in group:
                w = cases[k][0].shape[1]
                out[k] = res[:, col:col + w]
                col += w
    return out
