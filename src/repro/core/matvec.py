"""O(|B|) matrix-vector multiplication with the block transition matrix.

Vectorized form of the paper's Algorithm 1 (with the DistributeDown typo
fixed — see DESIGN.md):

    (QY)_i = sum_{(A,B) in B(x_i)} q_AB * T_B,   T_B = sum_{j in B} y_j

  CollectUp      -> level-major reshape sums produce T for all nodes, O(N C)
  per-block      -> c_block = q_AB * T[b];  segment-sum into c_node, O(|B| C)
  DistributeDown -> top-down prefix accumulation over levels, O(N C)

Leaves read their accumulated path sum.  Ghost leaves hold y = 0 so they
contribute nothing and receive garbage that is never read back.

Batched multi-RHS
-----------------
Every step of Algorithm 1 is linear and acts only on the trailing channel
axis, so a stacked right-hand side ``Y`` of shape ``(batch, N, C)`` can be
served two equivalent ways:

  * **level-major batched** — ``collect_up`` / ``_distribute_down`` /
    ``mpt_matvec_leaforder`` accept arbitrary leading batch dims natively
    (the reshapes and the segment-sum simply carry the extra axes);
  * **channel-folded** — fold the batch into the channel axis,
    ``(batch, N, C) -> (N, batch * C)``, run the single-RHS path once, and
    unfold.  One CollectUp, one segment-sum, and one DistributeDown serve
    the whole batch, so per-call dispatch and gather/scatter overhead is
    paid once instead of ``batch`` times.

``mpt_matvec`` auto-detects a 3-D ``y`` and takes the channel-folded fast
path; ``mpt_matvec_batched`` is the explicit spelling.  Parity of both paths
against stacked single-RHS calls (and against the dense ``Q @ Y``) is pinned
in ``tests/test_batched.py``.

The scan table
--------------
The per-block phase reads a *scan table* ``(a, b, q)`` built once per
fitted model by :func:`scan_table`: the active blocks alone, sorted by row
node ``a`` and then by ``b``, padded to a length bucket with blocks whose
row node ``a = n_nodes`` lies past the last segment.  The block partition's
own arrays keep refinement's capacity, most of it inactive slots; the scan
walks only what carries mass, and the segment-sum is told its ids are
sorted, so it does not sort them again every step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tree import PartitionTree

__all__ = [
    "collect_up",
    "fold_batch",
    "mpt_matvec",
    "mpt_matvec_batched",
    "mpt_matvec_leaforder",
    "prepare_q",
    "scan_slots",
    "scan_table",
    "table_matvec",
    "unfold_batch",
]


def prepare_q(active: jax.Array, log_q: jax.Array) -> jax.Array:
    """Block weights ``q = exp(log_q)`` with inactive/-inf entries zeroed.

    Hoist this out of per-iteration / per-request paths: a fitted tree's q
    never changes between refinements, so serving code computes it once and
    reuses the buffer across scheduler iterations instead of re-exponentiating
    inside every scan step.
    """
    return jnp.where(active & jnp.isfinite(log_q), jnp.exp(log_q), 0.0)


def scan_slots(n_blocks: int) -> int:
    """Length of the scan table that holds ``n_blocks`` active blocks.

    The next multiple of ``2**(floor(log2 n) - 4)``: at most 1/16 padding,
    and an active count that moves a little (a streaming publish) keeps
    the length, so the scan keeps its compiled program.
    """
    n = max(int(n_blocks), 1)
    granule = 1 << max(n.bit_length() - 5, 0)
    return -(-n // granule) * granule


def scan_table(a, b, active, q, n_nodes: int) -> tuple:
    """The block table the scan walks, ``(a, b, q)`` as host arrays.

    Keeps the ``active`` blocks of the capacity arrays ``a``, ``b``, ``q``,
    sorted by ``(a, b)`` so that each row node's blocks form one run and
    their ``T[b]`` reads stay close, and pads to :func:`scan_slots` with
    ``a = n_nodes``, ``b = 0``, ``q = 0``: the segment-sum drops ids past
    its last segment, and ``n_nodes`` keeps the ids sorted.  The only
    producer of what :func:`mpt_matvec_leaforder` reads.
    """
    a, b = np.asarray(a, np.int32), np.asarray(b, np.int32)
    q = np.asarray(q, np.float32)
    idx = np.flatnonzero(np.asarray(active, bool))
    idx = idx[np.lexsort((b[idx], a[idx]))]
    n, slots = idx.size, scan_slots(idx.size)
    ta = np.full(slots, n_nodes, np.int32)
    tb = np.zeros(slots, np.int32)
    tq = np.zeros(slots, np.float32)
    ta[:n], tb[:n], tq[:n] = a[idx], b[idx], q[idx]
    return ta, tb, tq


def fold_batch(ys: jax.Array) -> jax.Array:
    """(batch, N, C) -> (N, batch * C); the canonical channel folding.

    Single source of truth for the folded layout: folded column ``b*C + ch``
    holds batch ``b``, channel ``ch`` (per-batch channel blocks, batch-major
    across blocks); ``unfold_batch`` is its inverse.
    """
    batch, n, c = ys.shape
    return jnp.moveaxis(ys, 0, 1).reshape(n, batch * c)


def unfold_batch(y: jax.Array, batch: int, c: int) -> jax.Array:
    """(N, batch * C) -> (batch, N, C); inverse of ``fold_batch``."""
    return jnp.moveaxis(y.reshape(y.shape[0], batch, c), 1, 0)


@functools.partial(jax.jit, static_argnames=("L",))
def collect_up(y_leaf: jax.Array, L: int) -> jax.Array:
    """Per-node sums T (..., n_nodes, C) from leaf values (..., Np, C).

    Leading batch dims are carried through untouched — the level-major
    reshape sums only ever touch the last two axes.
    """
    levels = [y_leaf]
    cur = y_leaf
    for _ in range(L):
        cur = cur.reshape(*cur.shape[:-2], -1, 2, cur.shape[-1]).sum(axis=-2)
        # Pin the summation tree: each level must be computed FROM the
        # materialized level below it.  Without the barrier XLA is free to
        # fuse the tiny top levels into one reduction straight from a lower
        # level with a different association order, and which rewrite fires
        # depends on the surrounding program — so the same tree summed
        # inside two different jits (e.g. the single-device scan vs the
        # sharded engine's shard_map body) can disagree by ulps.  The
        # serving tier promises cross-engine *bit* parity, so the order is
        # part of the contract.
        cur = jax.lax.optimization_barrier(cur)
        levels.append(cur)
    return jnp.concatenate(levels[::-1], axis=-2)


@functools.partial(jax.jit, static_argnames=("L",))
def _distribute_down(c_node: jax.Array, L: int) -> jax.Array:
    """Top-down prefix accumulation; returns per-leaf path sums (..., Np, C)."""
    acc = c_node[..., 0:1, :]  # root, (..., 1, C)
    for lvl in range(L):
        lo, hi = (1 << (lvl + 1)) - 1, (1 << (lvl + 2)) - 1
        children = c_node[..., lo:hi, :]
        acc = jnp.repeat(acc, 2, axis=-2) + children
    return acc


@functools.partial(jax.jit, static_argnames=("L",))
def mpt_matvec_leaforder(
    y_leaf: jax.Array,       # (..., Np, C) values in leaf order (ghosts 0)
    a: jax.Array,            # (slots,) row nodes, sorted; n_nodes pads
    b: jax.Array,            # (slots,) column nodes
    q: jax.Array,            # (slots,) block parameters (0 at the pads)
    L: int,
) -> jax.Array:
    """(QY) in leaf order; any leading batch dims ride along level-major.

    ``(a, b, q)`` is a table from :func:`scan_table`: the segment-sum
    takes its row nodes as sorted.  Each phase runs under a
    ``jax.named_scope`` (``vdt.collect_up``, ``vdt.gather``,
    ``vdt.segment_sum``, ``vdt.distribute_down``), so the ops of a profile
    carry the phase in their op name.
    """
    n_nodes = (1 << (L + 1)) - 1
    with jax.named_scope("vdt.collect_up"):
        t = collect_up(y_leaf, L)                       # (..., n_nodes, C)
    with jax.named_scope("vdt.gather"):
        c_block = q[:, None] * jnp.take(t, b, axis=-2)  # (..., slots, C)
    with jax.named_scope("vdt.segment_sum"):
        c_block = jnp.moveaxis(c_block, -2, 0)          # (slots, ..., C)
        c_node = jax.ops.segment_sum(c_block, a, num_segments=n_nodes,
                                     indices_are_sorted=True)
        c_node = jnp.moveaxis(c_node, 0, -2)            # (..., n_nodes, C)
    with jax.named_scope("vdt.distribute_down"):
        return _distribute_down(c_node, L)


def mpt_matvec(
    tree: PartitionTree,
    a: jax.Array,
    b: jax.Array,
    active: jax.Array,
    log_q: jax.Array,
    y: jax.Array,            # (N,), (N, C) or (batch, N, C) in row order
) -> jax.Array:
    """(QY) in original row order; O(|B| C + N C).

    Takes the block partition's capacity arrays, concrete even where ``y``
    is traced, and builds their :func:`scan_table` on the host for the
    call; a fitted model keeps its table (``VariationalDualTree.matvec``).
    """
    with jax.ensure_compile_time_eval():
        q = prepare_q(jnp.asarray(active), jnp.asarray(log_q))
    table = scan_table(a, b, active, q, tree.n_nodes)
    return table_matvec(tree, table, y)


def table_matvec(tree: PartitionTree, table: tuple, y: jax.Array) -> jax.Array:
    """(QY) in original row order over a :func:`scan_table` ``(a, b, q)``.

    A 3-D ``y`` of shape ``(batch, N, C)`` is served by one device dispatch
    via channel folding: ``(batch, N, C) -> (N, batch * C)``.
    """
    y = jnp.asarray(y)
    if y.ndim == 3:
        batch, _, c = y.shape
        out = table_matvec(tree, table, fold_batch(y))
        return unfold_batch(out, batch, c)
    squeeze = y.ndim == 1
    if squeeze:
        y = y[:, None]
    y_leaf = jnp.zeros((tree.n_leaves, y.shape[1]), dtype=y.dtype)
    y_leaf = y_leaf.at[tree.slot_of].set(y)
    out_leaf = mpt_matvec_leaforder(y_leaf, *table, tree.L)
    out = out_leaf[tree.slot_of]
    return out[:, 0] if squeeze else out


def mpt_matvec_batched(
    tree: PartitionTree,
    a: jax.Array,
    b: jax.Array,
    active: jax.Array,
    log_q: jax.Array,
    ys: jax.Array,           # (batch, N, C) in original row order
) -> jax.Array:
    """Explicit batched multi-RHS (Q @ Y_b for every b) in one dispatch."""
    ys = jnp.asarray(ys)
    if ys.ndim != 3:
        raise ValueError(f"mpt_matvec_batched wants (batch, N, C), got {ys.shape}")
    return mpt_matvec(tree, a, b, active, log_q, ys)
