"""Public API: the Variational Dual-Tree transition-matrix approximation.

    vdt = VariationalDualTree.fit(x, max_blocks=4 * n)
    y_hat = vdt.matvec(y)                   # O(|B|) Q @ y
    y_lp  = vdt.label_propagate(y0)         # label propagation (eq. 15)
    q     = vdt.dense_q()                   # small-N debugging / tests

Pipeline (paper §3-§4): build the shared partition tree -> coarsest block
partition (|B| = 2(Np-1)) -> alternate q-optimization (eq. 7) with bandwidth
learning (eq. 12) -> greedy symmetric refinement to the block budget
(eq. 19) -> O(|B|) inference (Algorithm 1).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blocks as blocks_mod
from repro.core import divergence as div_mod
from repro.core import matvec as matvec_mod
from repro.core import qopt as qopt_mod
from repro.core import refine as refine_mod
from repro.core import sigma as sigma_mod
from repro.core.label_prop import (lp_scan_fused, lp_scan_fused_resume,
                                   lp_scan_leaforder, lp_scan_leaforder_resume)
from repro.core.tree import PartitionTree, build_tree

__all__ = ["VariationalDualTree", "VdtStats"]


@dataclasses.dataclass
class VdtStats:
    build_tree_s: float = 0.0
    init_qopt_s: float = 0.0
    refine_s: float = 0.0
    # rounds of greedy refinement, and the host clock's seconds of block
    # selection within refine_s (refine.refine_to_budget)
    refine_rounds: int = 0
    refine_select_s: float = 0.0
    sigma_iters: int = 0
    n_blocks: int = 0
    # length of the block table the VDT scan walks: n_blocks padded to
    # its bucket (matvec.scan_slots)
    scan_slots: int = 0
    bound: float = 0.0
    sigma: float = 0.0
    divergence: str = "sqeuclidean"


@dataclasses.dataclass
class VariationalDualTree:
    tree: PartitionTree
    bp: blocks_mod.BlockPartition
    qstate: qopt_mod.QState
    sigma: jax.Array
    stats: VdtStats
    # the Bregman divergence this model was fitted under, bound to `tree`
    # (block-stats precomputed); None means the default Gaussian kernel and
    # is lazily normalized to the bound sqeuclidean divergence
    divergence: Optional[div_mod.BoundDivergence] = None
    # device-resident dispatch buffers (the scan table a, b, q and the
    # leaf mask), built lazily and reused across serving calls / scheduler
    # iterations; the table never changes between refinements.
    _serve_cache: Optional[tuple] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)
    # points in original row order (exact-backend LP reads them); derived
    # from the tree's leaf-order copy once and reused
    _x_rows_cache: Optional[jax.Array] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)
    # mutable float64 host mirrors for streaming insert/delete
    # (core/streaming.py); rides on the newest epoch only and is rebuilt
    # transparently when absent or stale
    _stream: Optional[object] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)
    # CSR transition graph over the fitted points for the GRF backend
    # (core/grf.py), built from the dense eq.-3 kernel once and cached —
    # epochs are copy-on-write, so the cache is stable for this model's
    # lifetime and every dispatch against it walks identical bits
    _grf_cache: Optional[object] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    # ------------------------------------------------------------------ fit
    @classmethod
    def fit(
        cls,
        x,
        weights=None,
        max_blocks: Optional[int] = None,
        sigma: Optional[float] = None,
        learn_sigma: bool = True,
        refine_batch: int = 64,
        sigma_iters: int = 10,
        power_iters: int = 8,
        divergence="sqeuclidean",
        capacity: Optional[int] = None,
    ) -> "VariationalDualTree":
        """Build tree + coarsest partition, fit sigma/q, refine to budget.

        ``divergence`` selects the Bregman divergence the similarity kernel
        ``exp(-d(x_i, x_j) / 2 s^2)`` is built from — a registry name
        (``"sqeuclidean"`` default, ``"kl"``, ``"itakura_saito"``,
        ``"mahalanobis"``) or a :class:`~repro.core.divergence.Divergence`.
        Positive-domain divergences (KL, Itakura-Saito) validate ``x`` up
        front and raise ``ValueError`` on out-of-domain data.  ``sigma``
        keeps its role as the kernel temperature; ``sigma_init`` stays the
        Gaussian moment heuristic, which is only a starting scale for the
        eq.-12 alternation.

        ``capacity`` (>= N) reserves ghost leaf headroom for streaming
        inserts (:meth:`insert_points`); without it the tree only has the
        power-of-two rounding slack.
        """
        div = div_mod.resolve_divergence(divergence)
        div.validate_domain(x)  # fail fast, before any device work
        stats = VdtStats(divergence=div.name)
        x = jnp.asarray(x, jnp.float32)

        t0 = time.perf_counter()
        tree = build_tree(x, weights, power_iters=power_iters,
                          capacity=capacity)
        jax.block_until_ready(tree.W)
        stats.build_tree_s = time.perf_counter() - t0
        # bind via the memo so later public-API calls with the name form
        # reuse these stats instead of recomputing the O(N d) pass
        bound_div = div_mod.bind_divergence(div, tree)

        cap = max_blocks if max_blocks else 2 * tree.n_internal
        bp = blocks_mod.coarsest_partition(tree, cap=int(2.5 * cap))

        t0 = time.perf_counter()
        sig = jnp.asarray(
            sigma if sigma is not None else sigma_mod.sigma_init(x, weights),
            jnp.float32,
        )
        if learn_sigma and sigma is None:
            sig, qs, its = sigma_mod.fit_sigma_q(
                tree, jnp.asarray(bp.a), jnp.asarray(bp.b), jnp.asarray(bp.active),
                sig, max_iters=sigma_iters, divergence=bound_div,
            )
            stats.sigma_iters = its
        else:
            qs = qopt_mod.optimize_q(
                tree, jnp.asarray(bp.a), jnp.asarray(bp.b), jnp.asarray(bp.active),
                sig, divergence=bound_div,
            )
        jax.block_until_ready(qs.log_q)
        stats.init_qopt_s = time.perf_counter() - t0

        if max_blocks and max_blocks > bp.n_active:
            t0 = time.perf_counter()
            qs, sig = refine_mod.refine_to_budget(
                bp, tree, sig, max_blocks, batch=refine_batch,
                refit_sigma=learn_sigma, divergence=bound_div, stats=stats,
            )
            jax.block_until_ready(qs.log_q)
            stats.refine_s = time.perf_counter() - t0

        stats.n_blocks = bp.n_active
        stats.scan_slots = matvec_mod.scan_slots(bp.n_active)
        stats.bound = float(qs.bound)
        stats.sigma = float(sig)
        return cls(tree=tree, bp=bp, qstate=qs, sigma=sig, stats=stats,
                   divergence=bound_div)

    # ------------------------------------------------------------- inference
    @property
    def bound_divergence(self) -> div_mod.BoundDivergence:
        """The fitted divergence, normalized (``None`` -> bound sqeuclidean)."""
        if self.divergence is None:
            self.divergence = div_mod.bind_divergence(None, self.tree)
        return self.divergence

    @property
    def divergence_name(self) -> str:
        """Registry name of the fitted divergence (serving dispatch keys)."""
        return self.bound_divergence.name

    def _dispatch_buffers(self) -> tuple:
        """(a, b, q, leaf_mask) on device, cached across calls.

        ``(a, b, q)`` is the scan table of
        :func:`~repro.core.matvec.scan_table`: the active blocks sorted by
        row node, with the ready-to-use ``exp(log_q)``.  ``leaf_mask`` is
        1.0 exactly at leaf slots holding a real row (so the leaf-order LP
        scan can keep ghost slots at zero).  Invalidated by :meth:`refine`.
        """
        if self._serve_cache is None:
            # eager even when the first call comes from inside a trace (a
            # matvec closure under lax.scan): the table is built on the host
            # and the cache outlives the trace
            with jax.ensure_compile_time_eval():
                q = matvec_mod.prepare_q(jnp.asarray(self.bp.active),
                                         self.qstate.log_q)
                a, b, q = (jnp.asarray(t) for t in matvec_mod.scan_table(
                    self.bp.a, self.bp.b, self.bp.active, q, self.tree.n_nodes))
                mask = jnp.zeros((self.tree.n_leaves, 1), jnp.float32)
                mask = mask.at[self.tree.slot_of, 0].set(1.0)
            jax.block_until_ready(q)
            self._serve_cache = (a, b, q, mask)
        return self._serve_cache

    @property
    def x_rows(self) -> jax.Array:
        """The fitted points in original row order, (N, d), cached on device."""
        if self._x_rows_cache is None:
            self._x_rows_cache = self.tree.x_leaf[self.tree.slot_of]
        return self._x_rows_cache

    def matvec(self, y) -> jax.Array:
        """Q @ y in O(|B| + N) (Algorithm 1).

        Accepts a single RHS ``(N,)``/``(N, C)`` or a stacked multi-RHS
        ``(batch, N, C)``; the latter is served in ONE device dispatch via
        the channel-folded batched path (see ``core.matvec``).
        """
        a, b, q, _ = self._dispatch_buffers()
        return matvec_mod.table_matvec(self.tree, (a, b, q), y)

    def matvec_batched(self, ys) -> jax.Array:
        """Explicit batched multi-RHS: (batch, N, C) -> (batch, N, C)."""
        ys = jnp.asarray(ys)
        if ys.ndim != 3:
            raise ValueError(f"matvec_batched wants (batch, N, C), got {ys.shape}")
        return self.matvec(ys)

    def grf_graph(self):
        """The CSR transition graph the GRF backend walks, cached.

        Bridged from the fitted point cloud via the dense eq.-3 kernel
        (``core.grf.CSRGraph.from_points``), so GRF estimates are unbiased
        for exactly the matrix the ``"exact"`` backend serves.  Raises
        ``ValueError`` for positive-domain divergences (KL,
        Itakura-Saito) — see ``core/grf.py``.
        """
        from repro.core import grf as grf_mod

        if self._grf_cache is None:
            self._grf_cache = grf_mod.CSRGraph.from_points(
                self.x_rows, float(self.sigma),
                divergence=self.bound_divergence.div)
        return self._grf_cache

    def label_propagate(self, y0, alpha=0.01, n_iters: int = 500,
                        batched: Optional[bool] = None,
                        backend: str = "vdt",
                        n_walkers: Optional[int] = None, seed: int = 0):
        """Label propagation (eq. 15) from seed labels ``y0``.

        ``y0`` may be a single ``(N, C)`` label matrix or a stacked
        ``(batch, N, C)`` set of independent propagation problems over the
        same fitted tree.  ``batched=None`` infers from ``y0.ndim``; the
        batched path folds the batch into the channel axis once, runs the
        whole ``lax.scan`` in the folded ``(N, batch * C)`` layout (so every
        iteration is a single Algorithm-1 dispatch), and unfolds at the end.

        ``alpha`` may be a scalar, a per-column ``(C,)`` array (2-D ``y0``),
        or a per-request ``(batch,)`` array (3-D ``y0``) — LP is
        column-independent, so heterogeneous alphas are exact and share the
        one dispatch.  Alpha is a *traced* argument of the underlying jitted
        scan: serving different alphas never grows the compile cache.

        ``backend`` selects the transition matrix the walk runs on:

        * ``"vdt"`` (default) — the fitted O(|B|) approximation Q.  The scan
          runs in leaf order end-to-end (``lp_scan_leaforder``): the
          row<->leaf permutation costs one scatter + one gather per *call*
          instead of per iteration, and the jitted executable is cached per
          ``(n_iters, shape)`` so steady-state serving pays dispatch only.
        * ``"exact"`` — the exact eq.-3 matrix P, streamed through the
          distance-reusing fused Pallas kernel (``lp_scan_fused``): P is
          never materialized, and a batched stack pays the
          pairwise-distance/softmax work once per iteration for ALL
          requests.  O(N^2 d) per iteration — the accuracy-validation path,
          not the large-N serving path.
        * ``"grf"`` — the graph-random-features walker estimator
          (``core.grf.grf_label_propagate``) over the cached
          :meth:`grf_graph`: an unbiased Monte-Carlo estimate of the same
          eq.-15 walk, O(N * n_walkers) per iteration.  ``n_walkers``
          (default ``core.grf.DEFAULT_N_WALKERS``) is the accuracy dial —
          relative error ~ ``1/sqrt(n_walkers)`` — and ``seed`` makes the
          estimate deterministic (bit-identical per ``(seed, shapes)``).
          Both are ignored by the other backends.

        Profiler spans (``jax.profiler.TraceAnnotation``, nearly free when
        no trace runs), none inside another: ``vdt.h2d`` around each copy
        from the host; on the vdt backend ``vdt.permute`` around the fold,
        the row->leaf scatter, the leaf->row gather and the unfold, and
        ``vdt.scan`` around the launch of the walk, which returns before
        the device finishes it.
        """
        if not isinstance(y0, jax.Array):
            with jax.profiler.TraceAnnotation("vdt.h2d"):
                y0 = jnp.asarray(y0)
        if not jnp.issubdtype(y0.dtype, jnp.floating):
            y0 = y0.astype(jnp.float32)
        if backend not in ("vdt", "exact", "grf"):
            raise ValueError(
                f"backend must be 'vdt', 'exact' or 'grf', got {backend!r}")
        if backend == "grf":
            from repro.core import grf as grf_mod

            if batched and y0.ndim != 3:
                raise ValueError(
                    f"batched label_propagate wants (batch, N, C), got {y0.shape}")
            return grf_mod.grf_label_propagate(
                self.grf_graph(), y0, alpha=alpha, n_iters=int(n_iters),
                n_walkers=int(n_walkers or grf_mod.DEFAULT_N_WALKERS),
                seed=int(seed))
        if backend == "exact":
            if batched and y0.ndim != 3:
                raise ValueError(
                    f"batched label_propagate wants (batch, N, C), got {y0.shape}")
            return lp_scan_fused(self.x_rows, y0, float(self.sigma), alpha,
                                 int(n_iters),
                                 divergence=self.bound_divergence.div)
        if batched is None:
            batched = y0.ndim == 3
        if batched:
            if y0.ndim != 3:
                raise ValueError(
                    f"batched label_propagate wants (batch, N, C), got {y0.shape}")
            batch, _, c = y0.shape
            with jax.profiler.TraceAnnotation("vdt.h2d"):
                alpha = jnp.asarray(alpha, y0.dtype)
            with jax.profiler.TraceAnnotation("vdt.permute"):
                if alpha.ndim == 1:
                    if alpha.shape[0] != batch:
                        raise ValueError(
                            f"per-request alpha wants shape ({batch},), got {alpha.shape}")
                    # folded column b*C + ch belongs to request b (see fold_batch)
                    alpha = jnp.repeat(alpha, c)
                y = matvec_mod.fold_batch(y0)
            out = self.label_propagate(y, alpha=alpha, n_iters=n_iters,
                                       batched=False)
            with jax.profiler.TraceAnnotation("vdt.permute"):
                return matvec_mod.unfold_batch(out, batch, c)

        squeeze = y0.ndim == 1
        if squeeze:
            y0 = y0[:, None]
        tree = self.tree
        a, b, q, mask = self._dispatch_buffers()
        with jax.profiler.TraceAnnotation("vdt.permute"):
            y_leaf = jnp.zeros((tree.n_leaves, y0.shape[1]), y0.dtype)
            y_leaf = y_leaf.at[tree.slot_of].set(y0)
        with jax.profiler.TraceAnnotation("vdt.scan"):
            out_leaf = lp_scan_leaforder(
                y_leaf, mask, a, b, q, jnp.asarray(alpha, y0.dtype),
                tree.L, int(n_iters),
            )
        with jax.profiler.TraceAnnotation("vdt.permute"):
            out = out_leaf[tree.slot_of]
        return out[:, 0] if squeeze else out

    def label_propagate_resume(self, y, y0, alpha=0.01, n_iters: int = 500,
                               batched: Optional[bool] = None,
                               backend: str = "vdt"):
        """Continue an eq.-15 walk for ``n_iters`` more steps from carry ``y``.

        The segmented-dispatch counterpart of :meth:`label_propagate`: ``y``
        is the output of an earlier (shorter) propagation from the same seed
        ``y0``, and the continued walk is *bit-identical* to having run the
        combined iteration count monolithically — eq. 15 is a pure
        fixed-point iteration, so the split is exact (see
        ``core.label_prop.lp_scan_leaforder_resume`` /
        ``lp_scan_fused_resume``).  The serving engine calls this once per
        checkpointed segment, re-checking its queue between calls so a
        tight-deadline arrival can preempt a long in-flight dispatch.

        Shapes, ``alpha`` semantics, and ``backend`` match
        :meth:`label_propagate`; ``y`` must have ``y0``'s exact shape.
        """
        y0 = jnp.asarray(y0)
        if not jnp.issubdtype(y0.dtype, jnp.floating):
            y0 = y0.astype(jnp.float32)
        y = jnp.asarray(y, y0.dtype)
        if y.shape != y0.shape:
            raise ValueError(
                f"carry shape {y.shape} must match seed shape {y0.shape}")
        if backend == "grf":
            # the MC estimator is a weighted sum over walk prefixes, not a
            # fixed-point iteration: a carry is not its complete state, so
            # there is no exact resume primitive — grf dispatches are
            # always monolithic (the serving engine never segments them)
            raise ValueError(
                "backend='grf' does not support segmented resume; "
                "grf scans dispatch monolithically")
        if backend not in ("vdt", "exact"):
            raise ValueError(
                f"backend must be 'vdt' or 'exact', got {backend!r}")
        if backend == "exact":
            if batched and y0.ndim != 3:
                raise ValueError(
                    f"batched label_propagate wants (batch, N, C), got {y0.shape}")
            return lp_scan_fused_resume(
                self.x_rows, y, y0, float(self.sigma), alpha, int(n_iters),
                divergence=self.bound_divergence.div)
        if batched is None:
            batched = y0.ndim == 3
        if batched:
            if y0.ndim != 3:
                raise ValueError(
                    f"batched label_propagate wants (batch, N, C), got {y0.shape}")
            batch, _, c = y0.shape
            alpha = jnp.asarray(alpha, y0.dtype)
            if alpha.ndim == 1:
                if alpha.shape[0] != batch:
                    raise ValueError(
                        f"per-request alpha wants shape ({batch},), got {alpha.shape}")
                alpha = jnp.repeat(alpha, c)
            out = self.label_propagate_resume(
                matvec_mod.fold_batch(y), matvec_mod.fold_batch(y0),
                alpha=alpha, n_iters=n_iters, batched=False)
            return matvec_mod.unfold_batch(out, batch, c)

        squeeze = y0.ndim == 1
        if squeeze:
            y, y0 = y[:, None], y0[:, None]
        tree = self.tree
        a, b, q, mask = self._dispatch_buffers()
        # ghost slots are zero both in the seed and (by the re-masking
        # invariant) in any mid-walk carry, so scattering the row-order
        # carry into zeros reproduces the in-scan leaf state exactly
        y0_leaf = jnp.zeros((tree.n_leaves, y0.shape[1]), y0.dtype)
        y0_leaf = y0_leaf.at[tree.slot_of].set(y0)
        y_leaf = jnp.zeros((tree.n_leaves, y0.shape[1]), y0.dtype)
        y_leaf = y_leaf.at[tree.slot_of].set(y)
        out_leaf = lp_scan_leaforder_resume(
            y_leaf, y0_leaf, mask, a, b, q, jnp.asarray(alpha, y0.dtype),
            tree.L, int(n_iters),
        )
        out = out_leaf[tree.slot_of]
        return out[:, 0] if squeeze else out

    # ------------------------------------------------------------- streaming
    def insert_points(self, x_new, weights=None):
        """Insert points online; returns a StreamUpdate with the new epoch.

        O(k d log N) stat patching, no refit — see ``core/streaming.py``.
        Copy-on-write: ``self`` is untouched; serve from ``update.vdt``.
        """
        from repro.core.streaming import insert_points as _ins
        return _ins(self, x_new, weights=weights)

    def delete_points(self, rows):
        """Delete points by row id online; see :meth:`insert_points`."""
        from repro.core.streaming import delete_points as _del
        return _del(self, rows)

    # ------------------------------------------------------------- utilities
    def refine(self, max_blocks: int, batch: int = 64) -> None:
        stream = self._stream
        stale = None
        if stream is not None and stream.owner() is self:
            # streaming-touched blocks get the budget first
            stale = stream.stale
        t0 = time.perf_counter()
        self.qstate, self.sigma = refine_mod.refine_to_budget(
            self.bp, self.tree, self.sigma, max_blocks, batch=batch,
            divergence=self.bound_divergence, stale=stale, stats=self.stats,
        )
        self._serve_cache = None  # the scan table changed
        self._stream = None  # refinement regrew the partition; mirrors stale
        self.stats.n_blocks = self.bp.n_active
        self.stats.scan_slots = matvec_mod.scan_slots(self.bp.n_active)
        self.stats.bound = float(self.qstate.bound)
        self.stats.refine_s += time.perf_counter() - t0

    def _check_finite_q(self) -> None:
        """Guard against a divergence/domain mismatch poisoning the model.

        ``fit`` validates the data domain up front, but a hand-constructed
        model (or one whose q-state was recomputed under the wrong
        divergence) can carry NaN/-inf-everywhere q; surface that as a clear
        error instead of silently emitting NaN results downstream.
        """
        bound = np.asarray(self.qstate.bound)
        if not np.isfinite(bound):
            raise ValueError(
                f"non-finite variational state (bound={float(bound)}) under "
                f"divergence {self.divergence_name!r} — likely a "
                f"divergence/domain mismatch (e.g. 'kl' requires strictly "
                f"positive inputs); refit with in-domain data or the "
                f"right divergence")

    def dense_q(self) -> np.ndarray:
        """Dense (N, N) Q — small-N tests only."""
        self._check_finite_q()
        q = np.asarray(
            jnp.where(jnp.isfinite(self.qstate.log_q), jnp.exp(self.qstate.log_q), 0.0)
        )
        return blocks_mod.densify_q(self.bp, self.tree, q)

    def lower_bound(self, log_q=None) -> jax.Array:
        """l(D) for ``log_q`` (default: the fitted q) under the fitted divergence."""
        self._check_finite_q()
        lq = self.qstate.log_q if log_q is None else jnp.asarray(log_q)
        return qopt_mod.lower_bound(self.tree, jnp.asarray(self.bp.a),
                                    jnp.asarray(self.bp.b),
                                    jnp.asarray(self.bp.active), lq, self.sigma,
                                    divergence=self.bound_divergence)

    @property
    def n_blocks(self) -> int:
        return self.bp.n_active

    @property
    def bound(self) -> float:
        return float(self.qstate.bound)
