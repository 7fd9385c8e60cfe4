"""Batched multi-RHS engine: level-major + channel-folded paths, the fused
batched Pallas LP-step kernel, and the propagate_many serving path.

Parity chain pinned here (small N):

    batched mpt_matvec == stacked single-RHS mpt_matvec == dense Q @ Y
"""
import numpy as np
import pytest
from _hyp import given, settings, st

import jax.numpy as jnp

from repro.core.matvec import (collect_up, mpt_matvec, mpt_matvec_batched,
                               mpt_matvec_leaforder, prepare_q, scan_table)
from repro.kernels.fused_lp import (fused_lp_matvec_batched,
                                    fused_lp_matvec_batched_ref,
                                    fused_lp_scan_batched,
                                    fused_lp_scan_batched_ref,
                                    fused_lp_step_batched,
                                    fused_lp_step_batched_ref)
from repro.serving import PropagateRequest, propagate_many


def _mv_args(vdt):
    return (vdt.tree, jnp.asarray(vdt.bp.a), jnp.asarray(vdt.bp.b),
            jnp.asarray(vdt.bp.active), vdt.qstate.log_q)


# --------------------------------------------------------- core batched path
@pytest.mark.parametrize("batch", [1, 3, 8])  # incl. non-power-of-two
def test_batched_matvec_matches_stacked_and_dense(small_fitted_vdt, batch):
    x, vdt = small_fitted_vdt
    n = x.shape[0]
    r = np.random.RandomState(batch)
    ys = r.randn(batch, n, 3).astype(np.float32)

    got = np.asarray(mpt_matvec_batched(*_mv_args(vdt), jnp.asarray(ys)))
    stacked = np.stack(
        [np.asarray(mpt_matvec(*_mv_args(vdt), jnp.asarray(ys[i])))
         for i in range(batch)])
    dense = vdt.dense_q()
    want = np.einsum("ij,bjc->bic", dense, ys)

    assert got.shape == (batch, n, 3)
    np.testing.assert_allclose(got, stacked, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_level_major_leaforder_accepts_leading_batch(small_fitted_vdt):
    """collect_up / mpt_matvec_leaforder carry batch dims natively."""
    _, vdt = small_fitted_vdt
    tree = vdt.tree
    r = np.random.RandomState(0)
    y_leaf = r.randn(4, tree.n_leaves, 2).astype(np.float32)
    y_leaf *= np.asarray(tree.w_leaf)[None, :, None]  # zero the ghosts

    t_b = np.asarray(collect_up(jnp.asarray(y_leaf), tree.L))
    t_s = np.stack([np.asarray(collect_up(jnp.asarray(y_leaf[i]), tree.L))
                    for i in range(4)])
    np.testing.assert_allclose(t_b, t_s, rtol=1e-6, atol=1e-6)

    q = prepare_q(jnp.asarray(vdt.bp.active), vdt.qstate.log_q)
    a, b, q = scan_table(vdt.bp.a, vdt.bp.b, vdt.bp.active, q, tree.n_nodes)
    assert (np.diff(a) >= 0).all()  # the segment-sum takes them as sorted
    o_b = np.asarray(mpt_matvec_leaforder(jnp.asarray(y_leaf), a, b, q, tree.L))
    o_s = np.stack(
        [np.asarray(mpt_matvec_leaforder(jnp.asarray(y_leaf[i]), a, b, q,
                                         tree.L)) for i in range(4)])
    np.testing.assert_allclose(o_b, o_s, rtol=1e-5, atol=1e-6)


def test_batched_matvec_rejects_bad_rank(small_fitted_vdt):
    _, vdt = small_fitted_vdt
    with pytest.raises(ValueError):
        mpt_matvec_batched(*_mv_args(vdt), jnp.zeros((33, 2)))


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_batched_linearity_property(small_fitted_vdt, seed):
    """Q(aY1 + Y2) == a QY1 + QY2 through the batched path (shape-stable
    draws: only the seed varies, so tier-1 pays one compile)."""
    x, vdt = small_fitted_vdt
    n = x.shape[0]
    r = np.random.RandomState(seed)
    y1 = jnp.asarray(r.randn(2, n, 2).astype(np.float32))
    y2 = jnp.asarray(r.randn(2, n, 2).astype(np.float32))
    o1 = np.asarray(mpt_matvec_batched(*_mv_args(vdt), y1))
    o2 = np.asarray(mpt_matvec_batched(*_mv_args(vdt), y2))
    o12 = np.asarray(mpt_matvec_batched(*_mv_args(vdt), 3.0 * y1 + y2))
    np.testing.assert_allclose(o12, 3.0 * o1 + o2, rtol=1e-3, atol=1e-4)


# ------------------------------------------------------ batched LP (eq. 15)
def test_batched_label_propagate_matches_loop(small_fitted_vdt):
    """(batch=8, N, C) stack == 8 looped single-RHS propagations (atol 1e-5,
    the PR's acceptance criterion)."""
    x, vdt = small_fitted_vdt
    n = x.shape[0]
    r = np.random.RandomState(1)
    y0 = (r.rand(8, n, 3) > 0.8).astype(np.float32)

    got = np.asarray(vdt.label_propagate(y0, alpha=0.05, n_iters=60))
    want = np.stack(
        [np.asarray(vdt.label_propagate(y0[i], alpha=0.05, n_iters=60))
         for i in range(8)])
    assert got.shape == (8, n, 3)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_batched_label_propagate_batch_one(small_fitted_vdt):
    x, vdt = small_fitted_vdt
    n = x.shape[0]
    r = np.random.RandomState(2)
    y0 = (r.rand(1, n, 2) > 0.8).astype(np.float32)
    got = np.asarray(vdt.label_propagate(y0, alpha=0.1, n_iters=40))
    want = np.asarray(vdt.label_propagate(y0[0], alpha=0.1, n_iters=40))
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------- fused batched Pallas kernel
@pytest.mark.parametrize("batch,n,c", [(1, 40, 2), (3, 33, 3), (4, 64, 1)])
def test_fused_batched_matvec_matches_ref(rng, batch, n, c):
    x = jnp.asarray(rng.randn(n, 5), jnp.float32)
    ys = jnp.asarray(rng.randn(batch, n, c), jnp.float32)
    got = fused_lp_matvec_batched(x, ys, 1.0, block_m=16, block_n=16)
    want = fused_lp_matvec_batched_ref(x, ys, 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("batch", [1, 3])
def test_fused_batched_lp_step_matches_ref(rng, batch):
    n, c, alpha = 48, 2, 0.05
    x = jnp.asarray(rng.randn(n, 4), jnp.float32)
    ys = jnp.asarray(rng.randn(batch, n, c), jnp.float32)
    y0s = jnp.asarray(rng.randn(batch, n, c), jnp.float32)
    got = fused_lp_step_batched(x, ys, y0s, 1.0, alpha, block_m=16, block_n=16)
    want = fused_lp_step_batched_ref(x, ys, y0s, 1.0, alpha)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_fused_batched_row_stochastic_action(rng):
    """P @ 1 == 1 for every batch element through the batched kernel."""
    x = jnp.asarray(rng.randn(40, 3), jnp.float32)
    ones = jnp.ones((3, 40, 1), jnp.float32)
    got = np.asarray(fused_lp_matvec_batched(x, ones, 1.0,
                                             block_m=16, block_n=16))
    np.testing.assert_allclose(got, 1.0, rtol=1e-5)


# ------------------------------------------ distance-reusing batched kernel
@pytest.mark.parametrize("batch", [1, 3, 8])
@pytest.mark.parametrize("c", [1, 2, 16])
@pytest.mark.parametrize("n", [37])  # odd, non-power-of-two: exercises padding
def test_reuse_kernel_matches_perbatch_and_dense(rng, batch, c, n):
    """The distance-reusing layout == the per-batch-recompute layout == the
    dense eq.-15 reference, across batch/width/ragged-N combinations."""
    alpha, sigma = 0.05, 1.0
    x = jnp.asarray(rng.randn(n, 4), jnp.float32)
    ys = jnp.asarray(rng.randn(batch, n, c), jnp.float32)
    y0s = jnp.asarray(rng.randn(batch, n, c), jnp.float32)
    reuse = np.asarray(fused_lp_step_batched(
        x, ys, y0s, sigma, alpha, block_m=16, block_n=16, reuse=True))
    perbatch = np.asarray(fused_lp_step_batched(
        x, ys, y0s, sigma, alpha, block_m=16, block_n=16, reuse=False))
    dense = np.asarray(fused_lp_step_batched_ref(x, ys, y0s, sigma, alpha))
    np.testing.assert_allclose(reuse, perbatch, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(reuse, dense, rtol=1e-4, atol=1e-5)


def test_reuse_kernel_per_request_alpha(rng):
    """A traced (B,) alpha folds to per-column and matches the dense ref."""
    batch, n, c = 3, 40, 2
    x = jnp.asarray(rng.randn(n, 4), jnp.float32)
    ys = jnp.asarray(rng.randn(batch, n, c), jnp.float32)
    y0s = jnp.asarray(rng.randn(batch, n, c), jnp.float32)
    al = jnp.asarray([0.01, 0.2, 1.0], jnp.float32)
    got = np.asarray(fused_lp_step_batched(x, ys, y0s, 1.0, al,
                                           block_m=16, block_n=16))
    want = (np.asarray(al)[:, None, None]
            * np.asarray(fused_lp_matvec_batched_ref(x, ys, 1.0))
            + (1.0 - np.asarray(al)[:, None, None]) * np.asarray(y0s))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)


def test_reuse_scan_matches_iterated_dense(rng):
    """The multi-iteration reuse scan == explicit dense eq.-15 iterations."""
    batch, n, c, iters = 2, 33, 3, 4
    x = jnp.asarray(rng.randn(n, 4), jnp.float32)
    y0s = jnp.asarray((rng.rand(batch, n, c) > 0.8), jnp.float32)
    al = jnp.asarray([0.05, 0.3], jnp.float32)
    got = np.asarray(fused_lp_scan_batched(x, y0s, 1.0, al, iters,
                                           block_m=16, block_n=16))
    want = np.asarray(fused_lp_scan_batched_ref(x, y0s, 1.0, al, iters))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------ exact serving backend
def test_label_propagate_exact_backend_matches_dense(small_fitted_vdt):
    """backend='exact' runs eq. 15 on the exact P (streamed, never dense) —
    parity with an explicit dense-P iteration at the fitted sigma."""
    from repro.core.baselines import exact_transition_matrix

    x, vdt = small_fitted_vdt
    n = x.shape[0]
    r = np.random.RandomState(5)
    y0 = (r.rand(n, 3) > 0.8).astype(np.float32)
    got = np.asarray(vdt.label_propagate(y0, alpha=0.1, n_iters=6,
                                         backend="exact"))
    p = np.asarray(exact_transition_matrix(jnp.asarray(x), vdt.sigma))
    want = y0.copy()
    for _ in range(6):
        want = 0.1 * p @ want + 0.9 * y0
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    # batched with per-request alpha agrees with per-request exact calls
    y0s = (r.rand(2, n, 2) > 0.8).astype(np.float32)
    alphas = np.asarray([0.05, 0.2], np.float32)
    got_b = np.asarray(vdt.label_propagate(y0s, alpha=alphas, n_iters=6,
                                           backend="exact"))
    for b in range(2):
        want_b = np.asarray(vdt.label_propagate(
            y0s[b], alpha=float(alphas[b]), n_iters=6, backend="exact"))
        np.testing.assert_allclose(got_b[b], want_b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("resume", [False, True])
def test_wide_walk_runs_in_column_slabs(small_fitted_vdt, resume):
    """A walk wider than VDT_MAX_COLS runs slab by slab and equals the
    narrow walks of its column slices bit for bit (LP is
    column-independent), monolithic and resumed alike."""
    from repro.core.label_prop import VDT_MAX_COLS

    x, vdt = small_fitted_vdt
    k = 2 * VDT_MAX_COLS + 3
    r = np.random.RandomState(5)
    y0 = (r.rand(x.shape[0], k) > 0.8).astype(np.float32)
    alpha = r.uniform(0.0, 0.5, size=k).astype(np.float32)

    def walk(cols):
        if resume:
            return vdt.label_propagate_resume(2 * y0[:, cols], y0[:, cols],
                                              alpha=alpha[cols], n_iters=7)
        return vdt.label_propagate(y0[:, cols], alpha=alpha[cols], n_iters=7)

    wide = np.asarray(walk(slice(None)))
    for lo in range(0, k, VDT_MAX_COLS):
        cols = slice(lo, min(lo + VDT_MAX_COLS, k))
        np.testing.assert_array_equal(wide[:, cols], np.asarray(walk(cols)))


def test_label_propagate_rejects_unknown_backend(small_fitted_vdt):
    _, vdt = small_fitted_vdt
    with pytest.raises(ValueError):
        vdt.label_propagate(np.zeros((33, 2), np.float32), backend="dense")


# ------------------------------------------------------------ serving layer
def test_propagate_many_matches_single_calls(small_fitted_vdt):
    """Heterogeneous widths/alphas, answered in request order, each equal to
    its single-RHS label_propagate."""
    x, vdt = small_fitted_vdt
    n = x.shape[0]
    r = np.random.RandomState(4)
    recipes = [(2, 0.05, 30), (3, 0.05, 30), (5, 0.05, 30), (2, 0.1, 30),
               (2, 0.05, 30)]
    reqs = [PropagateRequest((r.rand(n, c) > 0.8).astype(np.float32),
                             alpha=a, n_iters=it) for c, a, it in recipes]
    outs = propagate_many(vdt, reqs, max_batch=2)
    assert len(outs) == len(reqs)
    for req, out in zip(reqs, outs):
        assert out.shape == req.y0.shape
        want = np.asarray(vdt.label_propagate(
            jnp.asarray(req.y0), alpha=req.alpha, n_iters=req.n_iters))
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)


def test_propagate_many_rejects_bad_shapes(small_fitted_vdt):
    _, vdt = small_fitted_vdt
    with pytest.raises(ValueError):
        propagate_many(vdt, [PropagateRequest(np.zeros((5, 2), np.float32))])
    with pytest.raises(ValueError):
        propagate_many(
            vdt, [PropagateRequest(np.zeros((33, 999), np.float32))])
