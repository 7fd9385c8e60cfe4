"""The VDT scan's block table (``core.matvec.scan_table``).

The scan walks the active blocks alone, sorted by row node, padded to a
length bucket with ids the segment-sum drops; its segment-sum takes the
ids as sorted.  Pinned here:

* the table holds exactly the active blocks, sorted by ``(a, b)``, and
  pads with ``a = n_nodes``, ``b = 0``, ``q = 0`` up to ``scan_slots``;
* a walk over the table matches a plain walk over the block partition's
  capacity arrays (unsorted ids, inactive slots weighted 0), at widths on
  both sides of the column slabs;
* a streaming publish that keeps the active count inside its bucket
  reuses the scan's compiled program;
* the slot count reaches ``VdtStats`` and the engine's metrics.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.label_prop import VDT_MAX_COLS, lp_scan_leaforder
from repro.core.matvec import (_distribute_down, collect_up, prepare_q,
                               scan_slots, scan_table)
from repro.serving import PropagateEngine, PropagateRequest


@pytest.fixture(scope="module")
def golden_vdt():
    """Model refit from the golden fixture's data (N = 33)."""
    from repro.core.vdt import VariationalDualTree

    x = np.load("tests/golden_sqeuclidean.npz")["x"]
    return x, VariationalDualTree.fit(x, max_blocks=4 * x.shape[0])


def _table(vdt):
    q = prepare_q(jnp.asarray(vdt.bp.active), vdt.qstate.log_q)
    return scan_table(vdt.bp.a, vdt.bp.b, vdt.bp.active, q, vdt.tree.n_nodes), q


@pytest.mark.parametrize("n, slots", [
    (0, 1), (1, 1), (16, 16), (17, 17), (31, 31), (32, 32), (33, 34),
    (1000, 1024), (334_716, 344_064),
])
def test_scan_slots_bucket(n, slots):
    """The next multiple of 2**(floor(log2 n) - 4): at most 1/16 padding."""
    assert scan_slots(n) == slots
    assert max(n, 1) <= slots <= max(n, 1) * 17 / 16


@pytest.mark.parametrize("model", ["golden_vdt", "small_fitted_vdt",
                                   "separated_clusters_vdt"])
def test_table_holds_the_active_blocks_sorted(request, model):
    vdt = request.getfixturevalue(model)[-1]
    (ta, tb, tq), q = _table(vdt)
    bp, n_nodes = vdt.bp, vdt.tree.n_nodes
    n = bp.n_active
    assert ta.shape == tb.shape == tq.shape == (scan_slots(n),)
    assert vdt.stats.scan_slots == ta.size and vdt.stats.n_blocks == n
    # sorted by (a, b): the segment-sum's ids never decrease
    assert (np.diff(ta) >= 0).all()
    key = ta[:n].astype(np.int64) * n_nodes + tb[:n]
    assert (np.diff(key) > 0).all()
    # exactly the active blocks, each with its own q
    act = np.flatnonzero(bp.active)
    want = sorted(zip(bp.a[act], bp.b[act], np.asarray(q)[act]))
    got = list(zip(ta[:n], tb[:n], tq[:n]))
    assert got == want
    # pads: dropped by the segment-sum, weight 0
    assert (ta[n:] == n_nodes).all() and (tb[n:] == 0).all() and (tq[n:] == 0).all()
    # the model serves this very table
    a, b, qq, _ = vdt._dispatch_buffers()
    for dev, host in ((a, ta), (b, tb), (qq, tq)):
        np.testing.assert_array_equal(np.asarray(dev), host)


def _capacity_walk(vdt, y0_leaf, mask, alpha, n_iters):
    """Eq. 15 over the capacity arrays: every slot, unsorted ids, no slabs."""
    tree = vdt.tree
    a, b = jnp.asarray(vdt.bp.a), jnp.asarray(vdt.bp.b)
    q = prepare_q(jnp.asarray(vdt.bp.active), vdt.qstate.log_q)

    @jax.jit
    def step(y):
        t = collect_up(y, tree.L)
        c_node = jax.ops.segment_sum(q[:, None] * jnp.take(t, b, axis=0), a,
                                     num_segments=tree.n_nodes)
        return mask * (alpha * _distribute_down(c_node, tree.L)) \
            + (1.0 - alpha) * y0_leaf

    y = y0_leaf
    for _ in range(n_iters):
        y = step(y)
    return y


@pytest.mark.parametrize("width", [2, 64, VDT_MAX_COLS + 8])
def test_table_walk_matches_capacity_walk(golden_vdt, width):
    x, vdt = golden_vdt
    tree = vdt.tree
    r = np.random.RandomState(width)
    y0 = (r.rand(x.shape[0], width) > 0.7).astype(np.float32)
    alpha = np.float32(0.3)
    got = np.asarray(vdt.label_propagate(y0, alpha=alpha, n_iters=20))

    _, _, _, mask = vdt._dispatch_buffers()
    y0_leaf = jnp.zeros((tree.n_leaves, width)).at[tree.slot_of].set(y0)
    want = np.asarray(_capacity_walk(vdt, y0_leaf, mask, alpha, 20))
    want = want[np.asarray(tree.slot_of)]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("op, max_blocks", [("insert", 148), ("delete", 150)])
def test_publish_inside_the_bucket_reuses_the_scan(op, max_blocks):
    """An epoch whose active count stays in its bucket walks a table of
    the same length, so the scan compiles nothing new."""
    from repro.core.vdt import VariationalDualTree

    r = np.random.RandomState(5)
    x = r.randn(40, 4).astype(np.float32)
    vdt = VariationalDualTree.fit(x, max_blocks=max_blocks, capacity=64)
    if op == "insert":
        new = vdt.insert_points(r.randn(2, 4).astype(np.float32)).vdt
    else:
        new = vdt.delete_points([3]).vdt
    assert new.n_blocks != vdt.n_blocks
    assert new.stats.scan_slots == vdt.stats.scan_slots  # inside the bucket

    def serve(eng, model):
        y0 = (r.rand(model.tree.n_points, 2) > 0.7).astype(np.float32)
        fut = eng.submit(PropagateRequest(y0, alpha=0.1, n_iters=7))
        eng.flush()
        return fut.result()

    eng = PropagateEngine(vdt, start=False, max_batch=2)
    try:
        serve(eng, vdt)
        compiled = lp_scan_leaforder._cache_size()
        eng.publish(new)
        out = serve(eng, new)
        assert lp_scan_leaforder._cache_size() == compiled
        assert out.shape == (new.tree.n_points, 2) and np.isfinite(out).all()
        assert eng.metrics().scan_slots == new.stats.scan_slots
    finally:
        eng.shutdown()


@pytest.mark.parametrize("path", ["model", "capacity_arrays"])
def test_table_built_inside_a_trace_outlives_it(path):
    """A matvec closure walked by the generic ``label_propagate`` (under
    ``lax.scan``) builds its table eagerly on the host; the model's cache
    holds concrete arrays that later calls reuse."""
    from repro.core.label_prop import label_propagate
    from repro.core.matvec import mpt_matvec
    from repro.core.vdt import VariationalDualTree

    r = np.random.RandomState(9)
    x = r.randn(40, 4).astype(np.float32)
    vdt = VariationalDualTree.fit(x, max_blocks=150)
    if path == "model":
        matvec = vdt.matvec
    else:
        bp = vdt.bp
        matvec = lambda y: mpt_matvec(vdt.tree, bp.a, bp.b, bp.active,  # noqa: E731
                                      vdt.qstate.log_q, y)
    y0 = (r.rand(40, 2) > 0.7).astype(np.float32)
    got = np.asarray(label_propagate(matvec, jnp.asarray(y0), alpha=0.1, n_iters=5))
    for buf in vdt._dispatch_buffers():
        assert not isinstance(buf, jax.core.Tracer)
    want = np.asarray(vdt.label_propagate(y0, alpha=0.1, n_iters=5))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
