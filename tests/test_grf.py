"""GRF backend: CLT-bounded unbiasedness, variance decay, invariants,
differentials vs the exact backend, routing boundaries, and engine serving.

Every stochastic assertion goes through ``tests/_stats.py``: bounds are
derived from the estimator's own sampled spread at Z = 5 — never a
hand-tuned atol — and all seeds are fixed, so each test is deterministic
(a pass today is a pass tomorrow; see the _stats module docstring).
"""
import math

import numpy as np
import pytest

from repro.core.grf import (CSRGraph, MAX_RTOL_WALKERS, grf_label_propagate,
                            grf_transition_action, sample_walks,
                            walkers_for_rtol)
from repro.core.label_prop import (AUTO_EXACT_MAX_N, AUTO_GRF_MAX_DENSITY,
                                   AUTO_GRF_MIN_RTOL, route_backend)
from repro.kernels.grf.ref import dense_lp_ref, dense_power_action_ref
from tests._stats import assert_unbiased, assert_variance_decays

N = 24          # graph size for the statistical harness — small enough
#                 that m = 2048 walkers per node stays cheap on CPU
DEG = 4         # out-degree of the random test graph (density 4/24 ~ 0.17)


def _random_graph(rng, n=N, deg=DEG):
    """Connected-ish random sparse digraph with non-uniform edge weights.

    Non-uniform weights matter: they exercise the importance correction
    ``deg(u) * P[u, v]`` (uniform weights make it degenerate to 1 on
    regular graphs, which would hide a broken multiplier).
    """
    indptr = np.arange(n + 1, dtype=np.int64) * deg
    indices = np.concatenate(
        [rng.choice(n, size=deg, replace=False) for _ in range(n)])
    weights = rng.rand(n * deg) + 0.1
    return CSRGraph.from_csr(indptr, indices, weights)


@pytest.fixture(scope="module")
def graph():
    return _random_graph(np.random.RandomState(11))


@pytest.fixture(scope="module")
def dense_p(graph):
    return graph.dense_p()


# -------------------------------------------------------- unbiasedness
@pytest.mark.parametrize("t", [0, 1, 3, 7])
def test_transition_action_unbiased(graph, dense_p, t):
    """Walker-mean of P^t y is within 5 SEMs of the dense oracle, per
    element, with the SEM measured from the walkers themselves."""
    rng = np.random.RandomState(100 + t)
    y = rng.randn(N).astype(np.float32)
    oracle = dense_power_action_ref(dense_p, y, t)
    est, samples = grf_transition_action(
        graph, y, t=t, n_walkers=2048, seed=t, return_samples=True)
    assert_unbiased(np.asarray(samples), np.asarray(oracle), axis=1,
                    what=f"P^{t} y walker mean")
    np.testing.assert_allclose(np.asarray(est),
                               np.asarray(samples).mean(axis=1),
                               rtol=1e-5, atol=1e-6)


def test_transition_action_unbiased_with_halting(graph, dense_p):
    """Terminating walks (p_halt > 0) stay unbiased: the 1/(1 - p_halt)
    survivor correction exactly cancels the kill probability."""
    rng = np.random.RandomState(7)
    y = rng.randn(N).astype(np.float32)
    t = 3
    oracle = dense_power_action_ref(dense_p, y, t)
    _, samples = grf_transition_action(
        graph, y, t=t, n_walkers=4096, seed=5, p_halt=0.15,
        return_samples=True)
    assert_unbiased(np.asarray(samples), np.asarray(oracle), axis=1,
                    what="terminating-walk mean")


def test_variance_decays_with_walkers(graph, dense_p):
    """MSE shrinks like 1/m: the 8x walker budget must cut the replicate
    MSE by at least the chi-square CLT floor (derived, not tuned)."""
    rng = np.random.RandomState(21)
    y = rng.randn(N).astype(np.float32)
    t, reps, m_small, m_big = 3, 24, 8, 64
    oracle = np.asarray(dense_power_action_ref(dense_p, y, t), np.float64)

    def mses(m):
        out = []
        for seed in range(reps):
            est = grf_transition_action(graph, y, t=t, n_walkers=m,
                                        seed=1000 + seed)
            out.append(np.mean((np.asarray(est, np.float64) - oracle) ** 2))
        return out

    assert_variance_decays(mses(m_small), mses(m_big),
                           m_small=m_small, m_big=m_big)


# ---------------------------------------------------------- invariants
def test_row_stochastic_and_nonnegative(graph):
    """P^t 1 = 1 (within CLT bounds) and the action preserves sign: a
    non-negative label vector can never produce a negative estimate
    (loads are products of non-negative multipliers)."""
    ones = np.ones(N, np.float32)
    _, samples = grf_transition_action(graph, ones, t=5, n_walkers=2048,
                                       seed=3, return_samples=True)
    assert_unbiased(np.asarray(samples), ones, axis=1,
                    what="row-sum estimate")
    assert (np.asarray(samples) >= 0.0).all()

    y = np.abs(np.random.RandomState(4).randn(N, 3)).astype(np.float32)
    est = grf_transition_action(graph, y, t=4, n_walkers=64, seed=9)
    assert (np.asarray(est) >= 0.0).all()


def test_walk_loads_nonnegative_and_t0_exact(graph):
    pos, load = sample_walks(graph, n_steps=4, n_walkers=16, seed=0)
    pos, load = np.asarray(pos), np.asarray(load)
    assert (load >= 0.0).all()
    # t=0 column: every walker sits at its start node with load exactly 1
    assert (pos[:, :, 0] == np.arange(N)[:, None]).all()
    assert (load[:, :, 0] == 1.0).all()


# --------------------------------------------- determinism / prefix pins
def test_walks_deterministic_and_prefix(graph):
    """Same seed -> bit-identical walks; a horizon-T walk set is a prefix
    of the horizon-T' one (step t's randomness is fold_in(key, t))."""
    p1, l1 = sample_walks(graph, n_steps=3, n_walkers=8, seed=42)
    p2, l2 = sample_walks(graph, n_steps=3, n_walkers=8, seed=42)
    assert np.array_equal(np.asarray(p1), np.asarray(p2))
    assert np.array_equal(np.asarray(l1), np.asarray(l2))
    p7, l7 = sample_walks(graph, n_steps=7, n_walkers=8, seed=42)
    assert np.array_equal(np.asarray(p1), np.asarray(p7)[:, :, :4])
    assert np.array_equal(np.asarray(l1), np.asarray(l7)[:, :, :4])
    p_other, _ = sample_walks(graph, n_steps=3, n_walkers=8, seed=43)
    assert not np.array_equal(np.asarray(p1), np.asarray(p_other))


def test_label_propagate_deterministic_and_fold_parity(graph):
    """Repeated LP dispatches are bit-identical per seed, and a batched
    (folded) dispatch reproduces each member's solo dispatch bit-for-bit
    — the property the serving tier's coalescing leans on (walker paths
    are label-independent, so the folded stack shares one walk set)."""
    rng = np.random.RandomState(6)
    y0a = rng.rand(N, 2).astype(np.float32)
    y0b = rng.rand(N, 2).astype(np.float32)
    kw = dict(n_iters=6, n_walkers=16, seed=12)
    solo_a = np.asarray(grf_label_propagate(graph, y0a, alpha=0.05, **kw))
    again = np.asarray(grf_label_propagate(graph, y0a, alpha=0.05, **kw))
    assert np.array_equal(solo_a, again)
    solo_b = np.asarray(grf_label_propagate(graph, y0b, alpha=0.2, **kw))
    batched = np.asarray(grf_label_propagate(
        graph, np.stack([y0a, y0b]), alpha=np.array([0.05, 0.2]), **kw))
    assert np.array_equal(batched[0], solo_a)
    assert np.array_equal(batched[1], solo_b)


def test_walker_mean_matches_numpy_loop():
    """The feature reduction is the load-weighted walker mean of gathered
    label rows, element for element."""
    from repro.kernels.grf.walkers import walker_mean

    rng = np.random.RandomState(13)
    pos = rng.randint(0, 7, size=(5, 3))
    load = rng.rand(5, 3).astype(np.float32)
    y = rng.randn(7, 2).astype(np.float32)
    want = np.zeros((5, 2), np.float64)
    for s in range(5):
        for w in range(3):
            want[s] += load[s, w] * y[pos[s, w]] / 3
    np.testing.assert_allclose(np.asarray(walker_mean(pos, load, y)), want,
                               rtol=1e-5, atol=1e-6)


# -------------------------------------------------- differential: LP
def test_lp_unbiased_vs_dense_reference(graph, dense_p):
    """grf_label_propagate across seeds is centred on the dense eq.-15
    fixed reference (seed-replicate CLT bound)."""
    rng = np.random.RandomState(17)
    y0 = rng.rand(N, 2).astype(np.float32)
    alpha, n_iters, reps = 0.1, 12, 16
    oracle = np.asarray(dense_lp_ref(dense_p, y0, alpha=alpha,
                                     n_iters=n_iters))
    ests = np.stack([
        np.asarray(grf_label_propagate(graph, y0, alpha=alpha,
                                       n_iters=n_iters, n_walkers=256,
                                       seed=s))
        for s in range(reps)])
    assert_unbiased(ests, oracle, axis=0, what="grf LP vs dense_lp_ref")


def test_lp_alpha_zero_and_zero_iters(graph):
    """Degenerate recipes are exact, not just unbiased: alpha=0 returns
    the seed labels untouched, and so does n_iters=0 (the t=0 term)."""
    y0 = np.random.RandomState(8).rand(N, 2).astype(np.float32)
    out0 = grf_label_propagate(graph, y0, alpha=0.0, n_iters=5,
                               n_walkers=4, seed=0)
    np.testing.assert_allclose(np.asarray(out0), y0, rtol=1e-6, atol=1e-6)
    outz = grf_label_propagate(graph, y0, alpha=0.3, n_iters=0,
                               n_walkers=4, seed=0)
    np.testing.assert_allclose(np.asarray(outz), y0, rtol=1e-6, atol=1e-6)


def test_grf_backend_unbiased_vs_exact_backend(small_fitted_vdt):
    """Model-level differential: VariationalDualTree.label_propagate
    (backend='grf') across seeds is centred on backend='exact' — both
    walk the SAME eq.-3 matrix (from_points bridges it), so any bias is
    a real estimator bug, not a model difference."""
    x, vdt = small_fitted_vdt
    rng = np.random.RandomState(23)
    y0 = (rng.rand(x.shape[0], 2) > 0.7).astype(np.float32)
    alpha, n_iters, reps = 0.1, 6, 16
    want = np.asarray(vdt.label_propagate(y0, alpha=alpha, n_iters=n_iters,
                                          backend="exact"))
    ests = np.stack([
        np.asarray(vdt.label_propagate(y0, alpha=alpha, n_iters=n_iters,
                                       backend="grf", n_walkers=128,
                                       seed=s))
        for s in range(reps)])
    assert_unbiased(ests, want, axis=0, what="grf backend vs exact backend")


def test_grf_graph_matches_exact_matrix(small_fitted_vdt):
    """The bridged CSR graph scatters back to exactly the dense eq.-3
    row-softmax the exact backend streams."""
    from repro.kernels.fused_lp.ref import dense_transition_ref

    x, vdt = small_fitted_vdt
    want = np.asarray(dense_transition_ref(x, float(vdt.sigma)))
    got = vdt.grf_graph().dense_p()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert vdt.grf_graph() is vdt.grf_graph()  # cached per instance


def test_grf_backend_rejects_resume(small_fitted_vdt):
    x, vdt = small_fitted_vdt
    y0 = np.zeros((x.shape[0], 2), np.float32)
    with pytest.raises(ValueError, match="resume"):
        vdt.label_propagate_resume(y0, y0, n_iters=2, backend="grf")


# ----------------------------------------------------- divergence gating
def test_positive_domain_divergences_rejected():
    x = (np.random.RandomState(5).rand(12, 3) + 0.5).astype(np.float32)
    for div in ("kl", "itakura_saito"):
        with pytest.raises(ValueError, match="grf"):
            CSRGraph.from_points(x, 1.0, divergence=div)
    CSRGraph.from_points(x, 1.0)  # euclidean path is fine


def test_kl_fitted_model_rejects_grf_backend():
    from repro.core.vdt import VariationalDualTree

    x = (np.random.RandomState(6).rand(12, 3) + 0.5).astype(np.float32)
    vdt = VariationalDualTree.fit(x, sigma=1.0, learn_sigma=False,
                                  divergence="kl", max_blocks=4 * 12)
    y0 = np.zeros((12, 1), np.float32)
    with pytest.raises(ValueError, match="grf"):
        vdt.label_propagate(y0, n_iters=2, backend="grf")


# ------------------------------------------------------ CSR construction
def test_csr_roundtrip_and_row_stochastic(graph, dense_p):
    assert dense_p.shape == (N, N)
    np.testing.assert_allclose(dense_p.sum(axis=1), 1.0, rtol=1e-5)
    assert (dense_p >= 0).all()
    assert graph.nnz == N * DEG
    assert graph.density == pytest.approx(DEG / N)
    back = CSRGraph.from_dense(dense_p)
    np.testing.assert_allclose(back.dense_p(), dense_p, rtol=1e-5,
                               atol=1e-7)


def test_csr_validation_errors():
    with pytest.raises(ValueError, match="monotone"):
        CSRGraph.from_csr([0, 2, 1], [0, 1])
    with pytest.raises(ValueError, match="outgoing edge"):
        CSRGraph.from_csr([0, 1, 1], [0])
    with pytest.raises(ValueError, match="indices"):
        CSRGraph.from_csr([0, 1, 2], [0, 5])
    with pytest.raises(ValueError, match="weights shape"):
        CSRGraph.from_csr([0, 1, 2], [0, 1], weights=[1.0])
    with pytest.raises(ValueError, match="finite"):
        CSRGraph.from_csr([0, 1, 2], [0, 1], weights=[1.0, -1.0])
    with pytest.raises(ValueError, match="zero total weight"):
        CSRGraph.from_csr([0, 1, 2], [0, 1], weights=[1.0, 0.0])
    with pytest.raises(ValueError, match="square"):
        CSRGraph.from_dense(np.zeros((2, 3)))
    with pytest.raises(ValueError, match="indptr"):
        CSRGraph.from_csr([0], [])


# ------------------------------------------------------------- routing
def test_walkers_for_rtol_clt_sizing():
    assert walkers_for_rtol(0.1) == 100
    assert walkers_for_rtol(0.05) == 400
    assert walkers_for_rtol(1.0) == 1
    assert walkers_for_rtol(1e-9) == MAX_RTOL_WALKERS  # capped
    assert walkers_for_rtol(0.07) == math.ceil(1 / 0.07 ** 2)
    with pytest.raises(ValueError):
        walkers_for_rtol(0.0)
    with pytest.raises(ValueError):
        walkers_for_rtol(-0.1)


def test_route_backend_exact_cutoff_boundary():
    """The auto exact/vdt cutoff is the named constant, inclusive at
    exactly AUTO_EXACT_MAX_N, and overridable per call."""
    assert AUTO_EXACT_MAX_N == 1024
    assert route_backend("auto", n=AUTO_EXACT_MAX_N) == "exact"
    assert route_backend("auto", n=AUTO_EXACT_MAX_N + 1) == "vdt"
    assert route_backend("auto", n=2000, auto_exact_max_n=4096) == "exact"
    assert route_backend("auto", n=8, auto_exact_max_n=4) == "vdt"


def test_route_backend_grf_grid():
    """auto -> grf iff BOTH density and rtol are stated and permissive
    (boundaries inclusive); missing either hint disqualifies grf."""
    d, r = AUTO_GRF_MAX_DENSITY, AUTO_GRF_MIN_RTOL
    assert route_backend("auto", density=d, rtol=r) == "grf"
    assert route_backend("auto", density=d / 2, rtol=0.5) == "grf"
    # one hint off the boundary -> falls through to the size rule
    assert route_backend("auto", n=10, density=d * 1.01, rtol=r) == "exact"
    assert route_backend("auto", n=10, density=d, rtol=r * 0.99) == "exact"
    # an unstated hint never routes grf
    assert route_backend("auto", n=10, rtol=0.5) == "exact"
    assert route_backend("auto", n=2000, density=0.01) == "vdt"


def test_route_backend_passthrough_and_errors():
    assert route_backend(None, "vdt") == "vdt"
    assert route_backend(None, "grf") == "grf"
    assert route_backend("grf") == "grf"
    # explicit tags ignore the hints entirely
    assert route_backend("exact", n=10 ** 9) == "exact"
    assert route_backend("vdt", density=0.001, rtol=0.5) == "vdt"
    with pytest.raises(ValueError, match="needs the problem size"):
        route_backend("auto")
    with pytest.raises(ValueError, match="backend must be one of"):
        route_backend("dense")


# ------------------------------------------------------------- serving
def test_engine_grf_coalesces_at_max_budget(small_fitted_vdt):
    """Heterogeneous walker budgets share ONE dispatch at the max budget
    (n_walkers is deliberately not in the group key); the gauge reports
    the budget device work actually ran at."""
    from repro.serving import PropagateEngine, PropagateRequest

    x, vdt = small_fitted_vdt
    n = x.shape[0]
    rng = np.random.RandomState(2)

    def mk():
        return (rng.rand(n, 2) > 0.8).astype(np.float32)

    eng = PropagateEngine(vdt, start=False, max_batch=8, backend="grf",
                          n_walkers=8)
    futs = [
        eng.submit(PropagateRequest(mk(), n_iters=4, n_walkers=32)),
        eng.submit(PropagateRequest(mk(), n_iters=4, rtol=0.25)),  # -> 16
        eng.submit(PropagateRequest(mk(), n_iters=4)),  # engine default 8
        eng.submit(PropagateRequest(mk(), alpha=0.2, n_iters=4)),
    ]
    eng.flush()
    for f in futs:
        assert f.result(timeout=0).shape == (n, 2)
    m = eng.metrics()
    assert m.dispatches == 1 and m.batched_requests == 4
    assert m.n_walkers == 32
    eng.shutdown()


def test_engine_grf_bit_identical_per_seed(small_fitted_vdt):
    """Two engines sharing grf_seed resolve the same requests to the same
    bits; a different grf_seed resolves differently."""
    from repro.serving import PropagateEngine, PropagateRequest

    x, vdt = small_fitted_vdt
    n = x.shape[0]

    def run(grf_seed):
        rng = np.random.RandomState(14)
        reqs = [PropagateRequest((rng.rand(n, 2) > 0.8).astype(np.float32),
                                 alpha=a, n_iters=4)
                for a in (0.01, 0.2, 0.05)]
        eng = PropagateEngine(vdt, start=False, max_batch=4, backend="grf",
                              n_walkers=8, grf_seed=grf_seed)
        futs = [eng.submit(q) for q in reqs]
        eng.flush()
        out = [np.asarray(f.result(timeout=0)) for f in futs]
        eng.shutdown()
        return out

    a, b, c = run(0), run(0), run(1)
    for ra, rb in zip(a, b):
        assert np.array_equal(ra, rb)
    assert any(not np.array_equal(ra, rc) for ra, rc in zip(a, c))


def test_engine_grf_mixed_backends_split_dispatch(small_fitted_vdt):
    """grf and vdt requests never share a dispatch (backend is in the
    group key), and each answer matches its single-model call."""
    from repro.serving import PropagateEngine, PropagateRequest

    x, vdt = small_fitted_vdt
    n = x.shape[0]
    rng = np.random.RandomState(15)
    y_grf = (rng.rand(n, 2) > 0.8).astype(np.float32)
    y_vdt = (rng.rand(n, 2) > 0.8).astype(np.float32)
    eng = PropagateEngine(vdt, start=False, max_batch=8, n_walkers=8)
    f_grf = eng.submit(PropagateRequest(y_grf, n_iters=4, backend="grf"))
    f_vdt = eng.submit(PropagateRequest(y_vdt, n_iters=4, backend="vdt"))
    eng.flush()
    assert eng.metrics().dispatches == 2
    want_vdt = vdt.label_propagate(y_vdt, alpha=0.01, n_iters=4)
    np.testing.assert_allclose(np.asarray(f_vdt.result(timeout=0)),
                               np.asarray(want_vdt), rtol=1e-5, atol=1e-6)
    assert f_grf.result(timeout=0).shape == (n, 2)
    eng.shutdown()


def test_engine_grf_warmup_and_validation_pins(small_fitted_vdt):
    from repro.serving import PropagateEngine, PropagateRequest

    x, vdt = small_fitted_vdt
    n = x.shape[0]
    eng = PropagateEngine(vdt, start=False, backend="grf", n_walkers=4)
    assert eng.warmup(widths=(2,), n_iters=(4,), backends=("grf",)) > 0
    y0 = np.zeros((n, 2), np.float32)
    for bad in (dict(rtol=0.0), dict(rtol=2.0), dict(rtol=float("nan")),
                dict(n_walkers=0), dict(n_walkers=-3)):
        with pytest.raises(ValueError):
            eng.submit(PropagateRequest(y0, n_iters=2, **bad))
    with pytest.raises(ValueError):
        PropagateEngine(vdt, start=False, backend="grf", n_walkers=0)
    eng.shutdown()


def test_engine_auto_never_routes_grf(small_fitted_vdt):
    """An engine serves the complete kernel graph (density ~1), so auto
    traffic — even with a permissive rtol — resolves to exact/vdt."""
    from repro.serving import PropagateEngine, PropagateRequest
    from repro.serving._batching import DEFAULT_WIDTH_BUCKETS

    x, vdt = small_fitted_vdt
    n = x.shape[0]
    req = PropagateRequest(np.zeros((n, 2), np.float32), n_iters=2,
                           backend="auto", rtol=0.5)
    resolved = req.validate(n=n, buckets=DEFAULT_WIDTH_BUCKETS)
    assert resolved.backend == "exact"  # n <= AUTO_EXACT_MAX_N size rule
    eng = PropagateEngine(vdt, start=False)
    fut = eng.submit(req)
    eng.flush()
    want = vdt.label_propagate(req.y0, alpha=req.alpha, n_iters=2,
                               backend="exact")
    np.testing.assert_allclose(np.asarray(fut.result(timeout=0)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    eng.shutdown()
