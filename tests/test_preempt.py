"""Preemptible segmented dispatch: bit-parity + engine preemption behavior.

Two layers under test:

* the segmented/resume scan primitives (``core.label_prop``): splitting an
  eq.-15 walk into carry-resumed segments must be BIT-identical to the
  monolithic scan, for both backends, any segment size, any batch/width —
  the property that makes preemption free of numerical consequences.  The
  model is rebuilt from the golden fixture so the parity grid is pinned to
  a deterministic fit;
* the engine's preemptible dispatch: a tight-deadline arrival landing
  mid-flight of a long segmented scan is served at the next segment
  boundary (instead of waiting out — and expiring behind — the whole
  scan), the suspended walk resumes bit-identically, and the
  ``preemptions`` / ``preempt_iters`` metrics record the yield.

The engine tests drive the deterministic scheduler (``start=False`` +
``step``) with a fake clock advanced by the dispatch itself, so preemption
decisions — which hinge on the measured per-iteration time — are
reproducible without real sleeps.
"""
import numpy as np
import pytest

from repro.core.label_prop import (lp_scan_fused, lp_scan_fused_segmented,
                                   lp_scan_leaforder,
                                   lp_scan_leaforder_segmented)
from repro.serving import PropagateEngine, PropagateRequest
from repro.serving._queue import QueueEntry, RequestQueue

ITERS = 13  # covers whole segments, a remainder, and a length-1 tail
SEGMENTS = (1, 2, 5, ITERS, ITERS + 7)  # incl. seg == and > n_iters


class FakeClock:
    """Deterministic time source (seconds)."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


@pytest.fixture(scope="module")
def golden_vdt():
    """Model refit from the golden fixture's data — a pinned parity anchor."""
    from repro.core.vdt import VariationalDualTree

    g = np.load("tests/golden_sqeuclidean.npz")
    x = g["x"]
    return x, VariationalDualTree.fit(x, max_blocks=4 * x.shape[0])


# ------------------------------------------------- scan-level bit-parity
@pytest.mark.parametrize("seg", SEGMENTS)
@pytest.mark.parametrize("width", [1, 3])
def test_leaforder_segmented_bit_identical(golden_vdt, seg, width):
    """lp_scan_leaforder_segmented == lp_scan_leaforder, exactly."""
    x, vdt = golden_vdt
    rng = np.random.RandomState(11)
    y0 = (rng.rand(x.shape[0], width) > 0.7).astype(np.float32)
    tree = vdt.tree
    a, b, q, mask = vdt._dispatch_buffers()
    y0_leaf = np.zeros((tree.n_leaves, width), np.float32)
    y0_leaf[np.asarray(tree.slot_of)] = y0
    alpha = np.float32(0.02)

    mono = np.asarray(lp_scan_leaforder(
        y0_leaf, mask, a, b, q, alpha, tree.L, ITERS))
    split = np.asarray(lp_scan_leaforder_segmented(
        y0_leaf, mask, a, b, q, alpha, tree.L, ITERS, seg))
    np.testing.assert_array_equal(mono, split)


@pytest.mark.parametrize("seg", SEGMENTS)
@pytest.mark.parametrize("shape", ["2d-1", "2d-3", "3d"])
def test_fused_segmented_bit_identical(golden_vdt, seg, shape):
    """lp_scan_fused_segmented == lp_scan_fused across the B x C grid.

    Includes the once-broken corner: a length-1 tail segment (e.g. 13 split
    by 2) used to drift 1 ulp because XLA constant-folds a static length-1
    scan into a differently-fused inline body; the resume primitives take
    the iteration count as a dynamic loop bound precisely so every segment
    runs the same while-loop executable.
    """
    x, vdt = golden_vdt
    rng = np.random.RandomState(13)
    if shape == "3d":
        y0 = rng.rand(2, x.shape[0], 2).astype(np.float32)
        alpha = np.array([0.01, 0.05], np.float32)  # per-request alphas
    else:
        width = int(shape.split("-")[1])
        y0 = rng.rand(x.shape[0], width).astype(np.float32)
        alpha = 0.02
    sigma = float(vdt.sigma)

    mono = np.asarray(lp_scan_fused(vdt.x_rows, y0, sigma, alpha, ITERS))
    split = np.asarray(lp_scan_fused_segmented(
        vdt.x_rows, y0, sigma, alpha, ITERS, segment_iters=seg))
    np.testing.assert_array_equal(mono, split)


@pytest.mark.parametrize("backend", ["vdt", "exact"])
def test_label_propagate_resume_chain_bit_identical(golden_vdt, backend):
    """Chained label_propagate_resume segments == one label_propagate.

    The exact call sequence the engine's preemptible dispatch makes —
    batched (B, N, C) stacks with per-request alpha, resuming through the
    row-order <-> leaf-order round trip on the vdt backend.
    """
    x, vdt = golden_vdt
    rng = np.random.RandomState(17)
    y0 = rng.rand(3, x.shape[0], 2).astype(np.float32)
    alpha = np.array([0.01, 0.05, 0.2], np.float32)

    mono = np.asarray(vdt.label_propagate(
        y0, alpha=alpha, n_iters=ITERS, batched=True, backend=backend))
    y, done = y0, 0
    while done < ITERS:
        k = min(4, ITERS - done)
        y = vdt.label_propagate_resume(
            np.asarray(y), y0, alpha=alpha, n_iters=k, batched=True,
            backend=backend)
        done += k
    np.testing.assert_array_equal(mono, np.asarray(y))


def test_segmented_rejects_bad_segment_iters(golden_vdt):
    x, vdt = golden_vdt
    y0 = np.zeros((x.shape[0], 1), np.float32)
    with pytest.raises(ValueError, match="segment_iters"):
        lp_scan_fused_segmented(vdt.x_rows, y0, float(vdt.sigma), 0.01, 4,
                                segment_iters=0)
    with pytest.raises(ValueError, match="carry shape"):
        vdt.label_propagate_resume(np.zeros((x.shape[0], 2), np.float32), y0)


# ------------------------------------------------------- queue urgency API
def test_queue_deadline_before_and_drain_urgent():
    clock = FakeClock()
    q = RequestQueue(16, discipline="edf", clock=clock)

    def entry(seq, deadline):
        from concurrent.futures import Future
        return QueueEntry(seq=seq, request=None, future=Future(),
                          t_submit=clock(), t_deadline=deadline)

    q.put(entry(0, 5.0))
    q.put(entry(1, 0.5))
    q.put(entry(2, None))
    assert q.deadline_before(1.0) and not q.deadline_before(0.5)

    # prefix drain: only the entry inside the horizon pops; heap order and
    # the deadline-less entry are untouched
    live, cancelled, expired = q.drain_urgent(8, horizon=1.0)
    assert [e.seq for e in live] == [1]
    assert not cancelled and not expired
    assert len(q) == 2 and q.next_deadline() == 5.0
    assert q.popped == 1  # the monotone pop counter saw exactly one pop

    # expired urgent entries fast-fail out of the urgent drain too
    clock.advance(10.0)
    live, cancelled, expired = q.drain_urgent(8, horizon=100.0)
    assert not live and [e.seq for e in expired] == [0]
    assert len(q) == 1  # deadline-less entry never drains urgently
    assert q.popped == 2


def test_drain_urgent_noop_outside_edf():
    q = RequestQueue(4, discipline="fifo")
    assert q.drain_urgent(4, horizon=1.0) == ([], [], [])
    assert not q.deadline_before(float("inf"))


# -------------------------------------------------- engine preemption path
class _InjectingVDT:
    """Proxy model: advances a fake clock per dispatch (so per-iteration
    time is measurable and deterministic) and submits an urgent request
    after the first segment — a mid-flight arrival, reproducibly."""

    ITER_S = 0.01  # simulated device seconds per LP iteration

    def __init__(self, inner, clock):
        self._inner = inner
        self._clock = clock
        self.engine = None
        self.urgent = None
        self.resume_calls = 0
        self.done_t: dict = {}  # fake-clock instants of future resolution

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def label_propagate(self, y0, *args, n_iters=500, **kw):
        self._clock.advance(self.ITER_S * n_iters)
        return self._inner.label_propagate(y0, *args, n_iters=n_iters, **kw)

    def label_propagate_resume(self, y, y0, *args, n_iters=500, **kw):
        self.resume_calls += 1
        self._clock.advance(self.ITER_S * n_iters)
        out = self._inner.label_propagate_resume(y, y0, *args,
                                                 n_iters=n_iters, **kw)
        if self.urgent is None:
            # first segment just finished: an urgent request lands NOW,
            # 35 iterations (~0.35s simulated) before the bulk scan ends
            self.urgent = self.engine.submit(PropagateRequest(
                y0=np.ones((y0.shape[-2], 1), np.float32), n_iters=5,
                deadline_ms=100.0))
            self.urgent.add_done_callback(
                lambda f: self.done_t.setdefault("urgent", self._clock()))
        return out


def test_midflight_urgent_arrival_preempts(small_fitted_vdt):
    """The tentpole behavior: a deadline-100ms request submitted one
    segment into a 40-iteration scan is served at the next segment
    boundary instead of expiring behind it, and the suspended scan's final
    answer is bit-identical to an unpreempted run."""
    x, vdt = small_fitted_vdt
    clock = FakeClock()
    proxy = _InjectingVDT(vdt, clock)
    eng = PropagateEngine(proxy, start=False, policy="edf", segment_iters=5,
                          clock=clock)
    proxy.engine = eng
    y0 = np.random.RandomState(23).rand(x.shape[0], 2).astype(np.float32)
    bulk = eng.submit(PropagateRequest(y0=y0, alpha=0.02, n_iters=40,
                                       deadline_ms=60_000.0))

    bulk.add_done_callback(
        lambda f: proxy.done_t.setdefault("bulk", clock()))
    eng.step()

    m = eng.metrics()
    # without preemption the urgent request (deadline 0.1s) could not have
    # survived the remaining 35 iterations (~0.35s simulated): it would
    # have expired in the post-scan drain.  Instead it completed, in time.
    assert proxy.urgent.result(timeout=0) is not None
    assert m.expired == 0 and m.completed == 2
    assert m.preemptions == 1
    assert m.preempt_iters == 35  # 40 - one 5-iteration segment
    # the urgent answer resolved mid-scan, not after the bulk walk
    assert proxy.done_t["urgent"] < proxy.done_t["bulk"]

    # the preempted walk is bit-identical to a never-preempted one
    mono = vdt.label_propagate(y0, alpha=0.02, n_iters=40)
    np.testing.assert_array_equal(np.asarray(bulk.result(timeout=0)),
                                  np.asarray(mono))
    eng.shutdown()


def test_no_preemption_without_urgency(small_fitted_vdt):
    """Segmented dispatch without a threatened deadline never yields, and
    segmenting under a deadline-less queue costs no correctness."""
    x, vdt = small_fitted_vdt
    clock = FakeClock()
    eng = PropagateEngine(vdt, start=False, policy="edf", segment_iters=4,
                          clock=clock)
    y0 = np.random.RandomState(29).rand(x.shape[0], 1).astype(np.float32)
    fut = eng.submit(PropagateRequest(y0=y0, n_iters=9))
    eng.step()
    m = eng.metrics()
    assert m.preemptions == 0 and m.preempt_iters == 0
    np.testing.assert_array_equal(
        np.asarray(fut.result(timeout=0)),
        np.asarray(vdt.label_propagate(y0, n_iters=9)))
    eng.shutdown()


def test_segmenting_inert_outside_edf(small_fitted_vdt):
    """segment_iters under fifo stays monolithic (no urgency signal): the
    resume path is never entered."""
    x, vdt = small_fitted_vdt

    calls = []
    real = vdt.label_propagate_resume

    class Spy:
        def __getattr__(self, name):
            return getattr(vdt, name)

        def label_propagate_resume(self, *a, **kw):
            calls.append(1)
            return real(*a, **kw)

    eng = PropagateEngine(Spy(), start=False, policy="fifo", segment_iters=2)
    fut = eng.submit(PropagateRequest(
        y0=np.zeros((x.shape[0], 1), np.float32), n_iters=8))
    eng.step()
    assert fut.result(timeout=0) is not None and not calls
    eng.shutdown()


def test_engine_rejects_bad_segment_iters(small_fitted_vdt):
    x, vdt = small_fitted_vdt
    with pytest.raises(ValueError, match="segment_iters"):
        PropagateEngine(vdt, start=False, segment_iters=0)
