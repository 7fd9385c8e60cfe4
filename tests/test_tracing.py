"""Profiler spans inside the program: which the serving path and the fit record,
that none contains another, and that tracing changes no answer.

The spans are ``jax.profiler.TraceAnnotation`` leaves on the profiler's
host clock; the benchmark's per-layer readers (``bench/metrics``) set them
against the device programs of the same trace.
"""
import collections
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.vdt import VariationalDualTree
from repro.serving import PropagateEngine, PropagateRequest

SERVE = ("serve.submit", "serve.linger", "serve.drain", "serve.stage",
         "vdt.h2d", "vdt.permute", "vdt.scan", "serve.wait", "serve.d2h",
         "serve.resolve")
FIT = ("fit.refine.gains", "fit.refine.select", "fit.refine.qopt")
ITERS = 4
N = 48


def _traced(fn, log_dir):
    """Run ``fn`` under the profiler; returns (its result, the program's
    spans as ``(name, start_ns, end_ns, thread)``)."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns, k)
                         for e in line.events if e.name in SERVE + FIT)
    return out, sorted(spans, key=lambda s: s[1])


@pytest.fixture(scope="module")
def fitted():
    x = np.random.RandomState(5).randn(N, 3).astype(np.float32)
    return x, VariationalDualTree.fit(x, max_blocks=4 * N, refine_batch=16)


def _requests(count, seed=0):
    rng = np.random.RandomState(seed)
    return [PropagateRequest((rng.rand(N, 1 + k % 3) > 0.7).astype(np.float32),
                             alpha=(0.05, 0.1, 0.2)[k % 3], n_iters=ITERS)
            for k in range(count)]


@pytest.fixture(scope="module")
def served(fitted, tmp_path_factory):
    """Ten requests through a threaded engine under the profiler."""
    _, vdt = fitted
    reqs = _requests(10)
    eng = PropagateEngine(vdt, max_batch=4, max_wait_ms=2.0)
    eng.warmup(widths=(4,), n_iters=(ITERS,), backends=("vdt",))

    def serve():
        futs = [eng.submit(r) for r in reqs]
        return [f.result(timeout=120) for f in futs]

    try:
        _, spans = _traced(serve, tmp_path_factory.mktemp("serve"))
        dispatches = eng.metrics().dispatches
    finally:
        eng.shutdown()
    return reqs, spans, dispatches


def test_engine_records_exactly_the_serving_spans(served):
    _, spans, _ = served
    assert {s[0] for s in spans} == set(SERVE)


def test_each_dispatch_records_one_stage_wait_copy_and_resolve(served):
    _, spans, dispatches = served
    count = collections.Counter(s[0] for s in spans)
    assert dispatches >= 1
    assert count["serve.stage"] == count["serve.wait"] == count["serve.d2h"] \
        == count["serve.resolve"] == dispatches


def test_each_dispatch_records_the_same_vdt_spans(served):
    _, spans, dispatches = served
    stages = [s[1] for s in spans if s[0] == "serve.stage"]
    per = [collections.Counter() for _ in stages]
    for name, start, _, _ in spans:
        if name.startswith("vdt."):
            k = int(np.searchsorted(stages, start, side="right")) - 1
            assert k >= 0, f"{name} before the first staging"
            per[k][name] += 1
    assert len(per) == dispatches
    assert all(c == per[0] for c in per)
    assert all(per[0][name] >= 1 for name in ("vdt.h2d", "vdt.permute", "vdt.scan"))


def test_each_request_records_one_submit_and_no_span_contains_another(served):
    reqs, spans, _ = served
    assert sum(s[0] == "serve.submit" for s in spans) == len(reqs)
    by_thread = collections.defaultdict(list)
    for name, start, end, thread in spans:
        by_thread[thread].append((start, end, name))
    for seq in by_thread.values():
        seq.sort()
        for (_, end, outer), (start, _, inner) in zip(seq, seq[1:]):
            assert start >= end, f"{inner} starts inside {outer}"


def test_answers_are_bit_identical_with_the_profiler_running(fitted, tmp_path):
    _, vdt = fitted
    reqs = _requests(7, seed=1)

    def serve():
        eng = PropagateEngine(vdt, start=False, max_batch=4)
        futs = [eng.submit(r) for r in reqs]
        eng.flush()
        eng.shutdown()
        return [np.asarray(f.result(timeout=0)) for f in futs]

    plain = serve()
    traced, _ = _traced(serve, tmp_path)
    for p, t in zip(plain, traced):
        assert p.dtype == t.dtype and np.array_equal(p, t)


@pytest.mark.parametrize("max_blocks", [4 * N, None])
def test_fit_counts_refinement_rounds_and_selection_time(max_blocks, tmp_path):
    x = np.random.RandomState(6).randn(N, 3).astype(np.float32)
    vdt, spans = _traced(
        lambda: VariationalDualTree.fit(x, max_blocks=max_blocks, refine_batch=16),
        tmp_path)
    st = vdt.stats
    count = collections.Counter(s[0] for s in spans)
    if max_blocks is None:
        assert st.refine_rounds == 0 and st.refine_select_s == 0.0
        assert not count
        return
    assert st.refine_rounds >= 1
    assert 0.0 < st.refine_select_s <= st.refine_s
    # one gains and one select span a round; the round that finds nothing
    # left to refine, if any, re-optimizes nothing
    assert count["fit.refine.gains"] == count["fit.refine.select"] == st.refine_rounds
    assert count["fit.refine.qopt"] in (st.refine_rounds, st.refine_rounds - 1)


def test_refine_adds_its_rounds_and_time_to_the_stats(fitted):
    x, _ = fitted
    vdt = VariationalDualTree.fit(x, max_blocks=2 * N, refine_batch=16)
    before = (vdt.stats.refine_rounds, vdt.stats.refine_select_s, vdt.stats.refine_s)
    vdt.refine(4 * N, batch=16)
    st = vdt.stats
    assert st.refine_rounds > before[0]
    assert st.refine_select_s > before[1] and st.refine_s > before[2]
    assert st.refine_select_s <= st.refine_s
