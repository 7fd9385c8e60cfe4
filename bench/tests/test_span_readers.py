"""The readers of the program's spans, on hand-built ``(name, start_ns, end_ns)`` events."""
import pytest

from bench import harness, tracing

MS = 1_000_000  # ns


def _read(metric, **kw):
    return harness._load_module(harness.BENCH / "metrics" / f"{metric}.py").read(
        _run(**kw))


def _run(host=(), modules=(), fit=None, max_batch=32):
    host = [("bench.window", 0, 100 * MS), *host]
    modules = list(modules)
    return harness.LayerRun(
        counters={}, fit=fit or {}, shapes={}, max_batch=max_batch, served=[],
        trace=tracing.Trace(modules=modules, ops=[], host=host), window_ns=100 * MS,
        busy_ns=tracing.union_ns(modules), peaks={}, modules=modules)


def _ms(events):
    return [(name, s * MS, e * MS) for name, s, e in events]


def test_dispatch_gap_counts_idle_time_under_the_spans_only():
    # device busy 10-40 and 60-90: idle 0-10, 40-60 and 90-100
    programs = _ms([("jit_lp_scan_leaforder(1)", 10, 40), ("jit_lp_scan_leaforder(1)", 60, 90)])
    spans = _ms([("serve.linger", 0, 8),        # idle, but not the dispatch path
                 ("serve.stage", 40, 45), ("vdt.h2d", 45, 52),
                 ("vdt.scan", 55, 56),
                 ("serve.wait", 56, 62),        # overlaps the next program by 2
                 ("serve.stage", 91, 92), ("serve.d2h", 92, 95), ("serve.resolve", 95, 97),
                 ("XlaLinearize", 50, 58)])     # a runtime event, in no program span
    # under the spans: 40-52, 55-60 and 91-97, 23 ms over two dispatches
    assert _read("host.dispatch_gap_ms", host=spans, modules=programs) == pytest.approx(11.5)


def test_dispatch_gap_is_absent_without_the_spans():
    programs = _ms([("jit_lp_scan_leaforder(1)", 10, 40)])
    assert _read("host.dispatch_gap_ms", modules=programs) is None


def test_queue_wait_pairs_submits_and_drains_first_in_first_out():
    spans = _ms([("serve.submit", 0, 1), ("serve.submit", 1, 2), ("serve.submit", 2, 3),
                 ("serve.drain", 5, 5.1),       # pops max_batch = 2: waits 4 and 3
                 ("serve.submit", 4, 6),        # ends after that drain starts
                 ("serve.drain", 10, 10.1)])    # the rest: waits 7 and 4
    assert _read("sched.queue_wait_ms", host=spans, max_batch=2) == pytest.approx(4.5)
    # with room for all three, the first drain takes them: waits 4, 3, 2 and 4
    assert _read("sched.queue_wait_ms", host=spans, max_batch=4) == pytest.approx(3.25)


def test_queue_wait_is_absent_without_the_spans():
    assert _read("sched.queue_wait_ms") is None


@pytest.mark.parametrize("fit,want", [({"refine_select_s": 1.25, "refine_s": 30.0}, 1.25),
                                      ({"refine_select_s": 0.0, "refine_s": 0.0}, None),
                                      ({"refine_s": 30.0}, None)],
                         ids=["refined", "not-refined", "program-without-the-field"])
def test_refine_select_reads_the_fit(fit, want):
    assert _read("fit.refine_select_s", fit=fit) == want
