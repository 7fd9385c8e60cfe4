"""The plain reference, the latency clock, and the output check against faults."""
import json
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bench import harness
from bench.traffic import Request
from conftest import tiny


@pytest.fixture(scope="module")
def fitted():
    from repro.core.vdt import VariationalDualTree

    cell = tiny("secstr.vdt.steady")
    x, labels = harness.make_data(cell.config, 11)
    vdt = VariationalDualTree.fit(x, max_blocks=4 * cell.config["n"], refine_batch=64)
    return cell, np.asarray(x), labels, vdt


def _ref():
    return harness._load_module(harness.BENCH / "reference" / "vdt.py")


def test_reference_q_and_walk_match_the_program(fitted):
    cell, x, labels, vdt = fitted
    ref = _ref()
    state, numbers = ref.prepare(x, ref.take(vdt), cell.config)
    assert numbers["partition_bad_leaves"] == numbers["blocks_short"] == 0
    assert numbers["sigma_gap"] < 1e-4 and numbers["bound_gain"] > 0
    act = np.asarray(vdt.bp.active[:vdt.bp.n])
    q_prog = np.exp(np.asarray(vdt.qstate.log_q[:vdt.bp.n], np.float64))[act]
    np.testing.assert_allclose(state["q"], q_prog, rtol=2e-4)
    y0 = harness.seed_labels(labels, 2, 100, 5, 0)
    want = ref.answers(state, [(y0, 0.1, 50)])[0]
    got = np.asarray(vdt.label_propagate(y0, alpha=0.1, n_iters=50))
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-5


def test_reference_finds_a_broken_partition(fitted):
    cell, x, _, vdt = fitted
    ref = _ref()
    fit = ref.take(vdt)
    fit["active"] = fit["active"].copy()
    fit["active"][np.flatnonzero(fit["active"])[7]] = False
    _, numbers = ref.prepare(x, fit, cell.config)
    assert numbers["partition_bad_leaves"] > 0 and numbers["blocks_short"] == 1


def test_reference_bound_matches_the_program(fitted):
    cell, x, _, vdt = fitted
    ref = _ref()
    fit = ref.take(vdt)
    a, b = fit["a"][fit["active"]], fit["b"][fit["active"]]
    W, S1, S2 = ref._node_sums(np.asarray(x, np.float64), fit["slot_of"], fit["L"])
    d2 = ref._d2(a, b, W, S1, S2)
    log_q = ref._log_q(a, b, W, d2, fit["sigma"], fit["L"])
    bound = ref._bound(a, b, W, d2, log_q, fit["sigma"], x.shape[1])
    assert bound == pytest.approx(vdt.stats.bound, rel=1e-5)


def test_control_fails_where_the_program_passes(fitted):
    import jax.numpy as jnp

    cell, x, labels, vdt = fitted
    ref = _ref()
    state, _ = ref.prepare(x, ref.take(vdt), cell.config)
    cases = [(harness.seed_labels(labels, 2, m, 9, k), a, 50)
             for k, (m, a) in enumerate([(100, 0.2), (1000, 0.05), (10000, 0.01)])]
    want = ref.answers(state, cases)
    prog = [np.asarray(vdt.label_propagate(y0, alpha=a, n_iters=n)) for y0, a, n in cases]
    control = ref.answers(state, cases, jnp.bfloat16)
    limit = harness.load_cell("secstr.vdt.steady").traffic["check"]["limits"]["walk_gap"]
    assert harness._gap(prog, want) < limit < harness._gap(control, want)


class _StallingEngine:
    """Answers after ``work_s`` each, one at a time; the first request stalls ``stall_s``."""

    max_batch = 1

    def __init__(self, stall_s, work_s):
        self.stall_s, self.work_s = stall_s, work_s
        self.queue, self.lock = [], threading.Condition()
        self.n = 0
        threading.Thread(target=self._serve, daemon=True).start()

    def submit(self, req):
        fut = Future()
        with self.lock:
            self.queue.append(fut)
            self.lock.notify()
        return fut

    def _serve(self):
        stalled = False
        while True:
            with self.lock:
                while not self.queue:
                    self.lock.wait()
            if not stalled:  # the stall starts with the first request
                time.sleep(self.stall_s)
                stalled = True
            with self.lock:
                fut = self.queue.pop(0)
            time.sleep(self.work_s)
            self.n += 1
            fut.set_result(np.zeros((4, 2), np.float32))

    def metrics(self):
        class M:
            pass
        m = M()
        for k in harness._COUNTERS:
            setattr(m, k, self.n if k in ("completed", "dispatches", "batched_requests") else 0)
        return m


def test_latency_counts_from_the_due_time_through_a_stall():
    engine = _StallingEngine(stall_s=0.5, work_s=0.001)
    server = harness.Server(x=None, labels=np.zeros(4, np.int64), vdt=None, engine=engine,
                            fit={}, shapes={})
    reqs = [Request(due_s=0.05 * k, width=2, n_iters=1, alpha=0.1, labelled=1,
                    backend="vdt") for k in range(20)]
    win = harness.run_window(server, reqs, 0, 1.0)
    lat = win.done - win.due
    assert not win.failed.any()
    # every request due during the stall waits until it ends, 0.5 s after the start
    for k, r in enumerate(reqs):
        if r.due_s < 0.45:
            assert lat[k] >= 0.5 - r.due_s - 0.01
    assert lat[-1] < 0.1
    e2e = harness.end_to_end(win, 1.0)
    # half the requests came after the stall: the tail holds it, the median not
    assert e2e["p95_ms"] >= 400.0 and e2e["p50_ms"] < 100.0


def test_a_request_that_never_completes_is_a_miss():
    engine = _StallingEngine(stall_s=30.0, work_s=0.0)
    server = harness.Server(x=None, labels=np.zeros(4, np.int64), vdt=None, engine=engine,
                            fit={}, shapes={})
    reqs = [Request(due_s=0.0, width=2, n_iters=1, alpha=0.1, labelled=1, backend="vdt")]
    win = harness.run_window(server, reqs, 0, 0.2, drain_s=0.2)
    assert win.failed.all()
    assert harness.end_to_end(win, 1.0)["p50_ms"] == 1e12


def _run(cell, capsys, trace=False):
    import jax

    harness.run(cell, 2 ** 32 + 77, 1.5, trace, jax.devices(), time.perf_counter())
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys, cpu_peaks):
    out = _run(tiny("secstr.vdt.steady"), capsys, trace=True)
    assert out["correct"] and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"sched.occupancy", "device.idle_pct",
                                   "fit.tree_qopt_s", "fit.refine_s"}


def _unchanged(self, vdt, stack, alphas, n_iters, backend, **kw):
    import jax.numpy as jnp

    return jnp.asarray(stack)


def _half_batch(self, vdt, stack, alphas, n_iters, backend, **kw):
    out = np.array(vdt.label_propagate(stack, alpha=alphas, n_iters=int(n_iters),
                                       batched=True))
    out[stack.shape[0] // 2:] = 0.0
    return out


def _altered(self, vdt, stack, alphas, n_iters, backend, **kw):
    out = np.array(vdt.label_propagate(stack, alpha=alphas, n_iters=int(n_iters),
                                       batched=True))
    out[:, 0, 0] += 1e-3
    return out


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered],
                         ids=["state-unchanged", "half-batch-left-out", "answer-altered"])
def test_faults_under_the_timed_path_fail_the_check(fault, capsys, monkeypatch, cpu_peaks):
    from repro.serving import PropagateEngine

    monkeypatch.setattr(PropagateEngine, "_scan", fault)
    cell = tiny("secstr.vdt.steady", rate=40.0, sample=40)
    out = _run(cell, capsys)
    assert out["correct"] is False


@pytest.mark.parametrize("fault,number", [("no_refine", "blocks_short"),
                                          ("no_refine", "bound_gain"),
                                          ("half_refine", "blocks_short"),
                                          ("sigma_not_refit", "sigma_gap"),
                                          ("sigma_unlearned", "sigma_gap")])
def test_faults_in_the_fit_fail_the_check(fault, number, capsys, monkeypatch, cpu_peaks):
    from bench import control

    fit = harness.fit

    def planted_fit(config, x):
        with control.planted(fault, config) as faulty:
            return fit(faulty, x)

    monkeypatch.setattr(harness, "fit", planted_fit)
    out = _run(tiny("secstr.vdt.steady"), capsys)
    assert out["correct"] is False
    assert not harness.within(out["checks"][number]["value"], out["checks"][number]["limit"])

