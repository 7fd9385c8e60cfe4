"""Pure functions of the harness: schedules, byte counts, trace reduction."""
import json
import pathlib

import numpy as np
import pytest

from bench import roofline, tracing
from bench.traffic import schedule

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRAFFIC = {"arrivals": {"process": "poisson", "rate_per_s": 20.0, "burst": 1},
           "requests": {"backend": "vdt", "widths": [2], "n_iters": [50],
                        "alphas": [0.01, 0.05, 0.1, 0.2], "labelled": [100, 1000, 10000]}}


def test_schedule_is_deterministic_in_the_seed():
    big = 2 ** 33 + 12345
    assert schedule(TRAFFIC, big, 10.0) == schedule(TRAFFIC, big, 10.0)
    assert schedule(TRAFFIC, big, 10.0) != schedule(TRAFFIC, big + 1, 10.0)


def test_every_seed_gets_the_same_work_in_another_order():
    a, b = schedule(TRAFFIC, 1, 10.0), schedule(TRAFFIC, 2, 10.0)
    assert len(a) == len(b) == 200
    for field in ("alpha", "labelled", "width", "n_iters"):
        assert sorted(getattr(r, field) for r in a) == sorted(getattr(r, field) for r in b)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    gaps = np.diff([r.due_s for r in a] + [10.0])
    # exponential quantiles: mean gap 1/rate, the longest about ln(2n)/rate
    assert np.mean(gaps) == pytest.approx(0.05) and 0.2 < gaps.max() < 0.35
    assert a[0].due_s == 0.0 and all(0 <= r.due_s < 10.0 for r in a)
    assert all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))


def test_bursts_share_their_due_time():
    t = json.loads(json.dumps(TRAFFIC))
    t["arrivals"]["burst"] = 4
    s = schedule(t, 3, 10.0)
    assert len(s) == 200 and len({r.due_s for r in s}) == 50


def test_vdt_walk_bytes_by_hand():
    # 4 leaves, 7 nodes, the coarsest partition's 6 blocks, 2 columns, 1 step:
    # table 3*6, CollectUp 4+7, blocks 2*6, DistributeDown 7+4, update 2*4
    words = 3 * 6 + 2 * ((4 + 7) + 2 * 6 + (7 + 4) + 2 * 4)
    assert roofline.vdt_walk_bytes(4, 6, 2, 1) == 4 * words
    assert roofline.vdt_walk_bytes(4, 6, 2, 50) == 50 * 4 * words
    # SecStr at |B| = 4N, 32 requests of width 2: 0.44 GB a step
    assert roofline.vdt_walk_bytes(1 << 17, 334716, 64, 1) == 443_826_128


def test_peaks_are_keyed_by_device_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_union_of_nested_and_overlapping_intervals():
    ev = [("a", 0, 10), ("b", 2, 4), ("c", 8, 15), ("d", 20, 25), ("e", 25, 26)]
    assert tracing.union_ns(ev) == 15 + 6
    assert tracing.clip(ev, 9, 21) == [("a", 9, 10), ("c", 9, 15), ("d", 20, 21)]
    gaps = tracing.idle_gaps(ev, [("x", 14, 19)], 0, 30)
    assert [g[1] for g in gaps] == pytest.approx([5e-9, 4e-9])
    assert gaps[0][0] == "x" and gaps[1][0] == "host idle"


def _recorded():
    raw = json.loads((DATA / "trace_small.json").read_text())
    t = tracing.Trace(modules=[tuple(e) for e in raw["modules"]],
                      ops=[tuple(e) for e in raw["ops"]],
                      host=[tuple(e) for e in raw["host"]])
    return t, raw


def test_recorded_trace_busy_time_is_the_union_of_programs():
    t, raw = _recorded()
    lo, hi = tracing.window(t)
    assert [lo, hi] == raw["window"]
    mods = tracing.clip(t.modules, lo, hi)
    busy = tracing.union_ns(mods)
    # the same union on a 1 us grid
    grid = np.zeros(int((hi - lo) // 1000) + 2, bool)
    for _, s, e in mods:
        grid[int((s - lo) // 1000):int(np.ceil((e - lo) / 1000))] = True
    assert abs(busy / 1000 - grid.sum()) <= 2 * len(mods) + 1  # 1 us rounding at each end
    assert 0 < busy <= sum(e - s for _, s, e in mods) <= hi - lo
    gaps = tracing.idle_gaps(mods, tracing.clip(t.host, lo, hi), lo, hi, k=10 ** 6)
    assert sum(g[1] for g in gaps) * 1e9 == pytest.approx(hi - lo - busy, rel=1e-9)


def test_recorded_trace_scan_time_found_by_name():
    t, _ = _recorded()
    lo, hi = tracing.window(t)
    mods = tracing.clip(t.modules, lo, hi)
    scans = [e - s for n, s, e in mods if n.startswith("jit_lp_scan_leaforder(")]
    assert scans, "the recorded window holds at least one walk"
    assert tracing.program_ns(mods, ["lp_scan_leaforder"]) == sum(scans)
    assert tracing.program_ns(mods, ["no_such_program"]) == 0
    top = tracing.top_ops(tracing.clip(t.ops, lo, hi))
    assert top and all(" = " in name for name, _ in top)
    assert not any(name.endswith(" while") for name, _ in top)


def test_secstr_rows_are_one_hot_windows_deterministic_in_the_seed():
    from bench import harness

    cfg = harness.load_cell("secstr.vdt.steady").config
    cfg = dict(cfg, n=2000)
    x, labels = harness.make_data(cfg, 2 ** 33 + 5)
    x = np.asarray(x)
    window, symbols = cfg["data"]["window"], len(cfg["data"]["freqs"][0])
    assert x.shape == (2000, window * symbols) == (2000, cfg["d"])
    # exactly one symbol at each position of the window
    assert (x.reshape(2000, window, symbols).sum(-1) == 1).all()
    x2, labels2 = harness.make_data(cfg, 2 ** 33 + 5)
    assert (np.asarray(x2) == x).all() and (labels2 == labels).all()
    assert not (np.asarray(harness.make_data(cfg, 2 ** 33 + 6)[0]) == x).all()
    # each class draws from its own frequencies
    freq = [x[labels == c].reshape(-1, window, symbols).mean((0, 1)) for c in (0, 1)]
    want = np.asarray(cfg["data"]["freqs"])
    assert np.abs(freq[0] - want[0]).max() < 0.01 and np.abs(freq[1] - want[1]).max() < 0.01

