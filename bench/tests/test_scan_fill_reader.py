"""The reader of ``vdt.scan_fill_pct``, on the fit counters a run carries."""
import dataclasses

import pytest

from bench import harness


def _read(fit):
    run = harness.LayerRun(counters={}, fit=fit, shapes={}, max_batch=32, served=[],
                           trace=None, window_ns=0, busy_ns=0, peaks={}, modules=[])
    return harness._load_module(
        harness.BENCH / "metrics" / "vdt.scan_fill_pct.py").read(run)


@pytest.mark.parametrize("fit, want", [
    # a program that does not count the table's slots reports nothing
    ({"n_blocks": 334_716, "bound": 1.0}, None),
    ({}, None),
    # one that does: SecStr's 4N blocks in their 344,064-slot bucket
    ({"n_blocks": 334_716, "scan_slots": 344_064}, 100.0 * 334_716 / 344_064),
    ({"n_blocks": 128, "scan_slots": 128}, 100.0),
])
def test_scan_fill_reads_blocks_over_slots(fit, want):
    got = _read(fit)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_scan_fill_reads_a_fitted_model():
    import numpy as np

    from repro.core.vdt import VariationalDualTree

    x = np.random.RandomState(0).randn(40, 4).astype(np.float32)
    vdt = VariationalDualTree.fit(x, max_blocks=150)
    a, _, _, _ = vdt._dispatch_buffers()
    assert _read(dataclasses.asdict(vdt.stats)) == pytest.approx(
        100.0 * vdt.n_blocks / a.shape[0])
