"""Harness self-tests: run by hand, ``python -m pytest -q bench/tests``.

They run on the CPU at tiny sizes; the chip is never needed.
"""
import copy
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import pytest  # noqa: E402


def tiny(name: str, n: int = 1500, rate: float = 12.0, sample: int = 12):
    """The cell ``name`` at a size the CPU runs in seconds: fewer rows, the same widths."""
    from bench import harness

    cell = harness.load_cell(name)
    cell.config = copy.deepcopy(dict(cell.config, n=n))
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic["arrivals"]["rate_per_s"] = rate
    cell.traffic["check"]["sample"] = sample
    # refinement buys fewer nats a row on a shallower tree (2.7-2.8 at
    # N = 1,500 against 2.90-2.96 at SecStr's N): at this size the limit
    # only asks that refinement bought something
    cell.traffic["check"]["limits"]["bound_gain"] = {"min": 1.0}
    return cell


@pytest.fixture
def cpu_peaks(monkeypatch):
    """A CPU has no row in the peak table; the traced path is given one."""
    from bench import roofline

    monkeypatch.setattr(roofline, "peaks", lambda kind: {"hbm_bytes_per_s": 8.19e11})
