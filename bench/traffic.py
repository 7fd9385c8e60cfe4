"""Open-loop request schedules generated from a traffic file and a seed.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters:

* ``arrivals``: ``process`` ("poisson"), ``rate_per_s`` and, optionally,
  ``burst`` (the requests that share one arrival instant; 1 by default);
* ``requests``: ``backend`` (one name) and the lists ``widths``,
  ``n_iters``, ``alphas``, ``labelled`` (seed rows carrying a label) and,
  optionally, ``deadline_ms`` (``null`` for none) that requests cycle
  through;
* ``engine``: keyword settings of ``PropagateEngine``;
* ``check``: how many answers the output check compares (``sample``) and
  the limit of each number it compares (``limits``), set from readings of
  the program and of the control at this cell's load.

Every seed of one mix gets the same work in another order: the same
arrival instants and the same multiset of request recipes, which the seed
shuffles over them (and the seed picks each request's labelled rows).  The
arrivals are quantiles of the exponential distribution, rescaled to fill
the window exactly, in one fixed shuffled order: they are the same for
every seed, because the order of the gaps alone moved the median latency
by several times the run-to-run noise of one seed.  So runs with different
seeds differ in data and in which request arrives when, not in load.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Request", "schedule"]


@dataclasses.dataclass(frozen=True)
class Request:
    """One request of the schedule; ``due_s`` counts from the window start."""

    due_s: float
    width: int
    n_iters: int
    alpha: float
    labelled: int
    backend: str
    deadline_ms: float | None = None


def _cycled(values, n: int, rng) -> np.ndarray:
    out = np.asarray([values[i % len(values)] for i in range(n)])
    return out[rng.permutation(n)]


def schedule(traffic: dict, seed: int, seconds: float) -> list[Request]:
    """The window's requests, sorted by due time; deterministic in ``seed``."""
    arr = traffic["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rate, burst = float(arr["rate_per_s"]), int(arr.get("burst", 1))
    n_bursts = max(1, int(round(rate * seconds / burst)))
    gaps = -np.log1p(-(np.arange(n_bursts) + 0.5) / n_bursts)
    gaps = gaps[np.random.default_rng(0).permutation(n_bursts)] * (seconds / gaps.sum())
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due = np.repeat(starts, burst)
    n = len(due)
    rng = np.random.default_rng(seed)
    req = traffic["requests"]
    widths = _cycled(req["widths"], n, rng)
    iters = _cycled(req["n_iters"], n, rng)
    alphas = _cycled(req["alphas"], n, rng)
    labelled = _cycled(req["labelled"], n, rng)
    deadlines = _cycled(req.get("deadline_ms", [None]), n, rng)
    return [Request(float(due[i]), int(widths[i]), int(iters[i]),
                    float(alphas[i]), int(labelled[i]), str(req["backend"]),
                    None if deadlines[i] is None else float(deadlines[i]))
            for i in range(n)]

