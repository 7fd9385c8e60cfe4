"""Scheduler batch occupancy: real requests per dispatch as a share of ``max_batch``.

Read from the engine's own counters (``batched_requests``, ``dispatches``)
over the traced window.  Moves ``p95_ms``: fuller dispatches serve the same
load in fewer walks, so requests wait behind fewer of them.
"""


def read(run):
    d = run.counters.get("dispatches", 0)
    if d <= 0:
        return None
    return 100.0 * run.counters["batched_requests"] / d / run.max_batch
