"""Scheduler: mean milliseconds from a request's queue push to the drain that took it.

Read from the program's spans: a request's push ends its ``serve.submit``
span, and the scheduler pops the queue in ``serve.drain``.  The pairing
is rebuilt first in, first out: each drain takes up to ``max_batch`` of
the submits that ended before it started, oldest first.  That is exact for
the default fifo discipline with nothing cancelled or expired, the cells'
discipline; under priority or edf it is not.  Moves ``p50_ms``: below the
knee a request waits about half of the walk in flight before its own.
"""
from bench.tracing import clip, window


def read(run):
    if run.trace is None or run.max_batch <= 0:
        return None
    lo, hi = window(run.trace)
    host = clip(run.trace.host, lo, hi)
    pushed = sorted(e for name, _, e in host if name == "serve.submit")
    drains = sorted(s for name, s, _ in host if name == "serve.drain")
    waits, k = [], 0
    for start in drains:
        taken = 0
        while k < len(pushed) and pushed[k] <= start and taken < run.max_batch:
            waits.append(start - pushed[k])
            k += 1
            taken += 1
    if not waits:
        return None
    return sum(waits) / len(waits) / 1e6
