"""Device idle share: 1 minus the union of device program intervals over the window.

Read from the profiler trace (the TPU plane's ``XLA Modules`` line, cut to
the harness's ``bench.window`` span).  Moves ``p50_ms``: idle device time
between dispatches is host work every request waits for.
"""


def read(run):
    if run.trace is None or run.window_ns <= 0:
        return None
    return 100.0 * (1.0 - run.busy_ns / run.window_ns)
