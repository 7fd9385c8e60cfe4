"""Host path: device-idle milliseconds per dispatch under the program's dispatch-path spans.

The device is idle where the traced window has no device program.  This
is the part of that idle time under the union of the program's own spans
below (``TraceAnnotation``, on the profiler's clock), over the number of
``serve.stage`` spans in the window, one per dispatched group: from
staging to resolving the futures.  ``serve.wait`` is among them because
the runtime lays a copy out for the device on its own threads after
``vdt.h2d`` returns, while the scheduler waits.  Lingering and draining the
queue are not counted.  Moves ``p50_ms``: every request waits for the host
path between the walk before it and its own.
"""
from bench.tracing import clip, union_ns, window

SPANS = ("serve.stage", "vdt.h2d", "vdt.permute", "vdt.scan", "serve.wait",
         "serve.d2h", "serve.resolve")


def read(run):
    if run.trace is None:
        return None
    lo, hi = window(run.trace)
    spans = clip([ev for ev in run.trace.host if ev[0] in SPANS], lo, hi)
    stages = sum(1 for ev in spans if ev[0] == "serve.stage")
    if stages == 0:
        return None
    # idle under the spans: what they add to the union of device programs
    idle_ns = union_ns(spans + list(run.modules)) - union_ns(run.modules)
    return idle_ns / 1e6 / stages
