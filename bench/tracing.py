"""Reduction of a JAX profiler trace to device busy time, program time and idle gaps.

A trace is read into plain tuples ``(name, start_ns, end_ns)``:

* ``modules``: the ``XLA Modules`` line of the first TPU device plane, one
  event per executed program (``jit_<function>(<fingerprint>)``);
* ``ops``: its ``XLA Ops`` line, one event per HLO operation;
* ``host``: events of the host plane's threads (runtime calls such as
  ``PjRt`` execution and the host-side ``Transpose`` of a result copy, and
  the harness's own ``TraceAnnotation`` spans).

Everything after :func:`read` works on those tuples, so it is tested on a
small recorded trace without the profiler.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

__all__ = ["Trace", "read", "clip", "union_ns", "program_ns", "top_ops",
           "idle_gaps"]

WINDOW_SPAN = "bench.window"
# ops that only contain other ops; counting them would count their body twice
_CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Trace:
    modules: list
    ops: list
    host: list


def read(log_dir: str) -> Trace:
    """Load the newest ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    modules, ops, host = [], [], []
    device_seen = False
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and not device_seen:
            device_seen = True
            for line in plane.lines:
                dest = {"XLA Modules": modules, "XLA Ops": ops}.get(line.name)
                if dest is not None:
                    dest.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
    return Trace(modules=modules, ops=ops, host=host)


def window(trace: Trace) -> tuple[float, float]:
    """The ``bench.window`` span the harness opened around the traced window."""
    spans = [(s, e) for name, s, e in trace.host if name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found {len(spans)}")
    return spans[0]


def clip(events, lo: float, hi: float) -> list:
    """Events cut to ``[lo, hi]``; those wholly outside are dropped."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def union_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def program_ns(modules, names) -> float:
    """Device time of the programs whose name is ``jit_<name>(...)`` for a listed name."""
    prefixes = tuple(f"jit_{n}(" for n in names)
    return float(sum(e - s for n, s, e in modules if n.startswith(prefixes)))


def _op_parts(text: str) -> tuple[str, str]:
    """``%fusion.27 = f32[262143,64]{1,0} fusion(...), kind=..`` ->
    (``fusion.27 = f32[262143,64] fusion``, ``fusion``)."""
    lhs, _, rhs = text.lstrip("%").partition(" = ")
    if not rhs:
        return lhs[:120], ""
    if rhs.startswith("("):  # a tuple shape: skip to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = "(...)", rhs[i + 1:]
    else:
        shape, _, rest = rhs.partition(" ")
        shape = shape.split("{", 1)[0]
    kind = rest.strip().split("(", 1)[0]
    return f"{lhs} = {shape} {kind}"[:120], kind


def top_ops(ops, k: int = 10) -> list:
    """The ``k`` operations that took the most device time, by name."""
    tot = collections.Counter()
    for text, s, e in ops:
        name, kind = _op_parts(text)
        if kind not in _CONTAINERS:
            tot[name] += e - s
    return [[name, ns / 1e9] for name, ns in tot.most_common(k)]


def idle_gaps(busy_events, host_events, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` longest stretches of ``[lo, hi]`` with no device program
    running, each named by the host event that covers most of it."""
    gaps, cur = [], lo
    for _, s, e in sorted(busy_events, key=lambda ev: ev[1]):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for g0, g1 in gaps[:k]:
        best, best_key = "host idle", (0.0, 0.0)
        for name, s, e in host_events:
            cover = min(e, g1) - max(s, g0)
            # the event that covers most of the gap names it; of equals,
            # the innermost (shortest)
            if name != WINDOW_SPAN and cover > 0 and (cover, s - e) > best_key:
                best, best_key = name, (cover, s - e)
        out.append([best[:120], (g1 - g0) / 1e9])
    return out
