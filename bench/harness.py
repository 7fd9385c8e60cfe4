"""One run of one benchmark cell: set-up, an open-loop window, the check, the result line.

Everything cell-specific is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json`` (with its data recipe
in ``bench/recipes/<recipe>.py``), its traffic, rate and check limits in
``bench/traffic/<traffic>.json``, the plain reference of each backend it
serves in ``bench/reference/<backend>.py`` and each per-layer metric's
reader in ``bench/metrics/<metric>.py``.  Adding a cell, a configuration,
a mix or a metric adds files and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"

__all__ = ["Cell", "load_cell", "require_chip", "setup", "run_window", "check",
           "run", "result_line"]


def _load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str) -> Cell:
    spec = _read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]

    def mine(metric):
        return name in metric.get("workloads", [name])

    return Cell(name=name, chips=int(w["chips"]),
                config=_read_json(BENCH / "configs" / f"{w['config']}.json"),
                traffic=_read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                per_layer=[m for m in spec["per_layer"] if mine(m)])


def require_chip(chips: int):
    """The devices to run on; exits non-zero, printing no result, off a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform is "
                         f"{devices[0].platform!r}); refusing to run")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def _key(seed: int):
    import jax

    # seeds may exceed 32 bits: fold the high word into the key
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


class _Compiles:
    """Counts backend compiles (including persistent-cache loads)."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@dataclasses.dataclass
class Server:
    """What set-up leaves for the window: the data, the fitted model, the engine."""

    x: object
    labels: np.ndarray
    vdt: object
    engine: object
    fit: dict
    shapes: dict


def make_data(config: dict, seed: int):
    import jax

    data = dict(config["data"])
    recipe = _load_module(BENCH / "recipes" / f"{data.pop('recipe')}.py")
    x, labels = recipe.make(_key(seed), n=config["n"], d=config["d"], **data)
    jax.block_until_ready(x)
    return x, np.asarray(labels)


def fit(config: dict, x):
    """The model, fitted to ``x`` as the configuration's ``fit`` states."""
    from repro.core.vdt import VariationalDualTree

    fit_cfg = config["fit"]
    bpp = fit_cfg.get("blocks_per_point")
    return VariationalDualTree.fit(
        x, max_blocks=None if bpp is None else int(bpp * config["n"]),
        refine_batch=int(fit_cfg["refine_batch"]),
        sigma_iters=int(fit_cfg["sigma_iters"]),
        learn_sigma=bool(fit_cfg["learn_sigma"]),
        divergence=fit_cfg["divergence"])


def setup(cell: Cell, seed: int) -> Server:
    """Data from the seed, the fit, the engine, and a warm-up of this mix's shapes."""
    from repro.serving import PropagateEngine

    x, labels = make_data(cell.config, seed)
    vdt = fit(cell.config, x)
    engine = PropagateEngine(vdt, **cell.traffic.get("engine", {}))
    req = cell.traffic["requests"]
    engine.warmup(widths=tuple(req["widths"]), n_iters=tuple(req["n_iters"]),
                  backends=(req["backend"],))
    return Server(x=x, labels=labels, vdt=vdt, engine=engine,
                  fit=dataclasses.asdict(vdt.stats),
                  shapes={"n_leaves": int(vdt.tree.n_leaves),
                          "n_blocks": int(vdt.n_blocks)})


def seed_labels(labels: np.ndarray, width: int, labelled: int, seed: int,
                k: int) -> np.ndarray:
    """Request ``k``'s seed matrix: one-hot rows for ``labelled`` random points."""
    n = labels.shape[0]
    rows = np.random.default_rng([seed, k]).choice(n, min(labelled, n), replace=False)
    y0 = np.zeros((n, width), np.float32)
    y0[rows, labels[rows] % width] = 1.0
    return y0


@dataclasses.dataclass
class Window:
    """Client-side record of one window: every due request and what became of it."""

    reqs: list
    t_start: float
    due: np.ndarray
    done: np.ndarray          # completion time; nan where it never completed
    failed: np.ndarray        # bool
    futures: list
    lateness_s: np.ndarray    # how late the generator submitted each request
    counters: dict            # engine counter deltas over the window and drain
    compiles: int             # compiles inside the window


_COUNTERS = ("submitted", "completed", "failed", "dispatches", "batched_requests",
             "preemptions", "expired", "scheduler_errors", "rejected")


def _counters(engine) -> dict:
    m = engine.metrics()
    return {k: int(getattr(m, k)) for k in _COUNTERS}


def run_window(server: Server, reqs, seed: int, seconds: float, compiles=None,
               drain_s: float = 60.0) -> Window:
    """Offer ``reqs`` open loop, each at its due time, then wait for every answer.

    Latency runs from the due time, so a stall of the server or of the
    generator shows in every request behind it.  The drain waits at most
    ``drain_s`` past the window's close.
    """
    from repro.serving import PropagateRequest

    engine = server.engine
    n = len(reqs)
    done = np.full(n, np.nan)
    failed = np.zeros(n, bool)
    lateness = np.zeros(n)
    futures = [None] * n
    before = _counters(engine)
    c0 = compiles.n if compiles is not None else 0

    def on_done(fut, k):
        done[k] = time.perf_counter()

    t_start = time.perf_counter()
    due = t_start + np.asarray([r.due_s for r in reqs])
    for k, r in enumerate(reqs):
        y0 = seed_labels(server.labels, r.width, r.labelled, seed, k)
        wait = due[k] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        lateness[k] = time.perf_counter() - due[k]
        try:
            fut = engine.submit(PropagateRequest(y0, alpha=r.alpha, n_iters=r.n_iters,
                                                 backend=r.backend,
                                                 deadline_ms=r.deadline_ms))
        except Exception as exc:  # a refused request is a miss, not a crash
            print(f"bench: request {k} refused: {exc!r}", file=sys.stderr)
            failed[k] = True
            continue
        fut.add_done_callback(lambda f, k=k: on_done(f, k))
        futures[k] = fut
    close = t_start + seconds
    while time.perf_counter() < close:
        time.sleep(min(0.05, close - time.perf_counter()))
    compiles_in = (compiles.n - c0) if compiles is not None else 0
    limit = close + drain_s
    for k, fut in enumerate(futures):
        if fut is None:
            continue
        try:
            fut.result(timeout=max(0.0, limit - time.perf_counter()))
        except Exception:
            failed[k] = True
    # a future's done callbacks run just after its waiters wake: give the
    # last ones a moment to stamp their completion time
    wait_until = time.perf_counter() + 1.0
    while (np.isnan(done) & ~failed & np.array([f is not None for f in futures])).any() \
            and time.perf_counter() < wait_until:
        time.sleep(0.001)
    after = _counters(engine)
    return Window(reqs=reqs, t_start=t_start, due=due, done=done,
                  failed=failed | np.isnan(done), futures=futures,
                  lateness_s=lateness,
                  counters={k: after[k] - before[k] for k in _COUNTERS},
                  compiles=compiles_in)


def _rank(sorted_vals, q: float) -> float:
    """Nearest-rank percentile."""
    return sorted_vals[max(0, math.ceil(q * len(sorted_vals)) - 1)]


def end_to_end(win: Window, setup_s: float) -> dict:
    lat = np.where(win.failed, np.inf, (win.done - win.due) * 1e3)
    lat.sort()
    ok = ~win.failed
    span = (np.nanmax(win.done[ok]) - win.t_start) if ok.any() else math.inf
    out = {"setup_s": setup_s,
           "p50_ms": float(_rank(lat, 0.50)),
           "p95_ms": float(_rank(lat, 0.95)),
           "req_per_s": float(ok.sum() / span)}
    return {k: (v if math.isfinite(v) else 1e12) for k, v in out.items()}


def _gap(got, want) -> float:
    """Widest gap between answers, each scaled by its reference's largest entry."""
    gap = 0.0
    for g, w in zip(got, want):
        gap = max(gap, float(np.abs(g - w).max() / max(np.abs(w).max(), 1e-30)))
    return gap


def release(server: Server) -> None:
    """Shut the engine down and drop the model, so their device memory is freed."""
    server.engine.shutdown()
    server.engine = server.vdt = None
    gc.collect()


def check(server: Server, win: Window, cell: Cell, seed: int,
          control: bool = False) -> tuple[dict, dict]:
    """Compare served answers with the plain references; returns (numbers, limits).

    Every answer is checked for shape and finiteness; a sample of
    ``check.sample`` requests drawn from the seed is compared with the
    reference of its backend, ``bench/reference/<backend>.py``.  The
    program's state is released before the references run.
    ``control`` also reads the control: each reference computed in
    bfloat16, one precision below the configuration's, put in the
    program's place (``control_<gap>``).
    """
    n, reqs = cell.config["n"], win.reqs
    bad = int(win.failed.sum())
    for k, fut in enumerate(win.futures):
        if fut is None or win.failed[k]:
            continue
        ans = fut.result()
        if ans.shape != (n, reqs[k].width) or not np.isfinite(ans).all():
            bad += 1
    size = min(int(cell.traffic["check"]["sample"]), len(reqs))
    pick = np.sort(np.random.default_rng([seed, 2]).choice(len(reqs), size, replace=False))
    served = {int(k): np.array(win.futures[k].result())
              for k in pick if not win.failed[k]}
    mods = {b: _load_module(BENCH / "reference" / f"{b}.py")
            for b in sorted({reqs[k].backend for k in served})}
    fitted = {b: m.take(server.vdt) for b, m in mods.items()}
    x, labels = server.x, server.labels
    release(server)
    numbers = {"answers_bad": bad}
    t_ref = time.perf_counter()
    x_host = np.asarray(x)
    for b, mod in mods.items():
        state, found = mod.prepare(x_host, fitted[b], cell.config)
        numbers.update(found)
        ks = [k for k in served if reqs[k].backend == b]
        cases = [(seed_labels(labels, reqs[k].width, reqs[k].labelled, seed, k),
                  reqs[k].alpha, reqs[k].n_iters) for k in ks]
        want = mod.answers(state, cases)
        numbers[mod.GAP] = _gap([served[k] for k in ks], want)
        if control:
            import jax.numpy as jnp

            numbers[f"control_{mod.GAP}"] = _gap(
                mod.answers(state, cases, jnp.bfloat16), want)
    print(f"bench: reference over {len(served)} answers took "
          f"{time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    return numbers, cell.traffic["check"]["limits"]


def within(value, limit) -> bool:
    """A number meets its limit: at most ``limit``, or at least ``limit["min"]``."""
    if isinstance(limit, dict):
        return value >= limit["min"]
    return limit is not None and value <= limit


def _within(numbers: dict, limits: dict) -> bool:
    return all(within(v, limits.get(k)) for k, v in numbers.items())


@dataclasses.dataclass
class LayerRun:
    """What a per-layer metric's reader sees of a traced run."""

    counters: dict
    fit: dict
    shapes: dict
    max_batch: int
    served: list
    trace: object
    window_ns: float
    busy_ns: float
    peaks: dict
    modules: list

    def program_ns(self, names) -> float:
        from bench.tracing import program_ns

        return program_ns(self.modules, names)


def per_layer(cell: Cell, server: Server, win: Window, trace, peaks: dict) -> tuple:
    from bench import tracing

    lo, hi = tracing.window(trace)
    modules = tracing.clip(trace.modules, lo, hi)
    run = LayerRun(counters=win.counters, fit=server.fit, shapes=server.shapes,
                   max_batch=server.engine.max_batch,
                   served=[r for r, f in zip(win.reqs, win.failed) if not f],
                   trace=trace, window_ns=hi - lo,
                   busy_ns=tracing.union_ns(modules), peaks=peaks, modules=modules)
    values = {}
    for m in cell.per_layer:
        value = _load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            values[m["name"]] = float(value)
    breakdown = {"device_ops": tracing.top_ops(tracing.clip(trace.ops, lo, hi)),
                 "idle_gaps": tracing.idle_gaps(modules, tracing.clip(trace.host, lo, hi),
                                                lo, hi)}
    return values, run.busy_ns / 1e9, run.window_ns / 1e9, breakdown


def result_line(correct, attempted, failed, metrics, units, device, numbers, limits,
                breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
           "metrics": {k: {"value": v, "unit": units[k]} for k, v in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": limits.get(k)} for k, v in numbers.items()}
    return json.dumps(out)


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices, t0: float) -> int:
    """Set up, run the window, check, print; returns the exit code."""
    import jax
    from bench.roofline import peaks as peak_table
    from bench.traffic import schedule
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    compiles = _Compiles()
    peaks = peak_table(devices[0].device_kind)
    server = setup(cell, seed)
    reqs = schedule(cell.traffic, seed, seconds)
    setup_s = time.perf_counter() - t0
    log_dir = None
    if trace:
        log_dir = tempfile.TemporaryDirectory()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir.name, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        win = run_window(server, reqs, seed, seconds, compiles)
    if trace:
        jax.profiler.stop_trace()
    if win.compiles:
        print(f"bench: {win.compiles} compiles inside the window", file=sys.stderr)
    late = win.lateness_s
    print(f"bench: generator lateness mean {late.mean() * 1e3:.3f} ms, "
          f"max {late.max() * 1e3:.3f} ms over {len(late)} requests", file=sys.stderr)
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    breakdown = None
    if trace:
        from bench import tracing

        tr = tracing.read(log_dir.name)
        log_dir.cleanup()
        metrics, busy_s, window_s, breakdown = per_layer(cell, server, win, tr, peaks)
        device.update(busy_s=busy_s, window_s=window_s)
    else:
        metrics = {k: v for k, v in end_to_end(win, setup_s).items() if k in units}

    numbers, limits = check(server, win, cell, seed)
    correct = _within(numbers, limits) and not win.failed.any()
    for k, v in numbers.items():
        print(f"check {k}: {v!r} limit {limits.get(k)!r}", file=sys.stderr)
    print(result_line(correct, len(win.reqs), int(win.failed.sum()), metrics, units,
                      device, numbers, limits, breakdown), flush=True)
    return 0
