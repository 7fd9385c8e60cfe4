"""Bring-up smoke: the LP server on one TPU chip at SecStr size.

    python chip_smoke.py              # one chip: fit, serve, check
    python chip_smoke.py --chips 4    # sharded engine vs one device, bit for bit

Fits the variational dual tree on a SecStr-shaped cloud (N = 83,679,
d = 315, the paper's Table 1) through the public API and serves requests
through ``PropagateEngine`` futures:

* vdt: 2-class requests from 5% labelled seeds, plus one coalesced group
  at the engine's widest folded layout (32 requests x width 128); every
  answer must equal a direct ``vdt.label_propagate`` call bit for bit;
* exact: answers must lie within ``EXACT_ATOL`` of a dense eq.-15 walk in
  ``jnp`` at ``precision="highest"``, computed in row blocks at full N;
* on a 4096-point cut (the most the dense N^2 grf graph allows): a vdt
  walk against the dense walk over ``vdt.dense_q()``, and a grf request
  against ``dense_lp_ref`` within the CLT bound of ``tests/_stats.py``.
  The cut is fitted at a fixed bandwidth, the RMS pairwise distance: at
  the learned one (about 0.35) the kernel graph is nearly a 1-NN graph,
  the walkers' importance weights ``deg * P`` reach 4095, and no CLT
  bound over a few dozen replicates holds.

``--chips 4`` runs only ``ShardedPropagateEngine`` over four chips against
``PropagateEngine`` on the first, on the same vdt and exact requests.

Progress lines go to stdout; the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script exits non-zero, without that line, when JAX finds no TPU or any
phase or check fails.  Times it prints are bring-up observations, not
benchmark results.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

N, D = 83679, 315          # SecStr, the paper's Table 1
N_CUT = 4096               # dense Q and the dense grf graph are N^2
LABELLED = 0.05            # share of seed rows carrying a label
VDT_ITERS, EXACT_ITERS, GRF_ITERS = 50, 20, 10
VDT_ALPHAS = (0.01, 0.05, 0.1, 0.2)
EXACT_ALPHAS = (0.5, 0.3, 0.1)
# one eq.-15 step of the exact kernel errs by at most 2^-8 * alpha * max|Y|
# if the MXU rounds both operands of P @ Y to bf16 (2^-9 each); over the
# whole walk the errors sum to at most 2^-8 * alpha / (1 - alpha) for the
# [0, 1] labels used here
EXACT_ATOL = 2.0 ** -8 * max(EXACT_ALPHAS) / (1.0 - max(EXACT_ALPHAS))
# the tier-1 tolerance of the O(|B|) matvec against dense Q
VDT_DENSE_RTOL, VDT_DENSE_ATOL = 1e-4, 1e-5
# 64 replicates: the z = 5 bound is checked on all 8192 entries, and with
# 16 the Student-t tail alone would trip about one of them
GRF_WALKERS, GRF_REPS = 100, 64
WIDE_BATCH, WIDE_WIDTH = 32, 128   # PropagateEngine's max_batch x widest bucket

_compile = {"s": 0.0, "hits": 0}


def log(msg: str) -> None:
    print(msg, flush=True)


def check(name: str, ok: bool, detail: str) -> None:
    log(f"check {name}: {'ok' if ok else 'FAILED'} ({detail})")
    if not ok:
        sys.exit(f"chip_smoke: check {name!r} failed: {detail}")


def watch_compiles() -> None:
    """Sum backend compile (or cache load) seconds and cache hits."""
    from jax import monitoring

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _compile["s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _compile["hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


class Phase:
    """Wall time plus compile seconds spent inside a ``with`` block."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0, self.c0, self.h0 = (time.perf_counter(), _compile["s"],
                                     _compile["hits"])
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            log(f"phase {self.name}: wall_s={time.perf_counter() - self.t0:.3f}"
                f" compile_s={_compile['s'] - self.c0:.3f}"
                f" cache_hits={_compile['hits'] - self.h0}")


def seeds(rng, labels, n_classes: int) -> np.ndarray:
    """One-hot rows for a random ``LABELLED`` share of the points."""
    y0 = np.zeros((labels.shape[0], n_classes), np.float32)
    rows = np.nonzero(rng.rand(labels.shape[0]) < LABELLED)[0]
    y0[rows, labels[rows] % n_classes] = 1.0
    return y0


def serve(engine, requests, flush: bool = False):
    """Submit, wait on the futures; returns answers and per-request seconds."""
    t_done = {}
    futs = []
    for i, req in enumerate(requests):
        t0 = time.perf_counter()
        fut = engine.submit(req)
        fut.add_done_callback(
            lambda f, i=i, t0=t0: t_done.__setitem__(i, time.perf_counter() - t0))
        futs.append(fut)
    if flush:
        engine.flush()
    answers = [np.asarray(f.result()) for f in futs]
    return answers, [t_done[i] for i in range(len(futs))]


def check_finite(name: str, answers) -> None:
    bad = sum(int(not np.isfinite(a).all()) for a in answers)
    check(f"{name} finite", bad == 0, f"{bad} of {len(answers)} answers non-finite")


def check_direct(name: str, vdt, requests, answers) -> None:
    """Engine answers equal direct ``label_propagate`` calls bit for bit."""
    diff = max(float(np.abs(a - np.asarray(vdt.label_propagate(
        r.y0, alpha=r.alpha, n_iters=r.n_iters))).max())
        for r, a in zip(requests, answers))
    check(f"{name} == direct label_propagate", diff == 0.0,
          f"max |diff| {diff:.3e} over {len(answers)} requests")


def engine_counts(engines) -> dict:
    tot = {"completed": 0, "failed": 0, "scheduler_errors": 0}
    for eng in engines:
        m = eng.metrics()
        for k in tot:
            tot[k] += int(getattr(m, k))
    return tot


def dense_exact_walk(x, y0, sigma, alpha, n_iters: int, rows: int = 4096):
    """Eq. 15 on the dense eq.-3 matrix, ``precision="highest"``.

    P is built ``rows`` rows at a time — row softmax of
    ``-||x_i - x_j||^2 / (2 sigma^2)`` with a zero diagonal — and never
    held whole; ``alpha`` is per column.
    """
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    n = x.shape[0]
    n_blocks = -(-n // rows)
    inv = 1.0 / (2.0 * float(sigma) ** 2)

    def p_times(x, xp, xx, y):
        def block(i):
            xb = jax.lax.dynamic_slice_in_dim(xp, i * rows, rows)
            d2 = (jnp.sum(xb * xb, axis=1)[:, None] + xx[None, :]
                  - 2.0 * jnp.dot(xb, x.T, precision=hi))
            logits = -jnp.maximum(d2, 0.0) * inv
            ids = i * rows + jnp.arange(rows)
            logits = jnp.where(ids[:, None] == jnp.arange(n)[None, :],
                               -jnp.inf, logits)
            return jnp.dot(jax.nn.softmax(logits, axis=1), y, precision=hi)
        return jax.lax.map(block, jnp.arange(n_blocks)).reshape(-1, y.shape[1])[:n]

    @jax.jit
    def walk(x, y0, alpha):
        xx = jnp.sum(x * x, axis=1)
        xp = jnp.pad(x, ((0, n_blocks * rows - n), (0, 0)))
        alpha = alpha[None, :]
        return jax.lax.fori_loop(
            0, n_iters,
            lambda _, y: alpha * p_times(x, xp, xx, y) + (1.0 - alpha) * y0, y0)

    return np.asarray(walk(jnp.asarray(x, jnp.float32),
                           jnp.asarray(y0, jnp.float32),
                           jnp.asarray(alpha, jnp.float32)))


def fit(x, label: str, **kw):
    """``VariationalDualTree.fit`` at ``max_blocks=4N``, ``refine_batch=256``
    (what ``benchmarks/serving.py`` fits) unless ``kw`` overrides them."""
    from repro.core.vdt import VariationalDualTree

    n = x.shape[0]
    t0 = time.perf_counter()
    vdt = VariationalDualTree.fit(
        x, **{"max_blocks": 4 * n, "refine_batch": 256, **kw})
    s = vdt.stats
    log(f"fit {label}: n={n} d={x.shape[1]} blocks={vdt.n_blocks} "
        f"sigma={float(vdt.sigma):.6g} sigma_iters={s.sigma_iters} "
        f"build_tree_s={s.build_tree_s:.3f} init_qopt_s={s.init_qopt_s:.3f} "
        f"refine_s={s.refine_s:.3f} total_s={time.perf_counter() - t0:.3f}")
    return vdt


def one_chip(seed: int) -> list:
    from repro.data.synthetic import secstr_like
    from repro.kernels.grf.ref import dense_lp_ref
    from repro.serving import PropagateEngine, PropagateRequest
    from tests._stats import NUMERIC_FLOOR, Z_SCORE
    import jax

    rng = np.random.RandomState(seed)
    data = secstr_like(n=N, d=D, seed=seed)
    with Phase("fit"):
        vdt = fit(data.x, "secstr")
    engines = []

    with PropagateEngine(vdt, max_batch=WIDE_BATCH) as eng:
        engines.append(eng)
        vdt_reqs = [PropagateRequest(seeds(rng, data.labels, 2), alpha=a,
                                     n_iters=VDT_ITERS) for a in VDT_ALPHAS]
        with Phase("serve vdt (first call compiles)"):
            vdt_out, lat = serve(eng, vdt_reqs[:1])
        with Phase("serve vdt"):
            more, lat2 = serve(eng, vdt_reqs[1:])
        vdt_out += more
        log("latency_s vdt: " + " ".join(f"{t:.4f}" for t in lat + lat2))

        exact_reqs = [PropagateRequest(seeds(rng, data.labels, 2), alpha=a,
                                       n_iters=EXACT_ITERS, backend="exact")
                      for a in EXACT_ALPHAS]
        with Phase("serve exact (first call compiles)"):
            exact_out, lat = serve(eng, exact_reqs)
        log("latency_s exact: " + " ".join(f"{t:.4f}" for t in lat))

    # the widest layout as ONE dispatch: queue the whole group, then flush
    eng = PropagateEngine(vdt, max_batch=WIDE_BATCH, start=False)
    engines.append(eng)
    wide_reqs = [PropagateRequest(seeds(rng, data.labels, WIDE_WIDTH),
                                  alpha=VDT_ALPHAS[i % len(VDT_ALPHAS)],
                                  n_iters=VDT_ITERS) for i in range(WIDE_BATCH)]
    with Phase(f"serve vdt {WIDE_BATCH}x{WIDE_WIDTH} (first call compiles)"):
        wide_out, lat = serve(eng, wide_reqs, flush=True)
    m = eng.metrics()
    eng.shutdown()
    log(f"latency_s vdt {WIDE_BATCH}x{WIDE_WIDTH}: max {max(lat):.4f}")
    check("widest layout is one dispatch",
          (m.dispatches, m.batched_requests) == (1, WIDE_BATCH),
          f"dispatches={m.dispatches} batched_requests={m.batched_requests}")

    check_finite("vdt", vdt_out + wide_out)
    check_finite("exact", exact_out)
    with Phase("direct vdt calls"):
        check_direct("vdt", vdt, vdt_reqs, vdt_out)
        check_direct(f"vdt {WIDE_BATCH}x{WIDE_WIDTH}", vdt, wide_reqs, wide_out)
    with Phase("exact reference"):
        want = dense_exact_walk(
            data.x, np.concatenate([r.y0 for r in exact_reqs], axis=1),
            vdt.sigma, np.repeat(EXACT_ALPHAS, 2), EXACT_ITERS)
    got = np.concatenate(exact_out, axis=1)
    err = float(np.abs(got - want).max())
    check("exact vs dense highest-precision walk", err <= EXACT_ATOL,
          f"max |err| {err:.3e} <= atol {EXACT_ATOL:.3e}")

    cut = secstr_like(n=N_CUT, d=D, seed=seed + 1)
    rms = float(np.sqrt(2.0 * ((cut.x - cut.x.mean(0)) ** 2).sum(1).mean()))
    with Phase("fit cut"):
        vdt_cut = fit(cut.x, "secstr cut", sigma=rms, learn_sigma=False)
    with PropagateEngine(vdt_cut, n_walkers=GRF_WALKERS) as eng:
        engines.append(eng)
        y0 = seeds(rng, cut.labels, 2)
        reqs = [PropagateRequest(y0, alpha=0.1, n_iters=VDT_ITERS),
                PropagateRequest(y0, alpha=0.1, n_iters=GRF_ITERS,
                                 backend="grf")]
        with Phase("serve cut vdt + grf (first call compiles)"):
            (vdt_cut_out, grf_out), lat = serve(eng, reqs)
        log("latency_s cut vdt grf: " + " ".join(f"{t:.4f}" for t in lat))
    check_finite("cut", [vdt_cut_out, grf_out])
    check_direct("cut vdt", vdt_cut, reqs[:1], [vdt_cut_out])
    with jax.default_matmul_precision("highest"):
        q = jax.numpy.asarray(vdt_cut.dense_q(), jax.numpy.float32)
        want = np.asarray(jax.jit(lambda q, y0: jax.lax.fori_loop(
            0, VDT_ITERS, lambda _, y: 0.1 * (q @ y) + 0.9 * y0, y0))(q, y0))
        p = vdt_cut.grf_graph().dense_p()
        grf_want = np.asarray(dense_lp_ref(p, y0, alpha=0.1, n_iters=GRF_ITERS))
    err = np.abs(vdt_cut_out - want)
    check("cut vdt vs dense-Q walk",
          bool((err <= VDT_DENSE_ATOL + VDT_DENSE_RTOL * np.abs(want)).all()),
          f"max |err| {float(err.max()):.3e}, rtol {VDT_DENSE_RTOL} "
          f"atol {VDT_DENSE_ATOL}")
    with Phase("grf replicates"):
        reps = np.stack([grf_out] + [np.asarray(vdt_cut.label_propagate(
            y0, alpha=0.1, n_iters=GRF_ITERS, backend="grf",
            n_walkers=GRF_WALKERS, seed=s)) for s in range(1, GRF_REPS)])
    # tests/_stats.py's bound: |mean - ref| <= z * sem + floor, elementwise
    err = np.abs(reps.mean(axis=0) - grf_want)
    sem = reps.std(axis=0, ddof=1) / np.sqrt(GRF_REPS)
    excess = float((err - Z_SCORE * sem - NUMERIC_FLOOR).max())
    check(f"grf vs dense_lp_ref (CLT, z={Z_SCORE:g})", excess <= 0.0,
          f"{GRF_REPS} replicates of {GRF_WALKERS} walkers, max |mean - ref| "
          f"{float(err.max()):.3e}, worst excess over the bound {excess:.3e}")
    return engines


def four_chips(seed: int, devices) -> list:
    from repro.data.synthetic import secstr_like
    from repro.serving import (PropagateEngine, PropagateRequest,
                               ShardedPropagateEngine)

    rng = np.random.RandomState(seed)
    data = secstr_like(n=N, d=D, seed=seed)
    # the coarsest partition: parity does not depend on the block budget,
    # and refining to 4N takes minutes of host-bound work on all four chips
    with Phase("fit"):
        vdt = fit(data.x, "secstr coarsest partition", max_blocks=None)
    reqs = ([PropagateRequest(seeds(rng, data.labels, 2), alpha=a,
                              n_iters=VDT_ITERS) for a in VDT_ALPHAS]
            + [PropagateRequest(seeds(rng, data.labels, 2), alpha=a,
                                n_iters=EXACT_ITERS, backend="exact")
               for a in EXACT_ALPHAS])
    engines, outs = [], {}
    for name, eng in (
            ("sharded", ShardedPropagateEngine(vdt, devices=devices,
                                               start=False)),
            ("single", PropagateEngine(vdt, start=False))):
        engines.append(eng)
        with Phase(f"serve {name} (first calls compile)"):
            outs[name], lat = serve(eng, reqs, flush=True)
        eng.shutdown()
        log(f"latency_s {name}: " + " ".join(f"{t:.4f}" for t in lat))
    log(f"sharded engine: {engines[0].n_devices} devices")
    check_finite("sharded", outs["sharded"])
    for backend in ("vdt", "exact"):
        pairs = [(a, b) for r, a, b in zip(reqs, outs["sharded"], outs["single"])
                 if (r.backend or "vdt") == backend]
        diff = max(float(np.abs(a - b).max()) for a, b in pairs)
        check(f"sharded == single-device, {backend}", diff == 0.0,
              f"max |diff| {diff:.3e} over {len(pairs)} requests")
    return engines


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is {platform!r})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX sees {len(devices)}")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.compile_cache import enable_compile_cache

    log(f"device: platform={platform} kind={devices[0].device_kind} "
        f"count={len(devices)}")
    log(f"compile cache: {enable_compile_cache()}")
    watch_compiles()

    if args.chips == 4:
        engines = four_chips(args.seed, devices[:4])
    else:
        engines = one_chip(args.seed)
    counts = engine_counts(engines)
    log("engines: " + " ".join(f"{k}={v}" for k, v in counts.items()))
    check("no engine failures",
          counts["failed"] == 0 and counts["scheduler_errors"] == 0,
          f"failed={counts['failed']} scheduler_errors={counts['scheduler_errors']}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
