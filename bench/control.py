"""Readings that the output check's limits are set from, over many seeds.

    python3 bench/control.py --workload secstr.vdt.steady --seeds 1,2,3 --seconds 5 \
        [--runs sound,no_refine,half_refine,random_refine,sigma_not_refit,sigma_unlearned]

``sound`` (the default) sets up the cell, serves a window of its traffic at
its rate, and reads the check's numbers for the program and for the control:
the reference walk in bfloat16, one precision below the configuration's
float32, in the program's place (``control_walk_gap``).  Every other run is
a fault planted in the fit, read by the reference's checks of the fit with
no window:

* ``no_refine``: the coarsest partition, refinement skipped;
* ``half_refine``: refinement stopped half way, at 3N blocks of 4N;
* ``random_refine``: refinement to the full budget, picking blocks at random
  instead of by their eq.-19 gain;
* ``sigma_not_refit``: refinement to the full budget without refitting
  sigma, which keeps the coarsest partition's;
* ``sigma_unlearned``: sigma left at its eq.-14 starting scale.

One JSON line per seed and run, then each number's largest and smallest
reading by run.  The benchmark's own runs never run any of this.  Exits
non-zero off a TPU.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


@contextlib.contextmanager
def planted(fault: str, config: dict):
    """Yields the configuration to fit with, the fault planted for the duration."""
    import jax
    import jax.numpy as jnp
    from repro.core import refine

    config = copy.deepcopy(config)
    fit = config["fit"]
    orig_gains, orig_refine = refine.refinement_gains, refine.refine_to_budget
    if fault == "no_refine":
        fit.pop("blocks_per_point")
    elif fault == "half_refine":
        fit["blocks_per_point"] = fit["blocks_per_point"] - 1
    elif fault == "random_refine":
        rounds = iter(range(1 << 30))

        def gains(*args, **kw):
            g = orig_gains(*args, **kw)
            r = jax.random.uniform(jax.random.key(next(rounds)), g.shape, g.dtype)
            return jnp.where(jnp.isfinite(g), r, g)

        refine.refinement_gains = gains
    elif fault == "sigma_not_refit":
        refine.refine_to_budget = lambda *a, **kw: orig_refine(*a, **{**kw, "refit_sigma": False})
    elif fault == "sigma_unlearned":
        fit["learn_sigma"] = False
    elif fault != "sound":
        raise ValueError(f"unknown run {fault!r}")
    try:
        yield config
    finally:
        refine.refinement_gains, refine.refine_to_budget = orig_gains, orig_refine


def fit_readings(cell, seed: int, fault: str) -> dict:
    """The reference's checks of a fit with ``fault`` planted; no window."""
    import numpy as np

    from bench import harness

    x, _ = harness.make_data(cell.config, seed)
    with planted(fault, cell.config) as config:
        vdt = harness.fit(config, x)
    mod = harness._load_module(harness.BENCH / "reference" / "vdt.py")
    fitted = mod.take(vdt)
    del vdt
    _, numbers = mod.prepare(np.asarray(x), fitted, cell.config)
    return numbers


def readings(cell, seed: int, seconds: float, fault: str) -> dict:
    from bench import harness
    from bench.traffic import schedule

    if fault != "sound":
        return {"seed": seed, "run": fault, **fit_readings(cell, seed, fault)}
    server = harness.setup(cell, seed)
    win = harness.run_window(server, schedule(cell.traffic, seed, seconds), seed, seconds)
    numbers, _ = harness.check(server, win, cell, seed, control=True)
    return {"seed": seed, "run": fault, "failed": int(win.failed.sum()), **numbers}


def summary(rows: list[dict]) -> dict:
    """Each number's largest and smallest reading, by run."""
    out: dict = {}
    for r in rows:
        by = out.setdefault(r["run"], {})
        for k, v in r.items():
            if k in ("seed", "run"):
                continue
            lo, hi = by.get(k, (v, v))
            by[k] = (min(lo, v), max(hi, v))
    return {run: {k: {"min": lo, "max": hi} for k, (lo, hi) in by.items()}
            for run, by in out.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--runs", default="sound", help="comma-separated: sound and faults")
    args = ap.parse_args(argv)

    from bench import harness
    from repro.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    harness.require_chip(cell.chips)
    enable_compile_cache()
    rows = []
    for fault in args.runs.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()
            rows.append(readings(cell, seed, args.seconds, fault))
            rows[-1]["s"] = round(time.perf_counter() - t, 1)
            print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"summary": summary([{k: v for k, v in r.items() if k != "s"}
                                          for r in rows])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
