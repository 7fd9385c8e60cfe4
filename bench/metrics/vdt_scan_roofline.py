"""VDT scan: least HBM time for the bytes its walks need, over the scan programs' device time.

Bytes come from ``bench.roofline.vdt_walk_bytes``, counted from the number
of leaves, the active blocks, the label columns of the requests served and
their iteration counts; time is the device duration of the programs named
below, read from the trace.  Moves ``p50_ms``: every vdt request waits for
whole walks.
"""
from bench.roofline import vdt_walk_bytes

# programs that run the walk itself (jit_<name>(...) in the trace)
PROGRAMS = ("lp_scan_leaforder", "lp_scan_leaforder_resume")


def read(run):
    if run.trace is None:
        return None
    reqs = [r for r in run.served if r.backend == "vdt"]
    dispatches = run.counters.get("dispatches", 0)
    secs = run.program_ns(PROGRAMS) / 1e9
    if not reqs or dispatches <= 0 or secs <= 0:
        return None
    # the block table is read once per walk step whatever the width: one
    # table pass per dispatch and iteration, the rest per column
    mean_iters = sum(r.n_iters for r in reqs) / len(reqs)
    table = vdt_walk_bytes(run.shapes["n_leaves"], run.shapes["n_blocks"], 0, 1)
    cols = sum(vdt_walk_bytes(run.shapes["n_leaves"], run.shapes["n_blocks"],
                              r.width, r.n_iters) - table * r.n_iters for r in reqs)
    need = cols + table * dispatches * mean_iters
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / secs
