"""Fit: seconds of greedy refinement to the block budget (``VdtStats.refine_s``).

Timed by the program on the host clock after ``block_until_ready``; absent
where the configuration does not refine.  Moves ``setup_s``.
"""


def read(run):
    secs = (run.fit or {}).get("refine_s", 0.0)
    return secs if secs > 0 else None
