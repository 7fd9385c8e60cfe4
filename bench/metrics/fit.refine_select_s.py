"""Fit: host seconds of block selection inside refinement (``VdtStats.refine_select_s``).

The program sums the host clock over each round's ``refine_topk``, the
part of ``fit.refine_s`` that never reaches the device; absent where the
configuration does not refine.  Moves ``setup_s``.
"""


def read(run):
    secs = (run.fit or {}).get("refine_select_s", 0.0)
    return secs if secs > 0 else None
