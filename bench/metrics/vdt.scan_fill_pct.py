"""VDT scan: active blocks as a share of the block slots each step of a walk reads.

Read from the fitted model's counters: ``VdtStats.n_blocks`` over
``VdtStats.scan_slots``, the length of the block table the scan walks.
Absent where the program does not count the table's slots.  Moves
``p50_ms``: every slot is gathered, weighted and summed at every step of
every walk, whether or not it holds a block.
"""


def read(run):
    fit = run.fit or {}
    slots = fit.get("scan_slots", 0)
    if slots <= 0:
        return None
    return 100.0 * fit["n_blocks"] / slots
