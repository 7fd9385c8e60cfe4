"""Fit: seconds to build the partition tree and fit q and sigma on the coarsest partition.

``VdtStats.build_tree_s + init_qopt_s``, timed by the program on the host
clock after ``block_until_ready``.  Moves ``setup_s``.
"""


def read(run):
    fit = run.fit
    if not fit:
        return None
    return fit["build_tree_s"] + fit["init_qopt_s"]
