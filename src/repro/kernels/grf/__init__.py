from repro.kernels.grf.ref import dense_lp_ref, dense_power_action_ref
from repro.kernels.grf.walkers import sample_walks, walk_step, walker_mean

__all__ = ["walker_mean", "dense_power_action_ref", "dense_lp_ref",
           "sample_walks", "walk_step"]
