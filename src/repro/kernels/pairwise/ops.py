"""jit'd public wrapper: Pallas on TPU, interpret-mode on CPU."""
import functools

import jax

from repro.kernels import interpret_mode
from repro.kernels.pairwise.pairwise import pairwise_sq_dists_kernel

__all__ = ["pairwise_sq_dists"]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n"))
def pairwise_sq_dists(x, y, block_m: int = 256, block_n: int = 256):
    return pairwise_sq_dists_kernel(
        x, y, block_m=block_m, block_n=block_n, interpret=interpret_mode())
