"""jit'd public wrapper for flash attention."""
import functools

import jax

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.flash_attention import flash_attention_kernel

__all__ = ["flash_attention"]


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    return flash_attention_kernel(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k, interpret=interpret_mode())
