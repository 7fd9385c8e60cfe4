"""Pallas TPU kernels for the perf-critical compute layers.

  pairwise        — tiled pairwise squared distances (kNN / exact-P build)
  fused_lp        — flash transition matvec: exact LP step in O(N*block) mem
  flash_attention — causal GQA attention for the LM substrate

Each kernel ships <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle), and a shape/dtype sweep test asserting
allclose against the oracle.  Every call site asks :func:`interpret_mode`
whether to run the kernel in the Pallas interpreter.
"""
import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels must run interpreted on the default backend.

    The one switch between real kernels and interpret mode: compiled
    kernels on a TPU, the interpreter on the CPU (tests, tiny runs).  Any
    other platform raises — a kernel that cannot lower there must fail
    loudly, not quietly fall back to the interpreter.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run on 'tpu' (compiled) or 'cpu' (interpret "
        f"mode); the default JAX backend is {platform!r}")
