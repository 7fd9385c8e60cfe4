"""jit'd public wrappers for the fused LP matvec / batched LP-step kernels.

Every wrapper asks :func:`repro.kernels.interpret_mode`: compiled kernels
on a TPU, the Pallas interpreter on the CPU (slow but exact), and an error
on any other platform.

Batched dispatch
----------------
``fused_lp_step_batched`` / ``fused_lp_matvec_batched`` default to the
**distance-reusing** layout (``reuse=True``): the batch folds into the
channel axis so each pairwise-divergence tile and its online-softmax
normalizer is computed once for all ``B`` right-hand sides (see
``batched.py``).  ``reuse=False`` selects the legacy per-batch-recompute
grid ``(B, M, N)`` — kept so the bench gate can measure the reuse win and
parity tests can pin both layouts to the dense reference.

On the reuse path ``alpha`` is a *traced* scalar or per-request ``(B,)``
array (serving different alphas never recompiles); the legacy path bakes a
static float ``alpha`` into the kernel as before.

``fused_lp_scan_batched`` / ``fused_lp_scan_folded`` run the whole
``n_iters`` LP recursion in one jitted ``lax.scan`` with ``Y`` resident on
device in the folded layout — the multi-iteration form the exact serving
backend (``core.label_prop.lp_scan_fused``) dispatches to.

Divergences
-----------
Every wrapper takes ``divergence=`` (``None`` | registry name |
``core.divergence.Divergence``) as a *static* jit argument: the kernel's
similarity tile is traced from the divergence's ``tile`` function, so each
divergence compiles its own executable and mixed-divergence traffic can
never share (or cross-contaminate) a compiled kernel.  ``None`` /
``"sqeuclidean"`` keeps the built-in squared-Euclidean tile — bit-identical
to the pre-Bregman kernels.
"""
import functools

import jax

from repro.kernels import interpret_mode
from repro.kernels.fused_lp.batched import (
    fused_lp_scan_batched_resume_kernel,
    fused_lp_scan_batched_reuse_kernel,
    fused_lp_scan_folded_kernel,
    fused_lp_scan_folded_resume_kernel,
    fused_lp_step_batched_kernel,
    fused_lp_step_batched_reuse_kernel,
    fused_lp_step_folded_kernel,
)
from repro.kernels.fused_lp.fused_lp import fused_lp_matvec_kernel

__all__ = ["fused_lp_matvec", "fused_lp_matvec_batched",
           "fused_lp_step_batched", "fused_lp_step_folded",
           "fused_lp_scan_folded", "fused_lp_scan_batched",
           "fused_lp_scan_folded_resume", "fused_lp_scan_batched_resume"]


def _static_div(divergence):
    """Normalize to the hashable ``Divergence`` BEFORE the jit boundary.

    A ``BoundDivergence`` carries device stats arrays and cannot be hashed
    as a static jit argument; unwrapping here means every public wrapper
    accepts ``None`` | name | ``Divergence`` | ``BoundDivergence`` uniformly
    (matching ``core.label_prop.lp_scan_fused``) instead of failing with an
    opaque unhashable-static-arg error for non-default divergences.
    """
    from repro.core.divergence import resolve_divergence

    return resolve_divergence(divergence)


@functools.partial(jax.jit,
                   static_argnames=("sigma", "block_m", "block_n",
                                    "divergence"))
def _matvec_impl(x, y, sigma: float, block_m: int, block_n: int, divergence):
    return fused_lp_matvec_kernel(
        x, y, sigma, block_m=block_m, block_n=block_n,
        interpret=interpret_mode(), divergence=divergence)


def fused_lp_matvec(x, y, sigma: float, block_m: int = 256,
                    block_n: int = 256, divergence=None):
    return _matvec_impl(x, y, sigma, block_m=block_m, block_n=block_n,
                        divergence=_static_div(divergence))


@functools.partial(jax.jit,
                   static_argnames=("sigma", "block_m", "block_n",
                                    "divergence"))
def _step_folded_impl(x, y, y0, sigma: float, alpha,
                      block_m: int, block_n: int, divergence):
    return fused_lp_step_folded_kernel(
        x, y, y0, sigma, alpha, block_m=block_m, block_n=block_n,
        interpret=interpret_mode(), divergence=divergence)


def fused_lp_step_folded(x, y, y0, sigma: float, alpha=1.0,
                         block_m: int = 256, block_n: int = 256,
                         divergence=None):
    """One eq.-15 step in the folded (N, K) layout, divergences computed once.

    ``alpha`` is traced: a scalar or a per-column ``(K,)`` array.
    """
    return _step_folded_impl(x, y, y0, sigma, alpha,
                             block_m=block_m, block_n=block_n,
                             divergence=_static_div(divergence))


@functools.partial(jax.jit,
                   static_argnames=("sigma", "block_m", "block_n",
                                    "divergence"))
def _step_batched_reuse(x, y, y0, sigma: float, alpha,
                        block_m: int = 256, block_n: int = 256,
                        divergence=None):
    return fused_lp_step_batched_reuse_kernel(
        x, y, y0, sigma, alpha, block_m=block_m, block_n=block_n,
        interpret=interpret_mode(), divergence=divergence)


@functools.partial(jax.jit,
                   static_argnames=("sigma", "alpha", "block_m", "block_n",
                                    "divergence"))
def _step_batched_perbatch(x, y, y0, sigma: float, alpha: float,
                           block_m: int = 256, block_n: int = 256,
                           divergence=None):
    return fused_lp_step_batched_kernel(
        x, y, y0, sigma, alpha, block_m=block_m, block_n=block_n,
        interpret=interpret_mode(), divergence=divergence)


def fused_lp_step_batched(x, y, y0, sigma: float, alpha=0.01,
                          block_m: int = 256, block_n: int = 256,
                          reuse: bool = True, divergence=None):
    """One fused eq.-15 LP update for a (B, N, C) stack of label matrices.

    ``reuse=True`` (default) computes each divergence tile once for the whole
    batch and accepts a traced scalar or per-request ``(B,)`` ``alpha``;
    ``reuse=False`` is the legacy per-batch-recompute kernel, which requires
    a static float ``alpha``.
    """
    divergence = _static_div(divergence)
    if reuse:
        return _step_batched_reuse(x, y, y0, sigma, alpha,
                                   block_m=block_m, block_n=block_n,
                                   divergence=divergence)
    return _step_batched_perbatch(x, y, y0, sigma, float(alpha),
                                  block_m=block_m, block_n=block_n,
                                  divergence=divergence)


def fused_lp_matvec_batched(x, ys, sigma: float, block_m: int = 256,
                            block_n: int = 256, reuse: bool = True,
                            divergence=None):
    """P @ Y[b] for a (B, N, C) stack; alpha=1 degenerates the LP step."""
    divergence = _static_div(divergence)
    if reuse:
        return _step_batched_reuse(x, ys, ys, sigma, 1.0,
                                   block_m=block_m, block_n=block_n,
                                   divergence=divergence)
    return _step_batched_perbatch(x, ys, ys, sigma, 1.0,
                                  block_m=block_m, block_n=block_n,
                                  divergence=divergence)


@functools.partial(jax.jit,
                   static_argnames=("sigma", "n_iters", "block_m", "block_n",
                                    "divergence"))
def _scan_folded_impl(x, y0, sigma: float, alpha, n_iters: int,
                      block_m: int, block_n: int, divergence):
    return fused_lp_scan_folded_kernel(
        x, y0, sigma, alpha, int(n_iters), block_m=block_m, block_n=block_n,
        interpret=interpret_mode(), divergence=divergence)


def fused_lp_scan_folded(x, y0, sigma: float, alpha, n_iters: int,
                         block_m: int = 256, block_n: int = 256,
                         divergence=None):
    """``n_iters`` fused eq.-15 steps, Y resident on device in folded layout."""
    return _scan_folded_impl(x, y0, sigma, alpha, int(n_iters),
                             block_m=block_m, block_n=block_n,
                             divergence=_static_div(divergence))


@functools.partial(jax.jit,
                   static_argnames=("sigma", "n_iters", "block_m", "block_n",
                                    "divergence"))
def _scan_batched_impl(x, y0s, sigma: float, alpha, n_iters: int,
                       block_m: int, block_n: int, divergence):
    return fused_lp_scan_batched_reuse_kernel(
        x, y0s, sigma, alpha, int(n_iters),
        block_m=block_m, block_n=block_n, interpret=interpret_mode(),
        divergence=divergence)


def fused_lp_scan_batched(x, y0s, sigma: float, alpha, n_iters: int,
                          block_m: int = 256, block_n: int = 256,
                          divergence=None):
    """Whole batched LP run over a (B, N, C) stack: fold once, scan, unfold.

    ``alpha`` is a traced scalar or per-request ``(B,)`` array.
    """
    return _scan_batched_impl(x, y0s, sigma, alpha, int(n_iters),
                              block_m=block_m, block_n=block_n,
                              divergence=_static_div(divergence))


@functools.partial(jax.jit,
                   static_argnames=("sigma", "block_m", "block_n",
                                    "divergence"))
def _scan_folded_resume_impl(x, y, y0, sigma: float, alpha, n_iters,
                             block_m: int, block_n: int, divergence):
    return fused_lp_scan_folded_resume_kernel(
        x, y, y0, sigma, alpha, n_iters, block_m=block_m,
        block_n=block_n, interpret=interpret_mode(), divergence=divergence)


def fused_lp_scan_folded_resume(x, y, y0, sigma: float, alpha, n_iters: int,
                                block_m: int = 256, block_n: int = 256,
                                divergence=None):
    """``n_iters`` folded eq.-15 steps entered from a mid-walk carry ``y``.

    The segmented-dispatch primitive: bit-identical continuation of the
    monolithic scan (eq. 15 is a pure fixed-point iteration), so a long
    walk can be split into preemptible segments whose carries re-enter here.
    ``n_iters`` is *traced* (dynamic ``fori_loop`` bound): every segment
    length — including odd remainders — reuses one compiled executable per
    shape, and a length-1 tail can never be constant-folded into a
    differently-fused (1-ulp-off) inline body.
    """
    return _scan_folded_resume_impl(x, y, y0, sigma, alpha, int(n_iters),
                                    block_m=block_m, block_n=block_n,
                                    divergence=_static_div(divergence))


@functools.partial(jax.jit,
                   static_argnames=("sigma", "block_m", "block_n",
                                    "divergence"))
def _scan_batched_resume_impl(x, ys, y0s, sigma: float, alpha, n_iters,
                              block_m: int, block_n: int, divergence):
    return fused_lp_scan_batched_resume_kernel(
        x, ys, y0s, sigma, alpha, n_iters,
        block_m=block_m, block_n=block_n, interpret=interpret_mode(),
        divergence=divergence)


def fused_lp_scan_batched_resume(x, ys, y0s, sigma: float, alpha,
                                 n_iters: int, block_m: int = 256,
                                 block_n: int = 256, divergence=None):
    """Batched LP segment over a (B, N, C) carry stack (see folded resume)."""
    return _scan_batched_resume_impl(x, ys, y0s, sigma, alpha, int(n_iters),
                                     block_m=block_m, block_n=block_n,
                                     divergence=_static_div(divergence))
