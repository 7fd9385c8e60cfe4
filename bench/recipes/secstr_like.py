"""SecStr-shaped data: a window of residues, one-hot, in two classes.

Each SecStr point is a window of ``window`` amino-acid positions, each
position one of ``symbols`` symbols (20 residues and "beyond the chain"),
one-hot coded: ``d = window * symbols`` binary features with exactly
``window`` ones per row.  Here each row draws its class with probability
1/2 and each position's symbol independently from that class's symbol
frequencies; all of it on the device from a JAX key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "d", "window"))
def make(key, n: int, d: int, window: int, freqs):
    """Returns ``(x, labels)``: ``(n, d)`` float32 in {0, 1}, ``(n,)`` int32.

    ``freqs`` is ``(2, symbols)``: each class's symbol frequencies.
    """
    freqs = jnp.asarray(freqs, jnp.float32)
    symbols = freqs.shape[1]
    if window * symbols != d:
        raise ValueError(f"window {window} x {symbols} symbols is not d = {d}")
    k_lab, k_sym = jax.random.split(key)
    labels = jax.random.bernoulli(k_lab, 0.5, (n,)).astype(jnp.int32)
    logits = jnp.log(freqs / freqs.sum(1, keepdims=True))[labels]
    sym = jax.random.categorical(k_sym, logits[:, None, :], shape=(n, window))
    x = jax.nn.one_hot(sym, symbols, dtype=jnp.float32).reshape(n, d)
    return x, labels
