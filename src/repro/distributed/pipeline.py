"""Pipeline parallelism over the ``pod`` axis (GPipe-style schedule).

Multi-pod default maps ``pod`` to outer data parallelism; this module is the
alternative: layers are split into ``n_stages`` contiguous stages, the global
batch into ``n_micro`` microbatches, and stages execute the classic pipelined
schedule expressed as a ``shard_map`` over the pod axis with
``jax.lax.ppermute`` moving activations stage->stage.  Bubble fraction is
(S-1)/(M+S-1); the §Perf log discusses when PP beats pod-level DP (it wins
when the DCN gradient all-reduce dominates, i.e. large models on few pods).

This is a reference implementation validated on CPU meshes in
tests/test_distributed.py (2 stages x small transformer); the dry-run keeps
pod=DP as its default.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_forward"]


def pipeline_forward(
    stage_fn: Callable,      # (stage_params, x, stage_idx) -> x
    stage_params,            # pytree stacked over stages on axis 0
    x: jax.Array,            # (n_micro, micro_batch, ...) microbatched input
    mesh: Mesh,
    axis: str = "pod",
):
    """GPipe forward over ``axis``.  Each device along ``axis`` holds one
    stage's params; activations flow via ppermute.  Returns final-stage
    outputs for all microbatches (on the last stage's shard)."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    steps = n_micro + n_stages - 1

    def body(params_local, x_local):
        # params_local: this stage's shard — leading stage dim is 1; strip it
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_local)
        # x_local: (n_micro, mb, ...) — only stage 0 reads it
        stage = jax.lax.axis_index(axis)

        def step(carry, t):
            acts, outs = carry
            # stage 0 injects microbatch t (if any left), others use incoming
            inject = jnp.where(t < n_micro, t, n_micro - 1)
            x_in = jnp.where(stage == 0, x_local[inject], acts)
            y = stage_fn(params_local, x_in, stage)
            # shift activations to the next stage
            acts_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # last stage emits microbatch (t - n_stages + 1)
            emit_idx = t - (n_stages - 1)
            outs = jax.lax.cond(
                (stage == n_stages - 1) & (emit_idx >= 0),
                lambda o: o.at[jnp.maximum(emit_idx, 0)].set(y),
                lambda o: o,
                outs,
            )
            return (acts_next, outs), None

        acts0 = jnp.zeros_like(x_local[0])
        outs0 = jnp.zeros((n_micro,) + x_local.shape[1:], x_local.dtype)
        (_, outs), _ = jax.lax.scan(step, (acts0, outs0), jnp.arange(steps))
        # only the last stage holds outputs; replicate via psum
        return jax.lax.psum(outs, axis)

    spec_params = P(axis)  # stage dim sharded across pods
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_params, P()),       # input replicated; stage params split
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)
