"""Opt-in pipeline parallelism (DESIGN.md §5): a GPipe-style microbatch
pipeline over a mesh axis, built on shard_map + collective_permute.

The baseline dry-run meshes treat pods as DP replicas (the paper's
technique is orthogonal to PP); this module provides the PP building
block for depth-dominated deployments: stage s holds layers
[s·L/S, (s+1)·L/S); microbatches stream through the ring with one
collective_permute per tick; the bubble is the standard (S-1)/(M+S-1).

Forward pipeline (serving/offload path).  For training, compose with
jax.grad per microbatch and the usual 1F1B schedule — the transport
primitive (ring permute of activations) is the same.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P


def gpipe_forward(stage_fn, stage_params, microbatches, *, mesh,
                  axis: str = "stage"):
    """Run ``microbatches`` (M, mb, ...) through S pipeline stages.

    ``stage_params``: pytree whose leaves have a leading stage dim S,
    sharded over ``axis``.  ``stage_fn(params_one_stage, x) -> y`` with
    y.shape == x.shape (homogeneous stages — transformer blocks).
    Returns (M, mb, ...) outputs, replicated.
    """
    S = mesh.shape[axis]
    M = microbatches.shape[0]
    T = M + S - 1                      # ticks incl. fill/drain bubble

    def local(params_l, xs):
        sid = jax.lax.axis_index(axis)
        my_params = jax.tree.map(lambda a: a[0], params_l)

        def tick(t, carry):
            buf_in, outs = carry
            # stage 0 injects microbatch t while t < M
            inject = jnp.clip(t, 0, M - 1)
            my_in = jnp.where(sid == 0, xs[inject], buf_in)
            y = stage_fn(my_params, my_in)
            # pass activations down the ring
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)])
            # last stage completes microbatch t-(S-1) at tick t
            oidx = jnp.clip(t - (S - 1), 0, M - 1)
            valid = (t >= S - 1) & (sid == S - 1)
            outs = jnp.where(valid, outs.at[oidx].set(y), outs)
            return nxt, outs

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        _, outs = jax.lax.fori_loop(0, T, tick, (buf0, outs0))
        # broadcast the last stage's results to every rank
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    pspecs = jax.tree.map(lambda _: P(axis), stage_params)
    return shard_map(
        local, mesh=mesh,
        in_specs=(pspecs, P()), out_specs=P(),
        check_vma=False)(stage_params, microbatches)


def pipeline_bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe bubble overhead: (S-1)/(M+S-1)."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
