"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis
(typically 'pod' — inter-pod links are the slowest, and PP's
point-to-point ppermute traffic is the cheapest collective pattern).

Implementation: the layer stack is split into `n_stages` equal stages
whose params are sharded on the leading axis over the pipeline mesh axis;
inside shard_map every stage runs the same tick loop — stage 0 feeds
microbatches in, each tick's activations hop to the next stage with
jax.lax.ppermute, and the last stage collects outputs. The whole loop is
differentiable (ppermute has a transpose rule), so pipelined training is
just jax.grad over the pipelined forward.

Bubble fraction is the usual (P-1)/(T+P-1); choose n_micro >= 4*P.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def split_stages(stacked_params: Any, n_stages: int) -> Any:
    """(L, ...) stacked layer params -> (n_stages, L/n_stages, ...)."""

    def one(x):
        L = x.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])

    return jax.tree_util.tree_map(one, stacked_params)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,          # this stage's slice (inside shard_map)
    x_micro: jax.Array,         # (n_micro, mb, ...) — consumed by stage 0
    *,
    axis: str,
    n_stages: int,
):
    """Run the tick loop inside shard_map. Returns (n_micro, mb, ...)
    outputs, valid on the LAST stage (zeros elsewhere); callers psum or
    read the last-stage shard."""
    idx = jax.lax.axis_index(axis)
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    # mark the carries as varying over the pipeline axis (shard_map VMA typing)
    buf = jax.lax.pvary(jnp.zeros_like(x_micro[0]), (axis,))
    outs = jax.lax.pvary(jnp.zeros_like(x_micro), (axis,))
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        buf, outs = carry
        feed = x_micro[jnp.clip(t, 0, n_micro - 1)]
        x_in = jnp.where(idx == 0, feed, buf)
        y = stage_fn(stage_params, x_in)
        out_t = t - (n_stages - 1)
        is_out = (idx == n_stages - 1) & (out_t >= 0)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(is_out, y, outs[jnp.clip(out_t, 0, n_micro - 1)]),
            jnp.clip(out_t, 0, n_micro - 1), axis=0,
        )
        buf = jax.lax.ppermute(y, axis, perm)
        return (buf, outs), None

    (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
    return outs


def make_pipelined_forward(
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    *,
    axis: str = "pod",
    n_micro: int = 8,
):
    """Builds f(stage_params, x) -> y where stage_params leaves carry a
    leading (n_stages, L/n_stages) axis (see split_stages) and x is
    (batch, ...) with batch % n_micro == 0. The pipeline axis size is
    mesh.shape[axis]."""
    n_stages = mesh.shape[axis]

    def stage_fn(params_slice, x):
        def body(c, lp):
            return layer_fn(lp, c), None

        y, _ = jax.lax.scan(body, x, params_slice)
        return y

    def fwd(stage_params, x):
        B = x.shape[0]
        assert B % n_micro == 0
        x_micro = x.reshape(n_micro, B // n_micro, *x.shape[1:])

        def inner(sp, xm):
            sp = jax.tree_util.tree_map(lambda a: a[0], sp)  # drop stage dim
            outs = pipeline_apply(stage_fn, sp, xm, axis=axis,
                                  n_stages=n_stages)
            # broadcast the last stage's outputs to all stages
            outs = jax.lax.psum(
                jnp.where(jax.lax.axis_index(axis) == n_stages - 1, outs, 0.0),
                axis,
            )
            return outs

        param_specs = jax.tree_util.tree_map(
            lambda a: P(axis, *([None] * (a.ndim - 1))), stage_params
        )
        outs = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=P(),
        )(stage_params, x_micro)
        return outs.reshape(B, *outs.shape[2:])

    return fwd
