"""Jit'd wrapper for the grouped EVA kernel + its plan backend.

The planner chooses this backend for a VQ weight whose experts are
stacked on a leading axis (spec kind "vq_grouped", impl="pallas"). Rows
arrive in ``core/ops.ExpertRows``' layout, sorted by expert and padded
per expert to whole EXPERT_TILE-row tiles. Indices stream in their
storage dtype (uint8) and are never padded here: the v-tile is a
divisor of V (32 rows when it divides, else 16 or 8, else the whole V)
and each tile spans the whole N.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import ops as core_ops
from repro.core import plan as plan_mod
from repro.core.vq import VQWeight
from repro.kernels.grouped_vq_matmul.kernel import grouped_vq_matmul_pallas


def grouped_block_v(V: int) -> int:
    """The v-tile: the first of 32, 16, 8 that divides V, else all of V."""
    return next((bv for bv in (32, 16, 8) if V % bv == 0), V)


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def grouped_vq_matmul(rows: core_ops.ExpertRows, vq: VQWeight, *,
                      interpret: bool = False, out_dtype=None) -> jax.Array:
    """y (R, N): row r times the weight of the expert its tile holds;
    ``vq`` stacked on a leading expert axis (idx (E, C, V, N)). Its jnp
    counterpart is ``core/ops.grouped_eva_matmul``."""
    out_dtype = out_dtype or rows.x.dtype
    R, K = rows.x.shape
    E, N, d = vq.idx.shape[0], vq.N, vq.d
    V = K // d
    y = grouped_vq_matmul_pallas(
        rows.x.reshape(R, V, d).astype(jnp.float32),
        vq.codebooks.astype(jnp.float32), vq.idx,
        vq.scale.astype(jnp.float32).reshape(E, 1, N),
        rows.tile_expert.astype(jnp.int32),
        jnp.reshape(rows.tiles, (1,)).astype(jnp.int32),
        m_tile=core_ops.EXPERT_TILE, block_v=grouped_block_v(V),
        interpret=interpret)
    return y.astype(out_dtype)


def _match(spec: plan_mod.LinearSpec, policy: plan_mod.PlanPolicy) -> bool:
    return (spec.kind == "vq_grouped" and policy.impl == "pallas"
            and policy.vq_mode in ("eva", "none"))


def _plan(spec: plan_mod.LinearSpec, policy: plan_mod.PlanPolicy
          ) -> plan_mod.MatmulPlan:
    out_dt = jnp.dtype(spec.out_dtype)
    interpret = policy.interpret
    bv = grouped_block_v(spec.V)

    def run(rows, vq):
        return grouped_vq_matmul(rows, vq, interpret=interpret,
                                 out_dtype=out_dt)

    return plan_mod.MatmulPlan(
        "grouped_eva_pallas", spec, policy,
        (("mt", core_ops.EXPERT_TILE), ("bv", bv), ("bn", spec.N)), run)


plan_mod.register_backend("grouped_eva_pallas", _match, _plan)
