"""Jit'd wrapper for the dequant-GEMV baseline kernel + its plan backend.

Registers "dequant_pallas" with core/plan.py — before the plan API,
``vq_matmul(mode="dequant")`` silently dropped ``impl``/``interpret`` and
this kernel was unreachable from the model layers; a
``PlanPolicy(vq_mode="dequant", impl="pallas")`` now routes here."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import ops as core_ops
from repro.core import plan as plan_mod
from repro.core.vq import VQWeight
from repro.kernels.dequant_gemv.kernel import dequant_gemv_pallas
from repro.kernels.gather import SUBLANES
from repro.kernels.dequant_gemv.ref import dequant_gemv_ref


def _auto_tiles(V: int, N: int) -> Tuple[int, int]:
    """(block_v, block_n): the paper's v=32 tile height and 512 output
    lanes, clamped to the problem. Per grid step the kernel holds the
    rebuilt weight slab (bv, d, bn) fp32 (512 KB at d=8) plus the index
    tile and its widening — independent of M, whose 8-row tiles the grid
    walks."""
    return min(core_ops.DEFAULT_BLOCK_V, V), min(512, N)


@functools.partial(
    jax.jit, static_argnames=("block_v", "block_n", "interpret", "use_pallas", "out_dtype")
)
def dequant_gemv(
    x: jax.Array,
    vq: VQWeight,
    *,
    block_v="auto",
    block_n="auto",
    interpret: bool = False,
    use_pallas: bool = True,
    out_dtype=None,
) -> jax.Array:
    """block_v/block_n accept "auto" or explicit ints; non-divisible V/N
    are padded (zeroed X rows gather index 0 -> contribute 0)."""
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    K, N, V, d, C = vq.K, vq.N, vq.V, vq.d, vq.C
    M = x.size // K
    X = x.reshape(M, V, d).astype(jnp.float32)
    # stream indices at storage width (uint8 for n<=8); in-kernel upcast
    I = vq.idx
    scale = vq.scale.astype(jnp.float32)

    if not use_pallas:
        cb = vq.codebooks.transpose(0, 2, 1).astype(jnp.float32)  # (C,k,d)
        y = dequant_gemv_ref(X, cb, I, scale)
        return y.reshape(*lead, N).astype(out_dtype)

    auto_bv, auto_bn = _auto_tiles(V, N)
    bv = auto_bv if block_v == "auto" else min(block_v, V)
    bn = auto_bn if block_n == "auto" else min(block_n, N)
    pad_v = (-V) % bv
    pad_n = (-N) % bn
    X = jnp.pad(X, ((0, (-M) % SUBLANES), (0, pad_v), (0, 0)))
    if pad_v:
        I = jnp.pad(I, ((0, 0), (0, pad_v), (0, 0)))
    if pad_n:
        I = jnp.pad(I, ((0, 0), (0, 0), (0, pad_n)))
        scale = jnp.pad(scale, (0, pad_n))
    y = dequant_gemv_pallas(X.reshape(X.shape[0], -1),
                            vq.codebooks.astype(jnp.float32), I,
                            scale[None, :], block_v=bv, block_n=bn,
                            interpret=interpret)
    return y[:M, :N].reshape(*lead, N).astype(out_dtype)


# ---------------------------------------------------------------------------
# Plan backend
# ---------------------------------------------------------------------------


def _match_dequant_pallas(spec: plan_mod.LinearSpec,
                          policy: plan_mod.PlanPolicy) -> bool:
    return (spec.kind == "vq" and policy.vq_mode == "dequant"
            and policy.impl == "pallas")


def _plan_dequant_pallas(spec: plan_mod.LinearSpec,
                         policy: plan_mod.PlanPolicy) -> plan_mod.MatmulPlan:
    auto_bv, bn = _auto_tiles(spec.V, spec.N)
    bv = auto_bv if policy.block_v is None else min(policy.block_v, spec.V)
    out_dt = jnp.dtype(spec.out_dtype)
    interpret = policy.interpret

    def run(x, vq):
        return dequant_gemv(x, vq, block_v=bv, block_n=bn,
                            interpret=interpret, out_dtype=out_dt)

    return plan_mod.MatmulPlan("dequant_pallas", spec, policy,
                               (("bv", bv), ("bn", bn)), run)


plan_mod.register_backend("dequant_pallas", _match_dequant_pallas,
                          _plan_dequant_pallas)
