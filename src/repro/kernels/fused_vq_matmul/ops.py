"""Jit'd wrapper for the fused EVA matmul kernel + its plan backend.

Accepts a VQWeight and activations of any leading shape; handles padding
(M to a whole number of token tiles, V/N to the block tiles), the
token-major activation layout, and dtype conversion.

The index matrix is handed to the kernel in its storage dtype (uint8 for
n <= 8) — the kernel upcasts per streamed tile, so HBM index traffic
stays at q bits/weight (see kernel.py's uint8 streaming contract). A
grouped projection family (VQWeight.splits non-empty) is just a wider N
here: one call, one OC scratch fill, every member's output columns swept
against the same VMEM-resident OC.

This module OWNS the fused kernel's tile model (`select_fused_tiles` /
`fused_oc_bytes`, checked against the OC budget in core/ops.py)
and registers the "eva_fused_pallas" backend with core/plan.py: the
planner freezes (m_tile, block_v, block_n) once per (spec, policy) and
execution re-derives nothing.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import ops as core_ops
from repro.core import plan as plan_mod
from repro.core.vq import VQWeight
from repro.kernels.gather import SUBLANES, token_tile
from repro.kernels.fused_vq_matmul.kernel import (fused_vq_matmul_pallas,
                                                  x_stage_bytes)
from repro.kernels.fused_vq_matmul.ref import fused_vq_matmul_ref


def fused_oc_bytes(V: int, C: int, k: int, block_v: int, m_tile: int) -> int:
    """VMEM held by the fused kernel's OC scratch: (C, m_tile, V_pad, k)
    fp32 — one token tile of the output codebook over the whole
    (block_v-padded) V."""
    v_padded = V + ((-V) % block_v)
    return 4 * C * m_tile * v_padded * k


def select_fused_tiles(M: int, V: int, N: int, C: int, k: int = 256, *,
                       block_v: int | None = None,
                       oc_budget: int = core_ops.FUSED_OC_SCRATCH_BYTES,
                       ) -> Tuple[int, int, int]:
    """(m_tile, block_v, block_n) for the fused Pallas wrapper.

    block_v is the paper's v=32 tile height (or the one pinned) and
    block_n 512 output lanes, each clamped to the problem. m_tile is every
    row of the call when the rows' OC scratch (fused_oc_bytes), (8, bn)
    f32 accumulators and activation stage (kernel.x_stage_bytes) fit
    ``oc_budget``, else the tile of a
    multiple of 8 rows that fits and pads M least (gather.token_tile); 0
    when not even 8 rows fit. The per-step index tile (C, bv, bn) and its
    int32 widening stay far below the budget."""
    bv = min(block_v or core_ops.DEFAULT_BLOCK_V, V)
    bn = min(512, N)
    per_token = (fused_oc_bytes(V, C, k, bv, 1) + 4 * SUBLANES * bn
                 + x_stage_bytes(bv, k))
    return token_tile(M, per_token, oc_budget), bv, bn


@functools.partial(
    jax.jit, static_argnames=("block_v", "block_n", "m_tile", "interpret",
                              "use_pallas", "out_dtype")
)
def fused_vq_matmul(
    x: jax.Array,
    vq: VQWeight,
    *,
    block_v="auto",
    block_n="auto",
    m_tile="auto",
    interpret: bool = False,
    use_pallas: bool = True,
    out_dtype=None,
) -> jax.Array:
    """block_v/block_n/m_tile default to "auto" (select_fused_tiles);
    explicit ints pin the tile sizes (plans pass fully-resolved tiles;
    tests / TPU tuning may too)."""
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    K, N, V, d, C = vq.K, vq.N, vq.V, vq.d, vq.C
    k = vq.codebooks.shape[-1]
    M = x.size // K
    X = x.reshape(M, V, d).astype(jnp.float32)
    # stream indices in their storage dtype (uint8 for n<=8) — the kernel
    # upcasts per tile; pre-widening here would 4x the index HBM traffic
    I = vq.idx
    scale = vq.scale.astype(jnp.float32)

    if not use_pallas:
        y = fused_vq_matmul_ref(X, vq.codebooks, I, scale)
        return y.reshape(*lead, N).astype(out_dtype)

    auto_mt, bv, auto_bn = select_fused_tiles(
        M, V, N, C, k, block_v=None if block_v == "auto" else block_v)
    bn = auto_bn if block_n == "auto" else min(block_n, N)
    mt = auto_mt if m_tile == "auto" else min(m_tile, M)
    if mt < 1:
        raise ValueError(f"the fused kernel's OC scratch for V={V} does not "
                         "fit its VMEM budget; plan the split backend")
    pad_m = (-M) % mt
    pad_v = (-V) % bv
    pad_n = (-N) % bn
    # token-major activations: each (token tile, v-tile) x block is
    # (mt, bv, d), so the kernel's OC slab lands in the (C, mt, V, k)
    # scratch without a relayout
    if pad_m or pad_v:
        X = jnp.pad(X, ((0, pad_m), (0, pad_v), (0, 0)))
    if pad_v:
        # padded V rows gather index 0 from zeroed X rows -> contribute 0
        I = jnp.pad(I, ((0, 0), (0, pad_v), (0, 0)))
    if pad_n:
        I = jnp.pad(I, ((0, 0), (0, 0), (0, pad_n)))
        scale = jnp.pad(scale, (0, pad_n))
    y = fused_vq_matmul_pallas(
        X, vq.codebooks.astype(jnp.float32), I, scale[None, :],
        m_tile=mt, block_v=bv, block_n=bn, interpret=interpret)
    return y[:M, :N].reshape(*lead, N).astype(out_dtype)


# ---------------------------------------------------------------------------
# Plan backend: the fused kernel is THE impl="pallas" execution of an EVA
# matmul — jnp epilogue requests are invalid there (loud, from the
# registration, exactly like the old wrapper-level error).
# ---------------------------------------------------------------------------


def _match_eva_fused(spec: plan_mod.LinearSpec, policy: plan_mod.PlanPolicy
                     ) -> bool:
    # the OC scratch of one token tile must fit VMEM; wider K leaves the
    # split backend
    return (spec.kind == "vq" and policy.impl == "pallas"
            and policy.vq_mode in ("eva", "none")
            and select_fused_tiles(spec.M, spec.V, spec.N, spec.C, spec.k,
                                   block_v=policy.block_v)[0] > 0)


def _plan_eva_fused(spec: plan_mod.LinearSpec, policy: plan_mod.PlanPolicy
                    ) -> plan_mod.MatmulPlan:
    if policy.epilogue != "auto":
        raise ValueError(
            "impl='pallas' always runs the fused tiled kernel; epilogue="
            f"{policy.epilogue!r} does not apply (pass block_v to size its "
            "v-tiles)")
    mt, bv, bn = select_fused_tiles(spec.M, spec.V, spec.N, spec.C, spec.k,
                                    block_v=policy.block_v)
    out_dt = jnp.dtype(spec.out_dtype)
    interpret = policy.interpret

    def run(x, vq):
        return fused_vq_matmul(x, vq, block_v=bv, block_n=bn, m_tile=mt,
                               interpret=interpret, out_dtype=out_dt)

    return plan_mod.MatmulPlan(
        "eva_fused_pallas", spec, policy,
        (("mt", mt), ("token_tiles", -(-spec.M // mt)), ("bv", bv),
         ("bn", bn)), run)


plan_mod.register_backend("eva_fused_pallas", _match_eva_fused,
                          _plan_eva_fused)
