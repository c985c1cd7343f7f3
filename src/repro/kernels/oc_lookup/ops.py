"""Jit'd wrapper for the OC-lookup kernel (padding + dtype handling) +
the two-kernel ``eva_split_pallas`` plan backend.

The split backend is the paper-faithful no-fusion formulation: kernel 1
(kernels/vq_gemm) materializes the full (C, M, V, 2^n) output-codebook
buffer in HBM, kernel 2 (this module's oc_lookup) runs the structured,
conflict-free gather + add-only reduction over it. Against the fused
kernel it trades one extra HBM round-trip of the OC buffer (priced as
``PlanCost.intermediate_bytes``) and a second launch for per-kernel tile
freedom — the ranked Planner decides per shape which side of that trade
wins (analytically the fused kernel; measured calibration can flip it).
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
from repro.kernels.oc_lookup.kernel import oc_lookup_pallas
from repro.kernels.oc_lookup.ref import oc_lookup_ref
from repro.kernels.vq_gemm.ops import select_gemm_block_mv, vq_gemm


def select_lookup_tiles(M: int, V: int, N: int, C: int, k: int = 256, *,
                        block_v: int | None = None) -> Tuple[int, int, int]:
    """(m_tile, block_v, block_n) for the lookup kernel: the paper's v=32
    tile height (or the one pinned) and 512 output lanes, clamped to the
    problem, and every row of the call per grid step while the streamed
    O tile (C, mt, bv, k) fp32 and the (mt, 8, bn) f32 accumulator fit
    the shared tile budget (else the tile of a multiple of 8 rows that
    pads M least, gather.token_tile; 8 rows at least, whose tiles stay
    far below the scoped VMEM even past the budget, so the backend takes
    every shape). The index tile and its int32 widening stay far below
    the scoped-VMEM budget."""
    bv = min(block_v or core_ops.DEFAULT_BLOCK_V, V)
    bn = min(512, N)
    mt = token_tile(M, 4 * C * bv * k + 4 * SUBLANES * bn,
                    core_ops.FUSED_GATHER_TILE_BYTES)
    return mt or SUBLANES, bv, bn


@functools.partial(
    jax.jit, static_argnames=("block_v", "block_n", "interpret", "use_pallas")
)
def oc_lookup(
    O: jax.Array,
    I: jax.Array,
    scale: jax.Array,
    *,
    block_v="auto",
    block_n="auto",
    interpret: bool = False,
    use_pallas: bool = True,
) -> jax.Array:
    """y (M, N) from the token-major output codebook O (C, M, V, k).
    block_v/block_n accept "auto" (select_lookup_tiles) or explicit
    ints; non-divisible V/N and M past one token tile are padded (padded
    O rows are zero -> contribute 0)."""
    C, M, V, k = O.shape
    N = I.shape[-1]
    # indices stream in their storage dtype (uint8 for n<=8); the kernel
    # upcasts per tile — see the uint8 streaming contract in kernel.py
    scale = scale.astype(jnp.float32)
    if not use_pallas:
        return oc_lookup_ref(O, I, scale)

    mt, bv, auto_bn = select_lookup_tiles(
        M, V, N, C, k, block_v=None if block_v == "auto" else block_v)
    bn = auto_bn if block_n == "auto" else min(block_n, N)
    pad_m = (-M) % mt
    pad_v = (-V) % bv
    pad_n = (-N) % bn
    if pad_v or pad_m:
        # padded rows gather index 0 from zeroed O rows -> contribute 0
        O = jnp.pad(O, ((0, 0), (0, pad_m), (0, pad_v), (0, 0)))
        I = jnp.pad(I, ((0, 0), (0, pad_v), (0, 0)))
    if pad_n:
        I = jnp.pad(I, ((0, 0), (0, 0), (0, pad_n)))
        scale = jnp.pad(scale, (0, pad_n))
    y = oc_lookup_pallas(O, I, scale[None, :], m_tile=mt, block_v=bv,
                         block_n=bn, interpret=interpret)
    return y[:M, :N]


# ---------------------------------------------------------------------------
# Two-kernel EVA matmul: vq_gemm -> HBM OC buffer -> oc_lookup (no fusion)
# ---------------------------------------------------------------------------


def eva_split_matmul(
    x: jax.Array,
    vq: VQWeight,
    *,
    block_mv="auto",
    block_v="auto",
    block_n="auto",
    interpret: bool = False,
    use_pallas: bool = True,
    out_dtype=None,
) -> jax.Array:
    """EVA decode matmul as TWO kernels with the (C, M, V, 2^n) output
    codebook (token-major) materialized in HBM between them — the paper's architecture
    drawn at kernel granularity, no fusion. A grouped family is just a
    wider N in the lookup stage (the OC buffer is N-independent, so the
    amortization argument is identical to the fused kernel's)."""
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    N = vq.N
    C, d, k = vq.codebooks.shape
    M = x.size // vq.K
    bmv = select_gemm_block_mv(M * vq.V, d, k) if block_mv == "auto" \
        else int(block_mv)
    O = vq_gemm(x, vq.codebooks, block_mv=bmv, interpret=interpret,
                use_pallas=use_pallas)                    # (C, M, V, k)
    y = oc_lookup(O, vq.idx, vq.scale, block_v=block_v, block_n=block_n,
                  interpret=interpret, use_pallas=use_pallas)
    return y.reshape(*lead, N).astype(out_dtype)


# ---------------------------------------------------------------------------
# Plan backend: eva_split_pallas competes with eva_fused_pallas under
# impl="pallas" — the first genuinely overlapping registration, resolved
# by the Planner's calibrated predicted-time ranking.
# ---------------------------------------------------------------------------


def _match_eva_split(spec: plan_mod.LinearSpec, policy: plan_mod.PlanPolicy
                     ) -> bool:
    # epilogue != "auto" stays the fused registration's loud error (jnp
    # epilogues never apply to a Pallas impl)
    return (spec.kind == "vq" and policy.impl == "pallas"
            and policy.vq_mode in ("eva", "none")
            and policy.epilogue == "auto")


def _plan_eva_split(spec: plan_mod.LinearSpec, policy: plan_mod.PlanPolicy
                    ) -> plan_mod.MatmulPlan:
    mt, bv, bn = select_lookup_tiles(spec.M, spec.V, spec.N, spec.C, spec.k,
                                     block_v=policy.block_v)
    bmv = select_gemm_block_mv(spec.M * spec.V, spec.d, spec.k)
    out_dt = jnp.dtype(spec.out_dtype)
    interpret = policy.interpret

    def run(x, vq):
        return eva_split_matmul(x, vq, block_mv=bmv, block_v=bv, block_n=bn,
                                interpret=interpret, out_dtype=out_dt)

    oc_bytes = 4 * spec.C * spec.M * spec.V * spec.k
    cost = plan_mod.PlanCost(
        macs=core_ops.vq_gemm_macs(spec.M, spec.K,
                                   max(spec.k.bit_length() - 1, 0),
                                   spec.C, spec.d),
        lookup_adds=core_ops.epilogue_adds(spec.M, spec.K, spec.N, spec.C,
                                           spec.d),
        weight_bytes=plan_mod.vq_weight_bytes(spec),
        intermediate_bytes=2 * oc_bytes,   # OC write + read-back through HBM
        launches=2,
    )
    return plan_mod.MatmulPlan(
        "eva_split_pallas", spec, policy,
        (("bmv", bmv), ("mt", mt), ("token_tiles", -(-spec.M // mt)),
         ("bv", bv), ("bn", bn)), cost, run)


plan_mod.register_backend("eva_split_pallas", _match_eva_split,
                          _plan_eva_split)
