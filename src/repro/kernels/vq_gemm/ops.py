"""Jit'd wrapper for the VQ-GEMM kernel (handles padding + reshape).

This module owns the kernel's tile model (`select_gemm_block_mv`): the
per-grid-step VMEM footprint is the x tile (bmv, d) plus the O tile
(bmv, k) fp32, sized against the shared FUSED_GATHER_TILE_BYTES budget
in core/ops.py. The two-kernel `eva_split_pallas` backend (registered
from kernels/oc_lookup/ops.py) consumes it to freeze block_mv at plan
time."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import ops as core_ops
from repro.kernels.vq_gemm.kernel import vq_gemm_pallas
from repro.kernels.vq_gemm.ref import vq_gemm_ref


def select_gemm_block_mv(MV: int, d: int, k: int) -> int:
    """Largest power-of-two MV tile whose (bmv, d) x tile + (bmv, k) O
    tile fp32 fit the shared tile budget, clamped to [8, 1024] AND to
    the next power of two above the actual problem (the wrapper pads MV
    up to a tile multiple — a decode-sized MV must not pad to a full
    budget-sized tile of dead rows)."""
    per_row = 4 * (d + k)
    bmv = max(8, core_ops.FUSED_GATHER_TILE_BYTES // max(per_row, 1))
    pow2_ceil_mv = 1 << max(int(MV) - 1, 1).bit_length()
    bmv = min(bmv, 1024, pow2_ceil_mv)
    return max(8, core_ops._pow2_floor(bmv))


@functools.partial(jax.jit, static_argnames=("block_mv", "interpret", "use_pallas"))
def vq_gemm(
    x: jax.Array,            # (..., K)
    codebooks: jax.Array,    # (C, d, k)
    *,
    block_mv: int = 256,
    interpret: bool = False,
    use_pallas: bool = True,
) -> jax.Array:
    """Compute the output codebook O (C, M, V, k) for activations x.

    O is token-major: for token m the slab O[c, m, v0:v0+8] holds the
    tables of 8 v-rows, the layout the oc_lookup kernel gathers from (an
    index register's sublanes are the same 8 v-rows)."""
    C, d, k = codebooks.shape
    K = x.shape[-1]
    assert K % d == 0
    V = K // d
    M = x.size // K
    x_flat = x.reshape(M * V, d)

    if not use_pallas:
        O = vq_gemm_ref(x_flat, codebooks)
    else:
        MV = M * V
        pad = (-MV) % block_mv
        if pad:
            x_flat = jnp.pad(x_flat, ((0, pad), (0, 0)))
        O = vq_gemm_pallas(x_flat, codebooks, block_mv=block_mv, interpret=interpret)
        if pad:
            O = O[:, :MV]
    return O.reshape(C, M, V, k)
