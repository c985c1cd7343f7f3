"""Jit'd wrapper for the VQ dequantization kernel.

Takes a VQWeight and returns its W (K, N) in float32, bit for bit
``core.vq.dequantize``: per element the codebooks' centroid coordinates
summed in the codebooks' dtype, then times the column's scale. N is
padded to whole 128-lane registers (index 0, scale 0) and sliced back,
so any N runs; a column block is 512 lanes where N allows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.vq import VQWeight
from repro.kernels.gather import LANES
from repro.kernels.vq_dequant.kernel import vq_dequant_pallas


@functools.partial(jax.jit, static_argnames=("interpret",))
def vq_dequant(vq: VQWeight, *, interpret: bool = False) -> jax.Array:
    """W (K, N) float32 of ``vq``; its oracle is ``core.vq.dequantize``."""
    N = vq.idx.shape[-1]
    I, scale = vq.idx, vq.scale.astype(jnp.float32)
    pad = (-N) % LANES
    if pad:
        I = jnp.pad(I, ((0, 0), (0, 0), (0, pad)))
        scale = jnp.pad(scale, (0, pad))
    bn = next(b for b in (512, 256, LANES) if (N + pad) % b == 0)
    W = vq_dequant_pallas(vq.codebooks, I, scale[None, :], block_n=bn,
                          interpret=interpret)
    return W[:, :N] if pad else W
