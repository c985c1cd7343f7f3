"""Baseline Pallas kernel: conventional VQ decode (paper Fig. 1(b)).

Reconstructs dequantized weight tiles in VMEM from (I, B) — the full
'1-to-many' centroid gather EVA eliminates — then multiplies. Per output
tile the kernel moves d x more gathered bytes than the OC lookup and
spends M*K*N MACs instead of M*K*2^n; it exists to expose that contrast
in the benchmarks (and as the memory-traffic-faithful baseline).

Reconstruction runs as in-register lane gathers (kernels/gather.py): for
one index row I[c, v, :] broadcast over the d sublanes, the codebook
(d, 2^n) of codebook c yields the d weight rows W[v*d:(v+1)*d, :] —
sublanes are the d coordinates, lanes the output columns. The rebuilt
(bv*d, bn) tile lands in VMEM scratch and one MXU matmul consumes it.

Grid: (num_m_tiles, num_n_tiles, num_v_tiles), V innermost,
output-stationary; token rows come in tiles of 8 (the wrapper pads M).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import (SUBLANES, centroid_rows, lane_width,
                                  row_group)


def _dequant_gemv_kernel(x_ref, cb_ref, i_ref, s_ref, y_ref, idx_scr, w_scr,
                         *, n_v_tiles: int):
    v = pl.program_id(2)

    @pl.when(v == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    C, d, _ = cb_ref.shape
    _, bv, bn = idx_scr.shape
    w = lane_width(bn)
    g = row_group(bv)
    idx_scr[...] = i_ref[...].astype(jnp.int32)      # per-tile upcast

    # centroid gather: W[v*d + e, j] = sum_c cb[c, e, idx[c, v, j]]
    def body(i, carry):
        j0 = pl.multiple_of(i * g, g)
        rows = [idx_scr[c, pl.ds(j0, g), :] for c in range(C)]  # (g, bn)
        for s in range(g):
            for q in range(bn // w):
                w_scr[j0 + s, :, q * w:(q + 1) * w] = centroid_rows(
                    [cb_ref[c] for c in range(C)],
                    [r[s:s + 1, q * w:(q + 1) * w] for r in rows])
        return carry

    jax.lax.fori_loop(0, bv // g, body, 0)
    y_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_scr[...].reshape(bv * d, bn),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(v == n_v_tiles - 1)
    def _scale():
        y_ref[...] *= s_ref[...].astype(jnp.float32)


def dequant_gemv_pallas(
    x: jax.Array,          # (M, V*d), M % 8 == 0
    codebooks: jax.Array,  # (C, d, k) fp32
    I: jax.Array,          # (C, V, N) uint8 (n<=8) or int32 (n>8)
    scale: jax.Array,      # (1, N)
    *,
    block_v: int = 32,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    M, K = x.shape
    C, d, k = codebooks.shape
    C2, V, N = I.shape
    assert C == C2 and K == V * d, (x.shape, codebooks.shape, I.shape)
    assert V % block_v == 0 and N % block_n == 0
    assert M % SUBLANES == 0, M
    mt = SUBLANES
    n_v_tiles = V // block_v
    grid = (M // mt, N // block_n, n_v_tiles)

    kernel = functools.partial(_dequant_gemv_kernel, n_v_tiles=n_v_tiles)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((mt, block_v * d), lambda m, n, v: (m, v)),
            pl.BlockSpec((C, d, k), lambda m, n, v: (0, 0, 0)),
            pl.BlockSpec((C, block_v, block_n), lambda m, n, v: (0, v, n)),
            pl.BlockSpec((1, block_n), lambda m, n, v: (0, n)),
        ],
        out_specs=pl.BlockSpec((mt, block_n), lambda m, n, v: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, block_v, block_n), jnp.int32),
                        pltpu.VMEM((block_v, d, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )(x, codebooks, I, scale)
