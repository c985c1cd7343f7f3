"""Pallas kernel: rebuild a VQ weight W (K, N) from its indices and
codebooks, on the chip, with the in-register lane gather.

For one index row I[c, v, 128 lanes] broadcast over the d sublanes, the
(d, 2^n) table of codebook c yields coordinate e of each column's
centroid in sublane e: the eight weight rows W[v*d:(v+1)*d, 128 columns]
in one vector register (``gather.centroid_rows``). The rows are summed
over the codebooks, scaled per column, and stored, so each grid step
writes a (V*d, bn) column block of W; indices stream as uint8 and are
widened in VMEM.

The whole V is one block (the kernel serves small weights: MLA's
``wkv_b`` has V = 64) and its v-loop is unrolled, so that the scheduler
overlaps one v-group's gathers with the next group's loads and index
handling. Grid: (N // bn,).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import centroid_rows, lane_width, row_group


def _vq_dequant_kernel(cb_ref, i_ref, s_ref, w_ref, idx_scr):
    C, d, _ = cb_ref.shape
    _, V, bn = idx_scr.shape
    w = lane_width(bn)
    g = row_group(V)
    idx_scr[...] = i_ref[...].astype(jnp.int32)      # per-block upcast
    tables = [cb_ref[c] for c in range(C)]
    scales = [s_ref[:, q * w:(q + 1) * w] for q in range(bn // w)]

    def body(i, carry):
        v0 = pl.multiple_of(i * g, g)
        rows = [idx_scr[c, pl.ds(v0, g), :] for c in range(C)]  # (g, bn)
        for s in range(g):
            for q in range(bn // w):
                wv = centroid_rows(
                    tables, [r[s:s + 1, q * w:(q + 1) * w] for r in rows])
                w_ref[pl.ds((v0 + s) * d, d), q * w:(q + 1) * w] = (
                    wv.astype(jnp.float32) * scales[q])
        return carry

    jax.lax.fori_loop(0, V // g, body, 0, unroll=True)


def vq_dequant_pallas(
    codebooks: jax.Array,  # (C, d, k)
    I: jax.Array,          # (C, V, N) uint8 (n<=8) or int32 (n>8)
    scale: jax.Array,      # (1, N) fp32
    *,
    block_n: int,
    interpret: bool = False,
) -> jax.Array:
    """Returns W (V*d, N) fp32. N % block_n == 0."""
    C, d, k = codebooks.shape
    C2, V, N = I.shape
    assert C == C2 and N % block_n == 0, (codebooks.shape, I.shape, block_n)
    return pl.pallas_call(
        _vq_dequant_kernel,
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((C, d, k), lambda n: (0, 0, 0)),
            pl.BlockSpec((C, V, block_n), lambda n: (0, 0, n)),
            pl.BlockSpec((1, block_n), lambda n: (0, n)),
        ],
        out_specs=pl.BlockSpec((V * d, block_n), lambda n: (0, n)),
        out_shape=jax.ShapeDtypeStruct((V * d, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, V, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="vq_dequant",
    )(codebooks, I, scale)
