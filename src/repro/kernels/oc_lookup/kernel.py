"""Pallas TPU kernel for EVA Step 2: conflict-free output-codebook lookup
with add-only reduction (the paper's Epilogue Unit, Fig. 6).

  y[m, j] = scale[j] * sum_c sum_v O[c, v, m, I[c, v, j]]

TPU mapping of the paper's bank argument: the OC is laid out v-major,
(C, V, M, 2^n), so each (c, v) table is one (8-row, 2^n) VMEM tile whose
sublanes are the 8 token rows and whose lanes are the table entries —
"one bank per OC row". One index row I[c, v, :] is shared by every token
row, so it is broadcast over the sublanes and each 128-column chunk is a
single in-register lane gather per 128-entry table tile
(kernels/gather.py). The reduction is a pure add chain over (c, v); the
only multiply is the final per-channel scale, exactly the paper's EU.

Grid: (num_m_tiles, num_n_tiles, num_v_tiles) with V innermost so the
(8, bn) output block stays resident in VMEM across the V accumulation
(output-stationary, matching Fig. 4's stationary output tile). Token
rows come in tiles of 8 (one sublane group); the wrapper pads M.

uint8 index-streaming contract: index tiles arrive in their storage
dtype (uint8 for n <= 8, int32 only for n > 8) and are widened to int32
per tile INSIDE the kernel (into a VMEM scratch the row loop reads), so
HBM->VMEM index traffic stays at the paper's q bits/weight. Callers must
not pre-widen I. For a grouped projection family (shared codebook set,
core/vq.py) N is the family's summed width — the same OC tile serves
every member's columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import SUBLANES, lookup_accumulate, vmem_limit


def _oc_lookup_kernel(o_ref, i_ref, s_ref, y_ref, idx_scr, *, n_v_tiles: int):
    v = pl.program_id(2)

    @pl.when(v == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    idx_scr[...] = i_ref[...].astype(jnp.int32)            # per-tile widen
    lookup_accumulate(lambda c, j: o_ref[c, j], idx_scr, y_ref)

    @pl.when(v == n_v_tiles - 1)
    def _scale():
        y_ref[...] *= s_ref[...].astype(jnp.float32)


def oc_lookup_pallas(
    O: jax.Array,        # (C, V, M, k) fp32, v-major, M % 8 == 0
    I: jax.Array,        # (C, V, N) uint8 (n<=8) or int32 (n>8)
    scale: jax.Array,    # (1, N) fp32
    *,
    block_v: int = 32,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns y (M, N) fp32. V % block_v == 0, N % block_n == 0 and
    M % 8 == 0 (the wrapper pads)."""
    C, V, M, k = O.shape
    C2, V2, N = I.shape
    assert (C, V) == (C2, V2), ((C, V), (C2, V2))
    assert V % block_v == 0 and N % block_n == 0, (V, block_v, N, block_n)
    assert M % SUBLANES == 0, M
    n_v_tiles = V // block_v
    mt = SUBLANES
    grid = (M // mt, N // block_n, n_v_tiles)
    resident = 2 * (4 * C * block_v * mt * k + C * block_v * block_n
                    * I.dtype.itemsize) + 4 * C * block_v * block_n

    kernel = functools.partial(_oc_lookup_kernel, n_v_tiles=n_v_tiles)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((C, block_v, mt, k), lambda m, n, v: (0, v, m, 0)),
            pl.BlockSpec((C, block_v, block_n), lambda m, n, v: (0, v, n)),
            pl.BlockSpec((1, block_n), lambda m, n, v: (0, n)),
        ],
        out_specs=pl.BlockSpec((mt, block_n), lambda m, n, v: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, block_v, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=vmem_limit(resident)),
        interpret=interpret,
    )(O, I, scale)
