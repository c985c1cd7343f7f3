"""Pallas TPU kernel for EVA Step 2: conflict-free output-codebook lookup
with add-only reduction (the paper's Epilogue Unit, Fig. 6).

  y[m, j] = scale[j] * sum_c sum_v O[c, m, v, I[c, v, j]]

TPU mapping of the paper's bank argument: the OC is laid out
token-major, (C, M, V, 2^n), so for token m the slab O[c, m, v0:v0+8]
is one (8-row, 2^n) VMEM tile whose sublanes are 8 v-rows of that token
and whose lanes are the table entries — "one bank per OC row". An index
register I[c, v0:v0+8, 128 columns] has the same v-rows on its
sublanes, so it gathers from every token's slab as it sits: one
in-register lane gather per 128-entry table tile (kernels/gather.py),
with the split of the indices into tile and in-tile bits done once per
register and shared by every token. The reduction is a pure add chain
over (c, v) — 8 sublanes of partial sums per token, summed once at the
end of the V sweep; the only multiply is the final per-channel scale,
exactly the paper's EU.

Grid: (num_m_tiles, num_n_tiles, num_v_tiles) with V innermost so the
(mt, bn) output block and its (mt, 8, bn) accumulator stay resident in
VMEM across the V accumulation (output-stationary, matching Fig. 4's
stationary output tile). A token tile is every row of the call when its
O tile fits the tile budget (the wrapper's tile model).

uint8 index-streaming contract: index tiles arrive in their storage
dtype (uint8 for n <= 8, int32 only for n > 8) and are widened to int32
per tile INSIDE the kernel (into a VMEM scratch the lookup reads), so
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

from repro.kernels.gather import (lookup_accumulate, row_group, vmem_limit,
                                  write_rows)


def _oc_lookup_kernel(o_ref, i_ref, s_ref, y_ref, idx_scr, acc_scr, *,
                      n_v_tiles: int):
    v = pl.program_id(2)

    @pl.when(v == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    idx_scr[...] = i_ref[...].astype(jnp.int32)            # per-tile widen
    g = acc_scr.shape[1]
    lookup_accumulate(lambda c, m, j: o_ref[c, m, pl.ds(j, g)],
                      idx_scr, acc_scr)

    @pl.when(v == n_v_tiles - 1)
    def _scale():
        write_rows(acc_scr, s_ref, y_ref)


def oc_lookup_pallas(
    O: jax.Array,        # (C, M, V, k) fp32, token-major
    I: jax.Array,        # (C, V, N) uint8 (n<=8) or int32 (n>8)
    scale: jax.Array,    # (1, N) fp32
    *,
    m_tile: int,
    block_v: int = 32,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns y (M, N) fp32. M % m_tile == 0, V % block_v == 0 and
    N % block_n == 0 (the wrapper pads)."""
    C, M, V, k = O.shape
    C2, V2, N = I.shape
    mt = m_tile
    assert (C, V) == (C2, V2), ((C, V), (C2, V2))
    assert M % mt == 0, (M, mt)
    assert V % block_v == 0 and N % block_n == 0, (V, block_v, N, block_n)
    n_v_tiles = V // block_v
    g = row_group(block_v)
    grid = (M // mt, N // block_n, n_v_tiles)
    resident = (2 * (4 * C * mt * block_v * k + C * block_v * block_n
                     * I.dtype.itemsize) + 4 * C * block_v * block_n
                + 4 * mt * g * block_n)

    kernel = functools.partial(_oc_lookup_kernel, n_v_tiles=n_v_tiles)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((C, mt, block_v, k), lambda m, n, v: (0, m, v, 0)),
            pl.BlockSpec((C, block_v, block_n), lambda m, n, v: (0, v, n)),
            pl.BlockSpec((1, block_n), lambda m, n, v: (0, n)),
        ],
        out_specs=pl.BlockSpec((mt, block_n), lambda m, n, v: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, block_v, block_n), jnp.int32),
                        pltpu.VMEM((mt, g, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=vmem_limit(resident)),
        interpret=interpret,
    )(O, I, scale)
