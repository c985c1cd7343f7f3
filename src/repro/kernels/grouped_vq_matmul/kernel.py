"""Grouped EVA kernel: the fused VQ-GEMM + output-codebook lookup of
``kernels/fused_vq_matmul`` over experts stacked on a leading axis, for
rows sorted by expert (``core/ops.ExpertRows``).

A grid step is one tile of EXPERT_TILE rows of one expert and one
v-tile. The tile's expert and the number of tiles that hold rows come
in by scalar prefetch, and the index maps read them: each step streams
only its own expert's uint8 indices (a (C, bv, N) tile: the whole N, so
an expert's indices are read once per tile of its rows), codebooks and
scales. It computes the output codebook of its rows for the v-tile on
the MXU and runs the lookup epilogue of the fused kernel
(``gather.lookup_accumulate``, looping over the 128-lane chunks) into a
(mt, 8, N) accumulator, scaled and written at the end of the V sweep.
An expert with no rows has no tile, so nothing of it is read; the
layout's spare tiles at the end map every block to the last real
step's and compute nothing, so they read nothing either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import (lookup_accumulate, row_group, vmem_limit,
                                  write_rows)


def _grouped_kernel(te_ref, nt_ref, x_ref, b_ref, i_ref, s_ref, y_ref,
                    idx_scr, oc_scr, acc_scr, *, n_v_tiles: int):
    t = pl.program_id(0)
    v = pl.program_id(1)

    @pl.when(t < nt_ref[0])
    def _tile():
        C, d, k = b_ref.shape
        mt, bv, _ = x_ref.shape
        x = x_ref[...].astype(jnp.float32).reshape(mt * bv, d)
        for c in range(C):
            oc_scr[c] = jax.lax.dot_general(
                x, b_ref[c].astype(jnp.float32), (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32).reshape(mt, bv, k)

        @pl.when(v == 0)
        def _init():
            acc_scr[...] = jnp.zeros_like(acc_scr)

        idx_scr[...] = i_ref[...].astype(jnp.int32)
        g = acc_scr.shape[1]
        lookup_accumulate(
            lambda c, m, j: oc_scr[c, m, pl.ds(pl.multiple_of(j, g), g)],
            idx_scr, acc_scr)

        @pl.when(v == n_v_tiles - 1)
        def _scale():
            write_rows(acc_scr, s_ref, y_ref)


def grouped_vq_matmul_pallas(
    x: jax.Array,            # (R, V, d) rows sorted by expert, token-major
    codebooks: jax.Array,    # (E, C, d, k)
    I: jax.Array,            # (E, C, V, N) uint8
    scale: jax.Array,        # (E, 1, N) fp32
    tile_expert: jax.Array,  # (R // m_tile,) int32
    tiles: jax.Array,        # (1,) int32: tiles that hold rows
    *,
    m_tile: int,
    block_v: int,
    interpret: bool = False,
) -> jax.Array:
    """Returns y (R, N) fp32. R % m_tile == 0 and V % block_v == 0."""
    R, V, d = x.shape
    E, C, d2, k = codebooks.shape
    N = I.shape[-1]
    mt, bv = m_tile, block_v
    assert d == d2 and I.shape[:3] == (E, C, V), (I.shape, E, C, V)
    assert R % mt == 0 and V % bv == 0, (R, mt, V, bv)
    nv = V // bv
    g = row_group(bv)

    def live(t, tl):
        return t < tl[0]

    def rows_at(t, v, te, tl):
        return (jnp.where(live(t, tl), t, tl[0] - 1),
                jnp.where(live(t, tl), v, nv - 1), 0)

    def idx_at(t, v, te, tl):
        return (te[t], 0, jnp.where(live(t, tl), v, nv - 1), 0)

    resident = (4 * C * mt * bv * k + 4 * mt * g * N + 4 * C * bv * N
                + 2 * C * bv * N * I.dtype.itemsize + 2 * 4 * mt * N)
    kernel = functools.partial(_grouped_kernel, n_v_tiles=nv)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R // mt, nv),
            in_specs=[
                pl.BlockSpec((mt, bv, d), rows_at),
                pl.BlockSpec((None, C, d, k),
                             lambda t, v, te, tl: (te[t], 0, 0, 0)),
                pl.BlockSpec((None, C, bv, N), idx_at),
                pl.BlockSpec((None, 1, N), lambda t, v, te, tl: (te[t], 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (mt, N), lambda t, v, te, tl: (rows_at(t, v, te, tl)[0], 0)),
            scratch_shapes=[pltpu.VMEM((C, bv, N), jnp.int32),
                            pltpu.VMEM((C, mt, bv, k), jnp.float32),
                            pltpu.VMEM((mt, g, N), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(resident)),
        interpret=interpret,
        name="grouped_vq_matmul",
    )(tile_expert, tiles, x, codebooks, I, scale)
