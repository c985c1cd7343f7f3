"""Flagship fused EVA kernel: VQ-GEMM + conflict-free OC lookup in one
pallas_call, with the output codebook resident in VMEM scratch.

This is the TPU realization of the paper's architecture (Fig. 3(c)/Fig. 4):

  * the weight codebook B (C·d·2^n fp32 ≈ 16-64 KB) is fully VMEM-resident
    (paper: 16 KB WC SRAM),
  * the output codebook O (C, mt, V, 2^n) is computed ONCE per token tile
    on the MXU during the first N-tile sweep and kept in VMEM scratch
    (paper: 192 KB OC SRAM, "output and WC remain stationary on-chip"),
  * the weight-index matrix I is streamed HBM->VMEM in (bv, bn) tiles
    (paper: "WI is streamed into the chip"),
  * the output tile (mt, bn) is accumulated output-stationary across the
    V sweep with add-only reduction + one final per-channel scale
    (paper's Epilogue Unit),
  * O never round-trips to HBM — the GEMM->EU handoff of Fig. 7(b).

uint8 index-streaming contract: I tiles arrive in their STORAGE dtype —
uint8 for n <= 8 (int32 only when n > 8) — and are upcast to int32
per-tile inside the kernel, after the HBM->VMEM copy, into a VMEM
scratch the lookup loop reads register by register. Callers must NOT
pre-widen the index matrix: a pre-call `astype(int32)` would stream 4x
the bytes the paper's q-bits/weight bandwidth model assumes (32 vs n
bits per index) and quadruple the VMEM index-tile footprint.

Grid: (num_m_tiles, num_n_tiles, num_v_tiles), V innermost. A token
tile is every row of the call when its OC fits the VMEM budget (the
wrapper's tile model; a decode step is one tile), so each index tile is
streamed, widened and split once for all rows. During the n==0 sweep of
a token tile each v-step additionally computes its OC slab into
scratch; later n-tiles reuse it. The scratch is token-major,
(C, mt, V, 2^n): for token m the slab O[c, m, v0:v0+8] has the table of
v-row v0+r on sublane r, the layout of an index register
I[c, v0:v0+8, 128 columns]. So the epilogue (kernels/gather.py,
gather.lookup_accumulate) gathers every token's slab at the index
register as it sits, with its low-bits/high-half split done once and
shared by every token; each token accumulates 8 sublanes of partial sums
over v in a (mt, 8, bn) scratch, summed and scaled once at the end of
the V sweep (gather.write_rows). For a grouped projection family
([Wq|Wk|Wv] or [W_gate|W_up] sharing one codebook set, core/vq.py) the N
sweep is simply wider: the same VMEM-resident OC scratch serves every
member's n-tiles, amortizing the VQ-GEMM stage g-fold instead of
recomputing it per projection. HBM traffic per token tile is therefore:
x once, I once (q bits/weight), y once — the paper's bandwidth claim
(d-fold reduction vs centroid streaming, 8/16-fold vs bf16 weights at
q=2).

VMEM budget: scratch is C*mt*V*2^n fp32, e.g. C=2, mt=16, V=1152
(K=9216), n=8 -> 37.7 MB; the kernel asks Mosaic for that much scoped
VMEM plus headroom (v5e has 128 MiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import (LANES, lookup_accumulate, row_group,
                                  vmem_limit, write_rows)


def x_stage_bytes(block_v: int, k: int) -> int:
    """VMEM per token row of the VQ-GEMM stage besides the OC scratch:
    the double-buffered (bv, d) activation block and its (bv, d) reshape,
    each padded to 128 lanes, and the (bv, 2^n) f32 product."""
    return 4 * block_v * (3 * LANES + k)


def _fused_kernel(
    x_ref, b_ref, i_ref, s_ref, y_ref, o_scr, idx_scr, acc_scr,
    *, n_v_tiles: int, block_v: int,
):
    n = pl.program_id(1)
    v = pl.program_id(2)
    C, d, k = b_ref.shape
    mt = y_ref.shape[0]
    v0 = pl.multiple_of(v * block_v, block_v)

    # ---- VQ-GEMM stage: fill this v-slab of the OC once (first N sweep) --
    @pl.when(n == 0)
    def _compute_oc():
        x = x_ref[...].astype(jnp.float32).reshape(mt * block_v, d)
        for c in range(C):  # C is tiny and static — unrolled
            o_c = jax.lax.dot_general(
                x, b_ref[c].astype(jnp.float32), (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )                                            # (mt*bv, k)
            o_scr[c, :, pl.ds(v0, block_v)] = o_c.reshape(mt, block_v, k)

    # ---- Epilogue stage: conflict-free lookup + add-only reduction -------
    @pl.when(v == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # per-tile upcast of the streamed uint8 (or int32 for n>8) index tile
    idx_scr[...] = i_ref[...].astype(jnp.int32)
    g = acc_scr.shape[1]
    lookup_accumulate(
        lambda c, m, j: o_scr[c, m, pl.ds(pl.multiple_of(v0 + j, g), g)],
        idx_scr, acc_scr)

    @pl.when(v == n_v_tiles - 1)
    def _scale():
        write_rows(acc_scr, s_ref, y_ref)


def fused_vq_matmul_pallas(
    x: jax.Array,          # (M, V, d) token-major activations
    codebooks: jax.Array,  # (C, d, k)
    I: jax.Array,          # (C, V, N) uint8 (n<=8) or int32 (n>8)
    scale: jax.Array,      # (1, N) fp32
    *,
    m_tile: int,
    block_v: int = 32,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns y (M, N) fp32. M % m_tile == 0, V % block_v == 0 and
    N % block_n == 0 (the wrapper pads)."""
    M, V, d = x.shape
    C, d2, k = codebooks.shape
    N = I.shape[-1]
    mt = m_tile
    assert d == d2 and I.shape[:2] == (C, V)
    assert M % mt == 0, (M, mt)
    assert V % block_v == 0 and N % block_n == 0, (V, block_v, N, block_n)
    n_v_tiles = V // block_v
    g = row_group(block_v)
    grid = (M // mt, N // block_n, n_v_tiles)
    resident = (4 * C * mt * V * k + 4 * mt * g * block_n
                + mt * x_stage_bytes(block_v, k) + 4 * C * block_v * block_n
                + 2 * C * block_v * block_n * I.dtype.itemsize)

    kernel = functools.partial(_fused_kernel, n_v_tiles=n_v_tiles,
                               block_v=block_v)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((mt, block_v, d), lambda m, n, v: (m, v, 0)),
            pl.BlockSpec((C, d, k), lambda m, n, v: (0, 0, 0)),
            pl.BlockSpec((C, block_v, block_n), lambda m, n, v: (0, v, n)),
            pl.BlockSpec((1, block_n), lambda m, n, v: (0, n)),
        ],
        out_specs=pl.BlockSpec((mt, block_n), lambda m, n, v: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, mt, V, k), jnp.float32),
                        pltpu.VMEM((C, block_v, block_n), jnp.int32),
                        pltpu.VMEM((mt, g, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=vmem_limit(resident)),
        interpret=interpret,
    )(x, codebooks, I, scale)
