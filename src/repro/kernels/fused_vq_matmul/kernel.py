"""Flagship fused EVA kernel: VQ-GEMM + conflict-free OC lookup in one
pallas_call, with the output codebook resident in VMEM scratch.

This is the TPU realization of the paper's architecture (Fig. 3(c)/Fig. 4):

  * the weight codebook B (C·d·2^n fp32 ≈ 16-64 KB) is fully VMEM-resident
    (paper: 16 KB WC SRAM),
  * the output codebook O (C, V, M, 2^n) is computed ONCE per token tile
    on the MXU during the first N-tile sweep and kept in VMEM scratch
    (paper: 192 KB OC SRAM, "output and WC remain stationary on-chip"),
  * the weight-index matrix I is streamed HBM->VMEM in (bv, bn) tiles
    (paper: "WI is streamed into the chip"),
  * the output tile (8, bn) is accumulated output-stationary across the V
    sweep with add-only reduction + one final per-channel scale (paper's
    Epilogue Unit),
  * O never round-trips to HBM — the GEMM->EU handoff of Fig. 7(b).

uint8 index-streaming contract: I tiles arrive in their STORAGE dtype —
uint8 for n <= 8 (int32 only when n > 8) — and are upcast to int32
per-tile inside the kernel, after the HBM->VMEM copy, into a VMEM
scratch the lookup loop reads row by row. Callers must NOT
pre-widen the index matrix: a pre-call `astype(int32)` would stream 4x
the bytes the paper's q-bits/weight bandwidth model assumes (32 vs n
bits per index) and quadruple the VMEM index-tile footprint.

Grid: (num_m_tiles, num_n_tiles, num_v_tiles), V innermost; token rows
come in tiles of 8 (one sublane group; the wrapper pads M). During the
n==0 sweep of an m-tile each v-step additionally computes its OC slab
into scratch; later n-tiles reuse it. The scratch is v-major,
(C, V, 8, 2^n), so each (c, v) table is one VMEM tile and the epilogue
is the oc_lookup kernel's in-register lane gather (kernels/gather.py,
gather.lookup_accumulate). For a grouped projection family
([Wq|Wk|Wv] or [W_gate|W_up] sharing one codebook set, core/vq.py) the N
sweep is simply wider: the same VMEM-resident OC scratch serves every
member's n-tiles, amortizing the VQ-GEMM stage g-fold instead of
recomputing it per projection. HBM traffic per m-tile is therefore: x
once, I once (q bits/weight), y once — the paper's bandwidth claim
(d-fold reduction vs centroid streaming, 8/16-fold vs bf16 weights at
q=2).

VMEM budget: scratch is C*V*mt*2^n fp32 (mt = 8 token rows), e.g.
C=2, V=1152 (K=9216), n=8 -> 18.9 MB; the kernel asks Mosaic for that
much scoped VMEM plus headroom (v5e has 128 MiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import SUBLANES, lookup_accumulate, vmem_limit


def _fused_kernel(
    x_ref, b_ref, i_ref, s_ref, y_ref, o_scr, idx_scr,
    *, n_v_tiles: int, block_v: int,
):
    n = pl.program_id(1)
    v = pl.program_id(2)
    C, d, k = b_ref.shape
    mt = y_ref.shape[0]

    # ---- VQ-GEMM stage: fill this v-slab of the OC once (first N sweep) --
    @pl.when(n == 0)
    def _compute_oc():
        x = x_ref[...].astype(jnp.float32).reshape(block_v * mt, d)
        for c in range(C):  # C is tiny and static — unrolled
            o_c = jax.lax.dot_general(
                x, b_ref[c].astype(jnp.float32), (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )                                            # (bv*mt, k)
            o_scr[c, pl.ds(v * block_v, block_v)] = o_c.reshape(block_v, mt, k)

    # ---- Epilogue stage: conflict-free lookup + add-only reduction -------
    @pl.when(v == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    # per-tile upcast of the streamed uint8 (or int32 for n>8) index tile
    idx_scr[...] = i_ref[...].astype(jnp.int32)
    v0 = v * block_v
    lookup_accumulate(lambda c, j: o_scr[c, v0 + j], idx_scr, y_ref)

    @pl.when(v == n_v_tiles - 1)
    def _scale():
        y_ref[...] *= s_ref[...].astype(jnp.float32)


def fused_vq_matmul_pallas(
    x: jax.Array,          # (V, M, d) v-major activations, M % 8 == 0
    codebooks: jax.Array,  # (C, d, k)
    I: jax.Array,          # (C, V, N) uint8 (n<=8) or int32 (n>8)
    scale: jax.Array,      # (1, N) fp32
    *,
    block_v: int = 32,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns y (M, N) fp32. V % block_v == 0, N % block_n == 0 and
    M % 8 == 0 (the wrapper pads)."""
    V, M, d = x.shape
    C, d2, k = codebooks.shape
    N = I.shape[-1]
    assert d == d2 and I.shape[:2] == (C, V)
    assert V % block_v == 0 and N % block_n == 0, (V, block_v, N, block_n)
    assert M % SUBLANES == 0, M
    mt = SUBLANES
    n_v_tiles = V // block_v
    grid = (M // mt, N // block_n, n_v_tiles)
    resident = (4 * C * V * mt * k + 4 * C * block_v * block_n
                + 2 * C * block_v * block_n * I.dtype.itemsize)

    kernel = functools.partial(_fused_kernel, n_v_tiles=n_v_tiles,
                               block_v=block_v)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_v, mt, d), lambda m, n, v: (v, m, 0)),
            pl.BlockSpec((C, d, k), lambda m, n, v: (0, 0, 0)),
            pl.BlockSpec((C, block_v, block_n), lambda m, n, v: (0, v, n)),
            pl.BlockSpec((1, block_n), lambda m, n, v: (0, n)),
        ],
        out_specs=pl.BlockSpec((mt, block_n), lambda m, n, v: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, V, mt, k), jnp.float32),
                        pltpu.VMEM((C, block_v, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=vmem_limit(resident)),
        interpret=interpret,
    )(x, codebooks, I, scale)
