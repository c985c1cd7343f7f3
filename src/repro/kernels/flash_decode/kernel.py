"""Pallas TPU kernel for single-token decode attention over a long KV
cache (flash-decoding): the cache is streamed HBM->VMEM in S-blocks with
an online-softmax accumulator held in VMEM — the second perf-critical
decode op next to the EVA matmul (at 32k context the cache read dominates
the decode step; see EXPERIMENTS.md §Roofline).

GQA layout: q (B, Hk, g, hd), cache viewed as (B, S, Hk*hd), groups
g = H // Hk. Grid: (B, Hk, num_s_blocks) with S innermost; per step the
kernel DMAs one (block_s, hd) column block of one kv head (a strided
copy out of the (S, Hk*hd) view — no cache relayout), scores it against
that head's g queries and folds it into the (m, l, acc) online-softmax
state in VMEM scratch. Valid lengths ride in SMEM as a scalar-prefetch
operand.

``_flash_decode_kvq_kernel`` is the vector-quantized variant — the EVA
trick in reverse. The cache stores uint8 codebook indices, never fp K/V:
the wrapper dots the query against the K codebook ONCE per step (a
(B, Hk, g, R*G, E) table whose cost is independent of S), the kernel
streams the uint8 index blocks, gathers per-token scores from that
table, runs the same online softmax, and reconstructs V contributions
from the V codebook rows after softmax weighting. HBM traffic per step
is the compressed cache (R*G bytes/token/head + one scale) instead of
2*hd fp values. Both gathers are in-register lane gathers
(kernels/gather.py): index planes arrive token-minor, (R*G, block_s) per
head, so tokens sit on lanes and each sublane row gathers from its own
256-entry table.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import lane_gather, lane_width, row_group


def _online_softmax_step(s, s_blk, block_s, length, m_scr, l_scr):
    """Mask scores ``s`` (g, bs) past ``length``, fold them into the
    running max/denominator and return (p, corr) for the accumulator."""
    pos = s_blk * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, -1e30)
    m_prev = m_scr[...]                               # (g, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    m_scr[...] = m_new
    return p, corr


def _flash_decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, n_s_blocks: int,
                         block_s: int):
    b = pl.program_id(0)
    s_blk = pl.program_id(2)

    @pl.when(s_blk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                # (g, hd)
    k = k_ref[...].astype(jnp.float32)                # (bs, hd)
    v = v_ref[...].astype(jnp.float32)                # (bs, hd)
    scale = 1.0 / math.sqrt(q.shape[-1])

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32) * scale
    p, corr = _online_softmax_step(s, s_blk, block_s, len_ref[b],
                                   m_scr, l_scr)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(s_blk == n_s_blocks - 1)
    def _finalize():
        o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = o.astype(o_ref.dtype)


def _flash_decode_kvq_kernel(len_ref, qd_ref, kidx_ref, vidx_ref, ks_ref,
                             vs_ref, cbv_ref, o_ref, m_scr, l_scr, acc_scr,
                             s_scr, vh_scr, *, n_s_blocks: int,
                             block_s: int):
    b = pl.program_id(0)
    s_blk = pl.program_id(2)

    @pl.when(s_blk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    g, RG, E = qd_ref.shape                           # query/K-code dots
    R, vd = cbv_ref.shape[:2]                         # V codebooks
    G = RG // R
    bs = kidx_ref.shape[-1]
    w = lane_width(bs)
    rr = row_group(RG)
    rg = row_group(G)

    # scores: the query/K-codebook dots are precomputed in qd (already
    # 1/sqrt(hd)-scaled); per token gather-and-sum the R*G entries its
    # indices select (rows of kidx, tokens on lanes), then scale
    kidx = kidx_ref[...].astype(jnp.int32)            # (RG, bs)
    for gq in range(g):
        for t in range(bs // w):
            part = jnp.zeros((rr, w), jnp.float32)
            for r0 in range(0, RG, rr):
                part = part + lane_gather(qd_ref[gq, r0:r0 + rr, :],
                                          kidx[r0:r0 + rr, t * w:(t + 1) * w])
            s_scr[gq:gq + 1, t * w:(t + 1) * w] = part.sum(axis=0,
                                                           keepdims=True)
    s = s_scr[...] * ks_ref[...].astype(jnp.float32)  # (g, bs)
    p, corr = _online_softmax_step(s, s_blk, block_s, len_ref[b],
                                   m_scr, l_scr)
    # fold the per-token V scale into the softmax weights
    pw = p * vs_ref[...].astype(jnp.float32)

    # V reconstruction after softmax weighting, one channel e of each
    # code group at a time: row gi of vh is sum_r cb_v[r, e, idx[r*G+gi]]
    vidx = vidx_ref[...].astype(jnp.int32)            # (RG, bs)
    for e in range(vd):
        for gi0 in range(0, G, rg):
            for t in range(bs // w):
                piece = jnp.zeros((rg, w), jnp.float32)
                for r in range(R):
                    table = cbv_ref[r, e, :rg, :]   # (rg, E), rows equal
                    rows = vidx[r * G + gi0:r * G + gi0 + rg,
                                t * w:(t + 1) * w]
                    piece = piece + lane_gather(table, rows)
                vh_scr[gi0:gi0 + rg, t * w:(t + 1) * w] = piece
        acc_scr[e] = acc_scr[e] * corr + jax.lax.dot_general(
            pw, vh_scr[...], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)       # (g, G)

    @pl.when(s_blk == n_s_blocks - 1)
    def _finalize():
        inv = 1.0 / jnp.maximum(l_scr[...], 1e-30)    # (g, 1)
        for e in range(vd):
            o_ref[e] = (acc_scr[e] * inv).astype(o_ref.dtype)


def flash_decode_kvq_pallas(
    qd: jax.Array,       # (B, Hk, g, R*G, E) f32 query/K-codebook dots
    k_idx: jax.Array,    # (B, Hk, R*G, S) uint8, token-minor
    v_idx: jax.Array,    # (B, Hk, R*G, S) uint8
    k_s: jax.Array,      # (B, Hk, 1, S) f32
    v_s: jax.Array,      # (B, Hk, 1, S) f32
    cb_v: jax.Array,     # (Hk, R, vd, 8, E) V codebooks, entries on lanes,
                         # each row repeated over one sublane tile
    lengths: jax.Array,  # (B,) int32
    *,
    out_dtype,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, Hk, vd, g, G): channel e of code group gi of query
    head (hk, gq) at [b, hk, e, gq, gi] (the wrapper interleaves)."""
    B, Hk, g, RG, E = qd.shape
    S = k_idx.shape[-1]
    _, R, vd, rows, _ = cb_v.shape
    G = RG // R
    assert S % block_s == 0, (S, block_s)
    n_s_blocks = S // block_s

    kernel = functools.partial(_flash_decode_kvq_kernel,
                               n_s_blocks=n_s_blocks, block_s=block_s)
    idx_spec = pl.BlockSpec((None, None, RG, block_s),
                            lambda b, h, s, lens: (b, h, 0, s))
    sc_spec = pl.BlockSpec((None, None, 1, block_s),
                           lambda b, h, s, lens: (b, h, 0, s))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hk, n_s_blocks),
        in_specs=[
            pl.BlockSpec((None, None, g, RG, E),
                         lambda b, h, s, lens: (b, h, 0, 0, 0)),
            idx_spec, idx_spec, sc_spec, sc_spec,
            pl.BlockSpec((None, R, vd, rows, E),
                         lambda b, h, s, lens: (h, 0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, vd, g, G),
                               lambda b, h, s, lens: (b, h, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((vd, g, G), jnp.float32),
            pltpu.VMEM((g, block_s), jnp.float32),
            pltpu.VMEM((G, block_s), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, vd, g, G), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, qd, k_idx, v_idx, k_s, v_s, cb_v)


def flash_decode_pallas(
    q: jax.Array,        # (B, Hk, g, hd)
    k: jax.Array,        # (B, S, Hk*hd)
    v: jax.Array,        # (B, S, Hk*hd)
    lengths: jax.Array,  # (B,) int32 valid cache lengths
    *,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, Hk, g, hd)."""
    B, Hk, g, hd = q.shape
    S = k.shape[1]
    assert k.shape[2] == Hk * hd and S % block_s == 0, (k.shape, block_s)
    n_s_blocks = S // block_s

    kernel = functools.partial(_flash_decode_kernel,
                               n_s_blocks=n_s_blocks, block_s=block_s)
    q_spec = pl.BlockSpec((None, None, g, hd),
                          lambda b, h, s, lens: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((None, block_s, hd),
                           lambda b, h, s, lens: (b, s, h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hk, n_s_blocks),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k, v)
