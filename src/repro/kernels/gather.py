"""Table lookup in the form the TPU kernel compiler (Mosaic) accepts.

Mosaic lowers a gather only inside one vector register: a
``take_along_axis`` over the lane axis of an (8, 128) table with an
(8, 128) index array, each sublane row gathering from its own row. The
lookups of this repo read 2^n-entry tables (256 for the paper's n=8), so
a table spans 2^n / 128 lane tiles: ``lane_gather`` gathers from every
tile with the low index bits and keeps, per element, the tile the high
bits name. The selection is exact — no arithmetic touches the values.
``lane_split`` (the index handling) and ``gather_split`` (the gathers
and the select) are its two halves, so that a lookup epilogue can split
one index register once and gather many tables at it.

In interpret mode the same code runs at any shape: index chunks narrower
than 128 lanes split the table into correspondingly narrower tiles, and
tables narrower than one chunk are zero-padded (their indices never
reach the pad).

``centroid_rows`` rebuilds a weight's rows from its indices and
codebooks with the same gather (the dequantizing kernels,
``dequant_gemv`` and ``vq_dequant``).

The output-codebook epilogue that the fused and the split EVA kernels
share (``lookup_accumulate`` and ``write_rows``: sublanes are 8 v-rows
of one token, one table per token), their token tile (``token_tile``)
and their scoped-VMEM request (``vmem_limit``) live here too.
"""
from __future__ import annotations

from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8

# Mosaic's scoped-VMEM default is 16 MiB; kernels that keep more resident
# ask for what they use plus this headroom (v5e has 128 MiB of VMEM)
_VMEM_HEADROOM = 8 * 1024 * 1024

# Tokens whose lookups share one index register's handling: their
# accumulators stay in vector registers (64 on a TPU core) through a
# v-tile's sweep
TOKEN_GROUP = 16

# 128-lane chunks of an index tile unrolled in the lookup (the fused
# kernel's 512-lane tiles); wider tiles loop over their chunks
UNROLLED_CHUNKS = 4


def vmem_limit(resident_bytes: int) -> int:
    """Scoped-VMEM request for a kernel keeping ``resident_bytes`` live."""
    return int(max(32 * 1024 * 1024, resident_bytes + _VMEM_HEADROOM))


def lane_width(n: int) -> int:
    """Index-chunk width for a block of ``n`` lanes: one vector register
    (128) when ``n`` tiles by it, else the whole (interpret-only) block."""
    return LANES if n % LANES == 0 else n


def row_group(n: int) -> int:
    """Rows handled per aligned group out of ``n``: a full sublane tile
    (8) when it divides ``n``, else the largest divisor of ``n`` below."""
    for g in range(min(SUBLANES, n), 0, -1):
        if n % g == 0:
            return g
    return 1


def lane_split(idx: jax.Array, k: int) -> Tuple[jax.Array, List[jax.Array]]:
    """Index handling of a lookup into a k-entry table, done once per
    index register and shared by every table gathered at it: the in-tile
    index ``lo`` and, for each table tile past the first, the mask of
    elements whose high bits name that tile."""
    w = idx.shape[-1]
    if k <= w:
        return idx, []
    if w & (w - 1) == 0:
        lo, hi = idx & (w - 1), idx >> (w.bit_length() - 1)
    else:
        lo, hi = idx % w, idx // w
    return lo, [hi == t for t in range(1, -(-k // w))]


def gather_split(table: jax.Array, lo: jax.Array,
                 masks: List[jax.Array]) -> jax.Array:
    """``out[r, j] = table[r, idx[r, j]]`` from ``lane_split(idx, k)``:
    one in-register gather per table tile, the tile picked by select."""
    w = lo.shape[-1]
    k = table.shape[-1]
    if k % w:
        table = jnp.pad(table, ((0, 0), (0, w - k % w)))
    out = jnp.take_along_axis(table[:, :w], lo, axis=1)
    for t, mask in enumerate(masks, start=1):
        part = jnp.take_along_axis(table[:, t * w:(t + 1) * w], lo, axis=1)
        out = jnp.where(mask, part, out)
    return out


def lane_gather(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``out[r, j] = table[r, idx[r, j]]`` for table (R, k) and int32
    idx (R, w) with values in [0, k)."""
    return gather_split(table, *lane_split(idx, table.shape[-1]))


def centroid_rows(tables: Sequence[jax.Array],
                  idx_rows: Sequence[jax.Array]) -> jax.Array:
    """The d weight rows of one v-row, rebuilt from its indices:
    ``out[e, j] = sum over c of tables[c][e, idx_rows[c][0, j]]``.

    ``tables[c]`` is codebook c as a (d, 2^n) table (sublane e holds
    coordinate e of every centroid) and ``idx_rows[c]`` the v-row's
    (1, w) int32 index row of codebook c, broadcast over the d sublanes
    so that one lane gather yields coordinate e of column j's centroid
    in sublane e. The sum over the codebooks is taken in the tables'
    dtype, from zero, in codebook order."""
    d = tables[0].shape[0]
    w = idx_rows[0].shape[-1]
    out = jnp.zeros((d, w), tables[0].dtype)
    for table, row in zip(tables, idx_rows):
        out = out + lane_gather(table, jnp.broadcast_to(row, (d, w)))
    return out


def token_tile(M: int, per_token_bytes: int, budget: int) -> int:
    """Token rows per grid step for a kernel holding ``per_token_bytes``
    of VMEM per row: all M when they fit ``budget``; else, of the tiles
    of a multiple of 8 rows (a sublane tile of the output block) that
    fit, the one that pads M least, the taller on a tie; 0 when not even
    8 rows fit."""
    if M * per_token_bytes <= budget:
        return M
    cap = budget // per_token_bytes
    return min(range(SUBLANES, cap + 1, SUBLANES),
               key=lambda mt: (-(-M // mt) * mt, -mt), default=0)


def _accumulate_cols(table_at: Callable, idx_scr, acc_scr, m0, size: int,
                     cols) -> None:
    """One lane chunk ``cols`` of the lookup for tokens m0..m0+size:
    the tile's v-groups in an unrolled loop, the accumulators carried in
    registers and stored once."""
    C, bv, _ = idx_scr.shape
    g = acc_scr.shape[1]

    def body(i, acc):
        j0 = pl.multiple_of(i * g, g)
        acc = list(acc)
        for c in range(C):
            tables = [table_at(c, m0 + t, j0) for t in range(size)]
            lo, masks = lane_split(idx_scr[c, pl.ds(j0, g), cols],
                                   tables[0].shape[-1])
            for t in range(size):
                acc[t] = acc[t] + gather_split(tables[t], lo, masks)
        return tuple(acc)

    # unrolled, so that the scheduler overlaps one v-group's gathers with
    # the next one's loads and index handling
    acc = jax.lax.fori_loop(
        0, bv // g, body,
        tuple(acc_scr[m0 + t, :, cols] for t in range(size)), unroll=True)
    for t in range(size):
        acc_scr[m0 + t, :, cols] = acc[t]


def lookup_accumulate(table_at: Callable, idx_scr, acc_scr) -> None:
    """Epilogue of one (v-tile, n-tile) step of the EVA lookup.

    ``idx_scr`` is the tile's widened (C, bv, bn) int32 index scratch and
    ``acc_scr`` the (mt, g, bn) f32 accumulator, g = row_group(bv).
    ``table_at(c, m, j0)`` is the (g, k) output-codebook slab of token m
    for the tile's v-rows j0..j0+g: its sublane r is the table of v-row
    j0 + r. So an index register idx_scr[c, j0:j0+g, chunk] gathers from
    the slab as it stands, with no broadcast, and its ``lane_split`` is
    done once and shared by every token: for each token a lookup is the
    gathers, the select and an add into that token's accumulator, whose
    sublanes hold partial sums over v (summed once, at the end of the V
    sweep, by the caller). Up to UNROLLED_CHUNKS 128-lane chunks of an
    index tile are unrolled; a wider tile (a grouped kernel's spans the
    whole N) loops over them, which keeps the kernel's code small."""
    bn = idx_scr.shape[-1]
    mt = acc_scr.shape[0]
    w = lane_width(bn)

    def token_group(m0, size):
        if bn // w > UNROLLED_CHUNKS:
            def chunk(q, carry):
                _accumulate_cols(table_at, idx_scr, acc_scr, m0, size,
                                 pl.ds(pl.multiple_of(q * w, w), w))
                return carry
            jax.lax.fori_loop(0, bn // w, chunk, 0)
            return
        for q in range(bn // w):
            _accumulate_cols(table_at, idx_scr, acc_scr, m0, size,
                             slice(q * w, (q + 1) * w))

    full, rest = divmod(mt, TOKEN_GROUP)
    if full == 1:
        token_group(0, TOKEN_GROUP)
    elif full:
        def group(i, carry):
            token_group(pl.multiple_of(i * TOKEN_GROUP, TOKEN_GROUP),
                        TOKEN_GROUP)
            return carry
        jax.lax.fori_loop(0, full, group, 0)
    if rest:
        token_group(full * TOKEN_GROUP, rest)


def write_rows(acc_scr, s_ref, y_ref) -> None:
    """y[m] = scale * (sum over the sublanes of token m's accumulator)."""
    scale = s_ref[...].astype(jnp.float32)
    for m in range(y_ref.shape[0]):
        y_ref[pl.ds(m, 1), :] = jnp.sum(acc_scr[m], axis=0,
                                        keepdims=True) * scale
