"""Table lookup in the form the TPU kernel compiler (Mosaic) accepts.

Mosaic lowers a gather only inside one vector register: a
``take_along_axis`` over the lane axis of an (8, 128) table with an
(8, 128) index array, each sublane row gathering from its own row. The
lookups of this repo read 2^n-entry tables (256 for the paper's n=8), so
a table spans 2^n / 128 lane tiles: ``lane_gather`` gathers from every
tile with the low index bits and keeps, per element, the tile the high
bits name. The selection is exact — no arithmetic touches the values.

In interpret mode the same code runs at any shape: index chunks narrower
than 128 lanes split the table into correspondingly narrower tiles, and
tables narrower than one chunk are zero-padded (their indices never
reach the pad).

The output-codebook epilogue that the fused and the split EVA kernels
share (``lookup_accumulate``) and their scoped-VMEM request
(``vmem_limit``) live here too.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8

# Mosaic's scoped-VMEM default is 16 MiB; kernels that keep more resident
# ask for what they use plus this headroom (v5e has 128 MiB of VMEM)
_VMEM_HEADROOM = 8 * 1024 * 1024


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


def lane_gather(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``out[r, j] = table[r, idx[r, j]]`` for table (R, k) and int32
    idx (R, w) with values in [0, k)."""
    rows, k = table.shape
    w = idx.shape[-1]
    if k % w:
        table = jnp.pad(table, ((0, 0), (0, w - k % w)))
        k = table.shape[-1]
    if k == w:
        return jnp.take_along_axis(table, idx, axis=1)
    if w & (w - 1) == 0:
        shift = w.bit_length() - 1
        lo, hi = idx & (w - 1), idx >> shift
    else:
        lo, hi = idx % w, idx // w
    out = jnp.take_along_axis(table[:, :w], lo, axis=1)
    for t in range(1, k // w):
        part = jnp.take_along_axis(table[:, t * w:(t + 1) * w], lo, axis=1)
        out = jnp.where(hi == t, part, out)
    return out


def lookup_accumulate(table_at: Callable, idx_scr, y_ref) -> None:
    """Epilogue of one (v-tile, n-tile) step: ``y_ref`` (mt, bn) +=
    sum over c and the tile's index rows j of ``table_at(c, j)`` (mt, k)
    gathered at ``idx_scr[c, j, :]``. ``idx_scr`` is the tile's widened
    (C, bv, bn) int32 index scratch."""
    C, bv, bn = idx_scr.shape
    mt = y_ref.shape[0]
    w = lane_width(bn)
    g = row_group(bv)

    def body(i, acc):
        j0 = pl.multiple_of(i * g, g)
        acc = list(acc)
        for c in range(C):
            rows = idx_scr[c, pl.ds(j0, g), :]             # (g, bn)
            for s in range(g):
                table = table_at(c, j0 + s)                 # (mt, k)
                for q in range(bn // w):
                    col = jnp.broadcast_to(rows[s:s + 1, q * w:(q + 1) * w],
                                           (mt, w))
                    acc[q] = acc[q] + lane_gather(table, col)
        return tuple(acc)

    zero = jnp.zeros((mt, w), jnp.float32)
    acc = jax.lax.fori_loop(0, bv // g, body, (zero,) * (bn // w))
    for q in range(bn // w):
        y_ref[:, q * w:(q + 1) * w] += acc[q]
