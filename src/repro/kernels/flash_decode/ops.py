"""Jit'd wrappers for the flash-decode kernels (padding + dispatch) and
the KV-VQ decode-attention plan backends.

``flash_decode``/``flash_decode_paged`` serve fp caches. The KV-VQ
entry points (``flash_decode_kvq``/``flash_decode_kvq_paged``) consume
vector-quantized caches natively — uint8 codebook indices + per-(token,
head) scales + params-resident codebooks — and register two backends
with core/plan.py, in this order (the first that matches is planned):

  "kvq_flash_pallas" : the fused kernel — query/K-codebook dot table
                       computed once per step, indices streamed and
                       gathered in-kernel (impl="pallas", on the shapes
                       ``_kvq_pallas_admits`` accepts).
  "kvq_dequant_jnp"  : reconstruct the fp cache through core.vq.kv_decode
                       then run the masked-softmax oracle (always
                       eligible for kind="kvq_attn"; the parity anchor).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import plan as plan_mod
from repro.kernels.flash_decode.kernel import (flash_decode_kvq_pallas,
                                               flash_decode_pallas)
from repro.kernels.flash_decode.ref import (flash_decode_kvq_ref,
                                            flash_decode_ref)
from repro.kernels.gather import SUBLANES


@functools.partial(jax.jit, static_argnames=("block_s", "interpret", "use_pallas"))
def flash_decode(
    q: jax.Array,        # (B, H, hd) or (B, 1, H, hd)
    k: jax.Array,        # (B, S, Hk, hd)
    v: jax.Array,
    lengths: jax.Array,  # (B,)
    *,
    block_s: int = 512,
    interpret: bool = False,
    use_pallas: bool = True,
) -> jax.Array:
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    if not use_pallas:
        o = flash_decode_ref(q, k, v, lengths)
    else:
        B, S, Hk, hd = k.shape
        H = q.shape[1]
        bs = min(block_s, S)
        pad = (-S) % bs
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # (B, S, Hk*hd) is a free view; the kernel DMAs per-head columns
        o = flash_decode_pallas(
            q.reshape(B, Hk, H // Hk, hd), k.reshape(B, S + pad, Hk * hd),
            v.reshape(B, S + pad, Hk * hd), lengths.astype(jnp.int32),
            block_s=bs, interpret=interpret).reshape(B, H, hd)
    return o[:, None] if squeeze else o


@functools.partial(jax.jit, static_argnames=("block_s", "interpret", "use_pallas"))
def flash_decode_paged(
    q: jax.Array,            # (B, H, hd) or (B, 1, H, hd)
    k_arena: jax.Array,      # (NB, bs, Hk, hd) shared block arena
    v_arena: jax.Array,
    block_table: jax.Array,  # (B, W) physical block ids (NB == sentinel)
    lengths: jax.Array,      # (B,)
    *,
    block_s: int = 512,
    interpret: bool = False,
    use_pallas: bool = True,
) -> jax.Array:
    """Flash decode over a paged KV cache (serve/paging.py): gather the
    per-request contiguous view through the block table, then run the
    unchanged kernel. ``W * bs`` equals the contiguous cache's time
    length by construction, so outputs are token-identical to
    ``flash_decode`` over the contiguous cache. Sentinel block ids clamp
    to in-bounds garbage masked by ``lengths`` (``mode="clip"`` — the
    default fill mode would inject NaN that survives masking)."""
    B, W = block_table.shape
    bs = k_arena.shape[1]
    k = jnp.take(k_arena, block_table, axis=0, mode="clip").reshape(
        (B, W * bs) + k_arena.shape[2:])
    v = jnp.take(v_arena, block_table, axis=0, mode="clip").reshape(
        (B, W * bs) + v_arena.shape[2:])
    return flash_decode(q, k, v, lengths, block_s=block_s,
                        interpret=interpret, use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# KV-VQ decode attention
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("block_s", "interpret", "use_pallas"))
def flash_decode_kvq(
    q: jax.Array,        # (B, H, hd) or (B, 1, H, hd)
    k_idx: jax.Array,    # (B, S, Hk, R*G) uint8 codebook indices
    v_idx: jax.Array,    # (B, S, Hk, R*G) uint8
    k_s: jax.Array,      # (B, S, Hk) per-(token, head) scales
    v_s: jax.Array,      # (B, S, Hk)
    lengths: jax.Array,  # (B,)
    cb_k: jax.Array,     # (Hk, R, E, vd) K codebooks
    cb_v: jax.Array,     # (Hk, R, E, vd) V codebooks
    *,
    block_s: int = 512,
    interpret: bool = False,
    use_pallas: bool = True,
) -> jax.Array:
    """Decode attention directly over a vector-quantized KV cache.

    The EVA trick in reverse: the query is dotted against the K codebook
    ONCE per step (a (B, Hk, g, R*G, E) table — cost independent of S),
    the kernel gathers per-token scores from the uint8 indices, and V
    contributions are reconstructed from the V codebook after softmax
    weighting. ``use_pallas=False`` runs the dequantize oracle
    (``flash_decode_kvq_ref``) instead.

    Returns: attention output shaped like ``q`` (in q.dtype).
    """
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    if not use_pallas:
        o = flash_decode_kvq_ref(q, k_idx, v_idx, k_s, v_s, lengths,
                                 cb_k, cb_v)
        return o[:, None] if squeeze else o
    B, H, hd = q.shape
    Hk, R, E, vd = cb_k.shape
    G = hd // vd
    g = H // Hk
    S = k_idx.shape[1]
    qg = q.reshape(B, Hk, g, G, vd).astype(jnp.float32)
    qd = jnp.einsum("bkgcd,kred->bkgrce", qg, cb_k.astype(jnp.float32))
    qd = (qd / math.sqrt(hd)).reshape(B, Hk, g, R * G, E)
    bs = min(block_s, S)
    pad = (-S) % bs

    def tokens_minor(a):  # (B, S, Hk, ...) -> (B, Hk, ..., S + pad)
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a, 1, -1)

    o = flash_decode_kvq_pallas(
        qd, tokens_minor(k_idx), tokens_minor(v_idx),
        tokens_minor(k_s.astype(jnp.float32))[:, :, None],
        tokens_minor(v_s.astype(jnp.float32))[:, :, None],
        # V tables gather one row per sublane: pre-broadcast each
        # codebook column over a sublane tile (Hk, R, vd, 8, E)
        jnp.broadcast_to(
            jnp.swapaxes(cb_v.astype(jnp.float32), 2, 3)[:, :, :, None],
            (Hk, R, vd, SUBLANES, E)),
        lengths.astype(jnp.int32),
        out_dtype=q.dtype, block_s=bs, interpret=interpret)
    # (B, Hk, vd, g, G) -> (B, Hk*g, G*vd): interleave each group's channels
    o = jnp.transpose(o, (0, 1, 3, 4, 2)).reshape(B, H, hd)
    return o[:, None] if squeeze else o


@functools.partial(jax.jit,
                   static_argnames=("block_s", "interpret", "use_pallas"))
def flash_decode_kvq_paged(
    q: jax.Array,             # (B, H, hd) or (B, 1, H, hd)
    k_arena: jax.Array,       # (NB, bs, Hk, R*G) uint8 index arena
    v_arena: jax.Array,
    ks_arena: jax.Array,      # (NB, bs, Hk) scale arenas
    vs_arena: jax.Array,
    block_table: jax.Array,   # (B, W) physical block ids (NB == sentinel)
    lengths: jax.Array,       # (B,)
    cb_k: jax.Array,
    cb_v: jax.Array,
    *,
    block_s: int = 512,
    interpret: bool = False,
    use_pallas: bool = True,
) -> jax.Array:
    """KV-VQ flash decode over a paged index arena: gather the per-slot
    contiguous view (uint8 gathers — a fraction of the fp cache's
    traffic), then run ``flash_decode_kvq`` unchanged. Sentinel ids
    clamp to in-bounds garbage masked by ``lengths``."""
    B, W = block_table.shape
    bs = k_arena.shape[1]

    def gather(a):
        return jnp.take(a, block_table, axis=0, mode="clip").reshape(
            (B, W * bs) + a.shape[2:])

    return flash_decode_kvq(
        q, gather(k_arena), gather(v_arena), gather(ks_arena),
        gather(vs_arena), lengths, cb_k, cb_v,
        block_s=block_s, interpret=interpret, use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# Plan backends over kind="kvq_attn" sites
# ---------------------------------------------------------------------------


def _kvq_pallas_admits(spec: plan_mod.LinearSpec) -> bool:
    """Shapes on which the fused kernel is planned over the dequantize
    path. This is the inequality the planner's former CPU-rate cost
    guess reduced to (5 GMAC/s, 0.5 G adds/s, 10 GB/s, 50 us a launch),
    kept so that the choice does not change: no chip run has measured
    either side, and the KV-VQ cell is to settle it. Roughly, the
    kernel's per-step query/codebook table must be paid back by the
    cache positions it does not reconstruct."""
    B, S, Hk, W, E, hd = spec.M, spec.K, spec.C, spec.V, spec.k, spec.d
    H = spec.N // hd
    return B * (H * E * (hd + 2 * W)
                + S * (10 * W * (H - Hk) - 2 * hd * (H + 2 * Hk))) < 500_000


def _match_kvq_pallas(spec: plan_mod.LinearSpec,
                      policy: plan_mod.PlanPolicy) -> bool:
    return (spec.kind == "kvq_attn" and policy.impl == "pallas"
            and _kvq_pallas_admits(spec))


def _plan_kvq_pallas(spec: plan_mod.LinearSpec,
                     policy: plan_mod.PlanPolicy) -> plan_mod.MatmulPlan:
    interpret = policy.interpret

    def run(operands, _leaf):
        return flash_decode_kvq(*operands, interpret=interpret)

    return plan_mod.MatmulPlan("kvq_flash_pallas", spec, policy, (), run)


def _match_kvq_jnp(spec: plan_mod.LinearSpec,
                   policy: plan_mod.PlanPolicy) -> bool:
    return spec.kind == "kvq_attn"


def _plan_kvq_jnp(spec: plan_mod.LinearSpec,
                  policy: plan_mod.PlanPolicy) -> plan_mod.MatmulPlan:
    def run(operands, _leaf):
        return flash_decode_kvq(*operands, use_pallas=False)

    return plan_mod.MatmulPlan("kvq_dequant_jnp", spec, policy, (), run)


plan_mod.register_backend("kvq_flash_pallas", _match_kvq_pallas,
                          _plan_kvq_pallas)
plan_mod.register_backend("kvq_dequant_jnp", _match_kvq_jnp, _plan_kvq_jnp)
