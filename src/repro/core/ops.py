"""Core matmul formulations for EVA.

Four execution paths, all algebraically computing ``y = x @ W_hat``:

  fp_matmul       : dense high-precision matmul (the FP16/BF16 baseline).
  int8_matmul     : int8 x int8 -> int32 GEMM (the paper's prefill path).
  dequant_matmul  : conventional VQ — reconstruct W_hat from (I, B, scale)
                    then GEMV/GEMM (the paper's Fig. 1(b) baseline with all
                    its memory traffic).
  eva_matmul      : the paper's contribution — VQ-GEMM (O = X·B) followed by
                    the conflict-free output-codebook lookup + add-only
                    reduction epilogue (Fig. 1(c)).

Formulation *selection* lives in `core/plan.py`: a frozen LinearSpec +
PlanPolicy resolve through an LRU-cached Planner to a MatmulPlan carrying
the chosen backend and every resolved number. This module keeps

  * the executable formulations themselves (`eva_epilogue_exec` runs one
    resolved jnp epilogue; the Pallas kernels live under `kernels/`),
  * the epilogue cost models (`select_epilogue` + the auto block sizers)
    that the jnp EVA backend registrations consult, and
  * `eva_matmul` / `vq_matmul` as thin convenience wrappers over
    `Planner.plan(...).execute(...)`. The PR-3 deprecation cycle is
    over: the legacy `flat_gather=` spelling is gone and passing None
    for `block_v` raises (use epilogue="direct" / block_v="auto").

The four jnp epilogue formulations (direct / flat / v-blocked gather /
v-blocked reconstruct-GEMM) are algebraically identical and chosen per
shape from explicit gather-work and cache-footprint cost models, so
"auto" callers stay >= 1x vs the dequant baseline across the whole M
sweep (the PR-1 batched-decode regression).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.vq import VQWeight

# Default V-tile for the blocked epilogue. Mirrors the paper's v=32 tile
# height (Tbl. II); on TPU this bounds the gathered intermediate to
# (C, M, 32, N_tile) in VMEM.
DEFAULT_BLOCK_V = 32


# ---------------------------------------------------------------------------
# Epilogue selection
#
# The jnp EVA epilogue has four formulations, all algebraically computing
#   y[m, j] = s[j] * sum_c sum_v O[c, m, v, I[c, v, j]]:
#
#   direct  : 4-D take_along_axis over the full O; XLA fuses gather into
#             the reduction. Gather work is C*M*V*N elements — the win
#             of the M=1 decode regime, where it is far below the
#             reconstruction cost of any weight-materializing path.
#   flat    : single-axis gather with precomputed flat indices; GSPMD
#             partitions 1-D gathers with a replicated operand locally
#             (the SPMD-friendly variant), same work as direct.
#   blocked : lax.scan over V-tiles of height block_v; the live gathered
#             intermediate shrinks from (C, M, V, N) to (C, M, block_v, N)
#             per step — the memory-constrained gather variant (mirrors
#             the paper's v=32 tiling).
#   recon   : v-blocked reconstruct-and-GEMM. Rebuilds W_hat in
#             (block_v*d, N) slabs from the centroid tables (C*V*N*d
#             gathered elements, independent of M) and accumulates
#             x_slab @ w_slab on the MXU/BLAS. Algebraically the dequant
#             formulation, but slab-tiled so the reconstructed weights
#             stay cache-resident instead of materializing (K, N) —
#             measured ~3.5-4x faster than dequant_matmul at M in
#             {8, 32} where it replaces the gather epilogues entirely.
#
# select_epilogue() picks among them from two explicit cost models —
# gather work (C*M*V*N vs the C*V*N*d reconstruction gathers) and the
# cache footprint of the gathered intermediate — called ONLY from the
# jnp EVA backend registrations in core/plan.py, so callers (linear ->
# RunConfig plan_policy epilogue="auto") never hand-tune block_v per
# shape. Measured regime table (K=N=4096, C=2, this CI host, min-of-7):
#
#     M   direct    flat  blocked(best)  recon(best)  dequant
#      1    9 ms   10 ms      43 ms        ~65 ms      259 ms
#      8  193 ms  201 ms     113 ms         63 ms      247 ms
#     32  790 ms  852 ms     417 ms         72 ms      260 ms
#
# i.e. direct wins while gather work < reconstruction work (M < d) and
# recon wins beyond it; the v-blocked gather only leads the gather
# family when the direct intermediate spills the cache budget at M < d
# (large-N mlp shapes). This is what fixed the batch-32 CPU regression
# (EVA < 1x vs dequant with the old always-direct default).
# ---------------------------------------------------------------------------

EPILOGUES = ("direct", "flat", "blocked", "recon")

# Working-set threshold for the un-blocked gather epilogues: the direct
# gather's intermediate is (C, M, V, N) fp32 on top of the O operand
# (C, M, V, 2^n); once that footprint is several multiples of the LLC the
# gather turns DRAM-thrash-bound and the v-blocked scan wins (measured:
# direct still led at a 71 MB footprint (M=4, K=N=4096) but lost ~2x at
# 184 MB (M=4, N=11008); the threshold sits between).
EPILOGUE_CACHE_BYTES = 96 * 1024 * 1024

# Cache target for the live slab of ONE v-block of the blocked-gather
# scan ((C, M, bv, N + 2^n) fp32) — distinct from the spill threshold
# above: a block must be comfortably cache-resident, not merely below
# the thrash point (measured best bv=64 at M=4, N=11008 -> ~24 MB).
EPILOGUE_SLAB_BYTES = 24 * 1024 * 1024

# Cache target for one reconstructed weight slab (block_v*d, N) fp32 of
# the recon epilogue (block_v=128 at N=4096 -> 16 MB, the measured
# sweet spot across M in {8, 32, 64}).
RECON_SLAB_BYTES = 16 * 1024 * 1024

# Floor for auto-sized v-blocks: below this the scan's per-step overhead
# dominates.
_MIN_BLOCK_V = 8

# Shared VMEM budgets for the Pallas kernels' tile models. The fused
# kernel's OC scratch holds C*mt*V_pad*2^n fp32 (one token tile: every
# row of the call, or as many as fit) for the whole sweep; when not even
# 8 rows fit this budget (of the 128 MiB VMEM of a v5e core) the plan
# leaves the shape to the split backend. The gather tile bounds
# each kernel's per-step streamed slab. The per-kernel tile *functions*
# live with their wrappers in kernels/*/ops.py — only the budgets are
# shared.
FUSED_OC_SCRATCH_BYTES = 96 * 1024 * 1024
FUSED_GATHER_TILE_BYTES = 2 * 1024 * 1024


def epilogue_gather_bytes(M: int, V: int, N: int, C: int, k: int = 256) -> int:
    """Cache footprint of one un-blocked epilogue pass: the gathered
    intermediate (C, M, V, N) fp32 plus the O operand (C, M, V, k) fp32."""
    return 4 * C * M * V * (N + k)


def _pow2_floor(x: int) -> int:
    return 1 << (int(x).bit_length() - 1)


def auto_block_v(M: int, V: int, N: int, C: int, k: int = 256,
                 *, slab_bytes: Optional[int] = None) -> int:
    """Largest v-block whose live gathered slab (C, M, bv, N+k) fp32 fits
    the slab budget, clamped to [_MIN_BLOCK_V, V] and rounded down to a
    power of two (tiling-friendly; the scan pads the remainder)."""
    budget = slab_bytes or EPILOGUE_SLAB_BYTES
    per_v = 4 * C * M * (N + k)
    bv = max(_MIN_BLOCK_V, budget // max(per_v, 1))
    bv = min(bv, V)
    return max(_MIN_BLOCK_V, _pow2_floor(bv))


def auto_recon_block_v(V: int, N: int, d: int) -> int:
    """v-block for the recon epilogue: size the reconstructed (bv*d, N)
    fp32 slab to RECON_SLAB_BYTES, clamped to [32, V], power of two."""
    bv = max(32, RECON_SLAB_BYTES // max(4 * d * N, 1))
    bv = min(bv, V)
    return max(1, _pow2_floor(bv))


def select_epilogue(
    M: int, V: int, N: int, C: int = 2, k: int = 256, d: int = 8,
    *,
    cache_bytes: Optional[int] = None,
    distributed: bool = False,
) -> Tuple[str, Optional[int]]:
    """Pick the jnp epilogue for an (M, K=V*d) x (K, N) EVA matmul.

    Returns (epilogue, block_v or None), epilogue in EPILOGUES.

      * distributed=True -> ("flat", None): under pjit the 1-D gather
        keeps indices V/N-sharded where the 4-D take_along_axis (and the
        V-block scans) force index all-gathers.
      * M < d (gather work C*M*V*N below the C*V*N*d reconstruction
        gathers) -> gather regime, the paper's memory-bound decode:
        ("direct", None) while the gathered intermediate fits
        EPILOGUE_CACHE_BYTES, else ("blocked", bv) with the live slab
        (C, M, bv, N + 2^n) sized to the budget.
      * M >= d -> ("recon", bv): batched decode is reconstruction-
        bound; the slab-tiled reconstruct-and-GEMM does the minimal
        C*V*N*d gathers once and rides BLAS for the M axis. This is the
        regime where the old always-direct default regressed below the
        dequant baseline (batch 32 on the CPU).
    """
    if distributed:
        return "flat", None
    if M >= d:
        return "recon", auto_recon_block_v(V, N, d)
    budget = cache_bytes or EPILOGUE_CACHE_BYTES
    if epilogue_gather_bytes(M, V, N, C, k) <= budget:
        return "direct", None
    bv = auto_block_v(M, V, N, C, k)
    if bv >= V:  # one block == direct, skip the scan machinery
        return "direct", None
    return "blocked", bv


def _in_mesh_context() -> bool:
    """True when tracing under an active mesh context (pjit / shard_map):
    the auto selection then prefers the SPMD-friendly flat epilogue — the
    V-block scans reshape the sharded V axis and the 4-D take_along_axis
    reshards its 3-tuple gather indices, both forcing collectives.

    Uses the same private thread_resources accessor as models/common.py's
    _maybe_constrain (no public ambient-mesh API on this jax); if a jax
    upgrade moves it, both degrade together to the
    single-host behavior and distributed callers should set
    PlanPolicy(epilogue="flat") explicitly."""
    try:
        from jax._src import mesh as mesh_lib

        return not mesh_lib.thread_resources.env.physical_mesh.empty
    except Exception:
        return False


def _eva_policy_args(epilogue, block_v, impl: str
                     ) -> Tuple[str, Optional[int]]:
    """Normalize the eva_matmul keyword surface to the plan API's
    (epilogue, block_v) pair.

    ``block_v="auto"`` means auto-sized (PlanPolicy None); a bare int
    with the default epilogue selects the v-blocked gather scan on jnp
    (and pins the kernel v-tiles on Pallas). Passing None for block_v
    was the pre-plan spelling of the direct epilogue and is REMOVED —
    it raises here so stale callers fail loudly instead of silently
    changing formulation."""
    if block_v is None:
        raise ValueError(
            "passing None for block_v was removed (it was the legacy "
            "spelling of the direct epilogue); pass epilogue='direct', "
            "block_v='auto' or an int")
    # "auto" -> None (auto-sized); ints pass through; anything else is left
    # for PlanPolicy's loud block_v validation
    bv = None if block_v == "auto" else block_v
    if epilogue is None:
        if isinstance(bv, int) and not isinstance(bv, bool) and impl == "jnp":
            # a bare int block_v selects the v-blocked gather scan
            return "blocked", bv
        return "auto", bv
    return epilogue, bv


def fp_matmul(x: jax.Array, w: jax.Array, *, out_dtype=None) -> jax.Array:
    """Dense baseline: y = x @ w with fp32 accumulation."""
    out_dtype = out_dtype or x.dtype
    y = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return y.astype(out_dtype)


def quantize_int8(x: jax.Array, axis: int = -1):
    """Symmetric per-slice int8 quantization: returns (q, scale)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_matmul(x: jax.Array, w: jax.Array, *, out_dtype=None) -> jax.Array:
    """Prefill path: dynamic per-token int8 activations x per-channel int8
    weights -> int32 accumulate -> fp dequant. Mirrors the paper's INT8
    systolic-array prefill mode (the TPU MXU is natively int8-capable)."""
    out_dtype = out_dtype or x.dtype
    xq, xs = quantize_int8(x, axis=-1)             # (..., K), (..., 1)
    wq, ws = quantize_int8(w, axis=0)              # (K, N), (1, N)
    acc = jax.lax.dot_general(
        xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return (acc.astype(jnp.float32) * xs * ws).astype(out_dtype)


def dequant_matmul(x: jax.Array, vq: VQWeight, *, out_dtype=None) -> jax.Array:
    """Conventional VQ baseline: on-the-fly reconstruct W_hat, then matmul.

    Expressed so the weight reconstruction materializes (K, N) — exactly the
    memory-traffic pattern EVA eliminates; used as the numerical oracle."""
    from repro.core.vq import dequantize

    out_dtype = out_dtype or x.dtype
    w_hat = dequantize(vq).astype(jnp.float32)
    return fp_matmul(x.astype(jnp.float32), w_hat, out_dtype=out_dtype)


def compute_output_codebook(x: jax.Array, vq: VQWeight) -> jax.Array:
    """Step 1 (VQ-GEMM): O = X·B.

    x: (..., K) -> O: (C, M, V, 2^n) fp32 where M = prod(leading dims).
    This is the GEMM the paper maps onto the 32x8 systolic array; cost is
    M*K*2^n MACs, independent of N.
    """
    K = vq.K
    M = x.size // K
    X = x.reshape(M, vq.V, vq.d).astype(jnp.float32)
    # (M, V, d) x (C, d, k) -> (C, M, V, k)
    return jnp.einsum("mvd,cdk->cmvk", X, vq.codebooks.astype(jnp.float32))


def _recon_epilogue(x: jax.Array, vq: VQWeight, bv: int) -> jax.Array:
    """v-blocked reconstruct-and-GEMM: lax.scan over V tiles, rebuilding
    one (bv*d, N) fp32 slab of W_hat per step (C centroid gathers summed)
    and accumulating x_slab @ w_slab. The slab stays cache-resident —
    unlike dequant_matmul, which materializes the full (K, N) — and the
    C*V*N*d gather work is independent of M, so BLAS carries the batch
    axis. Returns (M, N) fp32 including the per-channel scale."""
    C, V, N, d = vq.C, vq.V, vq.N, vq.d
    M = x.size // vq.K
    X = x.reshape(M, V, d).astype(jnp.float32)
    I = vq.idx.astype(jnp.int32)                              # (C, V, N)
    cb = vq.codebooks.transpose(0, 2, 1).astype(jnp.float32)  # (C, k, d)
    bv = min(bv, V)
    rem = (-V) % bv
    if rem:  # zero-padded X rows null the padded slabs' contribution
        X = jnp.pad(X, ((0, 0), (0, rem), (0, 0)))
        I = jnp.pad(I, ((0, 0), (0, rem), (0, 0)))
    nblk = X.shape[1] // bv
    X_blk = X.reshape(M, nblk, bv, d).transpose(1, 0, 2, 3)   # (nb, M, bv, d)
    I_blk = I.reshape(C, nblk, bv, N).transpose(1, 0, 2, 3)   # (nb, C, bv, N)

    def body(acc, blk):
        x_b, i_b = blk                                        # (M,bv,d), (C,bv,N)
        w = jnp.take(cb[0], i_b[0], axis=0)                   # (bv, N, d)
        for c in range(1, C):  # C is tiny and static — unrolled
            w = w + jnp.take(cb[c], i_b[c], axis=0)
        w = w.transpose(0, 2, 1).reshape(bv * d, N)
        acc = acc + jax.lax.dot_general(
            x_b.reshape(M, bv * d), w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc, None

    acc, _ = jax.lax.scan(body, jnp.zeros((M, N), jnp.float32), (X_blk, I_blk))
    return acc * vq.scale[None, :].astype(jnp.float32)


def eva_epilogue_exec(
    x: jax.Array,
    vq: VQWeight,
    *,
    kind: str,
    block_v: Optional[int] = None,
    out_dtype=None,
) -> jax.Array:
    """Execute ONE resolved jnp EVA formulation — no selection here.

      O = X·B                         (VQ-GEMM, MXU)
      y[m,j] = s[j] * sum_c sum_v O[c,m,v, I[c,v,j]]   (epilogue, add-only)

    ``kind`` is one of EPILOGUES and ``block_v`` the resolved v-block for
    the v-blocked kinds; both come frozen out of a MatmulPlan (the jnp
    EVA backend registrations in core/plan.py resolve them once per
    (spec, policy) via select_epilogue / the auto block sizers)."""
    K = vq.K
    M = x.size // K
    V, N, C = vq.V, vq.N, vq.C
    k = vq.codebooks.shape[-1] if hasattr(vq.codebooks, "shape") else 2 ** vq.n
    out_dtype = out_dtype or x.dtype
    lead_shape = x.shape[:-1]

    if kind == "recon":
        y = _recon_epilogue(x, vq, block_v)
        return y.reshape(*lead_shape, N).astype(out_dtype)

    O = compute_output_codebook(x, vq)  # (C, M, V, k)
    I = vq.idx.astype(jnp.int32)        # (C, V, N)

    if kind == "flat":
        v_iota = jnp.arange(V, dtype=jnp.int32)[None, :, None]
        c_iota = jnp.arange(C, dtype=jnp.int32)[:, None, None]
        flat = ((c_iota * V + v_iota) * k + I).reshape(-1)   # (C*V*N,)
        O2 = O.transpose(1, 0, 2, 3).reshape(M, C * V * k)
        g = jnp.take(O2, flat, axis=1)                       # (M, C*V*N)
        acc = g.reshape(M, C, V, N).sum(axis=(1, 2))
    elif kind == "direct":
        g = jnp.take_along_axis(O, I[:, None].astype(jnp.int32), axis=3)
        acc = g.sum(axis=(0, 2))                             # (M, N)
    elif kind == "blocked":
        bv = block_v
        # pad V to a multiple of bv (index 0 with zeroed O rows)
        rem = (-V) % bv
        if rem:
            O = jnp.pad(O, ((0, 0), (0, 0), (0, rem), (0, 0)))
            I = jnp.pad(I, ((0, 0), (0, rem), (0, 0)))
        nblk = O.shape[2] // bv
        O_blk = O.reshape(C, M, nblk, bv, O.shape[-1]).transpose(2, 0, 1, 3, 4)
        I_blk = I.reshape(C, nblk, bv, N).transpose(1, 0, 2, 3)

        def body(acc, blk):
            o_b, i_b = blk  # (C,M,bv,k), (C,bv,N)
            g = jnp.take_along_axis(o_b, i_b[:, None].astype(jnp.int32), axis=3)
            return acc + g.sum(axis=(0, 2)), None

        acc0 = jnp.zeros((M, N), jnp.float32)
        acc, _ = jax.lax.scan(body, acc0, (O_blk, I_blk))
    else:
        raise ValueError(f"unknown epilogue kind {kind!r}")
    y = acc * vq.scale[None, :].astype(jnp.float32)
    return y.reshape(*lead_shape, N).astype(out_dtype)


def eva_matmul(
    x: jax.Array,
    vq: VQWeight,
    *,
    epilogue: Optional[str] = None,
    block_v="auto",
    out_dtype=None,
    impl: str = "jnp",
    interpret: bool = False,
) -> jax.Array:
    """EVA decode matmul: y = x @ W_hat via output-codebook lookup.

    Thin convenience wrapper over ``Planner.plan(...).execute(...)`` —
    derives a LinearSpec from (x, vq), builds a PlanPolicy from the
    keyword surface and executes the cached plan. See core/plan.py for
    the dispatch layer and `select_epilogue` for the cost models /
    the measured regime table of the jnp epilogues:

      epilogue="auto" / block_v="auto" (the default): choose per shape —
        direct gather in the M < d decode regime, v-blocked gather once
        the gathered intermediate spills the cache budget, the v-blocked
        reconstruct-and-GEMM at M >= d, flat inside a mesh context.
      epilogue="direct" | "flat" | "blocked" | "recon": force a
        formulation; an int ``block_v`` pins the v-block of the
        v-blocked kinds.
      impl="pallas": the fused tiled kernel, or the two-kernel
        vq_gemm+oc_lookup split backend where the fused kernel's OC tile
        does not fit VMEM (an int ``block_v`` pins the chosen kernel's
        v-tiles; jnp epilogue requests are invalid there).
    """
    from repro.core import plan as plan_mod

    epi, bv = _eva_policy_args(epilogue, block_v, impl)
    policy = plan_mod.PlanPolicy(vq_mode="eva", impl=impl, epilogue=epi,
                                 block_v=bv, interpret=interpret)
    return plan_mod.plan_vq(x, vq, policy, out_dtype=out_dtype).execute(x, vq)


# ---------------------------------------------------------------------------
# Expert-grouped rows (dropless MoE): every routed (token, expert) pair is
# one row; rows are sorted by expert and each expert's rows padded to a
# whole number of EXPERT_TILE-row tiles, so a tile holds one expert's rows
# ---------------------------------------------------------------------------

EXPERT_TILE = 8  # rows per tile: one sublane tile of the kernel's output


class ExpertRows(NamedTuple):
    """Rows of a grouped expert matmul. ``x`` (R, K) holds each expert's
    rows in expert order, padded with zero rows to whole tiles; tile t
    (rows t*EXPERT_TILE ...) belongs to expert ``tile_expert[t]``. Tiles
    from ``tiles`` on hold no rows (their expert repeats the last real
    tile's). ``group_sizes`` (E,) counts each expert's padded rows."""
    x: jax.Array
    tile_expert: jax.Array
    tiles: jax.Array
    group_sizes: jax.Array


def expert_rows(x: jax.Array, expert_of: jax.Array, num_experts: int
                ) -> Tuple[ExpertRows, jax.Array]:
    """Sort routed rows ``x`` (S, K), row i going to expert
    ``expert_of[i]``, into the tiled expert layout. Returns the layout
    and ``dest`` (S,): the layout row that holds row i. A row whose
    expert is ``num_experts`` or more goes to an expert held elsewhere:
    it takes no row, and its ``dest`` is the layout's height. The layout
    has a static height: every route plus, at most, a tile's padding for
    each expert that can be hit."""
    S, E, t = expert_of.shape[0], num_experts, EXPERT_TILE
    counts = jnp.zeros((E,), jnp.int32).at[expert_of].add(1, mode="drop")
    padded = (counts + t - 1) // t * t
    ends = jnp.cumsum(padded)
    order = jnp.argsort(expert_of, stable=True)
    sorted_e = jnp.minimum(expert_of[order], E - 1)
    first = (jnp.cumsum(counts) - counts)[sorted_e]
    R = -(-(S + (t - 1) * min(E, S)) // t) * t
    at = (ends - padded)[sorted_e] + jnp.arange(S, dtype=jnp.int32) - first
    dest = jnp.zeros((S,), jnp.int32).at[order].set(
        jnp.where(expert_of[order] < E, at, R))
    rows = jnp.zeros((R, x.shape[-1]), x.dtype).at[dest].set(x, mode="drop")
    tiles = ends[-1] // t
    starts = jnp.arange(R // t, dtype=jnp.int32) * t
    te = jnp.sum(ends[None, :] <= starts[:, None], axis=1, dtype=jnp.int32)
    te = jnp.where(starts < ends[-1], te, te[jnp.maximum(tiles - 1, 0)])
    return ExpertRows(rows, jnp.minimum(te, E - 1), tiles, padded), dest


def grouped_eva_matmul(rows: ExpertRows, vq: VQWeight, *, out_dtype=None
                       ) -> jax.Array:
    """EVA over expert tiles in jnp (the CPU path of the grouped kernel):
    per tile, its expert's output codebook for the tile's rows, then the
    lookup-add over that expert's indices. ``vq`` is stacked on a
    leading expert axis."""
    out_dtype = out_dtype or rows.x.dtype
    R, K = rows.x.shape
    d = vq.d
    X = rows.x.reshape(R // EXPERT_TILE, EXPERT_TILE, K // d, d
                       ).astype(jnp.float32)

    def tile(args):
        xt, e = args
        O = jnp.einsum("mvd,cdk->cmvk", xt,
                       vq.codebooks[e].astype(jnp.float32))
        g = jnp.take_along_axis(O, vq.idx[e][:, None].astype(jnp.int32),
                                axis=3)
        return g.sum(axis=(0, 2)) * vq.scale[e].astype(jnp.float32)

    y = jax.lax.map(tile, (X, rows.tile_expert))
    return y.reshape(R, vq.N).astype(out_dtype)


def grouped_dequant_matmul(rows: ExpertRows, vq: VQWeight, *,
                           out_dtype=None) -> jax.Array:
    """The conventional-VQ baseline of a grouped expert matmul: every
    expert's weight reconstructed, then one ragged matmul over the rows."""
    from repro.core.vq import dequantize

    out_dtype = out_dtype or rows.x.dtype
    w = jax.vmap(dequantize)(vq)                           # (E, K, N)
    return grouped_fp_matmul(rows, w, out_dtype=out_dtype)


def grouped_fp_matmul(rows: ExpertRows, w: jax.Array, *, out_dtype=None
                      ) -> jax.Array:
    """rows.x @ w[e] per expert group, w (E, K, N) dense."""
    out_dtype = out_dtype or rows.x.dtype
    x = rows.x.astype(jnp.float32)
    y = jax.lax.ragged_dot(x, w.astype(jnp.float32), rows.group_sizes,
                           preferred_element_type=jnp.float32)
    return y.astype(out_dtype)


def split_grouped_outputs(y: jax.Array, vq: VQWeight) -> Tuple[jax.Array, ...]:
    """Slice the output of a grouped-family matmul (y = x @ [W1|..|Wg])
    back into per-projection outputs at the recorded split points.

    The wide matmul amortizes one VQ-GEMM / output-codebook computation
    over every member; this split is free (pure slicing)."""
    if not vq.splits:
        return (y,)
    offs = list(np.cumsum(vq.splits[:-1]))
    return tuple(jnp.split(y, offs, axis=-1))


def vq_matmul(
    x: jax.Array,
    vq: VQWeight,
    *,
    mode: str = "eva",
    epilogue: Optional[str] = None,
    block_v="auto",
    out_dtype=None,
    impl: str = "jnp",
    interpret: bool = False,
) -> jax.Array:
    """Unified VQ matmul entry point — a thin wrapper over
    ``Planner.plan(...).execute(...)`` (model layers dispatch through
    core/plan.py directly; this surface remains for scripts/tests).

    mode="eva" takes the epilogue surface of `eva_matmul`; for
    mode="dequant" the jnp baseline has no epilogue (an int ``block_v``
    pins the Pallas dequant kernel's v-tiles — impl="pallas" now actually
    reaches the `dequant_gemv` kernel instead of being silently ignored).
    """
    from repro.core import plan as plan_mod

    if mode == "eva":
        epi, bv = _eva_policy_args(epilogue, block_v, impl)
    elif mode == "dequant":
        epi = "auto"
        bv = block_v if isinstance(block_v, int) \
            and not isinstance(block_v, bool) else None
    else:
        raise ValueError(f"unknown vq matmul mode {mode!r}")
    policy = plan_mod.PlanPolicy(vq_mode=mode, impl=impl, epilogue=epi,
                                 block_v=bv, interpret=interpret)
    return plan_mod.plan_vq(x, vq, policy, out_dtype=out_dtype).execute(x, vq)


# ---------------------------------------------------------------------------
# Analytic op counts (the compute-collapse claim, pinned by tests)
# ---------------------------------------------------------------------------


def gemv_macs(M: int, K: int, N: int) -> int:
    return M * K * N


def vq_gemm_macs(M: int, K: int, n: int, C: int, d: int) -> int:
    """MACs of the VQ-GEMM stage: (M*K/d) rows x 2^n cols x d depth, per
    codebook."""
    return C * M * (K // d) * (2 ** n) * d


def compute_collapse_ratio(N: int, n: int) -> float:
    """Paper §III-B advantage 3: GEMV MACs / VQ-GEMM MACs = N / 2^n."""
    return N / float(2 ** n)


def grouped_compute_collapse_ratio(splits: Tuple[int, ...], n: int) -> float:
    """Effective collapse ratio of a grouped projection family: the single
    shared VQ-GEMM serves sum(N_i) output channels -> sum(N_i) / 2^n
    (vs N_i / 2^n for each member executed separately)."""
    return compute_collapse_ratio(sum(splits), n)
