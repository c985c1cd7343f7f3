"""Jit'd wrapper: quantize activations/weights and run the int8 GEMM.

Registers the "int8_pallas" backend with core/plan.py (the INT8 prefill
path of a dense weight under PlanPolicy(int8_prefill=True, impl="pallas"));
the planner freezes the (block_m, block_n, block_k) tiles per spec."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import plan as plan_mod
from repro.core.ops import quantize_int8
from repro.kernels.int8_gemm.kernel import int8_gemm_pallas
from repro.kernels.int8_gemm.ref import int8_gemm_ref


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret", "use_pallas", "out_dtype"),
)
def int8_matmul_kernel(
    x: jax.Array,   # (..., K) float
    w: jax.Array,   # (K, N) float
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
    use_pallas: bool = True,
    out_dtype=None,
) -> jax.Array:
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[-1]
    M = x.size // K
    xq, xs = quantize_int8(x.reshape(M, K), axis=-1)
    wq, ws = quantize_int8(w, axis=0)

    if not use_pallas:
        y = int8_gemm_ref(xq, wq, xs, ws)
        return y.reshape(*lead, N).astype(out_dtype)

    bm = min(block_m, max(8, M))
    bn = min(block_n, N)
    bk = min(block_k, K)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        xq = jnp.pad(xq, ((0, pm), (0, pk)))
        xs = jnp.pad(xs, ((0, pm), (0, 0)))
    if pk or pn:
        wq = jnp.pad(wq, ((0, pk), (0, pn)))
        ws = jnp.pad(ws, ((0, 0), (0, pn)))
    y = int8_gemm_pallas(xq, wq, xs, ws, block_m=bm, block_n=bn, block_k=bk, interpret=interpret)
    y = y[:M, :N]
    return y.reshape(*lead, N).astype(out_dtype)


# ---------------------------------------------------------------------------
# Plan backend
# ---------------------------------------------------------------------------


def _plan_int8_pallas(spec: plan_mod.LinearSpec,
                      policy: plan_mod.PlanPolicy) -> plan_mod.MatmulPlan:
    # this kernel's tile model: MXU-friendly 256x256x512 defaults clamped
    # to the actual GEMM extents (the wrapper pads the remainders)
    bm = min(256, max(8, spec.M))
    bn = min(256, spec.N)
    bk = min(512, spec.K)
    out_dt = jnp.dtype(spec.out_dtype)
    interpret = policy.interpret

    def run(x, w):
        return int8_matmul_kernel(x, w, block_m=bm, block_n=bn, block_k=bk,
                                  interpret=interpret, out_dtype=out_dt)

    return plan_mod.MatmulPlan("int8_pallas", spec, policy,
                               (("bm", bm), ("bn", bn), ("bk", bk)), run)


plan_mod.register_backend(
    "int8_pallas",
    lambda s, p: s.kind == "int8" and p.impl == "pallas",
    _plan_int8_pallas,
)
