"""VQ-Logits: a vector-quantized LM head (arXiv:2505.10202 style).

The dense output head is the single largest decode matmul: ``(M, D) @
(D, V)`` with V the (padded) vocabulary. VQ-Logits replaces the V
per-token output embeddings with a small codebook of ``Kc`` codeword
embeddings plus a ``(V,)`` token→codeword assignment and a per-token
scale: the implied dense head is

    W[:, v] = scale[v] * codebook[:, assign[v]]

so scoring factors into one small matmul against the codebook — ``(M, D)
@ (D, Kc)`` — followed by a gather ("scatter to full logits") along the
assignment. MACs drop from ``M*D*V`` to ``M*D*Kc`` with ``Kc << V``.

The head is a param-tree node ``{"vql": VQLogitsHead}``, attached by
``core.quantize.attach_vq_logits_head`` and consumed by
``models.common.linear`` through the same ``core.plan`` dispatch as
every other weight family: ``plan_node`` derives a ``kind="vq_logits"``
spec and one of the two jnp formulations below runs it —
gather-scoring (the point of the scheme) wherever ``_gather_admits``
accepts the shape, expand-to-dense (the exact oracle, also used by
parity tests) elsewhere.

Constructors mirror ``core.vq``: ``synthetic_logits_vq`` draws a random
head whose implied dense weight is exact by construction (for parity
tests), ``fit_logits_vq`` compresses a trained dense head by k-means
over its scale-normalized columns.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import ops
from repro.core import plan as plan_mod
from repro.core import vq as vq_mod


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class VQLogitsHead:
    """Compressed LM head: ``W[:, v] = scale[v] * codebook[:, assign[v]]``.

    codebook : (D, Kc) float — codeword output embeddings (columns)
    assign   : (V,) int32    — token → codeword id
    scale    : (V,) float32  — per-token magnitude (1.0 for synthetic)
    """

    codebook: jax.Array
    assign: jax.Array
    scale: jax.Array

    @property
    def D(self) -> int:
        return int(self.codebook.shape[0])

    @property
    def Kc(self) -> int:
        return int(self.codebook.shape[1])

    @property
    def V(self) -> int:
        return int(self.assign.shape[0])

    def tree_flatten(self):
        return (self.codebook, self.assign, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


def expand(head: VQLogitsHead) -> jax.Array:
    """Materialize the implied dense head ``(D, V)`` — the exact oracle."""
    w = jnp.take(head.codebook, head.assign, axis=1)
    return w * head.scale[None, :].astype(w.dtype)


def synthetic_logits_vq(key, d_model: int, vocab: int, kc: int,
                        dtype=jnp.float32) -> VQLogitsHead:
    """Random head whose implied dense weight is exact by construction
    (unit scales, so ``expand(head)`` holds the codebook's own columns):
    parity tests compare a model using this head against the same model
    with ``{"w": expand(head)}``. The two score through differently
    shaped GEMMs, ``(M, D) @ (D, Kc)`` then a gather against
    ``(M, D) @ (D, V)``, so their logits agree to float32 rounding, not
    bit for bit."""
    k_cb, k_as = jax.random.split(key)
    cb = (jax.random.normal(k_cb, (d_model, kc), jnp.float32)
          / jnp.sqrt(jnp.float32(d_model))).astype(dtype)
    assign = jax.random.randint(k_as, (vocab,), 0, kc, jnp.int32)
    return VQLogitsHead(cb, assign, jnp.ones((vocab,), jnp.float32))


def fit_logits_vq(key, w, kc: int, *, iters: int = 20) -> VQLogitsHead:
    """Compress a trained dense head ``w (D, V)`` by k-means over its
    scale-normalized columns. ``scale[v]`` is the column L2 norm, so the
    clustered points live on (near) the unit sphere and the codebook
    captures direction, not magnitude."""
    w = jnp.asarray(w, jnp.float32)
    d_model, vocab = w.shape
    scale = jnp.linalg.norm(w, axis=0)
    safe = jnp.maximum(scale, 1e-12)
    points = (w / safe[None, :]).T                       # (V, D)
    centroids, assign = vq_mod.kmeans(key, points, kc, iters=iters)
    return VQLogitsHead(centroids.T, assign.astype(jnp.int32),
                        scale.astype(jnp.float32))


def vq_logits_spec(head: VQLogitsHead, *, M: int, x_dtype,
                   out_dtype) -> plan_mod.LinearSpec:
    """Spec for a VQ-Logits head site. Field mapping (cf.
    ``kvq_attention_spec``): K=d_model, N=vocab, k=codebook size Kc."""
    return plan_mod.LinearSpec(
        M=int(M), K=head.D, N=head.V, kind="vq_logits",
        x_dtype=jnp.dtype(x_dtype).name, out_dtype=jnp.dtype(out_dtype).name,
        k=head.Kc,
    )


# ---------------------------------------------------------------------------
# Planner backends
# ---------------------------------------------------------------------------


def _gather_admits(spec: plan_mod.LinearSpec) -> bool:
    """Shapes on which gather-scoring is planned over expand-to-dense.
    This is the inequality the planner's former CPU-rate cost guess
    reduced to, kept so that the choice does not change; no chip run
    has measured it. Gather loses only where the codebook has about as
    many entries as the head has columns (``Kc`` near ``V``)."""
    M, D, V, Kc = spec.M, spec.K, spec.N, spec.k
    x_size = jnp.dtype(spec.x_dtype).itemsize
    out_size = jnp.dtype(spec.out_dtype).itemsize
    return (M * (2 * D * Kc + 20 * V + Kc * out_size)
            <= D * V * (2 * M + 20 + x_size))


def _plan_vql_gather(spec: plan_mod.LinearSpec,
                     policy: plan_mod.PlanPolicy) -> plan_mod.MatmulPlan:
    """Codebook-vocab scoring + gather: the VQ-Logits formulation."""
    out_dt = jnp.dtype(spec.out_dtype)

    def run(x, head: VQLogitsHead):
        cb = head.codebook
        if cb.dtype != x.dtype:
            cb = cb.astype(x.dtype)
        y = ops.fp_matmul(x, cb, out_dtype=out_dt)        # (..., Kc)
        y = jnp.take(y, head.assign, axis=-1)             # (..., V)
        return y * head.scale.astype(out_dt)

    return plan_mod.MatmulPlan("vql_gather_jnp", spec, policy, (), run)


def _plan_vql_dequant(spec: plan_mod.LinearSpec,
                      policy: plan_mod.PlanPolicy) -> plan_mod.MatmulPlan:
    """Expand-to-dense oracle: materialize the implied head, dense GEMM.
    Planned where gather-scoring declines the shape, and gather's
    fallback everywhere else; it anchors parity."""
    out_dt = jnp.dtype(spec.out_dtype)

    def run(x, head: VQLogitsHead):
        w = expand(head)
        if w.dtype != x.dtype:
            w = w.astype(x.dtype)
        return ops.fp_matmul(x, w, out_dtype=out_dt)

    return plan_mod.MatmulPlan("vql_dequant_jnp", spec, policy, (), run)


def _register_backends() -> None:
    plan_mod.register_backend(
        "vql_gather_jnp",
        lambda s, p: s.kind == "vq_logits" and _gather_admits(s),
        _plan_vql_gather,
    )
    plan_mod.register_backend(
        "vql_dequant_jnp",
        lambda s, p: s.kind == "vq_logits",
        _plan_vql_dequant,
    )


_register_backends()
