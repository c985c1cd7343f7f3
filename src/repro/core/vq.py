"""Vector-quantization core: k-means codebook fitting and additive
(multi-codebook, AQLM-style) residual quantization of weight matrices.

Terminology follows the paper (Tbl. II):
  W      : (K, N) weight matrix
  d      : vector dimension (default 8)
  n      : index bit-width (default 8 -> 2^n = 256 centroids)
  C      : number of additive codebooks (2/3/4 -> q = C*n/d bits/weight)
  V      : K // d, height of the index matrix
  I      : (C, V, N) uint8 weight-index matrix
  B      : (C, d, 2^n) codebooks (centroids stored column-wise: B[c,:,e])
  scale  : (N,) per-output-channel scale (fp32)

The quantized representation of W is
  W_hat[:, j] = scale[j] * concat_v( sum_c B[c, :, I[c, v, j]] )
i.e. each d-element group of column j is the *sum* of one centroid from
each codebook (additive VQ), times a per-column scale.

Grouped-codebook layout
-----------------------
Same-input projection families (Wq|Wk|Wv of one attention block, or
W_gate|W_up of one MLP) may be quantized as a SINGLE wide VQ weight of
shape (K, sum_i N_i): one codebook set B serves every member, the index
matrix is the column-concatenation of the members' indices, and
``splits`` records the member widths (N_1, ..., N_g) so outputs can be
sliced apart after one wide EVA matmul.  Because the VQ-GEMM stage
(O = X·B) is independent of N, the grouped weight amortizes the output-
codebook computation g-fold (3x for QKV, 2x for gate+up) and raises the
effective compute-collapse ratio from N_i/2^n to (sum_i N_i)/2^n.
``splits == ()`` means an ordinary ungrouped weight.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class VQWeight:
    """Quantized representation of a (K, N) weight matrix.

    For a grouped-projection family N = sum(splits); `splits` is static
    metadata (part of the pytree aux data, preserved under jit/vmap/scan).
    """

    idx: jax.Array        # (C, V, N) uint8 (n<=8) or int32 (n>8)
    codebooks: jax.Array  # (C, d, 2^n) fp32
    scale: jax.Array      # (N,) fp32
    # static metadata
    K: int = 0
    N: int = 0
    d: int = 8
    n: int = 8
    splits: Tuple[int, ...] = ()   # per-member widths of a grouped family

    def tree_flatten(self):
        return (self.idx, self.codebooks, self.scale), (
            self.K, self.N, self.d, self.n, self.splits)

    @classmethod
    def tree_unflatten(cls, aux, children):
        idx, codebooks, scale = children
        K, N, d, n, splits = aux
        return cls(idx=idx, codebooks=codebooks, scale=scale, K=K, N=N,
                   d=d, n=n, splits=splits)

    @property
    def C(self) -> int:
        # codebooks are (..., C, d, k): leading dims stack layers/experts
        return self.codebooks.shape[-3] if hasattr(self.codebooks, "shape") else 0

    @property
    def V(self) -> int:
        return self.K // self.d

    @property
    def bits_per_weight(self) -> float:
        return self.C * self.n / self.d

    def compressed_bytes(self) -> int:
        idx_bytes = self.C * self.V * self.N * (1 if self.n <= 8 else 4)
        cb_bytes = self.C * self.d * (2 ** self.n) * 4
        sc_bytes = self.N * 4
        return idx_bytes + cb_bytes + sc_bytes


# ---------------------------------------------------------------------------
# k-means (Lloyd) with k-means++ style init, fully jittable.
# ---------------------------------------------------------------------------


def _kmeans_pp_init(key: jax.Array, points: jax.Array, k: int) -> jax.Array:
    """k-means++ seeding. points: (P, d) -> (k, d) initial centroids."""
    P = points.shape[0]

    def body(carry, _):
        key, cents, dists, i = carry
        key, sub = jax.random.split(key)
        # sample next centroid proportional to squared distance
        probs = dists / jnp.maximum(dists.sum(), 1e-30)
        nxt = jax.random.choice(sub, P, p=probs)
        new_c = points[nxt]
        cents = cents.at[i].set(new_c)
        new_d = jnp.sum((points - new_c) ** 2, axis=-1)
        dists = jnp.minimum(dists, new_d)
        return (key, cents, dists, i + 1), None

    key, sub = jax.random.split(key)
    first = points[jax.random.randint(sub, (), 0, P)]
    cents = jnp.zeros((k, points.shape[1]), points.dtype).at[0].set(first)
    dists = jnp.sum((points - first) ** 2, axis=-1)
    (key, cents, dists, _), _ = jax.lax.scan(body, (key, cents, dists, 1), None, length=k - 1)
    return cents


def _assign(points: jax.Array, cents: jax.Array) -> jax.Array:
    """Nearest-centroid assignment. points (P,d), cents (k,d) -> (P,) int32."""
    # ||p - c||^2 = ||p||^2 - 2 p.c + ||c||^2 ; ||p||^2 constant per point.
    d2 = -2.0 * points @ cents.T + jnp.sum(cents ** 2, axis=-1)[None, :]
    return jnp.argmin(d2, axis=-1).astype(jnp.int32)


def _update(points: jax.Array, assign: jax.Array, k: int, key: jax.Array) -> jax.Array:
    """Recompute centroids; dead centroids re-seeded from random points."""
    P, d = points.shape
    onehot_sums = jax.ops.segment_sum(points, assign, num_segments=k)
    counts = jax.ops.segment_sum(jnp.ones((P,), points.dtype), assign, num_segments=k)
    cents = onehot_sums / jnp.maximum(counts, 1.0)[:, None]
    # re-seed empty clusters from random points to avoid centroid collapse
    rnd = points[jax.random.randint(key, (k,), 0, P)]
    return jnp.where((counts > 0)[:, None], cents, rnd)


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def kmeans(key: jax.Array, points: jax.Array, k: int, iters: int = 20) -> Tuple[jax.Array, jax.Array]:
    """Lloyd's k-means. Returns (centroids (k,d), assignment (P,))."""
    points = points.astype(jnp.float32)
    key, init_key = jax.random.split(key)
    cents = _kmeans_pp_init(init_key, points, k)

    def body(carry, key_i):
        cents = carry
        a = _assign(points, cents)
        cents = _update(points, a, k, key_i)
        return cents, None

    keys = jax.random.split(key, iters)
    cents, _ = jax.lax.scan(body, cents, keys)
    return cents, _assign(points, cents)


# ---------------------------------------------------------------------------
# Additive VQ fit (AQLM-style greedy residual + optional refinement)
# ---------------------------------------------------------------------------


def fit_vq(
    key: jax.Array,
    W: Union[jax.Array, Sequence[jax.Array]],
    *,
    d: int = 8,
    n: int = 8,
    C: int = 2,
    kmeans_iters: int = 20,
    refine_rounds: int = 1,
) -> VQWeight:
    """Quantize W (K, N) to an additive C-codebook VQ representation.

    Greedy residual fit: codebook c is k-means over the residual after
    subtracting codebooks < c, followed by `refine_rounds` of alternating
    re-fits (each codebook refit against the residual of all others) —
    the paper's AQLM configuration at d=8, n=8, C=q.

    Grouped mode: pass a sequence of same-K matrices ([Wq, Wk, Wv] or
    [W_gate, W_up]) and they are fitted as ONE (K, sum N_i) matrix sharing
    a single codebook set; the member widths are recorded in `splits`
    (see the module docstring's grouped-codebook layout).
    """
    splits: Tuple[int, ...] = ()
    if isinstance(W, (list, tuple)):
        Ks = {int(w.shape[0]) for w in W}
        if len(Ks) != 1:
            raise ValueError(f"grouped fit_vq requires equal K, got {Ks}")
        splits = tuple(int(w.shape[1]) for w in W)
        W = jnp.concatenate([jnp.asarray(w) for w in W], axis=1)
    K, N = W.shape
    assert K % d == 0, f"K={K} not divisible by d={d}"
    V = K // d
    k = 2 ** n
    W = W.astype(jnp.float32)

    # per-output-channel scale normalizes column energy (AQLM uses per-group
    # scales; per-column is the hardware-friendly variant the paper's
    # epilogue applies as a single fp multiply after accumulation).
    scale = jnp.maximum(jnp.sqrt(jnp.mean(W ** 2, axis=0)), 1e-8)  # (N,)
    Wn = W / scale[None, :]

    # view as points: column-major grouping — vectors are d consecutive
    # elements along K for every output channel j -> (V*N, d) points
    pts = Wn.reshape(V, d, N).transpose(0, 2, 1).reshape(V * N, d)

    codebooks = []
    assigns = []
    resid = pts
    for c in range(C):
        key, sub = jax.random.split(key)
        cents, a = kmeans(sub, resid, k, iters=kmeans_iters)
        codebooks.append(cents)
        assigns.append(a)
        resid = resid - cents[a]

    # alternating refinement: refit codebook c on (pts - sum_{c'!=c} contrib)
    for _ in range(refine_rounds):
        for c in range(C):
            recon_others = jnp.zeros_like(pts)
            for c2 in range(C):
                if c2 != c:
                    recon_others = recon_others + codebooks[c2][assigns[c2]]
            target = pts - recon_others
            key, sub = jax.random.split(key)
            cents, a = kmeans(sub, target, k, iters=max(kmeans_iters // 2, 5))
            codebooks[c] = cents
            assigns[c] = a

    B = jnp.stack([cb.T for cb in codebooks])  # (C, d, k): centroid e = B[c,:,e]
    idx_dtype = jnp.uint8 if n <= 8 else jnp.int32
    I = jnp.stack([a.reshape(V, N) for a in assigns]).astype(idx_dtype)  # (C, V, N)
    return VQWeight(idx=I, codebooks=B, scale=scale, K=K, N=N, d=d, n=n,
                    splits=splits)


def dequantize(vq: VQWeight) -> jax.Array:
    """Reconstruct W_hat (K, N) from the VQ representation (the
    'conventional VQ' path the paper's baselines execute)."""
    C, d, k = vq.codebooks.shape
    V, N = vq.idx.shape[1], vq.idx.shape[2]
    cb = vq.codebooks.transpose(0, 2, 1)  # (C, k, d): row e = centroid e
    # batched gather per codebook: cents[c, v, n, :] = cb[c, idx[c,v,n], :]
    cents = jax.vmap(lambda cbc, idxc: jnp.take(cbc, idxc, axis=0))(
        cb, vq.idx.astype(jnp.int32)
    )  # (C, V, N, d)
    cents = cents.sum(axis=0)  # additive sum over codebooks -> (V, N, d)
    W = cents.transpose(0, 2, 1).reshape(V * d, N)
    return W * vq.scale[None, :]


def synthetic_vq(
    key: jax.Array, K: int, N: int, *, d: int = 8, n: int = 8, C: int = 2,
    dtype=jnp.float32, splits: Tuple[int, ...] = (),
) -> VQWeight:
    """Random-but-valid VQ weight (for serving dry-runs / benchmarks where
    fitting k-means on a 72B model is pointless). Index distribution is
    uniform, matching the paper's Fig. 14(b) entropy argument. `splits`
    marks the result as a grouped family (must sum to N)."""
    if splits:
        assert sum(splits) == N, (splits, N)
    V = K // d
    k = 2 ** n
    k_idx, k_cb, k_sc = jax.random.split(key, 3)
    idx_dtype = jnp.uint8 if n <= 8 else jnp.int32
    idx = jax.random.randint(k_idx, (C, V, N), 0, k).astype(idx_dtype)
    # scale codebooks ~ 1/sqrt(K*C) so W_hat has unit-ish variance
    codebooks = (jax.random.normal(k_cb, (C, d, k), dtype) / np.sqrt(K * C)).astype(dtype)
    scale = jnp.ones((N,), jnp.float32)
    return VQWeight(idx=idx, codebooks=codebooks, scale=scale, K=K, N=N,
                    d=d, n=n, splits=splits)


def vq_specs(K: int, N: int, *, d: int = 8, n: int = 8, C: int = 2,
             splits: Tuple[int, ...] = ()) -> VQWeight:
    """ShapeDtypeStruct stand-in with identical tree structure (dry-run)."""
    V = K // d
    k = 2 ** n
    idx_dtype = jnp.uint8 if n <= 8 else jnp.int32
    return VQWeight(
        idx=jax.ShapeDtypeStruct((C, V, N), idx_dtype),
        codebooks=jax.ShapeDtypeStruct((C, d, k), jnp.float32),
        scale=jax.ShapeDtypeStruct((N,), jnp.float32),
        K=K, N=N, d=d, n=n, splits=splits,
    )


def splits_shard_aligned(splits: Tuple[int, ...], N: int, shards: int) -> bool:
    """True when every member boundary of a grouped projection family
    (column-concatenated widths ``splits`` summing to ``N``) falls on a
    shard boundary of the N axis split ``shards``-ways.

    Shared by the sharding rules (runtime/sharding.py: misaligned grouped
    leaves fall back to V-sharding) and by the quantization pass's
    shard-aware grouping (core/quantize.py: skip grouping such families
    so the members keep clean column sharding)."""
    if shards <= 1:
        return True
    if N % shards:
        return False
    if not splits:
        return True
    shard = N // shards
    off = 0
    for width in splits[:-1]:
        off += width
        if off % shard:
            return False
    return True


def split_grouped(vq: VQWeight) -> Tuple[VQWeight, ...]:
    """Slice a grouped VQWeight back into its per-projection members
    (shared codebooks; per-member index columns and scales)."""
    if not vq.splits:
        return (vq,)
    offs = np.cumsum((0,) + vq.splits)
    return tuple(
        VQWeight(
            idx=vq.idx[..., lo:hi], codebooks=vq.codebooks,
            scale=vq.scale[..., lo:hi], K=vq.K, N=hi - lo, d=vq.d, n=vq.n,
        )
        for lo, hi in zip(offs[:-1], offs[1:])
    )


def reconstruction_error(W: jax.Array, vq: VQWeight) -> jax.Array:
    """Relative Frobenius reconstruction error ||W - W_hat|| / ||W||."""
    W_hat = dequantize(vq)
    return jnp.linalg.norm(W - W_hat) / jnp.maximum(jnp.linalg.norm(W), 1e-30)


# ---------------------------------------------------------------------------
# KV-cache vector quantization (KV-VQ)
# ---------------------------------------------------------------------------
#
# The weight machinery above compresses *static* matrices offline; the
# KV cache is written one token at a time inside the jitted decode step,
# so KV-VQ uses a simpler per-head geometry that encodes in O(E) work
# per token:
#
#   vec_d : channels per code group (head_dim must divide)
#   R     : additive residual stages (stage r quantizes the residual of
#           stages < r, VecInfer/Kumar style)
#   E     : 256 entries per stage, so every index is exactly one uint8
#
# A (.., Hk, hd) K/V slice stores as uint8 indices (.., Hk, R*G) with
# G = hd // vec_d plus ONE fp scale per (token, head) — riding the int8
# `k_s`/`v_s` plumbing. Effective bits/channel = 8*R/vec_d, so
# KVQuantConfig(kv_bits=4) is 4-bit KV and kv_bits=2 is 2-bit KV.

KV_VARIANTS = ("outlier", "rms")


@dataclasses.dataclass(frozen=True)
class KVQuantConfig:
    """Frozen geometry/variant selector for vector-quantized KV caches.

    Args:
      kv_bits: effective stored bits per K/V channel (4 or 2).
      residual: number of additive codebook stages R (>= 1). More stages
        widen ``vec_d`` at fixed ``kv_bits`` (8*R/vec_d = kv_bits).
      variant: per-(token, head) scale rule applied before codebook
        assignment — "outlier" divides by the absmax channel so a single
        outlier can never saturate the codebook range (VecInfer's
        outlier suppression), "rms" divides by 2*rms (denser coverage of
        the bulk, outliers clip to the grid edge).
      entries: codebook entries per stage; fixed at 256 so one index is
        one uint8 and the paged arenas stay byte-addressed.

    Raises:
      ValueError: on unknown variant, unsupported kv_bits, entries != 256,
        or a (kv_bits, residual) pair with non-integral vec_d.
    """

    kv_bits: int = 4
    residual: int = 1
    variant: str = "outlier"
    entries: int = 256

    def __post_init__(self):
        if self.kv_bits not in (2, 4):
            raise ValueError(f"kv_bits must be 2 or 4, got {self.kv_bits}")
        if self.entries != 256:
            raise ValueError(
                f"entries is fixed at 256 (uint8 index), got {self.entries}")
        if self.variant not in KV_VARIANTS:
            raise ValueError(
                f"unknown variant {self.variant!r}; expected one of {KV_VARIANTS}")
        if self.residual < 1 or (8 * self.residual) % self.kv_bits:
            raise ValueError(
                f"residual={self.residual} does not give integral vec_d at "
                f"kv_bits={self.kv_bits}")

    @property
    def vec_d(self) -> int:
        """Channels per code group (8*R/kv_bits)."""
        return (8 * self.residual) // self.kv_bits

    def groups(self, dim: int) -> int:
        """Code groups per head of width ``dim``; dim must divide by vec_d."""
        if dim % self.vec_d:
            raise ValueError(
                f"head dim {dim} not divisible by vec_d={self.vec_d}")
        return dim // self.vec_d

    def idx_width(self, dim: int) -> int:
        """uint8 indices stored per (token, head): R * groups(dim)."""
        return self.residual * self.groups(dim)


def kv_scale(x: jax.Array, variant: str = "outlier") -> jax.Array:
    """Per-(token, head) normalization scale over the trailing channel
    axis. Returns fp32 ``x.shape[:-1]``, clamped away from zero."""
    xf = x.astype(jnp.float32)
    if variant == "outlier":
        s = jnp.max(jnp.abs(xf), axis=-1)
    elif variant == "rms":
        s = 2.0 * jnp.sqrt(jnp.mean(xf * xf, axis=-1))
    else:
        raise ValueError(f"unknown variant {variant!r}")
    return jnp.maximum(s, 1e-8)


def kv_grid_codebooks(num_heads: int, dim: int,
                      kvq: KVQuantConfig) -> jax.Array:
    """Deterministic per-head codebooks: a uniform lattice over the
    scale-normalized range [-1, 1]^vec_d, one refining lattice per
    residual stage (stage r shrinks by levels^-r). With vec_d=2 this is
    exactly a 16-level-per-channel (int4) grid; vec_d=4 a 4-level (2-bit)
    grid — the calibration-free default. Returns (Hk, R, 256, vec_d)."""
    vd, R = kvq.vec_d, kvq.residual
    levels = int(round(kvq.entries ** (1.0 / vd)))
    if levels ** vd != kvq.entries:
        raise ValueError(
            f"no integral grid: entries={kvq.entries} has no {vd}-th root "
            "(use fit_kv_codebooks for this geometry)")
    kvq.groups(dim)  # validate divisibility loudly here, not at encode
    axis = np.linspace(-1.0, 1.0, levels, dtype=np.float32)
    grid = np.stack(np.meshgrid(*([axis] * vd), indexing="ij"),
                    axis=-1).reshape(kvq.entries, vd)
    stages = np.stack([grid * float(levels) ** (-r) for r in range(R)])
    return jnp.broadcast_to(jnp.asarray(stages),
                            (num_heads, R, kvq.entries, vd))


def fit_kv_codebooks(key: jax.Array, samples: jax.Array,
                     kvq: KVQuantConfig, *, kmeans_iters: int = 12
                     ) -> jax.Array:
    """Fit per-head KV codebooks from calibration K/V samples.

    Args:
      key: PRNG key for k-means seeding.
      samples: (T, Hk, dim) calibration slices (e.g. prefill K or V of a
        calibration prompt, flattened over batch and time).
      kvq: geometry/variant to fit.
      kmeans_iters: Lloyd iterations per stage.

    Returns:
      (Hk, R, 256, vec_d) fp32 codebooks: stage r of head h is k-means
      over head h's scale-normalized residual after stages < r.
    """
    T, Hk, dim = samples.shape
    G, vd = kvq.groups(dim), kvq.vec_d
    s = kv_scale(samples, kvq.variant)                      # (T, Hk)
    pts = (samples.astype(jnp.float32) / s[..., None]).reshape(T, Hk, G, vd)
    pts = pts.transpose(1, 0, 2, 3).reshape(Hk, T * G, vd)  # per-head points
    stages = []
    for r in range(kvq.residual):
        cents, assign = jax.vmap(
            lambda p, k_=jax.random.fold_in(key, r): kmeans(
                k_, p, kvq.entries, iters=kmeans_iters))(pts)
        stages.append(cents)                                # (Hk, E, vd)
        take = jax.vmap(lambda c, a: c[a])
        pts = pts - take(cents, assign)
    return jnp.stack(stages, axis=1)                        # (Hk, R, E, vd)


def _flat_take(cb_flat: jax.Array, idx: jax.Array) -> jax.Array:
    """Gather rows of a flattened codebook table by integer index."""
    return jnp.take(cb_flat, idx, axis=0)


def kv_encode(x: jax.Array, cb: jax.Array, variant: str = "outlier"
              ) -> Tuple[jax.Array, jax.Array]:
    """Quantize a K/V slice against per-head codebooks.

    Args:
      x: (..., Hk, dim) fp K or V values.
      cb: (Hk, R, 256, vec_d) codebooks (kv_grid_codebooks /
        fit_kv_codebooks); geometry is derived from this shape.
      variant: scale rule — must match the KVQuantConfig the codebooks
        were built for.

    Returns:
      (idx, scale): uint8 indices (..., Hk, R*G) and fp32 per-(token,
      head) scales (..., Hk). ``kv_decode(idx, scale, cb)`` is the
      dequantize oracle.
    """
    Hk, R, E, vd = cb.shape
    lead = x.shape[:-2]
    dim = x.shape[-1]
    G = dim // vd
    scale = kv_scale(x, variant)                            # (..., Hk)
    xn = (x.astype(jnp.float32) / scale[..., None]).reshape(
        lead + (Hk, G, vd))
    cbf = cb.astype(jnp.float32)
    h_iota = jnp.arange(Hk, dtype=jnp.int32).reshape(
        (1,) * len(lead) + (Hk, 1))
    resid = xn
    idxs = []
    for r in range(R):
        cbr = cbf[:, r]                                     # (Hk, E, vd)
        dots = jnp.einsum("...hgc,hec->...hge", resid, cbr)
        d2 = jnp.sum(cbr * cbr, axis=-1)                    # (Hk, E)
        a = jnp.argmin(d2[:, None, :] - 2.0 * dots,
                       axis=-1).astype(jnp.int32)           # (..., Hk, G)
        chosen = _flat_take(cbr.reshape(Hk * E, vd), h_iota * E + a)
        resid = resid - chosen
        idxs.append(a.astype(jnp.uint8))
    idx = jnp.stack(idxs, axis=-2)                          # (..., Hk, R, G)
    return idx.reshape(lead + (Hk, R * G)), scale


def kv_decode(idx: jax.Array, scale: jax.Array, cb: jax.Array) -> jax.Array:
    """Dequantize-oracle reconstruction of a KV-VQ slice.

    Args:
      idx: (..., Hk, R*G) uint8 indices from ``kv_encode``.
      scale: (..., Hk) per-(token, head) scales (any float dtype).
      cb: (Hk, R, 256, vec_d) codebooks.

    Returns:
      (..., Hk, G*vec_d) fp32 reconstruction — the exact values every
      KV-VQ execution path (jnp and Pallas) is parity-pinned against.
    """
    Hk, R, E, vd = cb.shape
    lead = idx.shape[:-2]
    G = idx.shape[-1] // R
    a = idx.reshape(lead + (Hk, R, G)).astype(jnp.int32)
    h_iota = jnp.arange(Hk, dtype=jnp.int32).reshape(
        (1,) * len(lead) + (Hk, 1, 1))
    r_iota = jnp.arange(R, dtype=jnp.int32).reshape(
        (1,) * len(lead) + (1, R, 1))
    flat = (h_iota * R + r_iota) * E + a                    # (..., Hk, R, G)
    chosen = _flat_take(cb.astype(jnp.float32).reshape(Hk * R * E, vd), flat)
    xn = chosen.sum(axis=-3)                                # (..., Hk, G, vd)
    return xn.reshape(lead + (Hk, G * vd)) * scale[..., None].astype(jnp.float32)
