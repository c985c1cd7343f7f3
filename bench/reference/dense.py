"""Plain float32 reference of the dense decoder family.

A pre-norm decoder in straightforward ``jax.numpy``: RMSNorm, grouped-
query attention with rotary position embedding over the whole head
(rotate-half pairing, dimension i with i + head_dim/2), an optional
bias on q/k/v, a SwiGLU MLP, a final RMSNorm and an untied LM head. It
runs the configuration file's ``config`` section and nothing else, and
refuses a configuration it does not implement.

It imports nothing of the program and takes nothing the program made.
Its weights are made again from the run's seed by the documented
synthetic-weight procedure of the serving path (``Model.init_synthetic``
applied to ``PRNGKey(seed mod 2^32)``):

* ``ks = split(key, 5)``; the embedding is ``normal(ks[1], (V, D)) *
  0.02`` and the LM head ``normal(ks[3], (D, V)) / sqrt(D)``, both
  rounded to bfloat16 (V is the vocabulary padded to a multiple of 128);
  norm gains are 1 and biases 0.
* A VQ linear family with stacked shape ``(L, K, N)`` (q|k|v, o,
  gate|up, down) takes ``kk = fold_in(key, crc32(str((L, K, N))) mod
  2^31)``; layer l takes ``split(kk, L)[l]``, and from that one key its
  indices ``randint(., (C, K/d, N), 0, 2^n)`` and its codebooks
  ``normal(., (C, d, 2^n)) / sqrt(K*C)``; scales are 1. Column j of the
  weight is, for every group v of d rows, the sum over c of codebook
  entry ``idx[c, v, j]`` of codebook c, times the column's scale.

Each weight is rebuilt a layer at a time and dequantized in blocks of
columns, so the reference fits on one chip: every element is the
codebook entry its index selects, picked by comparing the index with
each of the 2^n entry numbers. Matrix products run at
``precision="highest"``. The caller pads the token rows, their length
and the compared positions to sizes fixed per cell, so every run of a
cell reuses the same compiled programs.

``control=True`` computes the same forward in float8 (e4m3): every
product's operands are rounded to e4m3 after scaling by their absolute
maximum (per row of activations, per column of weights), with float32
accumulation. It stands in for the program computed one precision
below the configuration's bfloat16, and the check has to fail it.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import zlib
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_COLS = 1024          # weight columns dequantized at a time
_VOCAB_COLS = 32768   # LM head columns multiplied at a time
POSITIONS = 512       # compared positions per LM head call
_E4M3_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Dims:
    L: int
    D: int
    F: int
    H: int
    Hk: int
    hd: int
    V: int
    theta: float
    eps: float
    bias: bool
    C: int
    d: int
    n: int

    @classmethod
    def of(cls, conf: Dict[str, Any]) -> "Dims":
        c, s = conf["config"], conf["serving"]
        expect = {"hidden_act": "silu", "mlp": "gated",
                  "normalization": "rmsnorm", "partial_rotary_factor": 1.0,
                  "tie_word_embeddings": False}
        for k, v in expect.items():
            if c[k] != v:
                raise ValueError(f"the dense reference implements {k}={v!r}, "
                                 f"the configuration states {c[k]!r}")
        return cls(L=int(c["num_hidden_layers"]), D=int(c["hidden_size"]),
                   F=int(c["intermediate_size"]),
                   H=int(c["num_attention_heads"]),
                   Hk=int(c["num_key_value_heads"]), hd=int(c["head_dim"]),
                   V=int(c["vocab_size"]), theta=float(c["rope_theta"]),
                   eps=float(c["norm_eps"]), bias=bool(c["attention_bias"]),
                   C=int(s["vq_C"]), d=int(s["vq_d"]), n=int(s["vq_n"]))

    @property
    def Vp(self) -> int:
        return (self.V + 127) // 128 * 128

    def families(self) -> Tuple[Tuple[int, int], ...]:
        q, kv = self.H * self.hd, self.Hk * self.hd
        return ((self.D, q + 2 * kv), (q, self.D), (self.D, 2 * self.F),
                (self.F, self.D))


def root_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed) % 2 ** 32)


def _family_keys(key, L: int, K: int, N: int) -> jax.Array:
    kk = jax.random.fold_in(key, zlib.crc32(str((L, K, N)).encode())
                            % (2 ** 31))
    return jax.random.split(kk, L)


def layer_keys(dims: Dims, key) -> jax.Array:
    """(L, families, 2) per-layer keys of the four VQ families."""
    return jnp.stack([_family_keys(key, dims.L, K, N)
                      for K, N in dims.families()], axis=1)


def _rne8(x: jax.Array, axis: int) -> jax.Array:
    """Round to e4m3 after scaling ``axis``'s absolute maximum to the
    format's largest value."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / _E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x: jax.Array, w: jax.Array, control: bool) -> jax.Array:
    """x (..., K) @ w (K, N) in float32."""
    if control:
        x, w = _rne8(x, -1), _rne8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _dequant(idx, cb):
    """(K, cols) float32 weight block from its indices ``idx`` (C, K/d,
    cols) and codebooks ``cb`` (C, d, 2^n): element (v*d + j, col) is the
    sum over c of ``cb[c, j, idx[c, v, col]]``."""
    C, d, k = cb.shape
    entries = jnp.arange(k, dtype=jnp.int32)[:, None, None]
    w = 0.0
    for c in range(C):
        hit = idx[c].astype(jnp.int32)[None] == entries        # (k, K/d, cols)
        w = w + jnp.stack([jnp.sum(jnp.where(hit, cb[c, j][:, None, None],
                                             0.0), axis=0)
                           for j in range(d)], axis=1)          # (K/d, d, cols)
    return w.reshape(-1, idx.shape[-1])


def _vq_linear(x, key, K: int, N: int, dims: Dims, control: bool):
    """x @ W for one layer's VQ family, W rebuilt from its key and
    dequantized a block of columns at a time."""
    idx = jax.random.randint(key, (dims.C, K // dims.d, N), 0, 2 ** dims.n
                             ).astype(jnp.uint8)
    cb = jax.random.normal(key, (dims.C, dims.d, 2 ** dims.n)) \
        / np.sqrt(K * dims.C)
    scale = jnp.ones((N,), jnp.float32)
    nb = -(-N // _COLS)
    idx = jnp.pad(idx, ((0, 0), (0, 0), (0, nb * _COLS - N)))
    blocks = idx.reshape(dims.C, K // dims.d, nb, _COLS).transpose(2, 0, 1, 3)
    scale = jnp.pad(scale, (0, nb * _COLS - N)).reshape(nb, _COLS)

    def block(args):
        blk, sc = args
        return _mm(x, _dequant(blk, cb) * sc, control)         # (..., _COLS)

    y = jax.lax.map(block, (blocks, scale))                     # (nb, ..., _COLS)
    y = jnp.moveaxis(y, 0, -2)
    return y.reshape(*y.shape[:-2], nb * _COLS)[..., :N]


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x (R, T, heads, hd), positions 0..T-1, rotate-half pairing."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("dims", "control"))
def _layer(x, keys, *, dims: Dims, control: bool):
    R, T, D = x.shape
    H, Hk, hd = dims.H, dims.Hk, dims.hd
    (Kq, Nq), (Ko, No), (Kg, Ng), (Kd, Nd) = dims.families()
    h = _rmsnorm(x, dims.eps)
    qkv = _vq_linear(h, keys[0], Kq, Nq, dims, control)
    if dims.bias:
        qkv = qkv + jnp.zeros((Nq,), jnp.float32)
    q = _rope(qkv[..., :H * hd].reshape(R, T, H, hd), dims.theta)
    k = _rope(qkv[..., H * hd:(H + Hk) * hd].reshape(R, T, Hk, hd),
              dims.theta)
    v = qkv[..., (H + Hk) * hd:].reshape(R, T, Hk, hd)
    g = H // Hk
    q = q.reshape(R, T, Hk, g, hd)
    if control:
        q, k, v = _rne8(q, -1), _rne8(k, -1), _rne8(v, 1)
    s = jnp.einsum("rqkgd,rskd->rkgqs", q, k, precision="highest") \
        / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if control:
        p = _rne8(p, -1)
    o = jnp.einsum("rkgqs,rskd->rqkgd", p, v, precision="highest")
    x = x + _vq_linear(o.reshape(R, T, H * hd), keys[1], Ko, No, dims,
                       control)
    h = _rmsnorm(x, dims.eps)
    gu = _vq_linear(h, keys[2], Kg, Ng, dims, control)
    a = jax.nn.silu(gu[..., :dims.F]) * gu[..., dims.F:]
    return x + _vq_linear(a, keys[3], Kd, Nd, dims, control)


@functools.partial(jax.jit, static_argnames=("dims",))
def _embed(key, tokens, *, dims: Dims):
    ks = jax.random.split(key, 5)
    emb = (jax.random.normal(ks[1], (dims.Vp, dims.D)) * 0.02
           ).astype(jnp.bfloat16)
    return jnp.take(emb, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "control"))
def _head(key, x, rows, cols, *, dims: Dims, control: bool):
    """Logits at positions (rows[i], cols[i]) of the last layer's output
    ``x``."""
    ks = jax.random.split(key, 5)
    w = (jax.random.normal(ks[3], (dims.D, dims.Vp)) * (1.0 / math.sqrt(dims.D))
         ).astype(jnp.bfloat16)
    h = _rmsnorm(x[rows, cols], dims.eps)
    outs = [_mm(h, w[:, lo:min(dims.Vp, lo + _VOCAB_COLS)].astype(jnp.float32),
                control)
            for lo in range(0, dims.Vp, _VOCAB_COLS)]
    return jnp.concatenate(outs, axis=-1)[:, :dims.V]


def logits(conf: Dict[str, Any], seed: int, tokens: np.ndarray,
           rows: Sequence[int], cols: Sequence[int], *,
           control: bool = False) -> jax.Array:
    """Float32 logits (P, vocab) at positions ``(rows[i], cols[i])`` of
    ``tokens`` (R, T): the prediction for position ``cols[i] + 1`` of row
    ``rows[i]``. Rows are independent sequences; padding after a row's
    end is never attended by its earlier positions. Positions go to the
    LM head ``POSITIONS`` at a time."""
    dims = Dims.of(conf)
    key = root_key(seed)
    P = len(rows)
    pad = -(-P // POSITIONS) * POSITIONS - P
    rows = np.concatenate([np.asarray(rows, np.int32), np.zeros(pad, np.int32)])
    cols = np.concatenate([np.asarray(cols, np.int32), np.zeros(pad, np.int32)])
    with jax.default_matmul_precision("highest"):
        x = _embed(key, jnp.asarray(tokens, jnp.int32), dims=dims)
        lk = layer_keys(dims, key)
        for layer in range(dims.L):
            x = _layer(x, lk[layer], dims=dims, control=control)
        out = [_head(key, x, jnp.asarray(rows[i:i + POSITIONS]),
                     jnp.asarray(cols[i:i + POSITIONS]), dims=dims,
                     control=control)
               for i in range(0, P + pad, POSITIONS)]
        return jnp.concatenate(out, axis=0)[:P]
