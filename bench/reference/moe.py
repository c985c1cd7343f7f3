"""Plain float32 reference of the mixture-of-experts decoder family with
latent attention (DeepSeek-V2).

A pre-norm decoder in straightforward ``jax.numpy``: RMSNorm; multi-head
latent attention in its expanded form (q and the compressed kv from the
block input, the kv latent RMS-normed and expanded through ``wkv_b``
into per-head keys and values, the decoupled rotary key shared by every
head) with YaRN rotary on the rope dimensions (rotate-half pairing) and
YaRN's softmax scale; ``first_k_dense_replace`` leading layers with a
SwiGLU MLP, then layers whose MLP is a softmax-scored greedy top-k
mixture of SwiGLU experts plus shared experts; a final RMSNorm and an
untied LM head. Every expert is computed on every position and weighted
by its gate where the token routed to it, 0 elsewhere. It runs the
configuration file's ``config`` section and refuses what it does not
implement (a query LoRA, grouped or non-greedy routing, another scoring
function, a routed scaling factor other than 1, a sliding window, other
rotary scaling).

It imports nothing of the program. Its weights are made again from the
run's seed by the serving path's documented synthetic-weight procedure
(``Model.init_synthetic`` applied to ``PRNGKey(seed mod 2^32)``):

* ``ks = split(key, 5)``: the embedding and the LM head as in
  ``bench/reference/dense.py``; norm gains are 1.
* A VQ family with stacked shape ``lead + (K, N)`` takes ``kk =
  fold_in(key, crc32(str(lead + (K, N))) mod 2^31)`` and its member i
  (row-major over ``lead``) ``split(kk, prod(lead))[i]``, from which its
  indices and codebooks come as in ``dense.py``. The families of the
  dense leading layers stack ``(first_k_dense_replace,)``: q|kv_a (K D,
  N H(dn+dr) + r + dr), wkv_b (r, H(dn+dv)), o (H dv, D), gate|up (D,
  2F), down (F, D). The expert layers stack ``(L - first_k,)``: q|kv_a,
  wkv_b, o, the shared experts' gate|up (D, 2 Fe S) and down (Fe S, D),
  and ``(L - first_k, E)``: each routed expert's gate|up (D, 2 Fe) and
  down (Fe, D).
* The router of expert layer l is ``normal(key_l, (D, E)) * (1 /
  sqrt(D))``, where ``key_l = split(split(split(ks[0], L - first_k)[l],
  4)[1], 5)[0]``, rounded to bfloat16 as the serving path rounds every
  dense leaf of 65536 elements or more (the routers of all expert
  layers together; at the published widths they are).

The embedding and all layers are one compiled program (the expert
layers one scan), and each VQ weight is dequantized with one reduction
per codebook, which keeps the reference's compile to a fraction of its
per-layer form. Matrix products run at ``precision="highest"``.
``control=True``
computes the same forward with every product's operands rounded to
float8 e4m3 (``dense.py``'s control), the router included.
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

from bench.reference import dense

POSITIONS = dense.POSITIONS
_BF16_MIN_SIZE = 65536   # dense serving leaves this large are bf16


@dataclasses.dataclass(frozen=True)
class Dims:
    L: int
    first: int
    D: int
    F: int
    H: int
    dn: int
    dr: int
    dv: int
    r: int
    E: int
    k: int
    Fe: int
    shared: int
    norm_topk: bool
    V: int
    theta: float
    eps: float
    yarn: Tuple[float, int, float, float, float, float]
    C: int
    d: int
    n: int

    @classmethod
    def of(cls, conf: Dict[str, Any]) -> "Dims":
        c, s = conf["config"], conf["serving"]
        expect = {"hidden_act": "silu", "tie_word_embeddings": False,
                  "attention_bias": False, "q_lora_rank": None,
                  "scoring_func": "softmax", "topk_method": "greedy",
                  "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
                  "routed_scaling_factor": 1}
        for key, v in expect.items():
            if c.get(key) != v:
                raise ValueError(f"the moe reference implements {key}={v!r}, "
                                 f"the configuration states {c.get(key)!r}")
        if c.get("sliding_window"):
            raise ValueError("the moe reference has no sliding window")
        rs = c["rope_scaling"]
        if rs.get("type") != "yarn":
            raise ValueError(f"the moe reference implements YaRN rotary "
                             f"scaling, the configuration states {rs!r}")
        return cls(
            L=int(c["num_hidden_layers"]), first=int(c["first_k_dense_replace"]),
            D=int(c["hidden_size"]), F=int(c["intermediate_size"]),
            H=int(c["num_attention_heads"]), dn=int(c["qk_nope_head_dim"]),
            dr=int(c["qk_rope_head_dim"]), dv=int(c["v_head_dim"]),
            r=int(c["kv_lora_rank"]), E=int(c["n_routed_experts"]),
            k=int(c["num_experts_per_tok"]),
            Fe=int(c["moe_intermediate_size"]),
            shared=int(c["n_shared_experts"]),
            norm_topk=bool(c["norm_topk_prob"]),
            V=int(c["vocab_size"]), theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]),
            yarn=(float(rs["factor"]),
                  int(rs["original_max_position_embeddings"]),
                  float(rs["beta_fast"]), float(rs["beta_slow"]),
                  float(rs["mscale"]), float(rs["mscale_all_dim"])),
            C=int(s["vq_C"]), d=int(s["vq_d"]), n=int(s["vq_n"]))

    @property
    def Vp(self) -> int:
        return (self.V + 127) // 128 * 128

    def attention(self) -> Tuple[Tuple[int, int], ...]:
        """(K, N) of q|kv_a, wkv_b and o."""
        H = self.H
        return ((self.D, H * (self.dn + self.dr) + self.r + self.dr),
                (self.r, H * (self.dn + self.dv)), (H * self.dv, self.D))


def _keys(key, shape: Tuple[int, ...]) -> jax.Array:
    """Per-member keys of the VQ family of stacked ``shape``, shaped
    ``lead + (2,)``."""
    kk = jax.random.fold_in(key, zlib.crc32(str(shape).encode()) % (2 ** 31))
    lead = shape[:-2]
    return jax.random.split(kk, int(np.prod(lead))).reshape(*lead, 2)


def layer_keys(dims: Dims, key) -> Dict[str, jax.Array]:
    """Keys of every VQ family, stacked per layer (and per expert)."""
    Lp, Lb = dims.first, dims.L - dims.first
    (Ka, Na), (Kb, Nb), (Ko, No) = dims.attention()
    Fs = dims.Fe * dims.shared
    return {
        "pre_attn": jnp.stack([_keys(key, (Lp, Ka, Na)), _keys(key, (Lp, Kb, Nb)),
                               _keys(key, (Lp, Ko, No))], axis=1),
        "pre_mlp": jnp.stack([_keys(key, (Lp, dims.D, 2 * dims.F)),
                              _keys(key, (Lp, dims.F, dims.D))], axis=1),
        "attn": jnp.stack([_keys(key, (Lb, Ka, Na)), _keys(key, (Lb, Kb, Nb)),
                           _keys(key, (Lb, Ko, No))], axis=1),
        "shared": jnp.stack([_keys(key, (Lb, dims.D, 2 * Fs)),
                             _keys(key, (Lb, Fs, dims.D))], axis=1),
        "experts": jnp.stack([_keys(key, (Lb, dims.E, dims.D, 2 * dims.Fe)),
                              _keys(key, (Lb, dims.E, dims.Fe, dims.D))],
                             axis=2),
    }


@functools.partial(jax.jit, static_argnames=("dims",))
def router_weights(key, *, dims: Dims) -> jax.Array:
    """(L - first, D, E) routers as the serving leaf holds them, in
    float32."""
    ks = jax.random.split(key, 5)
    lks = jax.random.split(ks[0], dims.L - dims.first)

    def one(k):
        k = jax.random.split(jax.random.split(k, 4)[1], 5)[0]
        return (jax.random.normal(k, (dims.D, dims.E), jnp.float32)
                * (1.0 / math.sqrt(dims.D)))

    wr = jax.vmap(one)(lks)
    if wr.size >= _BF16_MIN_SIZE:
        wr = wr.astype(jnp.bfloat16).astype(jnp.float32)
    return wr


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1.0 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(dims: Dims) -> np.ndarray:
    """DeepseekV2YarnRotaryEmbedding's frequencies of the dr rope
    dimensions."""
    factor, orig, fast, slow, _, _ = dims.yarn
    dim, base = dims.dr, dims.theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def softmax_scale(dims: Dims) -> float:
    factor, _, _, _, _, m_all = dims.yarn
    return _mscale(factor, m_all) ** 2 / math.sqrt(dims.dn + dims.dr)


def _rope(x, dims: Dims):
    """x (R, T, heads, dr) at positions 0..T-1, rotate-half pairing, cos
    and sin scaled by mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)."""
    factor, _, _, _, m, m_all = dims.yarn
    mag = _mscale(factor, m) / _mscale(factor, m_all)
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * jnp.asarray(yarn_inv_freq(dims)))
    cos = mag * jnp.cos(ang)[None, :, None]
    sin = mag * jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _dequant(idx, cb):
    """``dense._dequant`` with one reduction per codebook in place of one
    per codebook and sub-vector element: element (v*d + j, col) is the
    sum over c of ``cb[c, j, idx[c, v, col]]``, the same sums."""
    C, d, k = cb.shape
    entries = jnp.arange(k, dtype=jnp.int32)[:, None, None, None]
    w = 0.0
    for c in range(C):
        hit = idx[c].astype(jnp.int32)[None, :, None] == entries  # (k, K/d, 1, cols)
        w = w + jnp.sum(jnp.where(hit, cb[c].T[:, None, :, None], 0.0),
                        axis=0)                                 # (K/d, d, cols)
    return w.reshape(-1, idx.shape[-1])


def _linear(x, key, K: int, N: int, dims: Dims, control: bool):
    """x @ W for one layer's VQ family, as ``dense._vq_linear``: W
    rebuilt from its key and dequantized a block of columns at a time."""
    idx = jax.random.randint(key, (dims.C, K // dims.d, N), 0, 2 ** dims.n
                             ).astype(jnp.uint8)
    cb = jax.random.normal(key, (dims.C, dims.d, 2 ** dims.n)) \
        / np.sqrt(K * dims.C)
    cols = dense._COLS
    nb = -(-N // cols)
    idx = jnp.pad(idx, ((0, 0), (0, 0), (0, nb * cols - N)))
    blocks = idx.reshape(dims.C, K // dims.d, nb, cols).transpose(2, 0, 1, 3)
    y = jax.lax.map(lambda blk: dense._mm(x, _dequant(blk, cb), control),
                    blocks)                                     # (nb, ..., cols)
    y = jnp.moveaxis(y, 0, -2)
    return y.reshape(*y.shape[:-2], nb * cols)[..., :N]


def _mla(x, keys, dims: Dims, control: bool):
    R, T, _ = x.shape
    H, dn, dr, dv, r = dims.H, dims.dn, dims.dr, dims.dv, dims.r
    (Ka, Na), (Kb, Nb), (Ko, No) = dims.attention()
    h = dense._rmsnorm(x, dims.eps)
    qa = _linear(h, keys[0], Ka, Na, dims, control)
    q = qa[..., :H * (dn + dr)].reshape(R, T, H, dn + dr)
    lat = dense._rmsnorm(qa[..., H * (dn + dr):H * (dn + dr) + r], dims.eps)
    k_rope = _rope(qa[..., H * (dn + dr) + r:][:, :, None, :], dims)
    kv = _linear(lat, keys[1], Kb, Nb, dims, control).reshape(R, T, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], dims)], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (R, T, H, dr))], -1)
    v = kv[..., dn:]
    if control:
        q, k, v = dense._rne8(q, -1), dense._rne8(k, -1), dense._rne8(v, 1)
    s = jnp.einsum("rqhd,rshd->rhqs", q, k, precision="highest") \
        * softmax_scale(dims)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if control:
        p = dense._rne8(p, -1)
    o = jnp.einsum("rhqs,rshd->rqhd", p, v, precision="highest")
    return x + _linear(o.reshape(R, T, H * dv), keys[2], Ko, No, dims,
                       control)


def _swiglu(h, keys, F: int, dims: Dims, control: bool):
    gu = _linear(h, keys[0], dims.D, 2 * F, dims, control)
    a = jax.nn.silu(gu[..., :F]) * gu[..., F:]
    return _linear(a, keys[1], F, dims.D, dims, control)


@functools.partial(jax.jit, static_argnames=("dims", "control"))
def _dense_layer(x, attn_keys, mlp_keys, *, dims: Dims, control: bool):
    x = _mla(x, attn_keys, dims, control)
    return x + _swiglu(dense._rmsnorm(x, dims.eps), mlp_keys, dims.F, dims,
                       control)


def route(h, wr, dims: Dims, control: bool) -> jax.Array:
    """(..., E) gate of each expert: its softmax score where it is among
    the token's top k (ties to the lower index), else 0."""
    gates = jax.nn.softmax(dense._mm(h, wr, control), axis=-1)
    kth = jax.lax.top_k(gates, dims.k)[1]
    w = jnp.sum(jax.nn.one_hot(kth, dims.E, dtype=gates.dtype), axis=-2) * gates
    if dims.norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w


@functools.partial(jax.jit, static_argnames=("dims", "control"))
def _moe_layer(x, attn_keys, shared_keys, expert_keys, wr, *, dims: Dims,
               control: bool):
    x = _mla(x, attn_keys, dims, control)
    h = dense._rmsnorm(x, dims.eps)
    w = route(h, wr, dims, control)                         # (R, T, E)

    def expert(y, args):
        keys, we = args
        return y + we[..., None] * _swiglu(h, keys, dims.Fe, dims, control), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (expert_keys, jnp.moveaxis(w, -1, 0)))
    return x + y + _swiglu(h, shared_keys, dims.Fe * dims.shared, dims,
                           control)


@functools.partial(jax.jit, static_argnames=("dims",))
def _embed(key, tokens, *, dims: Dims):
    ks = jax.random.split(key, 5)
    emb = (jax.random.normal(ks[1], (dims.Vp, dims.D)) * 0.02
           ).astype(jnp.bfloat16)
    return jnp.take(emb, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "control"))
def _head(key, x, rows, cols, *, dims: Dims, control: bool):
    ks = jax.random.split(key, 5)
    w = (jax.random.normal(ks[3], (dims.D, dims.Vp)) * (1.0 / math.sqrt(dims.D))
         ).astype(jnp.bfloat16)
    h = dense._rmsnorm(x[rows, cols], dims.eps)
    cols_per = dense._VOCAB_COLS
    outs = [dense._mm(h, w[:, lo:min(dims.Vp, lo + cols_per)].astype(jnp.float32),
                      control)
            for lo in range(0, dims.Vp, cols_per)]
    return jnp.concatenate(outs, axis=-1)[:, :dims.V]


@functools.partial(jax.jit, static_argnames=("dims", "control"))
def _layers(key, tokens, *, dims: Dims, control: bool):
    """The embedding and every layer, in one program: the dense leading
    layers, then the expert layers as one scan over their stacked keys
    and routers."""
    x = _embed(key, tokens, dims=dims)
    lk = layer_keys(dims, key)
    for layer in range(dims.first):
        x = _dense_layer(x, lk["pre_attn"][layer], lk["pre_mlp"][layer],
                         dims=dims, control=control)

    def moe(x, args):
        attn, shared, experts, wr = args
        return _moe_layer(x, attn, shared, experts, wr, dims=dims,
                          control=control), None

    x, _ = jax.lax.scan(moe, x, (lk["attn"], lk["shared"], lk["experts"],
                                 router_weights(key, dims=dims)))
    return x


def logits(conf: Dict[str, Any], seed: int, tokens: np.ndarray,
           rows: Sequence[int], cols: Sequence[int], *,
           control: bool = False) -> jax.Array:
    """Float32 logits (P, vocab) at positions ``(rows[i], cols[i])`` of
    ``tokens`` (R, T): the prediction for position ``cols[i] + 1`` of row
    ``rows[i]``. Rows are independent sequences (routing is per token, so
    padding after a row's end changes none of its positions)."""
    dims = Dims.of(conf)
    key = dense.root_key(seed)
    P = len(rows)
    pad = -(-P // POSITIONS) * POSITIONS - P
    rows = np.concatenate([np.asarray(rows, np.int32), np.zeros(pad, np.int32)])
    cols = np.concatenate([np.asarray(cols, np.int32), np.zeros(pad, np.int32)])
    with jax.default_matmul_precision("highest"):
        x = _layers(key, jnp.asarray(tokens, jnp.int32), dims=dims,
                    control=control)
        out = [_head(key, x, jnp.asarray(rows[i:i + POSITIONS]),
                     jnp.asarray(cols[i:i + POSITIONS]), dims=dims,
                     control=control)
               for i in range(0, P + pad, POSITIONS)]
        return jnp.concatenate(out, axis=0)[:P]
