"""Shared model building blocks: configs, norms, rotary embeddings,
linear layers (dense / int8 / VQ), attention variants (GQA, SWA, local,
MLA), MoE, and cache containers.

Everything is pure-functional: params are pytrees of arrays (or VQWeight
nodes after quantization), and every block is written to be scanned over a
stacked leading layer axis with jax.lax.scan.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.vq import KVQuantConfig, VQWeight, kv_decode, kv_encode
from repro.core import ops as core_ops
from repro.core import plan as plan_mod
from repro.core.plan import PlanPolicy

Params = Any
PyTree = Any


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | xlstm | rglru | whisper | vision
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    # attention options
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    # YaRN rotary scaling (deepseek-v2 rope_scaling); yarn_factor 0 is
    # plain rotary
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    sliding_window: int = 0          # >0: SWA for all attn layers (mixtral)
    local_window: int = 0            # >0: local attention window (recurrentgemma)
    # MLA (deepseek)
    use_mla: bool = False
    kv_lora_rank: int = 0
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    # MoE
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    first_dense_layers: int = 0
    norm_topk_prob: bool = True      # renormalize the top-k gates to sum 1
    # hybrid (recurrentgemma): layers % pattern applied in order
    rec_pattern: Tuple[str, ...] = ()   # e.g. ("rec", "rec", "attn")
    d_rnn: int = 0
    conv_width: int = 4
    # xlstm
    xlstm_pattern: Tuple[str, ...] = ()  # e.g. ("mlstm", "slstm")
    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    encoder_layers: int = 0
    # vision (llama-3.2-vision): one cross-attn layer per `cross_attn_period`
    cross_attn_period: int = 0
    # misc
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # VQ config (paper defaults: d=8, n=8, C=q)
    vq_d: int = 8
    vq_n: int = 8
    vq_C: int = 2

    @property
    def act_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 128 for TP-friendly sharding
        (whisper's 51865 -> 51968; see DESIGN.md §4)."""
        return ((self.vocab_size + 127) // 128) * 128


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Static execution-mode knobs threaded through every block.

    How a matmul executes is a single typed field: ``plan_policy``
    (core/plan.py PlanPolicy) — vq_mode, impl, epilogue + block_v,
    int8_prefill and interpret in one frozen, validated object. Every
    linear layer derives a LinearSpec from its (input, weight) and
    fetches a MatmulPlan from the LRU-cached Planner under this policy;
    the plan carries the chosen backend and all resolved numbers
    (epilogue kind, v-blocks, kernel tiles), so nothing is re-derived at
    execute time. Contradictory policies raise ValueError at
    construction, not at the first matmul.

        RunConfig(mode="decode",
                  plan_policy=PlanPolicy(vq_mode="eva", impl="pallas"))

    The PR-3 flat-knob shims (vq_mode/impl/int8_prefill/interpret/
    epilogue/epilogue_block_v as RunConfig fields) finished their
    deprecation cycle and are REMOVED — constructing a RunConfig with
    one raises TypeError; to derive a config with a different execution
    knob, use the policy-replace helper:

        rc.replace_policy(vq_mode="dequant")

    Non-execution knobs (mode, attention chunking, remat, the §Perf
    levers) stay flat fields.
    """
    mode: str = "train"          # train | prefill | decode
    plan_policy: PlanPolicy = PlanPolicy()  # execution policy (see above)
    attn_chunk: int = 1024       # kv/q chunk for blocked attention
    attn_skip_oob_chunks: bool = False  # hillclimb: skip fully-masked chunks
    remat: bool = True
    # ---- perf-iteration levers (EXPERIMENTS.md §Perf) ----
    lm_head_last_only: bool = False  # prefill: project only the last token
    kv_cache_int8: bool = False      # int8-quantized KV cache (GQA decode)
    kv_cache_int4: bool = False      # int4-quantized KV cache (more aggressive)
    # vector-quantized KV cache (core/vq.py KVQuantConfig; frozen and
    # hashable). Carries the scale variant the append-time encoder must
    # use; cache detection itself is structural (uint8 "k"/"latent_s")
    kv_vq: Optional[KVQuantConfig] = None

    @property
    def policy(self) -> PlanPolicy:
        """The execution policy (alias of ``plan_policy``)."""
        return self.plan_policy

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)

    def replace_policy(self, **kw) -> "RunConfig":
        """Derive a RunConfig with some policy knobs replaced, e.g.
        ``rc.replace_policy(vq_mode="dequant")``."""
        return dataclasses.replace(
            self, plan_policy=dataclasses.replace(self.plan_policy, **kw))


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def _dense_init(key, K, N, dtype=jnp.float32):
    scale = 1.0 / math.sqrt(K)
    return jax.random.normal(key, (K, N), dtype) * scale


def make_linear(key, K, N, *, bias=False, dtype=jnp.float32) -> Params:
    p = {"w": _dense_init(key, K, N, dtype)}
    if bias:
        p["b"] = jnp.zeros((N,), dtype)
    return p


# ---------------------------------------------------------------------------
# Linear apply — the single place where EVA enters the model
# ---------------------------------------------------------------------------


def linear(p: Params, x: jax.Array, rc: RunConfig, *, out_dtype=None) -> jax.Array:
    """Apply a (possibly VQ-quantized) linear layer under the current
    execution mode.

      train           -> dense bf16/fp32 matmul
      prefill (+int8) -> int8 GEMM (paper's reconfigurable-PE INT8 mode)
      decode  (vq)    -> EVA VQ-GEMM + OC lookup (or dequant baseline)

    All formulation/impl/epilogue choice lives behind the plan API: the
    (spec, policy) pair resolves through the LRU-cached Planner to a
    MatmulPlan whose backend and tile numbers are frozen at plan time —
    this function contains no epilogue or impl branching, and inside a
    jitted step the planner is only consulted while tracing."""
    out_dtype = out_dtype or x.dtype
    pl = plan_mod.plan_node(p, x, mode=rc.mode, policy=rc.policy,
                            out_dtype=out_dtype)
    if "vq" in p:
        leaf = p["vq"]
    elif "vql" in p:
        leaf = p["vql"]
    else:
        leaf = p["w"]
    y = pl.execute(x, leaf)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def grouped_linear(p: Params, x: jax.Array, rc: RunConfig,
                   *, out_dtype=None) -> Tuple[jax.Array, ...]:
    """Apply a grouped-projection linear (one wide VQWeight holding a
    same-input family, e.g. [Wq|Wk|Wv]) and slice the output at the
    recorded split points.

    One EVA matmul serves the whole family: the VQ-GEMM / output-codebook
    computation is amortized over every member and the fused Pallas kernel
    sweeps one widened N with a single VMEM-resident OC scratch."""
    y = linear(p, x, rc, out_dtype=out_dtype)
    return core_ops.split_grouped_outputs(y, p["vq"])


# ---------------------------------------------------------------------------
# Norms & rotary
# ---------------------------------------------------------------------------


def make_rmsnorm(d: int) -> Params:
    return {"g": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * p["g"].astype(jnp.float32)).astype(dt)


def make_layernorm(d: int) -> Params:
    return {"g": jnp.ones((d,), jnp.float32), "b2": jnp.zeros((d,), jnp.float32)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["g"] + p["b2"]).astype(dt)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention-temperature factor 0.1 * mscale * ln(scale) + 1
    (1 for scale <= 1)."""
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, cfg: "ModelConfig") -> np.ndarray:
    """Rotary frequencies of ``dim`` dimensions under YaRN scaling, as
    DeepseekV2YarnRotaryEmbedding builds them: the plain frequencies
    ``freq_extra`` above the correction range, ``freq_extra / factor``
    below it, and a linear ramp between."""
    base, f = cfg.rope_theta, cfg.yarn_factor
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr_dim(rotations: float) -> float:
        return (dim * math.log(cfg.yarn_original_max_pos
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return (extra / f * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_tables(dim: int, cfg: "ModelConfig") -> Tuple[jax.Array, float]:
    """(inverse frequencies, cos/sin magnitude) of ``dim`` rotary
    dimensions: plain rotary, or YaRN's when ``cfg.yarn_factor`` is set."""
    if not cfg.yarn_factor:
        return rope_freqs(dim, cfg.rope_theta), 1.0
    mag = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
           / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return jnp.asarray(yarn_inv_freq(dim, cfg)), mag


def mla_softmax_scale(cfg: "ModelConfig") -> float:
    """MLA's attention scale: 1/sqrt(qk head dim), times YaRN's
    mscale(factor, mscale_all_dim) squared when YaRN is on."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def apply_rope(x: jax.Array, positions: jax.Array, theta: float, *,
               freqs: Optional[jax.Array] = None, mag: float = 1.0
               ) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32. Rotate-half pairing
    (dimension i with i + hd/2); ``freqs`` (hd/2,) and ``mag`` (the
    cos/sin magnitude) default to plain rotary at ``theta``."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                   # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = mag * jnp.cos(ang)[:, :, None, :]
    sin = mag * jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocked (flash-style) attention with online softmax
# ---------------------------------------------------------------------------


def _attn_chunk_scores(q, k, scale):
    # q: (B, Sq, H, hd), k: (B, Ck, Hk, hd) -> scores (B, H, Sq, Ck)
    B, Sq, H, hd = q.shape
    Hk = k.shape[2]
    group = H // Hk
    qg = q.reshape(B, Sq, Hk, group, hd)
    s = jnp.einsum("bshgd,bchd->bhgsc", qg.astype(jnp.float32), k.astype(jnp.float32))
    return (s * scale).reshape(B, Hk * group, Sq, k.shape[1])


def _attn_chunk_apply(p, v):
    # p: (B, H, Sq, Ck), v: (B, Ck, Hk, hd) -> (B, Sq, H, hd)
    B, H, Sq, Ck = p.shape
    Hk = v.shape[2]
    group = H // Hk
    pg = p.reshape(B, Hk, group, Sq, Ck)
    o = jnp.einsum("bhgsc,bchd->bshgd", pg, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hk * group, v.shape[-1])


def blocked_attention(
    q: jax.Array,              # (B, Sq, H, hd)
    k: jax.Array,              # (B, Skv, Hk, hd)
    v: jax.Array,              # (B, Skv, Hk, hd)
    *,
    causal: bool,
    window: int = 0,           # >0: only attend within `window` positions back
    q_offset: int = 0,         # absolute position of q[0] (for cached decode)
    chunk: int = 1024,
    skip_oob_chunks: bool = False,
    scale: Optional[float] = None,  # softmax scale; 1/sqrt(hd) by default
) -> jax.Array:
    """Memory-bounded attention: q processed in chunks (unrolled), kv scanned
    with online softmax. `skip_oob_chunks` statically skips kv chunks that
    are fully masked (causal future / outside the sliding window) — the
    'triangular schedule' perf option (§Perf)."""
    B, Sq, H, hd = q.shape
    hd_v = v.shape[-1]          # may differ from hd (MLA)
    Skv = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    cq = min(chunk, Sq)
    ck = min(chunk, Skv)
    # pad to multiples
    pq, pk = (-Sq) % cq, (-Skv) % ck
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = q.shape[1] // cq, k.shape[1] // ck

    k_chunks = k.reshape(B, nk, ck, *k.shape[2:]).transpose(1, 0, 2, 3, 4)
    v_chunks = v.reshape(B, nk, ck, *v.shape[2:]).transpose(1, 0, 2, 3, 4)
    kv_pos = (jnp.arange(nk * ck)).reshape(nk, ck)

    outs = []
    for iq in range(nq):
        qi = q[:, iq * cq:(iq + 1) * cq]
        q_pos = q_offset + iq * cq + jnp.arange(cq)          # (cq,)
        q_last = q_offset + iq * cq + cq - 1
        q_first = q_offset + iq * cq

        def kv_step(carry, inputs):
            m, l, acc = carry
            kc, vc, pos_c = inputs
            s = _attn_chunk_scores(qi, kc, scale)            # (B,H,cq,ck)
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= pos_c[None, :] <= q_pos[:, None]
            if window > 0:
                mask &= pos_c[None, :] > (q_pos[:, None] - window)
            # mask out kv padding
            mask &= (pos_c < Skv)[None, :]
            s = jnp.where(mask[None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            o = _attn_chunk_apply(p, vc)                     # (B,cq,H,hd)
            acc_new = acc * corr.transpose(0, 2, 1)[..., None] + o
            return (m_new, l_new, acc_new), None

        # choose which kv chunks this q chunk touches
        if skip_oob_chunks:
            sel = []
            for jk in range(nk):
                lo, hi = jk * ck, jk * ck + ck - 1
                if causal and lo > q_last:
                    continue
                if window > 0 and hi <= q_first - window:
                    continue
                sel.append(jk)
            sel = np.asarray(sel, np.int32)
        else:
            sel = np.arange(nk, dtype=np.int32)

        kc_sel = k_chunks[sel]
        vc_sel = v_chunks[sel]
        pos_sel = kv_pos[sel]
        m0 = jnp.full((B, H, cq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        a0 = jnp.zeros((B, cq, H, hd_v), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kc_sel, vc_sel, pos_sel))
        out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        outs.append(out)

    out = jnp.concatenate(outs, axis=1)[:, :Sq]
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,          # (B, Sq, H, hd) — Sq > 1 for speculative verify
    k_cache: jax.Array,    # (B, S, Hk, hd)
    v_cache: jax.Array,    # (B, S, Hk, hd)
    cache_len: jax.Array,  # (B,) valid lengths (ring caches pass full S)
    *,
    window: int = 0,
    ring: bool = False,
) -> jax.Array:
    """Attention over a (possibly ring-buffered) KV cache.

    ``cache_len`` counts entries INCLUDING the Sq queries just written:
    query i sits at absolute position ``cache_len - Sq + i`` and only
    attends entries at or before itself — at Sq == 1 this reduces to the
    classic ``pos < cache_len`` single-token mask. Ring (SWA) caches are
    single-token only."""
    B, S, Hk, hd = k_cache.shape
    Sq = q.shape[1]
    scale = 1.0 / math.sqrt(hd)
    s = _attn_chunk_scores(q, k_cache, scale)           # (B, H, Sq, S)
    pos = jnp.arange(S)[None, :]                        # (1, S)
    if ring:
        # ring buffer: every slot written within the last `window` steps is
        # valid once cache_len >= window; before that only slots < cache_len
        if Sq != 1:
            raise ValueError("ring caches decode one token at a time")
        valid = (pos < jnp.minimum(cache_len, S)[:, None])[:, None, :]
    else:
        qpos = cache_len[:, None] - Sq + jnp.arange(Sq)[None, :]  # (B, Sq)
        valid = pos[None] <= qpos[..., None]            # (B, Sq, S)
    s = jnp.where(valid[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = _attn_chunk_apply(p, v_cache)                   # (B,Sq,H,hd)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (covers dense archs, SWA, local attn, whisper self/cross)
# ---------------------------------------------------------------------------


def _paged_view(arena: jax.Array, block_table: jax.Array) -> jax.Array:
    """Gather a slot-contiguous view from a paged cache arena:
    ``(NB, bs, F...)`` indexed by a ``(B, W)`` block table ->
    ``(B, W*bs, F...)``. Sentinel ids (== NB) CLAMP to the last real
    block (never ``mode="fill"``: NaN fill values survive ``0 * NaN``
    through the masked softmax) — finite garbage the attention validity
    mask (``pos < len``) zeroes out. ``W*bs`` equals the contiguous
    cache's time length by construction (serve/paging.py), so
    downstream attention math is unchanged."""
    B, W = block_table.shape
    bs = arena.shape[1]
    view = jnp.take(arena, block_table, axis=0, mode="clip")
    return view.reshape((B, W * bs) + arena.shape[2:])


def _quantize_kv(x: jax.Array, dtype=jnp.int8):
    """Per-(token, head) symmetric int quantization of a K/V slice.
    x: (B, S, Hk, hd) -> (intN values, per-(B,S,Hk) scales)."""
    qmax = 127.0 if dtype == jnp.int8 else 7.0
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / qmax
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -qmax, qmax).astype(dtype)
    return q, scale.astype(jnp.bfloat16)


def _kvq_decode_attention(q, k_idx, v_idx, k_s, v_s, lengths, cb_k, cb_v,
                          rc: RunConfig, window: int) -> jax.Array:
    """Attend over a KV-VQ cache view (contiguous shape — paged callers
    gather first). Full-cache sites resolve through the planner: every
    backend matching kind="kvq_attn" (the dequantize-jnp oracle and,
    under impl="pallas", the fused kernel) is cost-ranked and the
    cheapest executes. Ring/SWA caches skip the planner — ring validity
    semantics live in decode_attention — and always dequantize, as do
    multi-query windows (speculative verify): the kvq_attn backends are
    single-query formulations."""
    if window == 0 and q.shape[1] == 1:
        B, S, Hk, idx_w = k_idx.shape
        H, hd = q.shape[2], q.shape[3]
        spec = plan_mod.kvq_attention_spec(
            B=B, S=S, H=H, Hk=Hk, hd=hd, idx_width=idx_w,
            entries=cb_k.shape[-2], x_dtype=q.dtype, out_dtype=q.dtype)
        kplan = plan_mod.plan(spec, rc.policy)
        return kplan.execute(
            (q, k_idx, v_idx, k_s, v_s, lengths, cb_k, cb_v), None)
    k_view = kv_decode(k_idx, k_s, cb_k)
    v_view = kv_decode(v_idx, v_s, cb_v)
    return decode_attention(q, k_view, v_view, lengths,
                            window=window, ring=window > 0)


def make_attention(key, cfg: ModelConfig, *, bias: Optional[bool] = None) -> Params:
    bias = cfg.qkv_bias if bias is None else bias
    ks = jax.random.split(key, 4)
    p = {
        "wq": make_linear(ks[0], cfg.d_model, cfg.q_dim, bias=bias),
        "wk": make_linear(ks[1], cfg.d_model, cfg.kv_dim, bias=bias),
        "wv": make_linear(ks[2], cfg.d_model, cfg.kv_dim, bias=bias),
        "wo": make_linear(ks[3], cfg.q_dim, cfg.d_model, bias=False),
    }
    if cfg.qk_norm:
        p["qnorm"] = make_rmsnorm(cfg.head_dim)
        p["knorm"] = make_rmsnorm(cfg.head_dim)
    return p


def attention_fwd(
    p: Params,
    x: jax.Array,                     # (B, S, D)
    rc: RunConfig,
    cfg: ModelConfig,
    *,
    positions: jax.Array,             # (B, S)
    cache: Optional[Dict] = None,     # {"k","v","len"} for decode
    window: int = 0,
    causal: bool = True,
    kv_source: Optional[jax.Array] = None,  # cross-attention memory (B, Skv, D)
) -> Tuple[jax.Array, Optional[Dict]]:
    B, S, D = x.shape
    H, Hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    kv_in = kv_source if kv_source is not None else x
    Skv_in = kv_in.shape[1]
    if "wqkv" in p:
        # grouped QKV (self-attention only — the quantization pass never
        # groups cross-attention): ONE wide EVA matmul, outputs sliced at
        # the recorded (q_dim, kv_dim, kv_dim) split points.
        if kv_source is not None:
            raise ValueError("grouped wqkv is invalid for cross-attention")
        q, k, v = grouped_linear(p["wqkv"], x, rc)
        q = q.reshape(B, S, H, hd)
        k = k.reshape(B, Skv_in, Hk, hd)
        v = v.reshape(B, Skv_in, Hk, hd)
    else:
        q = linear(p["wq"], x, rc).reshape(B, S, H, hd)
        k = linear(p["wk"], kv_in, rc).reshape(B, Skv_in, Hk, hd)
        v = linear(p["wv"], kv_in, rc).reshape(B, Skv_in, Hk, hd)

    if cfg.qk_norm:
        q = rmsnorm(p["qnorm"], q, cfg.norm_eps)
        k = rmsnorm(p["knorm"], k, cfg.norm_eps)
    if kv_source is None:  # rope only for self-attention
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if (rc.mode == "decode" and cache is not None and kv_source is None
            and "block_table" in cache):
        # paged decode (serve/paging.py): scatter the new token through
        # the slot's block table, attend over the gathered view. The
        # view is shape-identical to the contiguous cache, so the same
        # decode_attention / flash_decode math applies token-for-token;
        # sentinel rows (freed / mid-prefill slots) drop the write.
        bt = cache["block_table"]                      # (B, W)
        bs_blk = cache["k"].shape[1]
        W = bt.shape[1]
        Spage = W * bs_blk
        NB = cache["k"].shape[0]
        cache_len = cache["len"]                       # (B,)
        # S > 1: speculative verify writes the whole draft window at
        # absolute positions len..len+S-1; positions past the slot's
        # capacity route to the sentinel and drop (they can never belong
        # to an emitted token — the engine caps emission at `remaining`).
        pos_w = cache_len[:, None] + jnp.arange(S, dtype=cache_len.dtype)
        slot = (pos_w % Spage) if window > 0 else pos_w
        blk = jnp.take_along_axis(bt, jnp.clip(slot // bs_blk, 0, W - 1),
                                  axis=1)                # (B, S)
        phys = jnp.where(slot < Spage, blk, NB)
        off = slot % bs_blk
        new_len = cache_len + S
        if "k_s" in cache and cache["k"].dtype == jnp.uint8:
            # KV-VQ paged decode: encode the new token(s) against the
            # params-resident codebooks (p["kv_cb"]), scatter uint8
            # indices + scales through the block table, attend natively
            # over the compressed arena view.
            variant = rc.kv_vq.variant if rc.kv_vq is not None else "outlier"
            cb_k, cb_v = p["kv_cb"]["k"], p["kv_cb"]["v"]
            with jax.named_scope("kv_write"):
                k_idx, k_sc = kv_encode(k, cb_k, variant)
                v_idx, v_sc = kv_encode(v, cb_v, variant)
                k_arena = cache["k"].at[phys, off].set(k_idx, mode="drop")
                v_arena = cache["v"].at[phys, off].set(v_idx, mode="drop")
                ks_arena = cache["k_s"].at[phys, off].set(
                    k_sc.astype(cache["k_s"].dtype), mode="drop")
                vs_arena = cache["v_s"].at[phys, off].set(
                    v_sc.astype(cache["v_s"].dtype), mode="drop")
            with jax.named_scope("attend"):
                o = _kvq_decode_attention(
                    q, _paged_view(k_arena, bt), _paged_view(v_arena, bt),
                    _paged_view(ks_arena, bt), _paged_view(vs_arena, bt),
                    new_len, cb_k, cb_v, rc, window)
            new_cache = {"k": k_arena, "v": v_arena, "k_s": ks_arena,
                         "v_s": vs_arena, "len": new_len,
                         "block_table": bt}
        elif "k_s" in cache:
            cdt = cache["k"].dtype
            with jax.named_scope("kv_write"):
                kq, ks_ = _quantize_kv(k, cdt)
                vq_, vs_ = _quantize_kv(v, cdt)
                k_arena = cache["k"].at[phys, off].set(kq, mode="drop")
                v_arena = cache["v"].at[phys, off].set(vq_, mode="drop")
                ks_arena = cache["k_s"].at[phys, off].set(ks_, mode="drop")
                vs_arena = cache["v_s"].at[phys, off].set(vs_, mode="drop")
            with jax.named_scope("attend"):
                k_view = (_paged_view(k_arena, bt).astype(jnp.bfloat16)
                          * _paged_view(ks_arena, bt)[..., None].astype(jnp.bfloat16))
                v_view = (_paged_view(v_arena, bt).astype(jnp.bfloat16)
                          * _paged_view(vs_arena, bt)[..., None].astype(jnp.bfloat16))
                o = decode_attention(q, k_view, v_view, new_len,
                                     window=window, ring=window > 0)
            new_cache = {"k": k_arena, "v": v_arena, "k_s": ks_arena,
                         "v_s": vs_arena, "len": new_len,
                         "block_table": bt}
        else:
            with jax.named_scope("kv_write"):
                k_arena = cache["k"].at[phys, off].set(
                    k.astype(cache["k"].dtype), mode="drop")
                v_arena = cache["v"].at[phys, off].set(
                    v.astype(cache["v"].dtype), mode="drop")
            with jax.named_scope("attend"):
                if rc.policy.impl == "pallas" and window == 0 and S == 1:
                    from repro.kernels.flash_decode import flash_decode_paged

                    o = flash_decode_paged(q, k_arena, v_arena, bt, new_len,
                                           interpret=rc.policy.interpret)
                else:
                    o = decode_attention(
                        q, _paged_view(k_arena, bt), _paged_view(v_arena, bt),
                        new_len, window=window, ring=window > 0,
                    )
            new_cache = {"k": k_arena, "v": v_arena, "len": new_len,
                         "block_table": bt}
    elif rc.mode == "decode" and cache is not None and kv_source is None:
        # write the new token(s) into the (ring) cache. Multi-token
        # windows (speculative verify) scatter per position with
        # mode="drop" — NEVER dynamic_update_slice, whose clamped start
        # would shift the whole slab backward over committed entries
        # when len + S exceeds capacity.
        Sc = cache["k"].shape[1]
        cache_len = cache["len"]                       # (B,)
        pos_w = cache_len[:, None] + jnp.arange(S, dtype=cache_len.dtype)
        slot = (pos_w % Sc) if window > 0 else pos_w   # (B, S); OOB drops
        b_iota = jnp.arange(B)[:, None]
        kvq_cache = "k_s" in cache and cache["k"].dtype == jnp.uint8
        int8_cache = "k_s" in cache and not kvq_cache  # §Perf: int8/int4 KV
        if kvq_cache:
            # KV-VQ contiguous decode: encode the new tokens' K/V against
            # the per-head codebooks, write uint8 indices + scales into
            # the (ring) cache, attend via the planned backend
            variant = rc.kv_vq.variant if rc.kv_vq is not None else "outlier"
            cb_k, cb_v = p["kv_cb"]["k"], p["kv_cb"]["v"]
            with jax.named_scope("kv_write"):
                k_idx, k_sc = kv_encode(k, cb_k, variant)
                v_idx, v_sc = kv_encode(v, cb_v, variant)
                k_cache = cache["k"].at[b_iota, slot].set(k_idx, mode="drop")
                v_cache = cache["v"].at[b_iota, slot].set(v_idx, mode="drop")
                k_s = cache["k_s"].at[b_iota, slot].set(
                    k_sc.astype(cache["k_s"].dtype), mode="drop")
                v_s = cache["v_s"].at[b_iota, slot].set(
                    v_sc.astype(cache["v_s"].dtype), mode="drop")
            new_len = cache_len + S
            with jax.named_scope("attend"):
                o = _kvq_decode_attention(q, k_cache, v_cache, k_s, v_s,
                                          new_len, cb_k, cb_v, rc, window)
            new_cache = {"k": k_cache, "v": v_cache, "k_s": k_s, "v_s": v_s,
                         "len": new_len}
        elif int8_cache:
            cdt = cache["k"].dtype
            with jax.named_scope("kv_write"):
                kq, ks_ = _quantize_kv(k, cdt)
                vq_, vs_ = _quantize_kv(v, cdt)
                k_cache = cache["k"].at[b_iota, slot].set(kq, mode="drop")
                v_cache = cache["v"].at[b_iota, slot].set(vq_, mode="drop")
                k_s = cache["k_s"].at[b_iota, slot].set(ks_, mode="drop")
                v_s = cache["v_s"].at[b_iota, slot].set(vs_, mode="drop")
            new_len = cache_len + S
            with jax.named_scope("attend"):
                o = decode_attention(
                    q,
                    k_cache.astype(jnp.bfloat16) * k_s[..., None].astype(jnp.bfloat16),
                    v_cache.astype(jnp.bfloat16) * v_s[..., None].astype(jnp.bfloat16),
                    new_len, window=window, ring=window > 0,
                )
            new_cache = {"k": k_cache, "v": v_cache, "k_s": k_s, "v_s": v_s,
                         "len": new_len}
        else:
            with jax.named_scope("kv_write"):
                k_cache = cache["k"].at[b_iota, slot].set(
                    k.astype(cache["k"].dtype), mode="drop")
                v_cache = cache["v"].at[b_iota, slot].set(
                    v.astype(cache["v"].dtype), mode="drop")
            new_len = cache_len + S
            with jax.named_scope("attend"):
                if rc.policy.impl == "pallas" and window == 0 and S == 1:
                    from repro.kernels.flash_decode import flash_decode

                    o = flash_decode(q, k_cache, v_cache, new_len,
                                     interpret=rc.policy.interpret)
                else:
                    o = decode_attention(
                        q, k_cache, v_cache, new_len, window=window,
                        ring=window > 0,
                    )
            new_cache = {"k": k_cache, "v": v_cache, "len": new_len}
    elif rc.mode == "decode" and cache is not None and kv_source is not None:
        # cross-attention decode: static memory cache
        with jax.named_scope("attend"):
            o = decode_attention(q, cache["k"], cache["v"], cache["len"])
        new_cache = cache
    elif (cache is not None and "block_table" in cache
          and kv_source is None):
        # chunked-prefill continuation over a paged slot view
        # (serve/paging.slot_view): scatter this chunk's K/V through the
        # block table at their absolute positions, then attend over the
        # gathered view with the query offset at the committed history
        # length ``cache["len"]``. Pad positions beyond the chunk's true
        # length (``cache["prefill_len"]``) route to the sentinel and
        # drop, so bucket padding never corrupts committed prompt KV.
        if rc.mode != "prefill":
            raise ValueError(
                "paged cache reached attention_fwd outside decode/prefill")
        if "k_s" in cache:
            raise NotImplementedError(
                "chunked prefill over quantized (int8/KV-VQ) KV caches "
                "is not supported")
        if B != 1:
            raise ValueError(
                f"chunked-prefill continuation requires B == 1, got {B}")
        bt = cache["block_table"]                      # (1, W)
        bs_blk = cache["k"].shape[1]
        W = bt.shape[1]
        Spage = W * bs_blk
        NB = cache["k"].shape[0]
        hist = cache["len"]                            # (1,) committed len
        true_c = cache["prefill_len"]                  # (1,) chunk true len
        p0 = positions[0]                              # (S,) absolute
        idx = jnp.arange(S)
        valid = (idx < true_c[0]) & (p0 < Spage)
        blk_ids = jnp.take(bt[0], jnp.clip(p0 // bs_blk, 0, W - 1))
        phys = jnp.where(valid, blk_ids, NB)
        off = p0 % bs_blk
        with jax.named_scope("kv_write"):
            k_arena = cache["k"].at[phys, off].set(
                k[0].astype(cache["k"].dtype), mode="drop")
            v_arena = cache["v"].at[phys, off].set(
                v[0].astype(cache["v"].dtype), mode="drop")
        # traced q_offset forbids the static chunk-skip schedule
        with jax.named_scope("attend"):
            o = blocked_attention(
                q, _paged_view(k_arena, bt), _paged_view(v_arena, bt),
                causal=causal, window=window, q_offset=hist[0],
                chunk=rc.attn_chunk, skip_oob_chunks=False,
            )
        new_cache = {"k": k_arena, "v": v_arena, "len": hist + true_c,
                     "block_table": bt, "prefill_len": true_c}
    else:
        with jax.named_scope("attend"):
            o = blocked_attention(
                q, k, v,
                causal=causal, window=window,
                chunk=rc.attn_chunk, skip_oob_chunks=rc.attn_skip_oob_chunks,
            )
        if rc.mode == "prefill":
            new_cache = {"k": k, "v": v, "len": positions[:, -1] + 1}

    y = linear(p["wo"], o.reshape(B, S, H * hd), rc)
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA attention (deepseek-v2): compressed KV latent cache
# ---------------------------------------------------------------------------


def make_mla(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 6)
    H = cfg.num_heads
    qk_head = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq": make_linear(ks[0], cfg.d_model, H * qk_head),
        "wkv_a": make_linear(ks[1], cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_dim),
        "kv_norm": make_rmsnorm(cfg.kv_lora_rank),
        "wkv_b": make_linear(ks[2], cfg.kv_lora_rank, H * (cfg.qk_nope_dim + cfg.v_head_dim)),
        "wo": make_linear(ks[3], H * cfg.v_head_dim, cfg.d_model),
    }


def _mla_wkv_b(p: Params, rc: RunConfig, r: int, H: int, dn: int, dv: int
               ) -> Tuple[jax.Array, jax.Array]:
    """wkv_b as float32 (r, H, dn) key and (r, H, dv) value halves for
    the absorbed decode. A VQ'd wkv_b is rebuilt on every decode step,
    in every MLA layer: few bytes (r x H(dn+dv) weights), but as an XLA
    gather of one d-wide codebook row per index it took 27% of
    DeepSeek-V2-Lite's decode step on a v5e. Under impl="pallas" the
    ``vq_dequant`` kernel rebuilds it with in-register gathers, bit for
    bit ``dequantize``. A Mosaic kernel cannot be partitioned
    automatically, so under a mesh of several devices it runs in a
    ``shard_map`` on whole, replicated inputs (the indices of one layer
    are 0.5 MB)."""
    if "vq" in p["wkv_b"]:
        vq = p["wkv_b"]["vq"]
        if rc.policy.impl == "pallas":
            from repro.kernels.vq_dequant import vq_dequant

            def rebuild(vq):
                return vq_dequant(vq, interpret=rc.policy.interpret)

            mesh = _active_mesh()
            if mesh is not None:
                from jax.sharding import PartitionSpec as P

                rebuild = jax.shard_map(rebuild, mesh=mesh, in_specs=P(),
                                        out_specs=P(), check_vma=False)
            wb = rebuild(vq)
        else:
            from repro.core.vq import dequantize

            wb = dequantize(vq)
    else:
        wb = p["wkv_b"]["w"]
    wb = wb.astype(jnp.float32).reshape(r, H, dn + dv)
    return wb[..., :dn], wb[..., dn:]


def _einsum_f32(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """einsum over operands in their storage dtype with float32
    accumulation. XLA's CPU backend has no batched bfloat16 dot with a
    float32 result, so there the operands widen first (same values)."""
    if jax.default_backend() == "cpu":
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _mla_absorbed_attention(q_nope, q_rope, lat_cache, kr_cache, new_len,
                            Wk, Wv, scale: float) -> jax.Array:
    """Decode attention in the latent space: wkv_b folded into the query
    (``q_nope @ Wk``) and output (``@ Wv``) sides, so the S-length
    latent cache is read once, in its storage dtype, with float32
    accumulation, and never re-expanded. Returns (B, 1, H, dv) f32."""
    cdt = lat_cache.dtype
    f32 = jnp.float32
    q_eff = jnp.einsum("bshd,rhd->bshr", q_nope.astype(f32), Wk)  # (B,1,H,r)
    # queries are tiny — replicate them over 'model' so the scores stay
    # S-sharded like the latent cache (otherwise GSPMD all-to-alls the
    # whole cache to head-sharded layout, §Perf)
    dpq = ("pod", "data")
    q_eff = _maybe_constrain(q_eff.astype(cdt), (dpq, None, None, None))
    q_rope = _maybe_constrain(q_rope.astype(cdt), (dpq, None, None, None))
    s_nope = _einsum_f32("bshr,bSr->bhsS", q_eff, lat_cache)
    s_rope = _einsum_f32("bshd,bSd->bhsS", q_rope, kr_cache.astype(cdt))
    scores = (s_nope + s_rope) * scale
    valid = jnp.arange(lat_cache.shape[1])[None, :] < new_len[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    attn = jax.nn.softmax(scores, axis=-1)                        # (B,H,1,S)
    o_lat = _einsum_f32("bhsS,bSr->bshr", attn.astype(cdt), lat_cache)
    return jnp.einsum("bshr,rhv->bshv", o_lat, Wv)


def mla_fwd(
    p: Params,
    x: jax.Array,
    rc: RunConfig,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    cache: Optional[Dict] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    """Multi-head Latent Attention: KV compressed to (kv_lora_rank +
    qk_rope_dim) per token — the decode cache stores only the latent.
    Prefill expands the latent through wkv_b; decode runs absorbed
    (``_mla_absorbed_attention``). Rotary (YaRN when configured) covers
    the qk_rope dimensions, and the softmax scale is
    ``mla_softmax_scale``."""
    B, S, D = x.shape
    H = cfg.num_heads
    dn, dr, dv, r = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    freqs, mag = rope_tables(dr, cfg)
    scale = mla_softmax_scale(cfg)

    if "wq_kva" in p:
        # grouped q + kv_a (both consume x): ONE wide EVA matmul sliced at
        # the recorded (H*(dn+dr), r+dr) split points — the VQ-GEMM /
        # output-codebook stage is shared by both projections.
        q, kv_a = grouped_linear(p["wq_kva"], x, rc)
        q = q.reshape(B, S, H, dn + dr)
    else:
        q = linear(p["wq"], x, rc).reshape(B, S, H, dn + dr)
        kv_a = linear(p["wkv_a"], x, rc)                  # (B, S, r + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, freqs=freqs,
                        mag=mag)

    latent, k_rope = kv_a[..., :r], kv_a[..., r:]
    latent = rmsnorm(p["kv_norm"], latent, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        freqs=freqs, mag=mag)             # (B,S,1,dr)

    new_cache = None
    if (cache is not None and "block_table" in cache
            and rc.mode != "decode"):
        raise NotImplementedError(
            "chunked prefill for MLA latent caches is not supported "
            "(serve/engine.py gates chunking off for use_mla models)")
    if rc.mode == "decode" and cache is not None:
        if S != 1:
            raise ValueError("MLA decode runs one token per step")
        cache_len = cache["len"]
        new_len = cache_len + 1
        variant = rc.kv_vq.variant if rc.kv_vq is not None else "outlier"
        with jax.named_scope("kv_write"):
            if "block_table" in cache:
                # paged decode: scatter latent/k_rope through the block
                # table, attend over the gathered view — same math, view
                # shape == contiguous shape.
                bt = cache["block_table"]                  # (B, W)
                bs_blk = cache["latent"].shape[1]
                Sc = bt.shape[1] * bs_blk
                slot = jnp.minimum(cache_len, Sc - 1)
                blk = jnp.take_along_axis(bt, (slot // bs_blk)[:, None],
                                          axis=1)[:, 0]
                off = slot % bs_blk

                def put(arena, val):
                    return arena.at[blk, off].set(
                        val.astype(arena.dtype).reshape(B, -1), mode="drop")

                def view(arena):
                    return _paged_view(arena, bt)
                new_cache = {"len": new_len, "block_table": bt}
            else:
                slot = jnp.minimum(cache_len, cache["latent"].shape[1] - 1)

                def put(c, val):
                    return jax.vmap(
                        lambda c_, s_, n: jax.lax.dynamic_update_slice(
                            c_, n, (s_, 0)))(
                        c, slot, val.astype(c.dtype).reshape(B, 1, -1))

                def view(c):
                    return c
                new_cache = {"len": new_len}
            new_cache["k_rope"] = put(cache["k_rope"], k_rope)
            if "latent_s" in cache:
                # KV-VQ latent: encode against the (single-"head") latent
                # codebook, write uint8 indices + scale, then dequantize
                # the view — the absorbed math below is layout-blind.
                cb_lat = p["kv_cb"]["lat"]                 # (1, R, E, vd)
                idx, sc = kv_encode(latent[:, :, None, :], cb_lat, variant)
                new_cache["latent"] = put(cache["latent"], idx)
                new_cache["latent_s"] = put(cache["latent_s"], sc)
                lat_cache = kv_decode(
                    view(new_cache["latent"])[:, :, None, :],
                    view(new_cache["latent_s"]), cb_lat)[:, :, 0, :]
            else:
                new_cache["latent"] = put(cache["latent"], latent)
                lat_cache = view(new_cache["latent"])      # (B, Sc, r)
            kr_cache = view(new_cache["k_rope"])           # (B, Sc, dr)
        with jax.named_scope("attend"):
            Wk, Wv = _mla_wkv_b(p, rc, r, H, dn, dv)
            o = _mla_absorbed_attention(q_nope, q_rope, lat_cache, kr_cache,
                                        new_len, Wk, Wv, scale
                                        ).astype(x.dtype)
    else:
        kv = linear(p["wkv_b"], latent, rc).reshape(B, S, H, dn + dv)
        k_nope, vv = kv[..., :dn], kv[..., dn:]
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        with jax.named_scope("attend"):
            o = blocked_attention(
                qq, kk, vv, causal=True, chunk=rc.attn_chunk,
                skip_oob_chunks=rc.attn_skip_oob_chunks, scale=scale,
            )
        if rc.mode == "prefill":
            new_cache = {
                "latent": latent, "k_rope": k_rope.reshape(B, S, dr),
                "len": positions[:, -1] + 1,
            }

    y = linear(p["wo"], o.reshape(B, S, H * dv), rc)
    return y, new_cache


# ---------------------------------------------------------------------------
# MLPs and MoE
# ---------------------------------------------------------------------------


def make_mlp(key, d_model: int, d_ff: int) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "gate": make_linear(ks[0], d_model, d_ff),
        "up": make_linear(ks[1], d_model, d_ff),
        "down": make_linear(ks[2], d_ff, d_model),
    }


def mlp_fwd(p: Params, x: jax.Array, rc: RunConfig) -> jax.Array:
    if "gu" in p:  # grouped gate+up: one wide EVA matmul, sliced
        g, u = grouped_linear(p["gu"], x, rc)
        return linear(p["down"], jax.nn.silu(g) * u, rc)
    return linear(p["down"], jax.nn.silu(linear(p["gate"], x, rc)) * linear(p["up"], x, rc), rc)


def make_gelu_mlp(key, d_model: int, d_ff: int) -> Params:
    ks = jax.random.split(key, 2)
    return {"up": make_linear(ks[0], d_model, d_ff, bias=True),
            "down": make_linear(ks[1], d_ff, d_model, bias=True)}


def gelu_mlp_fwd(p: Params, x: jax.Array, rc: RunConfig) -> jax.Array:
    return linear(p["down"], jax.nn.gelu(linear(p["up"], x, rc)), rc)


def make_moe(key, cfg: ModelConfig) -> Params:
    """Experts stored stacked on a leading E axis (EP-shardable)."""
    ks = jax.random.split(key, 5)
    E, dff = cfg.num_experts, cfg.moe_d_ff or cfg.d_ff
    def stack_init(k, K, N):
        return jax.vmap(lambda kk: _dense_init(kk, K, N))(jax.random.split(k, E))
    p = {
        "router": {"wr": _dense_init(ks[0], cfg.d_model, E)},
        "experts": {
            "gate": {"w": stack_init(ks[1], cfg.d_model, dff)},
            "up": {"w": stack_init(ks[2], cfg.d_model, dff)},
            "down": {"w": stack_init(ks[3], dff, cfg.d_model)},
        },
    }
    if cfg.num_shared_experts:
        p["shared"] = make_mlp(ks[4], cfg.d_model, dff * cfg.num_shared_experts)
    return p


def expert_linear(p: Params, rows: core_ops.ExpertRows, rc: RunConfig
                  ) -> jax.Array:
    """One linear of every expert over the rows routed to it: a grouped
    matmul over weights stacked on a leading expert axis. VQ experts
    plan through core/plan.py (the grouped EVA kernel under
    impl="pallas"); dense experts run one ragged matmul."""
    if "w" in p:
        return core_ops.grouped_fp_matmul(rows, p["w"])
    pl = plan_mod.plan_node(p, rows, mode=rc.mode, policy=rc.policy)
    return pl.execute(rows, p["vq"])


def _expert_ffn(ep: Params, rows: core_ops.ExpertRows, rc: RunConfig
                ) -> jax.Array:
    """SwiGLU of each row's expert: gate|up as one grouped linear (two
    when ungrouped), then down."""
    if "gu" in ep:
        g, u = core_ops.split_grouped_outputs(expert_linear(ep["gu"], rows, rc),
                                              ep["gu"]["vq"])
    else:
        g = expert_linear(ep["gate"], rows, rc)
        u = expert_linear(ep["up"], rows, rc)
    return expert_linear(ep["down"], rows._replace(x=jax.nn.silu(g) * u), rc)


def _maybe_constrain(x: jax.Array, spec_axes) -> jax.Array:
    """Apply a sharding constraint when running under a mesh context.

    spec_axes maps axis -> preferred mesh axis name (skipped when the
    axis is absent)."""
    try:
        from jax._src import mesh as mesh_lib
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = mesh_lib.thread_resources.env.physical_mesh
        if mesh.empty:
            return x
        parts = []
        for ax in spec_axes:
            if ax is None or (isinstance(ax, str) and ax not in mesh.axis_names):
                parts.append(None)
            elif isinstance(ax, tuple):
                sel = tuple(a for a in ax if a in mesh.axis_names)
                parts.append(sel if sel else None)
            else:
                parts.append(ax)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, PartitionSpec(*parts))
        )
    except Exception:  # no mesh / incompatible: run unconstrained
        return x


def _active_mesh():
    """The active mesh when it holds more than one device, else None."""
    try:
        from jax._src import mesh as mesh_lib

        mesh = mesh_lib.thread_resources.env.physical_mesh
    except Exception:
        return None
    return None if mesh.empty or mesh.size == 1 else mesh


def _expert_mesh(num_experts: int):
    """The active mesh when it shards the expert axis over 'model'
    (runtime/sharding.py does wherever the axis divides the experts),
    else None."""
    mesh = _active_mesh()
    if (mesh is None or "model" not in mesh.axis_names
            or mesh.shape["model"] == 1 or num_experts % mesh.shape["model"]):
        return None
    return mesh


def _held_experts(ep: Params, xt: jax.Array, topi: jax.Array,
                  topv: jax.Array, first, rc: RunConfig
                  ) -> Tuple[jax.Array, jax.Array]:
    """The routes ``topi``/``topv`` (T, k) that go to the experts ``ep``
    holds, experts ``first`` on: each expert runs over exactly its own
    rows and the gated outputs sum per token; routes to experts held
    elsewhere add nothing. Returns (y (T, D) float32, held experts with
    at least one row)."""
    T, k = topi.shape
    held = jax.tree_util.tree_leaves(ep)[0].shape[0]
    local = topi.reshape(T * k) - first
    with jax.named_scope("moe_route"):
        rows, dest = core_ops.expert_rows(
            jnp.repeat(xt, k, axis=0),
            jnp.where((local >= 0) & (local < held), local, held), held)
    with jax.named_scope("moe_experts"):
        y_rows = _expert_ffn(ep, rows, rc)                  # (R, D)
        y = jnp.take(y_rows, dest, axis=0, mode="fill", fill_value=0)
        y = jnp.sum(y.astype(jnp.float32).reshape(T, k, -1)
                    * topv[..., None], axis=1)
    return y, jnp.count_nonzero(rows.group_sizes)


def moe_fwd(p: Params, x: jax.Array, rc: RunConfig, cfg: ModelConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """Dropless token-choice top-k MoE.

    A float32 softmax over every expert's router logit scores each
    token; the greedy top-k are its routes, their gates renormalized
    only where ``cfg.norm_topk_prob``. Each device runs the experts it
    holds over exactly their own rows (``_held_experts``): on one chip
    every expert; where the mesh shards the expert axis over 'model',
    each shard its own experts over every token's routes, and the
    shards' outputs sum over 'model'. Shared experts add unweighted. No
    route is dropped, so a token's output does not depend on the rest of
    its batch. Returns (y, experts with at least one row)."""
    D = x.shape[-1]
    xt = x.reshape(-1, D)                                   # (T, D)
    E, k = cfg.num_experts, cfg.top_k

    with jax.named_scope("moe_route"):
        logits = core_ops.fp_matmul(xt, p["router"]["wr"].astype(xt.dtype),
                                    out_dtype=jnp.float32)  # (T, E)
        gates = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(gates, k)                # (T, k)
        if cfg.norm_topk_prob:
            topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    mesh = _expert_mesh(E)
    if mesh is None:
        y, visits = _held_experts(p["experts"], xt, topi, topv, 0, rc)
    else:
        from jax.sharding import PartitionSpec as P

        def shard(ep, xt, topi, topv):
            first = jax.lax.axis_index("model") * (E // mesh.shape["model"])
            y, visits = _held_experts(ep, xt, topi, topv, first, rc)
            return jax.lax.psum(y, "model"), jax.lax.psum(visits, "model")

        especs = jax.tree_util.tree_map(lambda _: P("model"), p["experts"])
        # unchecked: the grouped Pallas kernel's outputs carry no
        # varying-axes type
        y, visits = jax.shard_map(
            shard, mesh=mesh, in_specs=(especs, P(), P(), P()),
            out_specs=(P(), P()), check_vma=False)(p["experts"], xt, topi,
                                                   topv)
    y = y.astype(x.dtype)
    if cfg.num_shared_experts:
        y = y + mlp_fwd(p["shared"], xt, rc)
    return y.reshape(x.shape), visits


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def make_embedding(key, vocab: int, d: int) -> Params:
    return {"emb": jax.random.normal(key, (vocab, d), jnp.float32) * 0.02}


def embed(p: Params, tokens: jax.Array, dtype) -> jax.Array:
    return jnp.take(p["emb"], tokens, axis=0).astype(dtype)


@jax.named_scope("lm_head")
def lm_head(p: Params, x: jax.Array, rc: RunConfig, emb_params=None) -> jax.Array:
    if p is None:  # tied
        w = emb_params["emb"].T
        return core_ops.fp_matmul(x, w.astype(x.dtype), out_dtype=jnp.float32)
    return linear(p, x, rc, out_dtype=jnp.float32)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array, mask=None) -> jax.Array:
    """logits (B,S,V) fp32, labels (B,S) int32."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()
