"""Decoder-only transformer family.

Covers the dense GQA archs (minitron-4b, qwen3-0.6b, llama3-8b, qwen2-72b,
llama2-7b) and the MoE archs (mixtral-8x22b with SWA, deepseek-v2-lite with
MLA + shared/routed experts + a leading dense layer), whose expert layers
are dropless (models/common.py moe_fwd).

Layer stacks are scanned (stacked params, one layer's HLO regardless of
depth); `first_dense_layers` splits the stack into an unstacked prefix +
a scanned body (deepseek's layer 0 is a dense MLP).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models.common import ModelConfig, RunConfig


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: ModelConfig, *, moe: bool) -> Any:
    ks = jax.random.split(key, 4)
    p = {
        "attn_norm": cm.make_rmsnorm(cfg.d_model),
        "mlp_norm": cm.make_rmsnorm(cfg.d_model),
    }
    if cfg.use_mla:
        p["attn"] = cm.make_mla(ks[0], cfg)
    else:
        p["attn"] = cm.make_attention(ks[0], cfg)
    if moe:
        p["moe"] = cm.make_moe(ks[1], cfg)
    else:
        p["mlp"] = cm.make_mlp(ks[1], cfg.d_model, cfg.d_ff)
    return p


def init_params(key, cfg: ModelConfig) -> Any:
    ks = jax.random.split(key, 5)
    n_scan = cfg.num_layers - cfg.first_dense_layers
    is_moe = cfg.family == "moe"

    layer_keys = jax.random.split(ks[0], n_scan)
    stacked = jax.vmap(lambda k: _init_layer(k, cfg, moe=is_moe))(layer_keys)

    params = {
        "embedding": cm.make_embedding(ks[1], cfg.padded_vocab, cfg.d_model),
        "layers": stacked,
        "final_norm": cm.make_rmsnorm(cfg.d_model),
    }
    if cfg.first_dense_layers:
        pre_keys = jax.random.split(ks[2], cfg.first_dense_layers)
        params["pre_layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, moe=False)
        )(pre_keys)
    if not cfg.tie_embeddings:
        params["lm_head"] = cm.make_linear(ks[3], cfg.d_model, cfg.padded_vocab)
    return params


# ---------------------------------------------------------------------------
# layer forward
# ---------------------------------------------------------------------------


def _layer_fwd(
    lp: Any,
    x: jax.Array,
    rc: RunConfig,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    cache: Optional[Dict],
    moe: bool,
) -> Tuple[jax.Array, Optional[Dict]]:
    h = cm.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
    if cfg.use_mla:
        a, new_cache = cm.mla_fwd(
            lp["attn"], h, rc, cfg, positions=positions, cache=cache
        )
    else:
        a, new_cache = cm.attention_fwd(
            lp["attn"], h, rc, cfg,
            positions=positions, cache=cache, window=cfg.sliding_window,
        )
    x = x + a
    h = cm.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps)
    if moe:
        f, visits = cm.moe_fwd(lp["moe"], h, rc, cfg)
    else:
        f, visits = cm.mlp_fwd(lp["mlp"], h, rc), None
    return x + f, (new_cache, visits)


def _scan_layers(
    stacked: Any,
    x: jax.Array,
    rc: RunConfig,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    caches: Optional[Any],
    moe: bool,
):
    body = functools.partial(_layer_fwd, rc=rc, cfg=cfg, positions=positions, moe=moe)

    def step(carry, xs):
        lp, cache = xs
        if rc.remat and rc.mode == "train":
            fn = jax.checkpoint(
                lambda lp_, x_, c_: body(lp_, x_, cache=c_),
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
            return fn(lp, carry, cache)
        return body(lp, carry, cache=cache)

    if caches is None:
        x, (new_caches, visits) = jax.lax.scan(
            lambda c, lp: step(c, (lp, None)), x, stacked
        )
    else:
        x, (new_caches, visits) = jax.lax.scan(step, x, (stacked, caches))
    return x, new_caches, (None if visits is None else jnp.sum(visits))


# ---------------------------------------------------------------------------
# model forward (train / prefill / decode)
# ---------------------------------------------------------------------------


def forward(
    params: Any,
    tokens: jax.Array,            # (B, S) int32
    rc: RunConfig,
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,
    caches: Optional[Any] = None,  # {"pre": ..., "body": ...} stacked per layer
    stats: Optional[Dict[str, Any]] = None,
) -> Tuple[jax.Array, Optional[Any]]:
    """Logits and caches. ``stats``, when given, receives
    ``moe_expert_visits``: the experts with at least one routed row,
    summed over the MoE layers (an int32 traced in the same program)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = cm.embed(params["embedding"], tokens, cfg.act_dtype)
    is_moe = cfg.family == "moe"

    new_caches: Dict[str, Any] = {}
    if cfg.first_dense_layers:
        pre_caches = None if caches is None else caches["pre"]
        x, nc, _ = _scan_layers(
            params["pre_layers"], x, rc, cfg,
            positions=positions, caches=pre_caches, moe=False,
        )
        new_caches["pre"] = nc

    body_caches = None if caches is None else caches["body"]
    x, nc, visits = _scan_layers(
        params["layers"], x, rc, cfg,
        positions=positions, caches=body_caches, moe=is_moe,
    )
    new_caches["body"] = nc
    if stats is not None and visits is not None:
        stats["moe_expert_visits"] = visits.astype(jnp.int32)

    if rc.mode == "prefill" and rc.lm_head_last_only:
        x = x[:, -1:]  # §Perf: skip the vocab projection for prompt tokens
    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = cm.lm_head(
        params.get("lm_head"), x, rc, emb_params=params["embedding"]
    )
    out_caches = new_caches if caches is not None or rc.mode == "prefill" else None
    return logits, out_caches


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None,
               kv_int8: bool = False, kv_int4: bool = False,
               kvq=None) -> Any:
    """Stacked decode caches. SWA archs get a ring buffer of window size;
    kv_int8/int4 store quantized values + per-(token, head) bf16 scales
    (§Perf). ``kvq`` (a core.vq.KVQuantConfig) selects the vector-
    quantized layout instead: uint8 codebook indices (R*G per head) +
    the same per-(token, head) bf16 scale leaves — 4-bit or 2-bit KV
    riding the int8 ``k_s``/``v_s`` plumbing (codebooks live in params,
    see core/quantize.attach_kv_codebooks)."""
    dtype = dtype or cfg.act_dtype
    S = max_len if cfg.sliding_window == 0 else min(max_len, cfg.sliding_window)
    n_scan = cfg.num_layers - cfg.first_dense_layers
    if kvq is not None and (kv_int8 or kv_int4):
        raise ValueError("kvq is mutually exclusive with kv_int8/kv_int4")

    def one_layer(_):
        if cfg.use_mla:
            if kvq is not None:
                return {
                    "latent": jnp.zeros(
                        (batch, S, kvq.idx_width(cfg.kv_lora_rank)),
                        jnp.uint8),
                    "latent_s": jnp.zeros((batch, S, 1), jnp.bfloat16),
                    "k_rope": jnp.zeros((batch, S, cfg.qk_rope_dim), dtype),
                    "len": jnp.zeros((batch,), jnp.int32),
                }
            return {
                "latent": jnp.zeros((batch, S, cfg.kv_lora_rank), dtype),
                "k_rope": jnp.zeros((batch, S, cfg.qk_rope_dim), dtype),
                "len": jnp.zeros((batch,), jnp.int32),
            }
        if kvq is not None:
            w = kvq.idx_width(cfg.head_dim)
            return {
                "k": jnp.zeros((batch, S, cfg.num_kv_heads, w), jnp.uint8),
                "v": jnp.zeros((batch, S, cfg.num_kv_heads, w), jnp.uint8),
                "k_s": jnp.zeros((batch, S, cfg.num_kv_heads), jnp.bfloat16),
                "v_s": jnp.zeros((batch, S, cfg.num_kv_heads), jnp.bfloat16),
                "len": jnp.zeros((batch,), jnp.int32),
            }
        if kv_int8 or kv_int4:
            qdt = jnp.int4 if kv_int4 else jnp.int8
            return {
                "k": jnp.zeros((batch, S, cfg.num_kv_heads, cfg.head_dim), qdt),
                "v": jnp.zeros((batch, S, cfg.num_kv_heads, cfg.head_dim), qdt),
                "k_s": jnp.zeros((batch, S, cfg.num_kv_heads), jnp.bfloat16),
                "v_s": jnp.zeros((batch, S, cfg.num_kv_heads), jnp.bfloat16),
                "len": jnp.zeros((batch,), jnp.int32),
            }
        return {
            "k": jnp.zeros((batch, S, cfg.num_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, S, cfg.num_kv_heads, cfg.head_dim), dtype),
            "len": jnp.zeros((batch,), jnp.int32),
        }

    body = jax.vmap(one_layer)(jnp.arange(n_scan))
    caches = {"body": body}
    if cfg.first_dense_layers:
        caches["pre"] = jax.vmap(one_layer)(jnp.arange(cfg.first_dense_layers))
    return caches
