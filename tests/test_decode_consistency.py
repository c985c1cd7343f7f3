"""Serving-path integration: prefill + step-by-step decode reproduces the
full-sequence forward exactly (fp32, drop-free MoE), for every family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import build_model
from repro.core.plan import PlanPolicy
from repro.models.common import RunConfig
from repro.serve.kvcache import pad_prefill_cache

KEY = jax.random.PRNGKey(0)
B, S_PROMPT, N_GEN, CAP = 2, 12, 4, 32


def _fp32_cfg(arch):
    return dataclasses.replace(get_smoke_config(arch), dtype="float32")


def _extras(cfg):
    ex = {}
    if cfg.family == "whisper":
        ex["frames"] = jax.random.normal(KEY, (B, 16, cfg.d_model), jnp.float32)
    if cfg.family == "vision":
        ex["image_embeds"] = jax.random.normal(KEY, (B, 8, cfg.d_model), jnp.float32)
    return ex


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_full_forward(arch):
    cfg = _fp32_cfg(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    tokens = jax.random.randint(KEY, (B, S_PROMPT + N_GEN), 0, cfg.vocab_size)
    extras = _extras(cfg)

    logits_full, _ = model.forward(
        params, {"tokens": tokens, **extras},
        RunConfig(mode="train", remat=False, attn_chunk=8),
    )
    logits_pre, caches = model.prefill(
        params, {"tokens": tokens[:, :S_PROMPT], **extras},
        RunConfig(mode="prefill", remat=False, attn_chunk=8),
    )
    np.testing.assert_allclose(
        np.asarray(logits_pre[:, -1]), np.asarray(logits_full[:, S_PROMPT - 1]),
        rtol=1e-4, atol=1e-4,
    )
    window = cfg.sliding_window or cfg.local_window
    caches = pad_prefill_cache(caches, CAP, window=window)
    rc_d = RunConfig(mode="decode", remat=False)
    for t in range(S_PROMPT, S_PROMPT + N_GEN):
        pos = jnp.full((B, 1), t, jnp.int32)
        logits_d, caches = model.decode(params, tokens[:, t:t + 1], pos,
                                        caches, rc_d)
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0]), np.asarray(logits_full[:, t]),
            rtol=1e-4, atol=1e-4,
        )


@pytest.mark.parametrize("arch", ["llama2_7b", "mixtral_8x22b",
                                  "recurrentgemma_2b", "xlstm_125m",
                                  "deepseek_v2_lite"])
def test_quantized_decode_eva_equals_dequant(arch):
    """Paper's exactness claim at model level: the EVA path and the
    conventional dequant path produce identical logits."""
    cfg = _fp32_cfg(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    qparams = model.quantize(params, method="synthetic", key=KEY)
    tokens = jax.random.randint(KEY, (B, S_PROMPT + 1), 0, cfg.vocab_size)
    extras = _extras(cfg)
    _, caches = model.prefill(
        params, {"tokens": tokens[:, :S_PROMPT], **extras},
        RunConfig(mode="prefill", remat=False, attn_chunk=8),
    )
    window = cfg.sliding_window or cfg.local_window
    caches = pad_prefill_cache(caches, CAP, window=window)
    pos = jnp.full((B, 1), S_PROMPT, jnp.int32)
    tok = tokens[:, S_PROMPT:S_PROMPT + 1]
    l_eva, _ = model.decode(qparams, tok, pos, caches,
                            RunConfig(mode="decode", plan_policy=PlanPolicy(vq_mode="eva"), remat=False))
    l_deq, _ = model.decode(qparams, tok, pos, caches,
                            RunConfig(mode="decode", plan_policy=PlanPolicy(vq_mode="dequant"), remat=False))
    np.testing.assert_allclose(np.asarray(l_eva), np.asarray(l_deq),
                               rtol=1e-4, atol=1e-4)


def test_quantized_decode_pallas_impl():
    cfg = _fp32_cfg("llama2_7b")
    model = build_model(cfg)
    params = model.init(KEY)
    qparams = model.quantize(params, method="synthetic", key=KEY)
    caches = model.init_cache(B, CAP)
    pos = jnp.zeros((B, 1), jnp.int32)
    tok = jnp.zeros((B, 1), jnp.int32)
    l_jnp, _ = model.decode(qparams, tok, pos, caches,
                            RunConfig(mode="decode", plan_policy=PlanPolicy(vq_mode="eva"), remat=False))
    l_pal, _ = model.decode(
        qparams, tok, pos, caches,
        RunConfig(mode="decode", remat=False, plan_policy=PlanPolicy(
            vq_mode="eva", impl="pallas", interpret=True)),
    )
    np.testing.assert_allclose(np.asarray(l_jnp), np.asarray(l_pal),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow  # ~58 s (W+24 single-token steps); the fast SWA smoke
# stays in test_prefill_decode_matches_full_forward[mixtral_8x22b]
def test_ring_cache_swa_long_decode():
    """SWA ring cache: decoding far past the window stays consistent with
    a full-cache reference restricted to the window."""
    cfg = _fp32_cfg("mixtral_8x22b")  # sliding_window=64 in smoke
    model = build_model(cfg)
    params = model.init(KEY)
    W = cfg.sliding_window
    total = W + 24  # run well past one window
    tokens = jax.random.randint(KEY, (1, total), 0, cfg.vocab_size)

    logits_full, _ = model.forward(
        params, {"tokens": tokens},
        RunConfig(mode="train", remat=False, attn_chunk=16),
    )
    _, caches = model.prefill(
        params, {"tokens": tokens[:, :8]},
        RunConfig(mode="prefill", remat=False, attn_chunk=16),
    )
    caches = pad_prefill_cache(caches, W, window=W)
    rc_d = RunConfig(mode="decode", remat=False)
    for t in range(8, total):
        pos = jnp.full((1, 1), t, jnp.int32)
        logits_d, caches = model.decode(params, tokens[:, t:t + 1], pos,
                                        caches, rc_d)
    np.testing.assert_allclose(
        np.asarray(logits_d[:, 0]), np.asarray(logits_full[:, -1]),
        rtol=1e-3, atol=1e-3,
    )
