"""Per-architecture smoke tests (required): reduced same-family config,
one forward + one train step on CPU, asserting output shapes + no NaNs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import SHAPES, build_model
from repro.models.common import RunConfig
from repro.optim import AdamWConfig, adamw_init, adamw_update

KEY = jax.random.PRNGKey(0)
B, S = 2, 16


def _batch(cfg):
    batch = {
        "tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
    }
    if cfg.family == "whisper":
        batch["frames"] = jax.random.normal(KEY, (B, 16, cfg.d_model), jnp.float32)
    if cfg.family == "vision":
        batch["image_embeds"] = jax.random.normal(KEY, (B, 8, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    rc = RunConfig(mode="train", remat=False, attn_chunk=8)
    logits, _ = model.forward(params, _batch(cfg), rc)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))


# the two slowest train-step archs (~40 s each on the CI host) are tier-2;
# every family keeps test_forward_shapes_and_finite as its fast smoke
_SLOW_TRAIN_ARCHS = ("recurrentgemma_2b", "deepseek_v2_lite")


@pytest.mark.parametrize("arch", [
    pytest.param(a, marks=pytest.mark.slow) if a in _SLOW_TRAIN_ARCHS else a
    for a in ARCH_IDS
])
def test_one_train_step(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    rc = RunConfig(mode="train", remat=True, attn_chunk=8)
    ocfg = AdamWConfig(lr=1e-3)
    opt = adamw_init(params, ocfg)
    batch = _batch(cfg)

    loss0, grads = jax.value_and_grad(lambda p: model.loss(p, batch, rc))(params)
    new_params, opt, gnorm = adamw_update(grads, opt, params, ocfg)
    loss1 = model.loss(new_params, batch, rc)
    assert np.isfinite(float(loss0)) and np.isfinite(float(loss1))
    assert float(gnorm) > 0
    # one step on the same batch should reduce loss
    assert float(loss1) < float(loss0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The full (dry-run) configs carry the exact published dimensions."""
    cfg = get_config(arch)
    expected = {
        "minitron_4b": (32, 3072, 24, 8, 9216, 256000),
        "qwen3_0_6b": (28, 1024, 16, 8, 3072, 151936),
        "llama3_8b": (32, 4096, 32, 8, 14336, 128256),
        "qwen2_72b": (80, 8192, 64, 8, 29568, 152064),
        "whisper_medium": (24, 1024, 16, 16, 4096, 51865),
        "xlstm_125m": (12, 768, 4, 4, 0, 50304),
        "deepseek_v2_lite": (27, 2048, 16, 16, 10944, 102400),
        "mixtral_8x22b": (56, 6144, 48, 8, 16384, 32768),
        "recurrentgemma_2b": (26, 2560, 10, 1, 7680, 256000),
        "llama_3_2_vision_11b": (40, 4096, 32, 8, 14336, 128256),
        "llama2_7b": (32, 4096, 32, 32, 11008, 32000),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected, (got, expected)


def test_moe_extras():
    ds = get_config("deepseek_v2_lite")
    assert (ds.num_experts, ds.num_shared_experts, ds.top_k) == (64, 2, 6)
    assert ds.kv_lora_rank == 512 and ds.use_mla
    mx = get_config("mixtral_8x22b")
    assert (mx.num_experts, mx.top_k, mx.sliding_window) == (8, 2, 4096)


def test_input_specs_cover_assigned_shapes():
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k", "long_500k"}
    assert SHAPES["train_4k"] == (4096, 256, "train")
    assert SHAPES["prefill_32k"] == (32768, 32, "prefill")
    assert SHAPES["decode_32k"] == (32768, 128, "decode")
    assert SHAPES["long_500k"] == (524288, 1, "decode")
    m = build_model(get_config("llama3_8b"))
    kind, specs = m.input_specs("decode_32k")
    assert kind == "decode"
    assert specs["tokens"].shape == (128, 1)
    assert "caches" in specs


def test_long_500k_applicability():
    """long_500k only for sub-quadratic archs (DESIGN.md §4)."""
    runs = {a: build_model(get_config(a)).supports_shape("long_500k")
            for a in ARCH_IDS}
    assert runs["xlstm_125m"] and runs["recurrentgemma_2b"] and runs["mixtral_8x22b"]
    for a in ("minitron_4b", "qwen3_0_6b", "llama3_8b", "qwen2_72b",
              "whisper_medium", "deepseek_v2_lite", "llama_3_2_vision_11b"):
        assert not runs[a], a


@pytest.mark.parametrize("arch", ["llama3_8b", "mixtral_8x22b", "xlstm_125m"])
def test_param_specs_no_allocation(arch):
    """Full-size param specs build instantly via eval_shape (no device mem)."""
    model = build_model(get_config(arch))
    specs = model.param_specs()
    total = sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(specs))
    assert total > 1e8  # full-size model described without allocating
    qspecs = model.param_specs(quantized=True)
    assert any(
        hasattr(x, "dtype") and x.dtype == jnp.uint8
        for x in jax.tree_util.tree_leaves(qspecs)
    )
