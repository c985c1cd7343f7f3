"""The DeepSeek-V2-Lite cell at smoke widths on the CPU: its configuration
file states the program's published sizes, the MoE reference rebuilds
the program's weights, the engine's prefill and cached decode agree with
the reference's logits while the float8 control does not, the reference
refuses what it does not implement, and the expert roofline's work
functions and reader."""
import copy
import importlib.util
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_smoke as smoke
from bench.lib import moe_work, registry

_spec = importlib.util.spec_from_file_location(
    "bench_run_moe", registry.BENCH / "run.py")
bench_run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_run)

CELL = "deepseek-v2-lite.long-decode-32"
# the program's smoke configuration (repro.configs.deepseek_v2_lite.SMOKE)
# in the configuration file's keys
SMALL = {"num_hidden_layers": 3, "hidden_size": 128, "intermediate_size": 512,
         "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 48,
         "vocab_size": 512, "kv_lora_rank": 64, "qk_nope_head_dim": 32,
         "qk_rope_head_dim": 16, "v_head_dim": 32, "n_routed_experts": 8,
         "num_experts_per_tok": 2, "moe_intermediate_size": 256}
# smoke widths, 4 clients, seeds 5-7: the program's widest gap 0.0147,
# 0, 0 (bf16 weights and activations against float32), the float8
# control's 1.554, 0.755, 0.730; the limit lies 6.8 times above the one
# and 7.3 times below the other
LIMIT = 0.1


@pytest.fixture
def small(monkeypatch):
    """The smoke-width configuration file, with the program's smoke
    configuration standing for ``deepseek_v2_lite``."""
    import repro.configs as configs

    real = configs.get_config
    monkeypatch.setattr(configs, "get_config", lambda a: (
        configs.get_smoke_config(a) if a == "deepseek_v2_lite" else real(a)))
    conf = copy.deepcopy(registry.config("deepseek-v2-lite"))
    conf["config"].update(SMALL)
    conf["config"]["rope_scaling"] = dict(
        conf["config"]["rope_scaling"], original_max_position_embeddings=64)
    return conf


def test_configuration_file_states_the_program_config():
    """The file's MoE, MLA and YaRN keys are the program configuration's
    fields, published and run alike (nothing reduced)."""
    from repro.configs import get_config

    conf = registry.config("deepseek-v2-lite")
    c, cfg = conf["config"], get_config(conf["program_arch"])
    assert c == conf["published"] and conf["reduced"] == []
    entry = {e["name"]: e for e in registry.benchmark()["configs"]}[
        "deepseek-v2-lite"]
    assert entry["reduced"] == [] and entry["source"] == conf["source"]
    rs = c["rope_scaling"]
    pairs = [
        (c["num_hidden_layers"], cfg.num_layers), (c["hidden_size"], cfg.d_model),
        (c["intermediate_size"], cfg.d_ff),
        (c["num_attention_heads"], cfg.num_heads),
        (c["num_key_value_heads"], cfg.num_kv_heads),
        (c["vocab_size"], cfg.vocab_size), (c["kv_lora_rank"], cfg.kv_lora_rank),
        (c["qk_nope_head_dim"], cfg.qk_nope_dim),
        (c["qk_rope_head_dim"], cfg.qk_rope_dim),
        (c["qk_nope_head_dim"] + c["qk_rope_head_dim"], cfg.head_dim),
        (c["v_head_dim"], cfg.v_head_dim),
        (c["n_routed_experts"], cfg.num_experts),
        (c["num_experts_per_tok"], cfg.top_k),
        (c["moe_intermediate_size"], cfg.moe_d_ff),
        (c["n_shared_experts"], cfg.num_shared_experts),
        (c["first_k_dense_replace"], cfg.first_dense_layers),
        (c["norm_topk_prob"], cfg.norm_topk_prob),
        (c["routed_scaling_factor"], 1),
        (c["rope_theta"], cfg.rope_theta), (c["rms_norm_eps"], cfg.norm_eps),
        (rs["factor"], cfg.yarn_factor),
        (rs["original_max_position_embeddings"], cfg.yarn_original_max_pos),
        (rs["beta_fast"], cfg.yarn_beta_fast),
        (rs["beta_slow"], cfg.yarn_beta_slow),
        (rs["mscale"], cfg.yarn_mscale),
        (rs["mscale_all_dim"], cfg.yarn_mscale_all_dim)]
    assert all(a == b for a, b in pairs), pairs
    assert cfg.family == conf["family"] == "moe" and cfg.use_mla
    # every number of the published config sits at the file's top level
    assert all(conf[k] == v for k, v in conf["published"].items()
               if k in ("hidden_size", "kv_lora_rank", "n_routed_experts",
                        "rope_scaling", "vocab_size"))


def test_reference_weights_are_the_programs(small):
    """The reference rebuilds the synthetic weights from the seed with
    its own code; at smoke widths they equal the program's: indices bit
    for bit and codebooks to one rounding for an attention, a dense-MLP,
    a shared-expert and a routed-expert family, the router to one
    rounding, and the embedding."""
    from bench.lib.loop import model_config
    from repro.models.api import build_model

    ref = registry.reference("moe")
    seed = 2 ** 31 + 11
    params = build_model(model_config(small)).init_synthetic(
        jax.random.PRNGKey(seed % 2 ** 32))
    dims = ref.Dims.of(small)
    key = jax.random.PRNGKey(seed % 2 ** 32)
    keys = ref.layer_keys(dims, key)
    (Ka, Na), (Kb, Nb), _ = dims.attention()
    body, pre = params["layers"], params["pre_layers"]

    def same(leaf, k, K, N):
        idx = jax.random.randint(k, (dims.C, K // dims.d, N), 0,
                                 2 ** dims.n).astype(jnp.uint8)
        cb = jax.random.normal(k, (dims.C, dims.d, 2 ** dims.n)) \
            / np.sqrt(K * dims.C)
        np.testing.assert_array_equal(idx, leaf.idx)
        np.testing.assert_allclose(cb, leaf.codebooks, rtol=3e-7, atol=0)

    same(jax.tree_util.tree_map(lambda a: a[0], pre["attn"]["wq_kva"]["vq"]),
         keys["pre_attn"][0, 0], Ka, Na)
    same(jax.tree_util.tree_map(lambda a: a[0], pre["mlp"]["down"]["vq"]),
         keys["pre_mlp"][0, 1], dims.F, dims.D)
    for layer in range(dims.L - dims.first):
        at = lambda a: a[layer]  # noqa: E731
        same(jax.tree_util.tree_map(at, body["attn"]["wkv_b"]["vq"]),
             keys["attn"][layer, 1], Kb, Nb)
        same(jax.tree_util.tree_map(at, body["moe"]["shared"]["gu"]["vq"]),
             keys["shared"][layer, 0], dims.D, 2 * dims.Fe * dims.shared)
        for e in (0, 3, dims.E - 1):
            ex = body["moe"]["experts"]
            same(jax.tree_util.tree_map(lambda a: a[layer, e], ex["gu"]["vq"]),
                 keys["experts"][layer, e, 0], dims.D, 2 * dims.Fe)
            same(jax.tree_util.tree_map(lambda a: a[layer, e],
                                        ex["down"]["vq"]),
                 keys["experts"][layer, e, 1], dims.Fe, dims.D)
    # one rounding, as the codebooks (XLA's fusion of the normal draw)
    np.testing.assert_allclose(
        ref.router_weights(key, dims=dims),
        np.asarray(body["moe"]["router"]["wr"], np.float32), rtol=3e-7,
        atol=0)
    tokens = jnp.arange(12, dtype=jnp.int32).reshape(2, 6)
    np.testing.assert_array_equal(
        ref._embed(key, tokens, dims=dims),
        jnp.take(params["embedding"]["emb"], tokens, axis=0
                 ).astype(jnp.float32))


def test_reference_yarn_matches_the_program():
    from repro.configs import get_config
    from repro.models import common as cm

    ref = registry.reference("moe")
    dims = ref.Dims.of(registry.config("deepseek-v2-lite"))
    cfg = get_config("deepseek_v2_lite")
    np.testing.assert_allclose(ref.yarn_inv_freq(dims),
                               cm.yarn_inv_freq(64, cfg), rtol=1e-7)
    assert ref.softmax_scale(dims) == pytest.approx(
        cm.mla_softmax_scale(cfg), rel=1e-12)


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("sliding_window", 4096),
    ("topk_method", "group_limited_greedy"), ("scoring_func", "sigmoid"),
    ("routed_scaling_factor", 16.0)])
def test_reference_refuses_what_it_does_not_implement(key, value):
    conf = copy.deepcopy(registry.config("deepseek-v2-lite"))
    conf["config"][key] = value
    with pytest.raises(ValueError):
        registry.reference("moe").Dims.of(conf)


def test_engine_agrees_with_the_reference_and_the_control_does_not(
        small, tmp_path, monkeypatch):
    """The harness's own check at smoke widths (Pallas in interpret
    mode): what the engine served (bucketed prefill, then cached absorbed
    decode through the grouped kernel) lies within LIMIT of the
    reference's best logit at every compared position; the float8
    control, judged by the same limit, does not."""
    cache = next(smoke.isolated_cache(tmp_path, monkeypatch))
    result, lines = bench_run.measure(
        CELL, 5, 1.0, False, interpret=True, conf=small,
        mix=smoke.mix(clients=4, outputs=(8, 12)),
        limits=smoke.limits(LIMIT), device_kind="TPU v5 lite", control=True,
        cache_dir=cache)
    assert result["correct"], lines
    assert result["readings"]["compared_tokens"] >= 8
    assert not result["control"]["correct"], lines
    assert result["control"]["checks"]["max_logit_gap"]["value"] > LIMIT


def test_expert_work_hand_values():
    """Per visited expert: gate|up 2048 x 2816 and down 1408 x 2048 at
    2 bits (K*N/4 bytes of indices), 16 KiB of codebooks each and f32
    scales; per routed row bf16 activations in and out."""
    ex = moe_work.Experts(registry.config("deepseek-v2-lite"))
    assert ex.visit_bytes() == (2048 * 2816 // 4 + 16384 + 2816 * 4
                                + 1408 * 2048 // 4 + 16384 + 2048 * 4)
    assert ex.row_bytes() == 2 * (2048 + 2816) + 2 * (1408 + 2048)
    pk = {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12}
    least = moe_work.least_seconds(ex, 61.3 * 26, 32 * 6 * 26, pk)
    assert least == pytest.approx(
        (61.3 * 26 * ex.visit_bytes() + 32 * 6 * 26 * ex.row_bytes()) / 819e9)
    assert 4e-3 < least < 5e-3


def test_expert_roofline_reader_needs_the_counters_and_the_kernel():
    """The reader returns nothing where the run has no routing counters
    (a program without them) or no grouped kernel in the trace, and the
    least time over the kernel's time otherwise."""
    reader = registry.metric_reader("eva_moe_roofline.decode")
    conf = registry.config("deepseek-v2-lite")

    class Run:
        def __init__(self, d):
            self.d = d

        def delta(self, name):
            return self.d[name]

    class Trace:
        def __init__(self, s):
            self.s = s

        def kernel_s(self, kernels, programs):
            assert kernels == ("grouped_vq_matmul",)
            return self.s

    pk = {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12}
    w = types.SimpleNamespace(trace=Trace(0.2), conf=conf, peak=pk,
                              run=Run({}))
    assert reader.read(w) is None
    w.run = Run({"moe_expert_visits": 1594.0, "moe_routed_rows": 4992.0})
    got = reader.read(w)
    want = 100 * moe_work.least_seconds(moe_work.Experts(conf), 1594.0,
                                        4992.0, pk) / 0.2
    assert got == pytest.approx(want)
    w.trace = Trace(0.0)
    assert reader.read(w) is None
    w.trace = None
    assert reader.read(w) is None
