"""Dropless expert layer, grouped EVA kernel, absorbed MLA decode and
YaRN rotary (models/common.py, core/ops.py, kernels/grouped_vq_matmul),
at smoke widths on the CPU."""
import dataclasses
import json
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core import ops as core_ops
from repro.core.plan import PlanPolicy
from repro.core.vq import VQWeight, dequantize, synthetic_vq
from repro.models import build_model
from repro.models import common as cm
from repro.models.common import RunConfig
from repro.serve import Engine, EngineConfig, GenerationRequest, SamplingParams
from repro.serve.kvcache import pad_prefill_cache

KEY = jax.random.PRNGKey(0)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ------------------------------------------------------------------ YaRN


def test_yarn_frequencies_and_scale_hand_values():
    """DeepSeek-V2-Lite: low = floor(64 ln(4096/(32*2pi)) / (2 ln 1e4)) =
    10, high = ceil(64 ln(4096/(2pi)) / (2 ln 1e4)) = 23; below low the
    plain frequencies, above high those over 40, a linear ramp between;
    cos/sin magnitude mscale/mscale_all_dim = 1; softmax scale
    mscale(40, 0.707)^2 / sqrt(192) with mscale = 0.1*0.707*ln 40 + 1."""
    cfg = get_config("deepseek_v2_lite")
    inv = cm.yarn_inv_freq(64, cfg)
    extra = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, extra / 40 * ramp + extra * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m - 1.2608) < 1e-4
    assert cm.mla_softmax_scale(cfg) == pytest.approx(m * m / math.sqrt(192),
                                                      rel=1e-12)
    freqs, mag = cm.rope_tables(64, cfg)
    assert mag == 1.0
    np.testing.assert_allclose(np.asarray(freqs), inv)
    # no YaRN: plain rotary, scale 1/sqrt(qk head)
    plain = dataclasses.replace(cfg, yarn_factor=0.0)
    assert cm.rope_tables(64, plain)[1] == 1.0
    assert cm.mla_softmax_scale(plain) == pytest.approx(192 ** -0.5)


# ------------------------------------------------------- grouped layout


def _routes(case, S, E, rng):
    if case == "empty_experts":        # only experts 1 and 4 hit
        return rng.choice([1, 4], size=S)
    if case == "one_expert":
        return np.full(S, 2)
    return rng.integers(0, E, size=S)  # rows not a multiple of 8 per expert


@pytest.mark.parametrize("case,S", [("empty_experts", 12),
                                    ("one_expert", 19), ("mixed", 13)])
def test_expert_rows_layout(case, S):
    E, K = 6, 16
    rng = np.random.default_rng(S)
    e = jnp.asarray(_routes(case, S, E, rng), jnp.int32)
    x = jnp.asarray(rng.normal(size=(S, K)), jnp.float32)
    rows, dest = core_ops.expert_rows(x, e, E)
    t = core_ops.EXPERT_TILE
    counts = np.bincount(np.asarray(e), minlength=E)
    np.testing.assert_array_equal(np.asarray(rows.group_sizes),
                                  -(-counts // t) * t)
    np.testing.assert_array_equal(np.asarray(rows.x)[np.asarray(dest)],
                                  np.asarray(x))
    assert int(rows.tiles) == int(np.sum(-(-counts // t)))
    te = np.asarray(rows.tile_expert)
    starts = np.concatenate([[0], np.cumsum(-(-counts // t) * t)])
    for tile in range(te.size):
        r = tile * t
        if tile < int(rows.tiles):
            assert starts[te[tile]] <= r < starts[te[tile] + 1]
            # every row of a tile is its expert's (or padding)
            held = np.nonzero((np.asarray(dest) >= r)
                              & (np.asarray(dest) < r + t))[0]
            assert np.all(np.asarray(e)[held] == te[tile])
        else:
            assert te[tile] == te[int(rows.tiles) - 1]
    # padding rows stay zero
    pad = np.setdiff1d(np.arange(rows.x.shape[0]), np.asarray(dest))
    assert not np.any(np.asarray(rows.x)[pad])


def test_expert_rows_skip_routes_held_elsewhere():
    """A route to an expert numbered past the ones held (an expert-
    parallel shard's view) takes no row: its dest is the layout's
    height, and the held experts' rows and tiles are laid out alone."""
    E, K, S = 4, 16, 15
    rng = np.random.default_rng(3)
    e = rng.integers(0, 2 * E, size=S)
    x = jnp.asarray(rng.normal(size=(S, K)), jnp.float32)
    rows, dest = core_ops.expert_rows(x, jnp.asarray(e, jnp.int32), E)
    held, dest = e < E, np.asarray(dest)
    t = core_ops.EXPERT_TILE
    counts = np.bincount(e[held], minlength=E)
    assert 0 < held.sum() < S
    assert np.all(dest[~held] == rows.x.shape[0])
    np.testing.assert_array_equal(np.asarray(rows.x)[dest[held]],
                                  np.asarray(x)[held])
    np.testing.assert_array_equal(np.asarray(rows.group_sizes),
                                  -(-counts // t) * t)
    assert int(rows.tiles) == int(np.sum(-(-counts // t)))
    others = np.setdiff1d(np.arange(rows.x.shape[0]), dest[held])
    assert not np.any(np.asarray(rows.x)[others])


def _stacked_vq(E, K, N, key):
    ks = jax.random.split(key, E)
    idx = jax.vmap(lambda k: jax.random.randint(k, (2, K // 8, N), 0, 256)
                   )(ks).astype(jnp.uint8)
    cbs = jax.vmap(lambda k: jax.random.normal(k, (2, 8, 256)))(ks)
    scale = 1.0 + 0.1 * jax.random.normal(key, (E, N))
    return VQWeight(idx=idx, codebooks=cbs, scale=scale, K=K, N=N, d=8, n=8)


@pytest.mark.parametrize("case,S", [("empty_experts", 12),
                                    ("one_expert", 19), ("mixed", 13)])
def test_grouped_kernel_interpret_matches_jnp_and_dense(case, S):
    """The Pallas kernel (interpret mode) against its jnp path and against
    each row times its expert's dequantized weight: experts with no rows,
    one expert with every row, and per-expert row counts that are not a
    multiple of 8. K=256, N=640: one v-tile of 32 and five 128-lane
    chunks, more than the lookup unrolls, so it loops over them as at
    the published widths."""
    from repro.kernels.grouped_vq_matmul import grouped_vq_matmul

    E, K, N = 6, 256, 640
    rng = np.random.default_rng(S)
    e = jnp.asarray(_routes(case, S, E, rng), jnp.int32)
    x = jnp.asarray(rng.normal(size=(S, K)), jnp.float32)
    vq = _stacked_vq(E, K, N, jax.random.PRNGKey(S))
    rows, dest = core_ops.expert_rows(x, e, E)
    y_pal = grouped_vq_matmul(rows, vq, interpret=True)
    y_jnp = core_ops.grouped_eva_matmul(rows, vq)
    w = jax.vmap(dequantize)(vq)                              # (E, K, N)
    want = jnp.einsum("sk,skn->sn", x, w[e],
                      precision=jax.lax.Precision.HIGHEST)
    live = np.arange(int(rows.tiles) * core_ops.EXPERT_TILE)
    np.testing.assert_allclose(np.asarray(y_pal)[live],
                               np.asarray(y_jnp)[live], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_pal)[np.asarray(dest)],
                               np.asarray(want), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(
        np.asarray(core_ops.grouped_dequant_matmul(rows, vq))[np.asarray(dest)],
        np.asarray(want), rtol=1e-4, atol=1e-3)


# ------------------------------------------------------------- dropless


def _fp32_moe(arch="deepseek_v2_lite"):
    return dataclasses.replace(get_smoke_config(arch), dtype="float32")


@pytest.mark.parametrize("arch", ["deepseek_v2_lite", "mixtral_8x22b"])
def test_a_requests_logits_do_not_depend_on_its_batch(arch):
    """Dropless routing: a sequence's prefill and decode logits are the
    same served alone and beside three others (capacity dispatch dropped
    routes at this batch and changed them)."""
    cfg = _fp32_moe(arch)
    model = build_model(cfg)
    params = model.quantize(model.init(KEY), method="synthetic", key=KEY)
    rc = RunConfig(mode="decode", remat=False, attn_chunk=8,
                   plan_policy=PlanPolicy(vq_mode="eva"))
    S = 6
    toks = jax.random.randint(KEY, (4, S + 1), 0, cfg.vocab_size)
    out = {}
    for B in (1, 4):
        logits, caches = model.prefill(params, {"tokens": toks[:B, :S]}, rc)
        caches = pad_prefill_cache(caches, 16,
                                   window=cfg.sliding_window)
        pos = jnp.full((B, 1), S, jnp.int32)
        dec, _ = model.decode(params, toks[:B, S:], pos, caches, rc)
        out[B] = (np.asarray(logits[0]), np.asarray(dec[0]))
    for alone, batched in zip(out[1], out[4]):
        np.testing.assert_allclose(alone, batched, rtol=1e-5, atol=1e-5)


def test_moe_routes_every_token_with_unnormalized_gates():
    """DeepSeek-V2-Lite's gates: softmax over every expert, top-k kept as
    they are (norm_topk_prob false), no route dropped: the layer equals
    the sum over experts of gate x expert SwiGLU, plus shared experts."""
    cfg = _fp32_moe()
    p = cm.make_moe(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, cfg.d_model))
    rc = RunConfig(mode="train", remat=False)
    y, visits = cm.moe_fwd(p, x, rc, cfg)
    xt = x.reshape(-1, cfg.d_model)
    gates = jax.nn.softmax(xt @ p["router"]["wr"], axis=-1)
    top = jax.lax.top_k(gates, cfg.top_k)[1]
    w = jnp.sum(jax.nn.one_hot(top, cfg.num_experts), axis=1) * gates
    ex = p["experts"]
    want = sum(w[:, e:e + 1] * (jax.nn.silu(xt @ ex["gate"]["w"][e])
                                * (xt @ ex["up"]["w"][e])) @ ex["down"]["w"][e]
               for e in range(cfg.num_experts))
    want = want + cm.mlp_fwd(p["shared"], xt, rc)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.d_model),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    assert int(visits) == len(np.unique(np.asarray(top)))


# ----------------------------------------------------- absorbed decode


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 3e-2)])
def test_absorbed_mla_decode_matches_expanded(dtype, tol):
    """Decode attends in the latent space (wkv_b folded into the query
    and output sides); the expanded form re-expands every cached latent
    through wkv_b. Both from the same cache, at the smoke MLA widths with
    YaRN. bfloat16: the cache is read in bf16 with f32 accumulation,
    where the expanded form here runs in f32 from the same bf16 cache,
    so they agree to bf16 rounding of the scores' operands."""
    cfg = dataclasses.replace(get_smoke_config("deepseek_v2_lite"),
                              dtype=dtype)
    p = cm.make_mla(KEY, cfg)
    B, S = 2, 9
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S + 1, cfg.d_model)
                          ).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(S + 1, dtype=jnp.int32), (B, S + 1))
    rc_p = RunConfig(mode="prefill", remat=False, attn_chunk=4)
    _, cache = cm.mla_fwd(p, x[:, :S], rc_p, cfg, positions=pos[:, :S])
    cache = {k: (jnp.pad(v, ((0, 0), (0, 16 - S), (0, 0))) if v.ndim == 3
                 else v) for k, v in cache.items()}
    rc_d = RunConfig(mode="decode", remat=False)
    y_abs, new = cm.mla_fwd(p, x[:, S:], rc_d, cfg, positions=pos[:, S:],
                            cache=cache)
    # expanded: every cached latent through wkv_b, plain attention
    H, dn, dr, dv = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, \
        cfg.v_head_dim
    f32 = jnp.float32
    lat, kr = new["latent"].astype(f32), new["k_rope"].astype(f32)
    kv = (lat @ p["wkv_b"]["w"]).reshape(B, 16, H, dn + dv)
    qa = (x[:, S:].astype(f32) @ jnp.concatenate(
        [p["wq"]["w"], p["wkv_a"]["w"]], axis=1))
    q = qa[..., :H * (dn + dr)].reshape(B, 1, H, dn + dr)
    freqs, mag = cm.rope_tables(dr, cfg)
    q_rope = cm.apply_rope(q[..., dn:], pos[:, S:], cfg.rope_theta,
                           freqs=freqs, mag=mag)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        kr[:, :, None, :], (B, 16, H, dr))], -1)
    qq = jnp.concatenate([q[..., :dn], q_rope], -1)
    s = jnp.einsum("bqhd,bshd->bhqs", qq, k) * cm.mla_softmax_scale(cfg)
    s = jnp.where(jnp.arange(16)[None, None, None] <= S, s, -1e30)
    o = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, -1), kv[..., dn:])
    want = o.reshape(B, 1, H * dv) @ p["wo"]["w"]
    np.testing.assert_allclose(np.asarray(y_abs, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)


def test_absorbed_mla_decode_pallas_equals_jnp_bit_for_bit():
    """Under impl="pallas" the absorbed decode rebuilds a VQ'd wkv_b with
    the vq_dequant kernel, under impl="jnp" with ``dequantize``: the same
    W to the bit, so the same output and cache. The other projections
    stay dense so that wkv_b is the only piece the policy moves, and the
    activations float32 so that no rounding to bfloat16 hides a bit."""
    cfg = dataclasses.replace(get_smoke_config("deepseek_v2_lite"),
                              dtype="float32")
    H, dn, dv, r = cfg.num_heads, cfg.qk_nope_dim, cfg.v_head_dim, \
        cfg.kv_lora_rank
    p = dict(cm.make_mla(KEY, cfg))
    vq = synthetic_vq(jax.random.PRNGKey(3), r, H * (dn + dv))
    p["wkv_b"] = {"vq": dataclasses.replace(
        vq, scale=jax.random.uniform(jax.random.PRNGKey(4), (vq.N,),
                                     minval=0.5, maxval=2.0))}
    B, S = 2, 9
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S + 1, cfg.d_model)
                          ).astype(cfg.dtype)
    pos = jnp.broadcast_to(jnp.arange(S + 1, dtype=jnp.int32), (B, S + 1))
    _, cache = cm.mla_fwd(p, x[:, :S], RunConfig(mode="prefill", remat=False,
                                                 attn_chunk=4),
                          cfg, positions=pos[:, :S])
    cache = {k: (jnp.pad(v, ((0, 0), (0, 16 - S), (0, 0))) if v.ndim == 3
                 else v) for k, v in cache.items()}
    got = [cm.mla_fwd(p, x[:, S:], RunConfig(
        mode="decode", remat=False, plan_policy=PlanPolicy(
            vq_mode="eva", impl=impl, interpret=True)),
        cfg, positions=pos[:, S:], cache=cache) for impl in ("jnp", "pallas")]
    (y_jnp, c_jnp), (y_pal, c_pal) = got
    np.testing.assert_array_equal(np.asarray(y_pal, np.float32),
                                  np.asarray(y_jnp, np.float32))
    assert c_pal.keys() == c_jnp.keys()
    for k in c_jnp:
        np.testing.assert_array_equal(np.asarray(c_pal[k], np.float32),
                                      np.asarray(c_jnp[k], np.float32))


# --------------------------------------------------------------- engine


def _engine(arch, slots=4):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init_synthetic(KEY)
    rc = RunConfig(mode="decode", remat=False, attn_chunk=16,
                   plan_policy=PlanPolicy(vq_mode="eva"))
    return Engine(model, params, rc, EngineConfig(num_slots=slots,
                                                  max_len=48))


def test_engine_counts_routing_and_buckets_moe_prefill():
    eng = _engine("deepseek_v2_lite")
    cfg = eng.model.cfg
    assert eng._bucketed
    prompts = [np.arange(5 + 3 * i, dtype=np.int32) for i in range(4)]
    eng.generate(prompts, 5)
    m = eng.metrics()
    layers = cfg.num_layers - cfg.first_dense_layers
    assert m["moe_routed_rows"] == m["decode_steps"] * 4 * cfg.top_k * layers
    assert 0 < m["moe_expert_visits"] <= m["decode_steps"] * layers * \
        min(cfg.num_experts, 4 * cfg.top_k)
    # prompts of 5-14 tokens fall in two buckets: two prefill traces
    assert eng.trace_counts["prefill"] == 2
    assert eng.trace_counts["decode"] == 1


def test_dense_engine_returns_and_counts_no_routing():
    eng = _engine("minitron_4b", slots=2)
    out = eng._decode_fn(
        eng.params, eng.caches, *[jnp.asarray(a) for a in (
            np.zeros(2, np.int32), np.zeros(2, np.int32), eng.rng_keys,
            eng.temperature, eng.top_k, eng.top_p, eng.greedy, eng.stop_ids,
            eng.remaining, eng.active, np.zeros(2, np.float32))])
    assert out[0].shape == (2,)    # the token readback carries nothing else
    eng.generate([np.arange(6, dtype=np.int32)], 3)
    m = eng.metrics()
    assert m["moe_routed_rows"] == 0 and m["moe_expert_visits"] == 0


def test_engine_serves_a_request_alike_alone_and_in_a_full_batch():
    """Greedy tokens and their log-probabilities of one request, served
    alone and beside three others on the Pallas path."""
    cfg = get_smoke_config("deepseek_v2_lite")
    model = build_model(cfg)
    params = model.init_synthetic(KEY)
    rc = RunConfig(mode="decode", remat=False, attn_chunk=16,
                   plan_policy=PlanPolicy(vq_mode="eva", impl="pallas",
                                          interpret=True))
    sp = SamplingParams(logprobs=True)
    prompts = [np.arange(3 + i, 9 + i, dtype=np.int32) for i in range(4)]
    got = []
    for n in (1, 4):
        eng = Engine(model, params, rc, EngineConfig(num_slots=4, max_len=16))
        uids = [eng.submit(GenerationRequest(prompt=p, max_new_tokens=3,
                                             sampling=sp))
                for p in prompts[:n]]
        while not eng.idle:
            eng.step()
        out = eng.output(uids[0])
        got.append((out.tokens, out.logprobs))
    assert got[0][0] == got[1][0]
    np.testing.assert_allclose(got[0][1], got[1][1], rtol=1e-5, atol=1e-5)


# ------------------------------------------------------ expert parallel

_EP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_smoke_config
    from repro.core.plan import PlanPolicy
    from repro.models import build_model
    from repro.models import common as cm
    from repro.models.common import RunConfig

    cfg = get_smoke_config("deepseek_v2_lite")
    params = build_model(cfg).init_synthetic(jax.random.PRNGKey(0))
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 7, cfg.d_model)
                          ).astype(cfg.dtype)
    out = {}
    for impl in ("jnp", "pallas"):
        rc = RunConfig(mode="decode", remat=False, plan_policy=PlanPolicy(
            vq_mode="eva", impl=impl, interpret=True))
        fn = jax.jit(lambda p, x: cm.moe_fwd(p, x, rc, cfg))
        y1, v1 = fn(p, x)
        for shape in ((2, 2), (1, 4)):
            mesh = jax.make_mesh(shape, ("data", "model"))
            rep = NamedSharding(mesh, P())
            ps = dict(jax.tree_util.tree_map(lambda a: jax.device_put(a, rep),
                                             p))
            ps["experts"] = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, NamedSharding(mesh, P("model"))),
                p["experts"])
            with mesh:
                hlo = fn.lower(ps, x).compile().as_text()
                y, v = fn(ps, x)
            out[f"{impl} {shape}"] = {
                "diff": float(np.max(np.abs(np.asarray(y, np.float32)
                                            - np.asarray(y1, np.float32)))),
                "scale": float(np.max(np.abs(np.asarray(y1, np.float32)))),
                "visits": [int(v), int(v1)],
                "all_gather": hlo.count("all-gather"),
                "all_reduce": hlo.count("all-reduce")}
    # training: float32 dense experts, gradients through the shards
    import dataclasses
    import jax.numpy as jnp
    cfg = dataclasses.replace(cfg, dtype="float32")
    p = cm.make_moe(jax.random.PRNGKey(2), cfg)
    x = x.astype(jnp.float32)
    rc = RunConfig(mode="train", remat=False)
    grad = jax.jit(jax.grad(lambda p, x: jnp.sum(
        cm.moe_fwd(p, x, rc, cfg)[0] ** 2), argnums=(0, 1)))
    g1 = grad(p, x)
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    ps = dict(p, experts=jax.tree_util.tree_map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P("model"))),
        p["experts"]))
    with mesh:
        g2 = grad(ps, x)
    out["grad_rel"] = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
              / np.max(np.abs(np.asarray(a))))
        for a, b in zip(jax.tree_util.tree_leaves(g1),
                        jax.tree_util.tree_leaves(g2)))
    print("RESULT" + json.dumps(out))
""")


def test_expert_parallel_layer_matches_one_device():
    """Where a mesh shards the expert axis over 'model', each shard runs
    its own experts over every token's routes and the outputs sum over
    'model': the layer equals the one-device layer (jnp path and the
    Pallas kernel in interpret mode, meshes (data 2, model 2) and
    (1, 4)), counts the same experts hit, and gathers no expert's
    weights (no all-gather in the compiled layer; one all-reduce at
    least); its training gradients equal the one-device ones."""
    env = dict(os.environ, PYTHONPATH=SRC, TF_CPP_MIN_LOG_LEVEL="2")
    proc = subprocess.run([sys.executable, "-c", _EP_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][-1]
    got = json.loads(line[len("RESULT"):])
    # float32 sums in another order: rounding only
    assert got.pop("grad_rel") < 1e-5
    for case, r in got.items():
        # bf16 outputs: the shards' float32 partial sums add in another
        # order than one device's, then round once to bfloat16
        assert r["diff"] <= 1e-2 * r["scale"], (case, r)
        assert r["visits"][0] == r["visits"][1], (case, r)
        assert r["all_gather"] == 0 and r["all_reduce"] >= 1, (case, r)
