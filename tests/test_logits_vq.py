"""VQ-Logits compressed LM head tests: parity of the gather
formulation against the expanded dense oracle (standalone and through a
full smoke transformer), planner registration and choice, the
attach pass, and end-to-end serving with a compressed head."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import logits_vq as lvq
from repro.core import plan as plan_mod
from repro.core import quantize
from repro.models import build_model
from repro.models.common import RunConfig
from repro.serve import Engine, EngineConfig, GenerationRequest

KEY = jax.random.PRNGKey(0)


def _policy():
    return plan_mod.PlanPolicy()


def test_expand_matches_definition():
    head = lvq.synthetic_logits_vq(KEY, 16, 64, 7)
    w = np.asarray(lvq.expand(head))
    cb = np.asarray(head.codebook)
    assign = np.asarray(head.assign)
    scale = np.asarray(head.scale)
    for v in (0, 13, 63):
        np.testing.assert_array_equal(w[:, v], scale[v] * cb[:, assign[v]])


def test_gather_backend_exact_vs_dense_oracle():
    head = lvq.synthetic_logits_vq(KEY, 32, 128, 9)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32), jnp.float32)
    spec = lvq.vq_logits_spec(head, M=4, x_dtype=x.dtype,
                              out_dtype=jnp.float32)
    gather = lvq._plan_vql_gather(spec, _policy())
    dequant = lvq._plan_vql_dequant(spec, _policy())
    y_g = np.asarray(gather.run(x, head))
    y_d = np.asarray(dequant.run(x, head))
    y_ref = np.asarray(x @ lvq.expand(head))
    # The two formulations hold the same weights but score through
    # differently shaped float32 GEMMs, (4, 32) @ (32, 9) then a gather
    # against (4, 32) @ (32, 128), whose 32-term sums may round in a
    # different order. With |y| up to about 2 (one ulp about 2.4e-7),
    # 1e-6 allows four ulps; a wrong gather would be off by O(1).
    np.testing.assert_allclose(y_g, y_d, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y_g, y_ref, rtol=1e-6, atol=1e-6)


def test_planner_ranks_gather_over_dequant_and_dispatches():
    head = lvq.synthetic_logits_vq(KEY, 64, 512, 16)
    x = jnp.ones((2, 64), jnp.float32)
    plan = plan_mod.plan_node({"vql": head}, x, mode="decode",
                              policy=_policy(), out_dtype=jnp.float32)
    assert plan.spec.kind == "vq_logits" and plan.spec.k == 16
    # Kc << V: gather-scoring admits the shape, and it is registered
    # before the expand-to-dense oracle
    assert lvq._gather_admits(plan.spec)
    assert plan.backend == "vql_gather_jnp"
    y = np.asarray(plan.execute(x, head))
    np.testing.assert_allclose(y, np.asarray(x @ lvq.expand(head)),
                               rtol=1e-6, atol=1e-6)


def test_fit_reconstructs_clustered_head_exactly():
    """Columns drawn from kc distinct directions (with varying scales)
    are exactly recoverable by the k-means fit."""
    kc, d, v = 4, 16, 64
    dirs = jax.random.normal(KEY, (kc, d))
    dirs = dirs / jnp.linalg.norm(dirs, axis=1, keepdims=True)
    assign = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (v,), 0, kc))
    scales = np.asarray(jax.random.uniform(jax.random.PRNGKey(3), (v,),
                                           minval=0.5, maxval=2.0))
    w = (np.asarray(dirs)[assign] * scales[:, None]).T     # (D, V)
    head = lvq.fit_logits_vq(jax.random.PRNGKey(4), w, kc, iters=30)
    np.testing.assert_allclose(np.asarray(lvq.expand(head)), w,
                               rtol=1e-4, atol=1e-5)


def test_attach_pass_idempotent_and_guarded():
    cfg = dataclasses.replace(get_smoke_config("llama2_7b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    q = quantize.attach_vq_logits_head(params, 32)
    assert "vql" in q["lm_head"] and q["lm_head"]["vql"].Kc == 32
    # idempotent: re-attach refits from the implied dense weight
    q2 = quantize.attach_vq_logits_head(q, 16)
    assert q2["lm_head"]["vql"].Kc == 16
    # tied-embedding models have no separate head
    tied = {k: v for k, v in params.items() if k != "lm_head"}
    with pytest.raises(ValueError, match="lm_head"):
        quantize.attach_vq_logits_head(tied, 8)


def test_smoke_transformer_logits_exact_with_synthetic_head():
    """A synthetic head consumed natively through models.common.linear
    produces bit-comparable logits to the same model with the expanded
    dense head substituted in."""
    cfg = dataclasses.replace(get_smoke_config("llama2_7b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    head = lvq.synthetic_logits_vq(jax.random.PRNGKey(5), cfg.d_model,
                                   cfg.padded_vocab, 24)
    p_vql = dict(params)
    p_vql["lm_head"] = {"vql": head}
    p_dense = dict(params)
    p_dense["lm_head"] = {"w": lvq.expand(head)}
    toks = jax.random.randint(jax.random.PRNGKey(6), (2, 7), 0,
                              cfg.vocab_size, jnp.int32)
    rc = RunConfig(mode="prefill", remat=False)
    lo_v, _ = model.prefill(params=p_vql, batch={"tokens": toks}, rc=rc)
    lo_d, _ = model.prefill(params=p_dense, batch={"tokens": toks}, rc=rc)
    np.testing.assert_allclose(np.asarray(lo_v), np.asarray(lo_d),
                               rtol=1e-5, atol=1e-5)


def test_engine_serves_with_vql_head_matches_expanded_dense():
    """End-to-end: the serving engine decoding through a VQ-Logits head
    emits the same greedy stream as with the equivalent dense head."""
    cfg = dataclasses.replace(get_smoke_config("llama2_7b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    head = lvq.synthetic_logits_vq(jax.random.PRNGKey(7), cfg.d_model,
                                   cfg.padded_vocab, 24)
    rc = RunConfig(mode="decode", remat=False, attn_chunk=16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 8)]

    def serve(lm_head_node):
        p = dict(params)
        p["lm_head"] = lm_head_node
        eng = Engine(model, p, rc, EngineConfig(num_slots=2, max_len=32))
        uids = [eng.submit(GenerationRequest(prompt=pr, max_new_tokens=6))
                for pr in prompts]
        steps = 0
        while not eng.idle:
            eng.step()
            steps += 1
            assert steps < 100
        return [list(eng.output(u).tokens) for u in uids]

    assert serve({"vql": head}) == serve({"w": lvq.expand(head)})


def test_preplan_covers_vql_nodes():
    head = lvq.synthetic_logits_vq(KEY, 64, 512, 16)
    params = {"lm_head": {"vql": head}}
    plans = plan_mod.preplan_params(params, _policy(), mode="decode", m=2,
                                    act_dtype=jnp.float32)
    assert any(pl.spec.kind == "vq_logits" for _, pl in plans)
