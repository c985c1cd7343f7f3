"""Plan-once execution API (core/plan.py): planner cache behavior,
plan-vs-legacy parity across every registered backend (all epilogues x
{jnp, pallas-interpret} x grouped/ungrouped), loud ValueError on
contradictory policies, the newly reachable Pallas dequant path from
RunConfig, shard-aware grouping, and the engine's pre-planned shapes."""
import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import logits_vq  # noqa: F401  (registers vq_logits backends)
from repro.core import ops
from repro.core import plan as plan_mod
from repro.core.plan import LinearSpec, PlanPolicy
from repro.core.vq import synthetic_vq

KEY = jax.random.PRNGKey(0)


def _mk(K, N, splits, M):
    vq = synthetic_vq(KEY, K, N, d=8, n=8, C=2, splits=splits)
    x = jax.random.normal(jax.random.fold_in(KEY, K * N + M), (M, K),
                          jnp.float32)
    return x, vq


class TestPlannerCache:
    def test_same_spec_policy_same_plan_object(self):
        x, vq = _mk(80, 70, (), 2)
        pol = PlanPolicy(vq_mode="eva")
        assert plan_mod.plan_vq(x, vq, pol) is plan_mod.plan_vq(x, vq, pol)

    def test_distinct_policy_distinct_plan(self):
        x, vq = _mk(80, 70, (), 2)
        p1 = plan_mod.plan_vq(x, vq, PlanPolicy(vq_mode="eva"))
        p2 = plan_mod.plan_vq(x, vq, PlanPolicy(vq_mode="dequant"))
        assert p1 is not p2 and p1.backend != p2.backend

    def test_spec_is_hashable_cache_key(self):
        x, vq = _mk(96, 96, (50, 26, 20), 2)
        s1 = LinearSpec.for_vq(vq, M=2, x_dtype=x.dtype, out_dtype=x.dtype)
        s2 = LinearSpec.for_vq(vq, M=2, x_dtype=x.dtype, out_dtype=x.dtype)
        assert s1 == s2 and hash(s1) == hash(s2)
        assert s1 != dataclasses.replace(s1, M=3)

    def test_plan_not_reentered_inside_traced_decode_step(self):
        """The planner is consulted while TRACING only: executing the
        jitted step again must not touch the cache at all."""
        x, vq = _mk(80, 70, (), 2)
        planner = plan_mod.default_planner()

        @jax.jit
        def step(a):
            return ops.vq_matmul(a, vq, out_dtype=jnp.float32)

        jax.block_until_ready(step(x))           # trace: plans once
        before = planner.cache_info()
        jax.block_until_ready(step(x))           # executed path only
        after = planner.cache_info()
        assert (after.hits, after.misses) == (before.hits, before.misses)

    def test_lru_eviction_bounded(self):
        planner = plan_mod.Planner(maxsize=4)
        for M in range(1, 10):
            x, vq = _mk(80, 70, (), M)
            spec = LinearSpec.for_vq(vq, M=M, x_dtype=x.dtype,
                                     out_dtype=x.dtype)
            planner.plan(spec, PlanPolicy(vq_mode="eva"))
        assert planner.cache_info().currsize <= 4


class TestPlanParity:
    """Plan-vs-legacy-oracle parity for every registered backend."""

    @pytest.mark.parametrize("K,N,splits", [(80, 70, ()),
                                            (96, 96, (50, 26, 20))])
    @pytest.mark.parametrize("M", [1, 8])
    @pytest.mark.parametrize("policy_kw,backend", [
        (dict(vq_mode="eva", epilogue="direct"), "eva_direct"),
        (dict(vq_mode="eva", epilogue="flat"), "eva_flat"),
        (dict(vq_mode="eva", epilogue="blocked", block_v=4), "eva_blocked"),
        (dict(vq_mode="eva", epilogue="recon", block_v=4), "eva_recon"),
        (dict(vq_mode="eva", impl="pallas", interpret=True),
         "eva_fused_pallas"),
        (dict(vq_mode="dequant"), "dequant_jnp"),
        (dict(vq_mode="dequant", impl="pallas", interpret=True),
         "dequant_pallas"),
    ])
    def test_vq_backends_match_dequant_oracle(self, K, N, splits, M,
                                              policy_kw, backend):
        x, vq = _mk(K, N, splits, M)
        pl = plan_mod.plan_vq(x, vq, PlanPolicy(**policy_kw),
                              out_dtype=jnp.float32)
        assert pl.backend == backend
        got = pl.execute(x, vq)
        ref = ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_auto_selects_per_regime(self):
        x1, vq = _mk(4096, 4096, (), 1)
        x32, _ = _mk(4096, 4096, (), 32)
        auto = PlanPolicy(vq_mode="eva", epilogue="auto")
        assert plan_mod.plan_vq(x1, vq, auto).backend == "eva_direct"
        assert plan_mod.plan_vq(x32, vq, auto).backend == "eva_recon"

    def test_dense_backends(self):
        w = jax.random.normal(KEY, (64, 48), jnp.float32) * 0.1
        x = jax.random.normal(jax.random.fold_in(KEY, 1), (4, 64),
                              jnp.float32)
        ref = np.asarray(x) @ np.asarray(w)
        for mode, pol, backend in (
                ("decode", PlanPolicy(), "fp"),
                ("prefill", PlanPolicy(int8_prefill=True), "int8_jnp"),
                ("prefill", PlanPolicy(int8_prefill=True, impl="pallas",
                                       interpret=True), "int8_pallas"),
        ):
            pl = plan_mod.plan_node({"w": w}, x, mode=mode, policy=pol,
                                    out_dtype=jnp.float32)
            assert pl.backend == backend
            got = np.asarray(pl.execute(x, w))
            tol = 0.15 if backend.startswith("int8") else 1e-5
            np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)


class TestContradictoryPolicies:
    """Ported from the resolve_epilogue error tests: contradictions are
    loud at PlanPolicy construction or at planning time."""

    def test_unknown_values(self):
        with pytest.raises(ValueError, match="unknown epilogue"):
            PlanPolicy(epilogue="bogus")
        with pytest.raises(ValueError, match="unknown impl"):
            PlanPolicy(impl="cuda")
        with pytest.raises(ValueError, match="unknown vq_mode"):
            PlanPolicy(vq_mode="int4")

    def test_block_v_validation(self):
        for bad in (0, -3, "huge", True):
            with pytest.raises(ValueError, match="block_v"):
                PlanPolicy(block_v=bad)

    def test_block_v_requires_v_blocked_epilogue_on_jnp(self):
        for epi in ("direct", "flat", "auto"):
            with pytest.raises(ValueError, match="block_v"):
                PlanPolicy(epilogue=epi, block_v=8)
        # ...but pins the kernel v-tiles under pallas
        PlanPolicy(epilogue="auto", block_v=8, impl="pallas")

    def test_dequant_mode_keeps_ignoring_block_v(self):
        """Documented pre-plan behavior: the dequant baseline has no
        epilogue, so block_v stays accepted-and-ignored on jnp (and pins
        the Pallas dequant kernel's v-tiles)."""
        PlanPolicy(vq_mode="dequant", block_v=8)  # must not raise
        x, vq = _mk(80, 70, (), 2)
        got = ops.vq_matmul(x, vq, mode="dequant", block_v=8,
                            out_dtype=jnp.float32)
        ref = ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        from repro.models.common import RunConfig

        rc = RunConfig(mode="decode",
                       plan_policy=PlanPolicy(vq_mode="dequant", block_v=8))
        assert rc.policy.block_v == 8

    def test_pallas_rejects_jnp_epilogues_at_plan_time(self):
        x, vq = _mk(80, 70, (), 2)
        with pytest.raises(ValueError, match="pallas"):
            plan_mod.plan_vq(x, vq, PlanPolicy(
                vq_mode="eva", impl="pallas", epilogue="flat"))

    def test_runconfig_flat_knobs_are_removed(self):
        """The PR-3 shim cycle is over: the flat execution knobs are no
        longer RunConfig fields — they raise TypeError at construction
        instead of silently building a policy."""
        from repro.models.common import RunConfig

        for bad in (dict(vq_mode="eva"), dict(impl="pallas"),
                    dict(int8_prefill=True), dict(interpret=True),
                    dict(epilogue="flat"), dict(epilogue_block_v=8)):  # lint-ok
            with pytest.raises(TypeError):
                RunConfig(mode="decode", **bad)  # lint-ok (removal test)
        rc = RunConfig(mode="decode")
        assert not hasattr(rc, "vq_mode") and not hasattr(rc, "impl")
        assert rc.policy == PlanPolicy()

    def test_runconfig_replace_policy(self):
        from repro.models.common import RunConfig

        rc = RunConfig(mode="decode", plan_policy=PlanPolicy(
            vq_mode="eva", impl="pallas", interpret=True))
        rc2 = rc.replace_policy(vq_mode="dequant")
        assert rc2.policy.vq_mode == "dequant"
        assert rc2.policy.impl == "pallas"  # untouched knobs survive
        rc3 = rc2.replace(plan_policy=PlanPolicy(vq_mode="eva"))
        assert rc3.policy == PlanPolicy(vq_mode="eva")


class TestRankedSelection:
    """The Planner plans the first healthy match in registration order.
    impl='pallas' is where two EVA backends overlap — eva_fused_pallas,
    registered first, and the two-kernel eva_split_pallas — so these
    tests pin the order there."""

    PALLAS = PlanPolicy(vq_mode="eva", impl="pallas", interpret=True)

    def _spec(self, x, vq):
        return LinearSpec.for_vq(vq, M=x.size // vq.K, x_dtype=x.dtype,
                                 out_dtype=jnp.float32)

    def test_analytic_fallback_ranks_fused_first(self):
        """The fused kernel is planned wherever it fits, and the split
        backend is next in its fallback chain: it takes over once the
        fused backend is quarantined, and matches the dequant oracle."""
        x, vq = _mk(96, 96, (50, 26, 20), 2)  # grouped family too
        spec = self._spec(x, vq)
        planner = plan_mod.Planner(cooloff_s=60.0)
        assert planner.plan(spec, self.PALLAS).backend == "eva_fused_pallas"
        matched = [be.name for be in plan_mod.Planner._match_all(
            spec, self.PALLAS)]
        assert matched == ["eva_fused_pallas", "eva_split_pallas"]
        planner.record_backend_failure("eva_fused_pallas")
        pl = planner.plan(spec, self.PALLAS)
        assert pl.backend == "eva_split_pallas"
        got = pl.execute(x, vq)
        ref = ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_split_registers_after_fused_whatever_imports_first(self):
        """The split backend's module is imported first in a fresh
        process, and the fused backend still precedes it."""
        import os
        import subprocess
        import sys

        code = ("import repro.kernels.oc_lookup.ops\n"
                "from repro.core import plan\n"
                "print(' '.join(plan.registered_backends()))\n")
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src)
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        names = proc.stdout.split()
        assert names.index("eva_fused_pallas") < names.index(
            "eva_split_pallas")

    def test_choice_is_deterministic_across_planners(self):
        x, vq = _mk(80, 70, (), 1)
        spec = self._spec(x, vq)
        a = plan_mod.Planner().plan(spec, self.PALLAS)
        b = plan_mod.Planner().plan(spec, self.PALLAS)
        assert a.backend == b.backend == "eva_fused_pallas"
        assert a.config == b.config
        assert "pred=" not in a.describe()

    def test_split_plan_freezes_two_kernel_tiles(self):
        """At K=53248 the fused kernel's OC tile of 8 rows does not fit
        VMEM, so for 16 rows its matcher declines and the split backend
        is the plan, every row in one lookup tile."""
        spec = LinearSpec(M=16, K=53248, N=512, kind="vq", x_dtype="float32",
                          out_dtype="float32", C=2, V=53248 // 8, k=256, d=8)
        from repro.kernels.fused_vq_matmul.ops import select_fused_tiles

        assert select_fused_tiles(spec.M, spec.V, spec.N, spec.C,
                                  spec.k)[0] == 0
        pl = plan_mod.Planner().plan(spec, self.PALLAS)
        assert pl.backend == "eva_split_pallas"
        cfg = pl.config_dict
        assert set(cfg) == {"bmv", "mt", "token_tiles", "bv", "bn"}
        assert cfg["mt"] == 16 and cfg["token_tiles"] == 1

    def test_engine_logs_predicted_time_ranking(self, caplog):
        """serve/engine.py's pre-plan log names the backend each leaf was
        planned to (the fused kernel under the pallas decode policy)."""
        import dataclasses as dc
        import logging

        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.models.common import RunConfig
        from repro.serve import Engine, EngineConfig

        cfg = dc.replace(get_smoke_config("llama2_7b"), dtype="float32")
        model = build_model(cfg)
        params = model.quantize(model.init(KEY), method="synthetic", key=KEY)
        rc = RunConfig(mode="decode", remat=False, attn_chunk=16,
                       plan_policy=PlanPolicy(vq_mode="eva", impl="pallas",
                                              interpret=True))
        with caplog.at_level(logging.INFO, logger="repro.serve.engine"):
            Engine(model, params, rc, EngineConfig(num_slots=2, max_len=16))
        plan_lines = [r.message for r in caplog.records
                      if r.message.startswith("decode plan [")]
        assert any("eva_fused_pallas" in m for m in plan_lines)
        assert not any("ranking" in r.message for r in caplog.records)


# (spec, policy) -> the backend the planner chose for it before choice by
# registration order replaced the priced ranking, recorded by running
# that planner. Each side of the kvq_attn and vq_logits rules, both sides
# of the fused kernel's VMEM fit at the two benchmark cells' linear
# shapes, and every single-candidate kind under each impl.
def _vq(M, K, N, splits=(), kind="vq"):
    return LinearSpec(M=M, K=K, N=N, kind=kind, x_dtype="bfloat16",
                      out_dtype="bfloat16", C=2, V=K // 8, k=256, d=8,
                      splits=splits)


def _dense(M, K, N, kind="dense"):
    return LinearSpec(M=M, K=K, N=N, kind=kind, x_dtype="bfloat16",
                      out_dtype="bfloat16")


def _kvq(B, S, H, Hk, hd, W):
    return plan_mod.kvq_attention_spec(
        B=B, S=S, H=H, Hk=Hk, hd=hd, idx_width=W, entries=256,
        x_dtype=jnp.bfloat16, out_dtype=jnp.bfloat16)


def _vql(M, D, V, Kc, x_dtype="float32"):
    return LinearSpec(M=M, K=D, N=V, kind="vq_logits", x_dtype=x_dtype,
                      out_dtype="float32", k=Kc)


_JNP, _PAL = PlanPolicy(), PlanPolicy(impl="pallas")
_EVA, _EVA_PAL = PlanPolicy(vq_mode="eva"), PlanPolicy(vq_mode="eva",
                                                       impl="pallas")
_DQ, _DQ_PAL = PlanPolicy(vq_mode="dequant"), PlanPolicy(vq_mode="dequant",
                                                         impl="pallas")
_MINITRON_KV = dict(H=24, Hk=8, hd=128)  # W=32 at kv_bits=2, 64 at 4

PARENT_CHOICES = {
    "kvq-minitron-kv2-B16-S1152": (_kvq(16, 1152, W=32, **_MINITRON_KV),
                                   _PAL, "kvq_flash_pallas"),
    "kvq-minitron-kv2-B16-S128": (_kvq(16, 128, W=32, **_MINITRON_KV),
                                  _PAL, "kvq_dequant_jnp"),
    "kvq-minitron-kv4-B16-S1152": (_kvq(16, 1152, W=64, **_MINITRON_KV),
                                   _PAL, "kvq_dequant_jnp"),
    "kvq-minitron-kv2-B1-S256": (_kvq(1, 256, W=32, **_MINITRON_KV),
                                 _PAL, "kvq_flash_pallas"),
    "kvq-minitron-kv2-B1-S64": (_kvq(1, 64, W=32, **_MINITRON_KV),
                                _PAL, "kvq_dequant_jnp"),
    "kvq-small-B2-S32": (_kvq(2, 32, 4, 2, 8, 4), _PAL, "kvq_flash_pallas"),
    "kvq-small-B4-S64": (_kvq(4, 64, 8, 8, 64, 32), _PAL, "kvq_flash_pallas"),
    "kvq-small-B8-S64": (_kvq(8, 64, 8, 8, 64, 32), _PAL, "kvq_dequant_jnp"),
    "kvq-jnp": (_kvq(16, 1152, W=32, **_MINITRON_KV), _JNP,
                "kvq_dequant_jnp"),
    "vql-decode-kc4096": (_vql(16, 3072, 256000, 4096, "bfloat16"), _JNP,
                          "vql_gather_jnp"),
    "vql-small-pallas": (_vql(1, 64, 512, 32), _PAL, "vql_gather_jnp"),
    "vql-kc-eq-vocab-M8": (_vql(8, 64, 128, 128), _JNP, "vql_gather_jnp"),
    "vql-kc-eq-vocab-M256": (_vql(256, 64, 128, 128), _JNP,
                             "vql_dequant_jnp"),
    "vq-pallas-minitron-qkv": (_vq(16, 3072, 5120, (3072, 1024, 1024)),
                               _EVA_PAL, "eva_fused_pallas"),
    "vq-pallas-minitron-o": (_vq(16, 3072, 3072), _EVA_PAL,
                             "eva_fused_pallas"),
    "vq-pallas-minitron-gate-up": (_vq(16, 3072, 18432, (9216, 9216)),
                                   _EVA_PAL, "eva_fused_pallas"),
    "vq-pallas-minitron-down": (_vq(16, 9216, 3072), _EVA_PAL,
                                "eva_fused_pallas"),
    "vq-pallas-deepseek-q-kv-a": (_vq(32, 2048, 3648, (3072, 576)),
                                  _EVA_PAL, "eva_fused_pallas"),
    "vq-pallas-deepseek-o": (_vq(32, 2048, 2048), _EVA_PAL,
                             "eva_fused_pallas"),
    "vq-pallas-deepseek-dense-gate-up": (
        _vq(32, 2048, 21888, (10944, 10944)), _EVA_PAL, "eva_fused_pallas"),
    "vq-pallas-deepseek-dense-down": (_vq(32, 10944, 2048), _EVA_PAL,
                                      "eva_fused_pallas"),
    "vq-pallas-deepseek-shared-gate-up": (
        _vq(32, 2048, 5632, (2816, 2816)), _EVA_PAL, "eva_fused_pallas"),
    "vq-pallas-deepseek-shared-down": (_vq(32, 2816, 2048), _EVA_PAL,
                                       "eva_fused_pallas"),
    "vq-pallas-oc-too-wide-M16": (_vq(16, 53248, 2048), _EVA_PAL,
                                  "eva_split_pallas"),
    "vq-pallas-oc-too-wide-M32": (_vq(32, 53248, 2048), _EVA_PAL,
                                  "eva_split_pallas"),
    "grouped-pallas-gate-up": (
        _vq(640, 2048, 2816, (1408, 1408), "vq_grouped"), _EVA_PAL,
        "grouped_eva_pallas"),
    "grouped-pallas-down": (_vq(640, 1408, 2048, kind="vq_grouped"),
                            _EVA_PAL, "grouped_eva_pallas"),
    "grouped-jnp-eva": (_vq(640, 2048, 2816, (1408, 1408), "vq_grouped"),
                        _EVA, "grouped_eva_jnp"),
    "grouped-jnp-dequant": (
        _vq(640, 2048, 2816, (1408, 1408), "vq_grouped"), _DQ,
        "grouped_dequant_jnp"),
    "grouped-pallas-dequant": (
        _vq(640, 2048, 2816, (1408, 1408), "vq_grouped"), _DQ_PAL,
        "grouped_dequant_jnp"),
    "dense-jnp": (_dense(16, 3072, 256000), _JNP, "fp"),
    "dense-pallas": (_dense(16, 3072, 256000), _PAL, "fp"),
    "int8-jnp": (_dense(128, 3072, 3072, "int8"), _JNP, "int8_jnp"),
    "int8-pallas": (_dense(128, 3072, 3072, "int8"), _PAL, "int8_pallas"),
    "vq-jnp-eva-M1": (_vq(1, 4096, 4096), _EVA, "eva_direct"),
    "vq-jnp-eva-M16": (_vq(16, 3072, 3072), _EVA, "eva_recon"),
    "vq-jnp-eva-in-mesh": (dataclasses.replace(_vq(16, 3072, 3072),
                                               in_mesh=True),
                           _EVA, "eva_flat"),
    "vq-jnp-eva-prefill": (_vq(2048, 3072, 9216), _EVA, "eva_recon"),
    "vq-jnp-dequant": (_vq(16, 3072, 3072), _DQ, "dequant_jnp"),
    "vq-pallas-dequant": (_vq(16, 3072, 3072), _DQ_PAL, "dequant_pallas"),
}


@pytest.mark.parametrize("case", sorted(PARENT_CHOICES))
def test_choice_matches_parent(case):
    spec, policy, backend = PARENT_CHOICES[case]
    assert plan_mod.Planner().plan(spec, policy).backend == backend


class TestDequantPallasReachable:
    """Satellite bugfix: vq_matmul(mode='dequant') used to silently drop
    impl/interpret, so a pallas+dequant RunConfig policy never
    reached the dequant_gemv kernel from model layers."""

    def test_model_layer_routes_to_dequant_pallas(self):
        from repro.models.common import RunConfig, linear

        x, vq = _mk(80, 70, (), 2)
        rc = RunConfig(mode="decode", plan_policy=PlanPolicy(
            vq_mode="dequant", impl="pallas", interpret=True), remat=False)
        pl = plan_mod.plan_node({"vq": vq}, x, mode=rc.mode, policy=rc.policy,
                                out_dtype=jnp.float32)
        assert pl.backend == "dequant_pallas"
        got = linear({"vq": vq}, x, rc, out_dtype=jnp.float32)
        ref = ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_wrapper_routes_to_dequant_pallas(self):
        x, vq = _mk(80, 70, (), 2)
        got = ops.vq_matmul(x, vq, mode="dequant", impl="pallas",
                            interpret=True, out_dtype=jnp.float32)
        ref = ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


class TestShardAwareGrouping:
    """Satellite: quantization skips grouping a family whose member
    boundaries are not shard-aligned under the target mesh, and the
    decision lands in the quantize report."""

    def _quantize(self, shards, report):
        from repro.configs import get_smoke_config
        from repro.models import build_model

        cfg = dataclasses.replace(get_smoke_config("llama2_7b"),
                                  dtype="float32")
        model = build_model(cfg)
        params = model.init(KEY)
        return model, model.quantize(params, method="synthetic", key=KEY,
                                     mesh=shards, report=report)

    def test_misaligned_family_stays_ungrouped(self):
        report = []
        # smoke llama2 qkv widths (128,128,128): boundary 128 is not a
        # multiple of the 384/16=24-wide shards -> ungrouped
        model, q = self._quantize(16, report)
        qkv = [r for r in report if r["family"] == "wqkv"]
        assert qkv and not qkv[0]["grouped"]
        assert "not aligned" in qkv[0]["reason"]
        leaves = q["layers"]["attn"]
        assert "wqkv" not in leaves and "vq" in leaves["wq"]

    def test_aligned_family_groups(self):
        report = []
        # gate/up (384,384): boundary 384 is shard-aligned at 16 shards
        model, q = self._quantize(16, report)
        gu = [r for r in report if r["family"] == "gu"]
        assert gu and gu[0]["grouped"] and gu[0]["reason"] == "aligned"
        assert "gu" in q["layers"]["mlp"]

    def test_unsharded_mesh_groups_everything(self):
        report = []
        model, q = self._quantize(None, report)
        assert all(r["grouped"] for r in report)
        assert "wqkv" in q["layers"]["attn"]

    def test_splits_shard_aligned_helper(self):
        from repro.runtime.sharding import splits_shard_aligned

        assert splits_shard_aligned((64, 64), 128, 2)
        assert not splits_shard_aligned((4096, 1024, 1024), 6144, 16)
        assert splits_shard_aligned((), 128, 2)
        assert not splits_shard_aligned((), 130, 4)
        assert splits_shard_aligned((13, 7), 20, 1)


class TestEnginePreplan:
    def test_engine_preplans_and_logs(self, caplog):
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.models.common import RunConfig
        from repro.serve import Engine, EngineConfig

        cfg = dataclasses.replace(get_smoke_config("llama2_7b"),
                                  dtype="float32")
        model = build_model(cfg)
        params = model.quantize(model.init(KEY), method="synthetic", key=KEY)
        rc = RunConfig(mode="decode", plan_policy=PlanPolicy(vq_mode="eva"),
                       remat=False, attn_chunk=16)
        with caplog.at_level(logging.INFO, logger="repro.serve.engine"):
            eng = Engine(model, params, rc,
                         EngineConfig(num_slots=3, max_len=32))
        assert eng.plans["decode"]
        # decode plans at slot capacity (M = num_slots); prefill entries
        # are EXACT per-bucket plans at the padded execution lengths
        # (replacing the old single capacity-bound prefill@cap estimate)
        vq_decode = [pl for _p, pl in eng.plans["decode"]
                     if pl.spec.kind == "vq"]
        assert vq_decode and all(pl.spec.M == 3 for pl in vq_decode)
        assert "prefill@cap" not in eng.plans
        for m in (8, 16, 32):
            assert all(pl.spec.M == m for _p, pl in eng.plans[f"prefill@{m}"])
        assert any("plan" in r.message for r in caplog.records)

    def test_decode_preplan_warms_traced_step(self):
        """The decode entries must be exact cache warm-ups: tracing the
        batched decode step at slot capacity re-uses the pre-planned
        (spec, policy) keys for every vq leaf (no new misses for them)."""
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.models.common import RunConfig
        from repro.serve import Engine, EngineConfig

        cfg = dataclasses.replace(get_smoke_config("llama2_7b"),
                                  dtype="float32")
        model = build_model(cfg)
        params = model.quantize(model.init(KEY), method="synthetic", key=KEY)
        rc = RunConfig(mode="decode", plan_policy=PlanPolicy(vq_mode="eva"),
                       remat=False, attn_chunk=16)
        eng = Engine(model, params, rc, EngineConfig(num_slots=2, max_len=32))
        planner = plan_mod.default_planner()
        from repro.serve import api as serve_api

        before = planner.cache_info()
        eng._decode_fn(  # traces: decode + in-jit sampling state
            params, eng.caches,
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.zeros((2, 2), jnp.uint32), jnp.ones((2,), jnp.float32),
            jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.float32),
            jnp.ones((2,), bool),
            jnp.full((2, serve_api.MAX_STOP_IDS), -1, jnp.int32),
            jnp.ones((2,), jnp.int32), jnp.ones((2,), bool),
            jnp.zeros((2,), jnp.float32),  # per-lane fault-injection poison
        )
        after = planner.cache_info()
        # tracing plans each call site; every vq-leaf spec was pre-planned
        # (dense sites may differ in out_dtype, e.g. the fp32 lm_head)
        assert after.hits > before.hits
        new_misses = after.misses - before.misses
        assert new_misses <= 1  # at most the fp32-out lm_head site
