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

    def test_cost_estimates_present(self):
        x, vq = _mk(80, 70, (), 1)
        pl = plan_mod.plan_vq(x, vq, PlanPolicy(vq_mode="eva"))
        assert pl.cost.macs > 0 and pl.cost.weight_bytes > 0
        assert "eva" in pl.describe() and "M=1" in pl.describe()


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
    """Tentpole: the Planner collects every matching backend and picks
    the cheapest predicted time. impl='pallas' is the genuinely
    overlapping registration — eva_fused_pallas vs the two-kernel
    eva_split_pallas — so these tests pin the ranking there, with and
    without a calibration."""

    PALLAS = PlanPolicy(vq_mode="eva", impl="pallas", interpret=True)

    def _spec(self, x, vq):
        return LinearSpec.for_vq(vq, M=x.size // vq.K, x_dtype=x.dtype,
                                 out_dtype=jnp.float32)

    @staticmethod
    def _entry(overhead, rows=8):
        from repro.core import calibrate

        return calibrate.BackendCalibration(
            overhead_us=overhead, us_per_mac=0.0, us_per_add=0.0,
            us_per_byte=0.0, rows=rows)

    @classmethod
    def _calib(cls, fused_overhead, split_overhead, rows=8):
        from repro.core import calibrate

        return calibrate.Calibration(
            version=calibrate.SCHEMA, source="test",
            backends={"eva_fused_pallas": cls._entry(fused_overhead, rows),
                      "eva_split_pallas": cls._entry(split_overhead, rows)},
            device=jax.devices()[0].device_kind)

    def test_analytic_fallback_ranks_fused_first(self):
        """No calibration: the analytic model prices the split backend's
        OC round-trip + second launch, so fused wins — deterministically,
        with both candidates recorded and provenance labeled."""
        x, vq = _mk(80, 70, (), 2)
        planner = plan_mod.Planner(calibration=None)
        pl = planner.plan(self._spec(x, vq), self.PALLAS)
        assert pl.backend == "eva_fused_pallas"
        assert pl.provenance == "analytic"
        assert [b for b, _ in pl.ranking] == ["eva_fused_pallas",
                                              "eva_split_pallas"]
        us = [u for _, u in pl.ranking]
        assert us == sorted(us) and us[0] < us[1]
        assert "pred=" in pl.describe() and "analytic" in pl.describe()
        assert "eva_split_pallas" in pl.describe_ranking()

    def test_calibration_flips_choice_to_split(self):
        """A calibration that prices the fused kernel above the split
        backend must flip the ranked choice — and the split plan must
        match the dequant oracle (two kernels, OC buffer in between)."""
        x, vq = _mk(96, 96, (50, 26, 20), 2)  # grouped family too
        planner = plan_mod.Planner(calibration=self._calib(1e6, 1.0))
        pl = planner.plan(self._spec(x, vq), self.PALLAS)
        assert pl.backend == "eva_split_pallas"
        assert pl.provenance == "eva-calibration/v1"
        assert [b for b, _ in pl.ranking] == ["eva_split_pallas",
                                              "eva_fused_pallas"]
        got = pl.execute(x, vq)
        ref = ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_partial_calibration_never_mixes_models(self):
        """When only ONE of the competing backends has a fitted entry,
        the ranking must fall back to the analytic model for BOTH —
        fitted microseconds vs analytic fantasy numbers is not a
        comparison (a partial CALIBRATION.json must not flip choices)."""
        from repro.core import calibrate

        x, vq = _mk(80, 70, (), 2)
        partial = calibrate.Calibration(
            version=calibrate.SCHEMA, source="partial",
            backends={"eva_split_pallas": self._entry(1.0)})
        planner = plan_mod.Planner(calibration=partial)
        pl = planner.plan(self._spec(x, vq), self.PALLAS)
        assert pl.backend == "eva_fused_pallas"  # analytic order holds
        assert pl.provenance == "analytic"

    @pytest.mark.parametrize("device", ["", "TPU v5 lite"])
    def test_calibration_from_another_device_not_applied(self, device):
        """A calibration whose rows name no device, or a device other
        than this process's, leaves the ranking analytic."""
        import dataclasses as dc

        x, vq = _mk(80, 70, (), 2)
        foreign = dc.replace(self._calib(1e6, 1.0), device=device)
        planner = plan_mod.Planner(calibration=foreign)
        pl = planner.plan(self._spec(x, vq), self.PALLAS)
        assert pl.backend == "eva_fused_pallas"
        assert pl.provenance == "analytic"

    def test_underfitted_entries_not_trusted_for_ranking(self):
        """Entries resting on fewer than MIN_FIT_ROWS samples (NNLS with
        4 free parameters fits 1-3 rows perfectly but arbitrarily) must
        not drive the ranking."""
        from repro.core import calibrate

        x, vq = _mk(80, 70, (), 2)
        thin = self._calib(1e6, 1.0, rows=calibrate.MIN_FIT_ROWS - 1)
        planner = plan_mod.Planner(calibration=thin)
        pl = planner.plan(self._spec(x, vq), self.PALLAS)
        assert pl.backend == "eva_fused_pallas"
        assert pl.provenance == "analytic"

    def test_choice_is_deterministic_across_planners(self):
        x, vq = _mk(80, 70, (), 1)
        for calib in (None, self._calib(10.0, 1e6), self._calib(1e6, 10.0)):
            a = plan_mod.Planner(calibration=calib)
            b = plan_mod.Planner(calibration=calib)
            pa = a.plan(self._spec(x, vq), self.PALLAS)
            pb = b.plan(self._spec(x, vq), self.PALLAS)
            assert pa.backend == pb.backend
            assert pa.ranking == pb.ranking

    def test_cache_identity_unchanged_under_calibration_reload(self):
        """Reloading calibration swaps the cost model for FUTURE misses
        only: a cached (spec, policy) keeps returning the SAME plan
        object, so traced programs and cache stats stay coherent."""
        x, vq = _mk(80, 70, (), 2)
        planner = plan_mod.Planner(calibration=None)
        spec = self._spec(x, vq)
        p1 = planner.plan(spec, self.PALLAS)
        assert p1.backend == "eva_fused_pallas"
        planner.reload_calibration(self._calib(1e6, 1.0))
        assert planner.plan(spec, self.PALLAS) is p1  # identity preserved
        hits = planner.cache_info().hits
        assert hits >= 1
        # a NEW spec planned after the reload uses the new constants
        x2, vq2 = _mk(88, 132, (), 2)
        p2 = planner.plan(self._spec(x2, vq2), self.PALLAS)
        assert p2.backend == "eva_split_pallas"
        # clearing the cache re-ranks the original spec under the reload
        planner.cache_clear()
        assert planner.plan(spec, self.PALLAS).backend == "eva_split_pallas"

    def test_split_plan_freezes_two_kernel_tiles(self):
        x, vq = _mk(256, 512, (), 1)
        planner = plan_mod.Planner(calibration=self._calib(1e6, 1.0))
        pl = planner.plan(self._spec(x, vq), self.PALLAS)
        cfg = pl.config_dict
        assert set(cfg) == {"bmv", "mt", "token_tiles", "bv", "bn"}
        assert cfg["mt"] == 1 and cfg["token_tiles"] == 1
        assert pl.cost.launches == 2
        # the HBM OC round-trip is priced: write + read of (C, M, V, 2^n)
        assert pl.cost.intermediate_bytes == 2 * 4 * vq.C * 1 * vq.V * 256

    def test_single_candidate_sites_report_no_ranking(self):
        x, vq = _mk(80, 70, (), 1)
        pl = plan_mod.plan_vq(x, vq, PlanPolicy(vq_mode="eva"))
        assert len(pl.ranking) == 1 and pl.describe_ranking() == ""
        assert pl.predicted_us is not None

    def test_first_match_backend_reports_registration_order(self):
        x, vq = _mk(80, 70, (), 1)
        spec = self._spec(x, vq)
        # registration order: fused_vq_matmul.ops imports before
        # oc_lookup.ops in _KERNEL_BACKEND_MODULES
        assert plan_mod.first_match_backend(spec, self.PALLAS) == \
            "eva_fused_pallas"
        assert plan_mod.first_match_backend(
            spec, PlanPolicy(vq_mode="eva")) == "eva_direct"

    def test_engine_logs_predicted_time_ranking(self, caplog):
        """serve/engine.py pre-plan logs surface the ranking when >1
        backend was eligible (the pallas decode policy)."""
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
        ranking_lines = [r.message for r in caplog.records
                         if "ranking" in r.message]
        assert ranking_lines
        assert any("eva_split_pallas" in m and "eva_fused_pallas" in m
                   for m in ranking_lines)


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
