"""Epilogue-selection subsystem: every epilogue formulation (direct /
flat / blocked at several block sizes / recon / "auto") must match the
dequant oracle on odd V/N, grouped splits and M in {1, 8, 32}; the
selection heuristic's regime boundaries are pinned; conflicting argument
combinations raise loudly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ops
from repro.core.vq import split_grouped, synthetic_vq

KEY = jax.random.PRNGKey(0)

# (K, N, splits): odd V (K=80 -> V=10, K=88 -> V=11) and N that pad
# against the explicit block sizes below; one grouped family with odd
# member widths.
SHAPES = [
    (80, 70, ()),
    (88, 132, ()),
    (96, 96, (50, 26, 20)),
]

# (epilogue kwarg, block_v kwarg)
EPILOGUE_ARGS = [
    ("direct", "auto"),
    ("flat", "auto"),
    ("blocked", 4),
    ("blocked", 8),
    ("blocked", 32),
    ("blocked", "auto"),
    ("recon", 4),
    ("recon", "auto"),
    ("auto", "auto"),
]


def _mk(K, N, splits, M):
    vq = synthetic_vq(KEY, K, N, d=8, n=8, C=2, splits=splits)
    x = jax.random.normal(jax.random.fold_in(KEY, K * N + M), (M, K),
                          jnp.float32)
    return x, vq


class TestEquivalence:
    @pytest.mark.parametrize("K,N,splits", SHAPES)
    @pytest.mark.parametrize("M", [1, 8, 32])
    @pytest.mark.parametrize("epilogue,block_v", EPILOGUE_ARGS)
    def test_epilogue_matches_dequant_oracle(self, K, N, splits, M,
                                             epilogue, block_v):
        x, vq = _mk(K, N, splits, M)
        got = ops.eva_matmul(x, vq, epilogue=epilogue, block_v=block_v,
                             out_dtype=jnp.float32)
        ref = ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_bare_int_block_v_selects_blocked_scan(self):
        x, vq = _mk(80, 70, (), 3)
        ref = ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
        # supported spellings: bare int block_v (v-blocked scan), defaults
        for kw in (dict(block_v=5), dict()):
            got = ops.eva_matmul(x, vq, out_dtype=jnp.float32, **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)

    def test_removed_legacy_spellings_raise(self):
        """The PR-3 deprecation cycle is over: flat_gather= is gone from
        the signature and passing None for block_v raises instead of
        selecting the direct epilogue."""
        x, vq = _mk(80, 70, (), 3)
        with pytest.raises(TypeError):
            ops.eva_matmul(x, vq, flat_gather=True)  # lint-ok (removal test)
        with pytest.raises(ValueError, match="removed"):
            ops.eva_matmul(x, vq, block_v=None)  # lint-ok (removal test)

    def test_grouped_auto_epilogue_matches_per_member_oracles(self):
        """One wide auto-epilogue matmul + split == independent dequant
        oracles per member, in both the direct (M=1) and recon (M=32)
        regimes."""
        for M in (1, 32):
            x, vq = _mk(96, 96, (50, 26, 20), M)
            y = ops.eva_matmul(x, vq, out_dtype=jnp.float32)
            parts = ops.split_grouped_outputs(y, vq)
            for part, member in zip(parts, split_grouped(vq)):
                ref = ops.dequant_matmul(x, member, out_dtype=jnp.float32)
                np.testing.assert_allclose(np.asarray(part), np.asarray(ref),
                                           rtol=2e-4, atol=2e-4)

    def test_auto_is_default_through_vq_matmul(self):
        x, vq = _mk(80, 70, (), 8)
        got = ops.vq_matmul(x, vq, out_dtype=jnp.float32)
        ref = ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


class TestSelection:
    """Pin the heuristic's regime boundaries (measured crossovers on the
    CI host, benchmarks/measured.py batch + crossover sweeps)."""

    def test_single_token_decode_is_direct(self):
        # paper decode shape M=1, llama-2-7b layer: footprint 17 MB
        assert ops.select_epilogue(1, 512, 4096, 2, 256, 8) == ("direct", None)

    def test_small_batch_stays_direct_below_spill(self):
        # M=4, K=N=4096: 71 MB gathered footprint, still direct (measured
        # ~36 ms direct vs ~180 ms blocked)
        assert ops.select_epilogue(4, 512, 4096, 2, 256, 8) == ("direct", None)

    def test_small_batch_spills_to_blocked_on_wide_n(self):
        # M=4, N=11008: 184 MB footprint thrashes -> v-blocked gather
        kind, bv = ops.select_epilogue(4, 512, 11008, 2, 256, 8)
        assert kind == "blocked"
        assert ops._MIN_BLOCK_V <= bv < 512
        # the live slab must fit the slab budget
        assert 4 * 2 * 4 * bv * (11008 + 256) <= ops.EPILOGUE_SLAB_BYTES

    def test_batched_decode_is_recon(self):
        # M >= d: gather work C*M*V*N exceeds the C*V*N*d reconstruction
        # gathers -> slab-tiled reconstruct-and-GEMM (the measured/batch32
        # fix: recon ~72 ms vs dequant ~260 ms vs direct ~790 ms)
        for M in (8, 16, 32):
            kind, bv = ops.select_epilogue(M, 512, 4096, 2, 256, 8)
            assert kind == "recon"
            assert 1 <= bv <= 512
            # reconstructed slab (bv*d, N) fp32 within its cache target
            assert 4 * bv * 8 * 4096 <= ops.RECON_SLAB_BYTES

    def test_boundary_is_at_m_equals_d(self):
        assert ops.select_epilogue(7, 512, 4096, 2, 256, 8)[0] != "recon"
        assert ops.select_epilogue(8, 512, 4096, 2, 256, 8)[0] == "recon"
        # d=4 weights cross over at M=4
        assert ops.select_epilogue(4, 512, 4096, 2, 256, 4)[0] == "recon"

    def test_distributed_is_flat(self):
        for M in (1, 32):
            assert ops.select_epilogue(M, 512, 4096, distributed=True) == \
                ("flat", None)

    def test_block_v_shrinks_with_n(self):
        _, bv_small = ops.select_epilogue(4, 2048, 11008, 2, 256, 8)
        _, bv_large = ops.select_epilogue(4, 2048, 44032, 2, 256, 8)
        assert bv_large <= bv_small

    def test_tiny_shapes_never_scan(self):
        # smoke-model shapes: one block would cover V -> direct
        assert ops.select_epilogue(1, 8, 64, 2, 256, 8) == ("direct", None)

    def test_gather_footprint_model(self):
        assert ops.epilogue_gather_bytes(1, 512, 4096, 2) == \
            4 * 2 * 512 * (4096 + 256)

    def test_auto_under_mesh_context_selects_flat(self):
        """Inside an active mesh context the auto resolution must pick the
        SPMD-friendly flat epilogue (the V-block scans would reshape a
        sharded V axis into collectives). The mesh flag is captured into
        the LinearSpec at derivation, so the cached plans differ."""
        from jax.sharding import Mesh
        from repro.core import plan as plan_mod

        x, vq = _mk(4096, 4096, (), 32)  # M=32 >= d -> recon off-mesh
        auto = plan_mod.PlanPolicy(vq_mode="eva", epilogue="auto")
        assert plan_mod.plan_vq(x, vq, auto).backend == "eva_recon"
        with Mesh(np.array(jax.devices()[:1]), ("model",)):
            assert plan_mod.plan_vq(x, vq, auto).backend == "eva_flat"
            # explicit requests still win over the mesh preference
            forced = plan_mod.PlanPolicy(vq_mode="eva", epilogue="recon",
                                         block_v=64)
            pl = plan_mod.plan_vq(x, vq, forced)
            assert pl.backend == "eva_recon" and pl.config_dict["bv"] == 64


class TestResolveErrors:
    """The epilogue arguments are one coherent policy with loud errors on
    conflicting combinations — statically contradictory ones raise from
    PlanPolicy at construction, legacy-surface conflicts from the
    eva_matmul wrapper."""

    def _call(self, **kw):
        x, vq = _mk(80, 70, (), 2)
        return ops.eva_matmul(x, vq, **kw)

    def test_block_v_with_non_blocked_epilogue(self):
        for epi in ("direct", "flat", "auto"):
            with pytest.raises(ValueError, match="block_v"):
                self._call(epilogue=epi, block_v=8)

    def test_none_block_v_always_raises(self):
        # the legacy "None means direct" spelling is removed for EVERY
        # epilogue — including an explicit direct request
        for epi in ("blocked", "recon", "auto", "flat", "direct", None):
            with pytest.raises(ValueError, match="removed"):
                self._call(epilogue=epi, block_v=None)  # lint-ok

    def test_unknown_epilogue(self):
        with pytest.raises(ValueError, match="unknown epilogue"):
            self._call(epilogue="bogus")

    def test_bad_block_v_values(self):
        with pytest.raises(ValueError, match="block_v"):
            self._call(block_v=0)
        with pytest.raises(ValueError, match="block_v"):
            self._call(block_v="huge")

    def test_pallas_rejects_jnp_epilogues(self):
        with pytest.raises(ValueError, match="pallas"):
            self._call(impl="pallas", epilogue="flat", interpret=True)

    def test_pallas_validates_block_v(self):
        # the pallas branch shares the jnp path's loud block_v contract
        for bad in (0, -3, "huge"):
            with pytest.raises(ValueError, match="block_v"):
                self._call(impl="pallas", interpret=True, block_v=bad)

    def test_pallas_accepts_auto_and_block_v(self):
        x, vq = _mk(80, 70, (), 2)
        ref = ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
        for kw in (dict(), dict(block_v=4)):
            got = ops.eva_matmul(x, vq, impl="pallas", interpret=True,
                                 out_dtype=jnp.float32, **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)


class TestFusedTiles:
    """The fused Pallas wrapper's auto tile/m-tile sizing — the tile
    model now lives with the kernel wrapper (kernels/fused_vq_matmul),
    sized against the shared VMEM budgets in core/ops."""

    def test_oc_scratch_budget_respected(self):
        from repro.kernels.fused_vq_matmul.ops import select_fused_tiles

        mt, bv, bn = select_fused_tiles(64, 512, 4096, 2, 256)
        v_pad = 512 + ((-512) % bv)
        # the OC scratch and the (mt, 8, bn) accumulator of one token tile
        assert (2 * mt * v_pad * 256 * 4 + mt * 8 * bn * 4
                <= ops.FUSED_OC_SCRATCH_BYTES)
        # the widened index tile streamed per grid step
        assert 2 * bv * bn * 4 <= ops.FUSED_GATHER_TILE_BYTES

    def test_small_shapes_single_tile(self):
        from repro.kernels.fused_vq_matmul.ops import select_fused_tiles

        mt, bv, bn = select_fused_tiles(1, 10, 70, 2, 256)
        # one token tile of every row, unpadded; v/n tiles clamp to the
        # problem
        assert mt == 1 and bv == 10 and bn == 70

    def test_block_v_upper_bound_is_paper_tile(self):
        from repro.kernels.fused_vq_matmul.ops import select_fused_tiles

        _, bv, _ = select_fused_tiles(1, 512, 4096, 2, 256)
        assert bv <= ops.DEFAULT_BLOCK_V

    def test_fused_plan_freezes_tiles(self):
        """The eva_fused_pallas plan carries (mt, bv, bn) resolved once —
        nothing re-derived at execute time."""
        from repro.core import plan as plan_mod
        from repro.kernels.fused_vq_matmul.ops import select_fused_tiles

        x, vq = _mk(4096, 4096, (), 4)
        pl = plan_mod.plan_vq(x, vq, plan_mod.PlanPolicy(
            vq_mode="eva", impl="pallas", interpret=True))
        cfgd = pl.config_dict
        _, bv, bn = select_fused_tiles(4, vq.V, vq.N, vq.C, 256)
        assert pl.backend == "eva_fused_pallas"
        assert cfgd["bv"] == bv and cfgd["bn"] == bn
        # every row in one token tile
        assert cfgd["mt"] == 4 and cfgd["token_tiles"] == 1

    @pytest.mark.parametrize("M,k,mt", [(16, 256, 16), (16, 1024, 8),
                                        (3, 1024, 3), (3, 8192, 8)])
    def test_split_token_tile_takes_every_shape(self, M, k, mt):
        """The split backend's token tile: every row while the O tile
        fits the tile budget, else 8 rows at least, so the backend that
        takes what the fused kernel cannot never plans an empty tile."""
        from repro.kernels.oc_lookup.ops import select_lookup_tiles

        assert select_lookup_tiles(M, 1152, 3072, 2, k)[0] == mt

    @pytest.mark.parametrize("M,mt,tiles", [(16, 16, 1), (32, 16, 2),
                                            (24, 8, 3), (17, 8, 3),
                                            (40, 8, 5), (48, 16, 3)])
    def test_token_tiles_follow_the_oc_budget(self, M, mt, tiles):
        """Every row while their OC fits the budget, else the tile of a
        multiple of 8 rows that pads M least; nothing when not even 8
        rows fit (the plan then leaves the shape to the split
        backend)."""
        from repro.kernels.fused_vq_matmul.kernel import x_stage_bytes
        from repro.kernels.fused_vq_matmul.ops import (fused_oc_bytes,
                                                       select_fused_tiles)

        V, C = 1152, 2
        # per row: the OC scratch, the (8, bn) accumulator and the
        # activation stage of the VQ-GEMM
        room = lambda rows: rows * (fused_oc_bytes(V, C, 256, 32, 1)
                                    + 4 * 8 * 512 + x_stage_bytes(32, 256))
        got = select_fused_tiles(M, V, 3072, C, 256, oc_budget=room(16))[0]
        assert (got, -(-M // got)) == (mt, tiles)
        assert select_fused_tiles(M, V, 3072, C, 256,
                                  oc_budget=room(M))[0] == M
        assert select_fused_tiles(M, V, 3072, C, 256,
                                  oc_budget=room(8) - 1)[0] == 0
