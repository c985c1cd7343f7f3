"""Grouped-projection VQ correctness: a same-input family ([Wq|Wk|Wv],
[W_gate|W_up]) quantized as ONE wide VQ weight sharing a codebook set must
match independent per-projection oracles, through every execution path —
jnp EVA, the fused Pallas kernel (uint8 index streaming, interpret mode),
padding, the quantization pass, checkpointing, and model-level decode."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ops as core_ops
from repro.core.plan import PlanPolicy
from repro.core.vq import (
    VQWeight, dequantize, fit_vq, split_grouped, synthetic_vq, vq_specs,
)
from repro.kernels.fused_vq_matmul import fused_vq_matmul
from repro.kernels.fused_vq_matmul.kernel import fused_vq_matmul_pallas

KEY = jax.random.PRNGKey(0)

# (K, splits, M, d, n, C) — includes non-multiple V and N vs the kernel
# block sizes used below (block_v=8, block_n=64)
GROUPED_SWEEP = [
    (64, (128, 32, 32), 1, 8, 8, 2),     # paper decode M=1, qkv-like
    (80, (40, 18, 12), 3, 8, 8, 2),      # V=10, N=70: pads V and N
    (128, (96, 96), 2, 8, 4, 1),         # gate+up-like, n=4
    (96, (50, 26, 20), 4, 8, 5, 3),      # odd widths, C=3
]


def _grouped(K, splits, M, d, n, C):
    vq = synthetic_vq(KEY, K, sum(splits), d=d, n=n, C=C, splits=splits)
    x = jax.random.normal(jax.random.fold_in(KEY, K + M), (M, K), jnp.float32)
    return x, vq


class TestGroupedCore:
    def test_fit_vq_grouped_records_splits(self):
        Wq = jax.random.normal(KEY, (64, 48)) * 0.1
        Wk = jax.random.normal(jax.random.fold_in(KEY, 1), (64, 16)) * 0.1
        g = fit_vq(KEY, [Wq, Wk], d=8, n=5, C=2, kmeans_iters=5,
                   refine_rounds=0)
        assert g.splits == (48, 16) and g.N == 64
        # grouped reconstruction approximates the concatenated matrix
        err = float(np.linalg.norm(np.asarray(dequantize(g))
                                   - np.concatenate([Wq, Wk], axis=1)))
        assert np.isfinite(err)

    def test_fit_vq_grouped_rejects_mismatched_K(self):
        with pytest.raises(ValueError, match="equal K"):
            fit_vq(KEY, [jnp.zeros((64, 8)), jnp.zeros((32, 8))], d=8)

    def test_grouped_collapse_ratio(self):
        # one shared VQ-GEMM serves sum(N_i) channels: (4096+2*1024)/2^8
        members = (4096, 1024, 1024)
        assert core_ops.grouped_compute_collapse_ratio(members, 8) == \
            pytest.approx(24.0)
        # grouped ratio is the sum of the members' individual ratios
        assert core_ops.grouped_compute_collapse_ratio(members, 8) == \
            pytest.approx(sum(core_ops.compute_collapse_ratio(m, 8)
                              for m in members))

    def test_split_grouped_members_reconstruct(self):
        _, vq = _grouped(64, (128, 32, 32), 1, 8, 8, 2)
        members = split_grouped(vq)
        assert tuple(m.N for m in members) == vq.splits
        w = np.asarray(dequantize(vq))
        off = 0
        for m in members:
            np.testing.assert_allclose(
                np.asarray(dequantize(m)), w[:, off:off + m.N], rtol=1e-6)
            off += m.N

    @pytest.mark.parametrize("K,splits,M,d,n,C", GROUPED_SWEEP)
    def test_grouped_eva_matches_per_projection_oracles(self, K, splits, M,
                                                        d, n, C):
        """One wide EVA matmul + split == independent dequant_matmul
        oracles on each member (the tentpole's exactness requirement)."""
        x, vq = _grouped(K, splits, M, d, n, C)
        y = core_ops.eva_matmul(x, vq, out_dtype=jnp.float32)
        parts = core_ops.split_grouped_outputs(y, vq)
        assert tuple(p.shape[-1] for p in parts) == splits
        for part, member in zip(parts, split_grouped(vq)):
            ref = core_ops.dequant_matmul(x, member, out_dtype=jnp.float32)
            np.testing.assert_allclose(np.asarray(part), np.asarray(ref),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("K,splits,M,d,n,C", GROUPED_SWEEP)
    def test_grouped_fused_pallas_interpret(self, K, splits, M, d, n, C):
        """The fused Pallas kernel on a grouped weight (single OC scratch,
        widened N sweep, uint8 index tiles) matches the jnp oracle,
        including the non-multiple V/N padding paths."""
        x, vq = _grouped(K, splits, M, d, n, C)
        assert vq.idx.dtype == jnp.uint8  # n<=8 storage dtype
        got = fused_vq_matmul(x, vq, interpret=True, block_v=8, block_n=64,
                              out_dtype=jnp.float32)
        ref = core_ops.eva_matmul(x, vq, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


class TestUint8Streaming:
    def test_pallas_call_consumes_uint8_indices(self):
        """The fused kernel's pallas_call input must be the uint8 index
        matrix itself — no pre-call int32 upcast (which would stream 4x
        the bytes the paper's q-bits/weight bandwidth model assumes)."""
        x, vq = _grouped(64, (128, 32, 32), 1, 8, 8, 2)
        jaxpr = jax.make_jaxpr(
            lambda a, b: fused_vq_matmul(a, b, interpret=True)
        )(x, vq)

        def find_pallas(jxp, out):
            for eqn in jxp.eqns:
                if eqn.primitive.name == "pallas_call":
                    out.append(eqn)
                for sub in eqn.params.values():
                    if hasattr(sub, "jaxpr"):
                        find_pallas(sub.jaxpr, out)
            return out

        calls = find_pallas(jaxpr.jaxpr, [])
        assert calls, "no pallas_call found in fused_vq_matmul jaxpr"
        idx_shape = vq.idx.shape  # (C, V, N); no padding at these shapes
        for eqn in calls:
            dtypes = {v.aval.shape: v.aval.dtype for v in eqn.invars}
            assert dtypes.get(idx_shape) == jnp.uint8, dtypes

    def test_kernel_level_uint8_input(self):
        """fused_vq_matmul_pallas accepts storage-dtype (uint8) index tiles
        directly and upcasts per tile in-kernel."""
        x, vq = _grouped(64, (64, 32, 32), 2, 8, 8, 2)
        # the kernel's layout: token-major activations (M, V, d), every
        # row in one token tile, scale as a (1, N) row
        got = fused_vq_matmul_pallas(
            x.reshape(2, vq.V, vq.d), vq.codebooks, vq.idx,
            vq.scale[None, :], m_tile=2, block_v=4, block_n=64,
            interpret=True,
        )
        ref = core_ops.eva_matmul(x, vq, out_dtype=jnp.float32)
        assert vq.idx.dtype == jnp.uint8
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


class TestGroupedQuantizePass:
    def test_specs_match_synthetic_for_grouped_tree(self):
        from repro.configs import get_smoke_config
        from repro.core.quantize import quantize_params
        from repro.models import build_model

        cfg = get_smoke_config("llama2_7b")
        model = build_model(cfg)
        params = model.init(KEY)
        syn = quantize_params(params, cfg, method="synthetic", key=KEY)
        spec = quantize_params(jax.eval_shape(lambda: params), cfg,
                               method="specs")
        # same treedef (incl. splits aux) and leaf shapes/dtypes
        ts = jax.tree_util.tree_structure(syn)
        tp = jax.tree_util.tree_structure(spec)
        assert ts == tp
        for s, y in zip(jax.tree_util.tree_leaves(spec),
                        jax.tree_util.tree_leaves(syn)):
            assert s.shape == y.shape and s.dtype == y.dtype

    def test_group_projections_off_preserves_legacy_layout(self):
        from repro.configs import get_smoke_config
        from repro.core.quantize import quantize_params
        from repro.models import build_model

        cfg = get_smoke_config("llama2_7b")
        model = build_model(cfg)
        params = model.init(KEY)
        q = quantize_params(params, cfg, method="synthetic", key=KEY,
                            group_projections=False)
        assert "wq" in q["layers"]["attn"] and "wqkv" not in q["layers"]["attn"]
        assert q["layers"]["attn"]["wq"]["vq"].splits == ()

    def test_grouped_bias_concatenated(self):
        from repro.configs import get_smoke_config
        from repro.core.quantize import quantize_params
        from repro.models import build_model

        cfg = get_smoke_config("whisper_medium")  # qkv_bias=True family
        model = build_model(cfg)
        params = model.init(KEY)
        q = quantize_params(params, cfg, method="synthetic", key=KEY)
        enc_attn = q["encoder"]["attn"]
        assert "wqkv" in enc_attn
        vq = enc_attn["wqkv"]["vq"]
        # bias is the member concatenation (stacked layer dims preserved)
        assert enc_attn["wqkv"]["b"].shape[-1] == vq.N
        # cross-attention is never grouped (q consumes a different input)
        assert "wq" in q["decoder"]["cross_attn"]


class TestGroupedCheckpoint:
    def test_splits_survive_roundtrip(self, tmp_path):
        from repro.checkpoint.manager import CheckpointManager

        vq = synthetic_vq(KEY, 64, 48, d=8, n=8, C=2, splits=(32, 8, 8))
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(0, {"params": {"wqkv": {"vq": vq}}}, block=True)
        _, state = mgr.restore()
        back = state["params"]["wqkv"]["vq"]
        assert isinstance(back, VQWeight)
        assert back.splits == (32, 8, 8)
        assert back.idx.dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(back.idx),
                                      np.asarray(vq.idx))


class TestGroupedNewFamilies:
    """xlstm mLSTM wq/wk/wv and MLA wq/wkv_a grouped families: the grouped
    block forward must match the same block run on per-projection members
    (split_grouped keeps the identical codebooks/indices, so this is an
    exact per-projection oracle through the real model code)."""

    @staticmethod
    def _ungroup(block, gkey, member_names):
        out = {k: v for k, v in block.items() if k != gkey}
        members = split_grouped(block[gkey]["vq"])
        for name, m in zip(member_names, members):
            out[name] = {"vq": m}
        return out

    def test_xlstm_mlstm_grouped_matches_split_members(self):
        from repro.configs import get_smoke_config
        from repro.core.quantize import quantize_params
        from repro.models import xlstm
        from repro.models.common import RunConfig

        cfg = dataclasses.replace(get_smoke_config("xlstm_125m"),
                                  dtype="float32")
        block = xlstm.make_mlstm_block(KEY, cfg)
        pg = quantize_params({"groups": {"b": block}}, cfg,
                             method="synthetic", key=KEY)["groups"]["b"]
        assert pg["wqkv"]["vq"].splits == (128, 128, 128)
        ps = self._ungroup(pg, "wqkv", ("wq", "wk", "wv"))
        x = jax.random.normal(KEY, (2, 3, cfg.d_model), jnp.float32)
        rc = RunConfig(mode="decode", remat=False,
                       plan_policy=PlanPolicy(vq_mode="eva"))
        yg, _ = xlstm.mlstm_block_fwd(pg, x, rc, cfg)
        ys, _ = xlstm.mlstm_block_fwd(ps, x, rc, cfg)
        np.testing.assert_allclose(np.asarray(yg), np.asarray(ys),
                                   rtol=1e-4, atol=1e-4)

    def test_mla_grouped_matches_split_members(self):
        from repro.configs import get_smoke_config
        from repro.core.quantize import quantize_params
        from repro.models.common import RunConfig, make_mla, mla_fwd

        cfg = dataclasses.replace(get_smoke_config("deepseek_v2_lite"),
                                  dtype="float32")
        block = make_mla(KEY, cfg)
        pg = quantize_params({"layers": {"attn": block}}, cfg,
                             method="synthetic", key=KEY)["layers"]["attn"]
        assert pg["wq_kva"]["vq"].splits == (192, 80)
        ps = self._ungroup(pg, "wq_kva", ("wq", "wkv_a"))
        x = jax.random.normal(KEY, (2, 3, cfg.d_model), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(3, dtype=jnp.int32)[None], (2, 3))
        rc = RunConfig(mode="prefill", remat=False, attn_chunk=8,
                       plan_policy=PlanPolicy(vq_mode="eva"))
        yg, _ = mla_fwd(pg, x, rc, cfg, positions=pos)
        ys, _ = mla_fwd(ps, x, rc, cfg, positions=pos)
        np.testing.assert_allclose(np.asarray(yg), np.asarray(ys),
                                   rtol=1e-4, atol=1e-4)


class TestGroupedModelDecode:
    def test_grouped_decode_eva_equals_dequant(self):
        """Model-level parity on grouped params: the single-wide-matmul
        decode path (wqkv + gu) and the dequant oracle agree exactly."""
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.models.common import RunConfig

        cfg = dataclasses.replace(get_smoke_config("llama2_7b"),
                                  dtype="float32")
        model = build_model(cfg)
        params = model.init(KEY)
        q = model.quantize(params, method="synthetic", key=KEY)
        assert "wqkv" in q["layers"]["attn"] and "gu" in q["layers"]["mlp"]
        caches = model.init_cache(2, 16)
        tok = jnp.zeros((2, 1), jnp.int32)
        pos = jnp.zeros((2, 1), jnp.int32)
        l_eva, _ = model.decode(
            q, tok, pos, caches,
            RunConfig(mode="decode", remat=False,
                      plan_policy=PlanPolicy(vq_mode="eva")))
        l_deq, _ = model.decode(
            q, tok, pos, caches,
            RunConfig(mode="decode", remat=False,
                      plan_policy=PlanPolicy(vq_mode="dequant")))
        np.testing.assert_allclose(np.asarray(l_eva), np.asarray(l_deq),
                                   rtol=1e-4, atol=1e-4)

    def test_grouped_decode_pallas_uint8(self):
        """Grouped decode through the fused Pallas kernel (interpret) ==
        the jnp path — the full stack streams uint8 indices."""
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.models.common import RunConfig

        cfg = dataclasses.replace(get_smoke_config("llama2_7b"),
                                  dtype="float32")
        model = build_model(cfg)
        params = model.init(KEY)
        q = model.quantize(params, method="synthetic", key=KEY)
        assert q["layers"]["attn"]["wqkv"]["vq"].idx.dtype == jnp.uint8
        caches = model.init_cache(1, 8)
        tok = jnp.zeros((1, 1), jnp.int32)
        pos = jnp.zeros((1, 1), jnp.int32)
        l_jnp, _ = model.decode(
            q, tok, pos, caches,
            RunConfig(mode="decode", remat=False,
                      plan_policy=PlanPolicy(vq_mode="eva")))
        l_pal, _ = model.decode(
            q, tok, pos, caches,
            RunConfig(mode="decode", remat=False,
                      plan_policy=PlanPolicy(vq_mode="eva", impl="pallas",
                                             interpret=True)))
        np.testing.assert_allclose(np.asarray(l_jnp), np.asarray(l_pal),
                                   rtol=1e-4, atol=1e-4)
