"""Model-level quantization pass: eligibility, structure, compression."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.quantize import (
    compressed_model_bytes, count_vq_layers, quantize_params,
)
from repro.core.vq import VQWeight
from repro.models import build_model

KEY = jax.random.PRNGKey(0)


def _params(arch="llama2_7b"):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    return cfg, model, model.init(KEY)


class TestEligibility:
    def test_fc_layers_quantized_embeddings_not(self):
        cfg, model, params = _params()
        q = quantize_params(params, cfg, method="synthetic", key=KEY)
        assert count_vq_layers(q) > 0
        # embedding and lm_head stay dense
        assert "emb" in q["embedding"]
        assert "w" in q["lm_head"]
        # same-input projection families grouped into single wide leaves
        wqkv = q["layers"]["attn"]["wqkv"]["vq"]
        assert isinstance(wqkv, VQWeight)
        assert wqkv.splits == (cfg.q_dim, cfg.kv_dim, cfg.kv_dim)
        assert wqkv.N == cfg.q_dim + 2 * cfg.kv_dim
        gu = q["layers"]["mlp"]["gu"]["vq"]
        assert isinstance(gu, VQWeight)
        assert gu.splits == (cfg.d_ff, cfg.d_ff)
        assert isinstance(q["layers"]["mlp"]["down"]["vq"], VQWeight)
        assert q["layers"]["mlp"]["down"]["vq"].splits == ()
        # norms untouched
        assert "g" in q["final_norm"]

    def test_moe_experts_quantized_router_not(self):
        cfg, model, params = _params("mixtral_8x22b")
        q = quantize_params(params, cfg, method="synthetic", key=KEY)
        moe = q["layers"]["moe"]
        # expert gate+up grouped into one wide leaf per expert
        assert isinstance(moe["experts"]["gu"]["vq"], VQWeight)
        assert len(moe["experts"]["gu"]["vq"].splits) == 2
        assert "wr" in moe["router"]  # router stays dense
        # stacked leading dims preserved: (L, E, C, V, N)
        assert moe["experts"]["gu"]["vq"].idx.ndim == 5

    def test_gates_and_recurrence_not_quantized(self):
        cfg, model, params = _params("xlstm_125m")
        q = quantize_params(params, cfg, method="synthetic", key=KEY)
        g0 = q["groups"]["b0_mlstm"]
        assert "w" in g0["w_if"]          # per-head gates stay dense
        # mLSTM wq/wk/wv (same input h) grouped into one wide leaf
        assert "wq" not in g0
        wqkv = g0["wqkv"]["vq"]
        assert isinstance(wqkv, VQWeight)
        di = 2 * cfg.d_model
        assert wqkv.splits == (di, di, di)
        g1 = q["groups"]["b1_slstm"]
        assert "rz" in g1                  # recurrent weights untouched
        assert "wqkv" not in g1            # sLSTM wz/wi/wf/wo never grouped

    def test_mla_q_kva_grouped(self):
        cfg, model, params = _params("deepseek_v2_lite")
        q = quantize_params(params, cfg, method="synthetic", key=KEY)
        for block in (q["layers"]["attn"], q["pre_layers"]["attn"]):
            assert "wq" not in block and "wkv_a" not in block
            vq = block["wq_kva"]["vq"]
            assert isinstance(vq, VQWeight)
            assert vq.splits == (
                cfg.num_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim),
                cfg.kv_lora_rank + cfg.qk_rope_dim,
            )
            # wkv_b / wo stay independent leaves
            assert isinstance(block["wkv_b"]["vq"], VQWeight)
            assert block["wkv_b"]["vq"].splits == ()


class TestStructure:
    def test_idempotent(self):
        cfg, model, params = _params()
        q1 = quantize_params(params, cfg, method="synthetic", key=KEY)
        q2 = quantize_params(q1, cfg, method="synthetic", key=KEY)
        assert count_vq_layers(q1) == count_vq_layers(q2)

    def test_specs_mode_matches_synthetic_structure(self):
        cfg, model, params = _params()
        spec_tree = quantize_params(jax.eval_shape(lambda: params), cfg,
                                    method="specs")
        syn_tree = quantize_params(params, cfg, method="synthetic", key=KEY)
        s_leaves = jax.tree_util.tree_leaves(spec_tree)
        y_leaves = jax.tree_util.tree_leaves(syn_tree)
        assert len(s_leaves) == len(y_leaves)
        for s, y in zip(s_leaves, y_leaves):
            assert s.shape == y.shape and s.dtype == y.dtype

    def test_compression_ratio(self):
        cfg, model, params = _params()
        q = quantize_params(params, cfg, method="synthetic", key=KEY)
        vq_bytes, dense_bytes = compressed_model_bytes(q)
        # q = C*n/d = 2 bits/weight vs bf16 -> ~1/8 (+ codebook overhead,
        # large on smoke-size layers)
        assert vq_bytes < dense_bytes * 0.5
        assert vq_bytes > dense_bytes * 0.1

    def test_fit_matches_dequant_quality(self):
        """fit on real weights reconstructs better than synthetic junk."""
        from repro.core.vq import dequantize
        cfg, model, params = _params()
        cfg2 = dataclasses.replace(cfg, vq_n=6)
        qf = quantize_params(params, cfg2, method="fit", key=KEY)
        vq = qf["layers"]["mlp"]["gu"]["vq"]      # grouped [W_gate|W_up]
        W = np.concatenate(
            [np.asarray(params["layers"]["mlp"]["gate"]["w"]),
             np.asarray(params["layers"]["mlp"]["up"]["w"])], axis=-1,
        )  # (L, K, 2*d_ff)
        assert vq.splits == (cfg.d_ff, cfg.d_ff)
        errs = []
        for l in range(W.shape[0]):
            wl = W[l]
            vql = VQWeight(idx=vq.idx[l], codebooks=vq.codebooks[l],
                           scale=vq.scale[l], K=vq.K, N=vq.N, d=vq.d, n=vq.n)
            w_hat = np.asarray(dequantize(vql))
            errs.append(np.linalg.norm(wl - w_hat) / np.linalg.norm(wl))
        assert max(errs) < 0.9  # random-gaussian bound; structured << this


_SYNTHETIC_DIGEST = """
import hashlib, jax, numpy as np
from repro.configs import get_smoke_config
from repro.models import build_model
params = build_model(get_smoke_config("minitron_4b")).init_synthetic(
    jax.random.PRNGKey(7))
h = hashlib.sha256()
for leaf in jax.tree_util.tree_leaves(params):
    h.update(np.asarray(leaf).tobytes())
print(h.hexdigest())
"""


def test_synthetic_weights_depend_only_on_the_seed():
    """Two processes with different string-hash salts build identical
    synthetic params from one seed."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    digests = []
    for salt in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=salt, JAX_PLATFORMS="cpu",
                   PYTHONPATH=src)
        proc = subprocess.run([sys.executable, "-c", _SYNTHETIC_DIGEST],
                              env=env, capture_output=True, text=True,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        digests.append(proc.stdout.split()[-1])
    assert digests[0] == digests[1]
