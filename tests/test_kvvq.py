"""KV-VQ cache subsystem (core/vq.py + serve/kvcache.py + paging +
kernels/flash_decode): encode/decode round-trip geometry, kernel parity
against the dequantize oracle (contiguous AND paged), planner backend
registration/ranking, per-family logit-drift bounds vs the fp cache,
paged-vs-contiguous byte identity of the uint8 index arenas, and
engine-level token identity at 4-bit on a mixed workload."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import plan as plan_mod
from repro.core.plan import PlanPolicy
from repro.core.quantize import attach_kv_codebooks, kv_codebook_tree
from repro.core.vq import (KVQuantConfig, kv_decode, kv_encode,
                           kv_grid_codebooks)
from repro.kernels.flash_decode import (flash_decode_kvq,
                                        flash_decode_kvq_paged,
                                        flash_decode_kvq_ref)
from repro.models import build_model
from repro.models.common import RunConfig
from repro.serve import (BlockPool, Engine, EngineConfig, GenerationRequest,
                         SamplingParams, make_paging_config)
from repro.serve import paging
from repro.serve.kvcache import encode_prefill_cache, pad_prefill_cache

KEY = jax.random.PRNGKey(0)
CAP = 32


# ------------------------------------------------------------- encode/decode


class TestEncodeDecode:
    @pytest.mark.parametrize("kv_bits,residual", [(4, 1), (4, 2), (2, 1)])
    def test_geometry_and_roundtrip_error(self, kv_bits, residual):
        """Index width follows R*G = R*dim/vec_d; grid reconstruction
        error is bounded by half a lattice cell per stage (activations
        are scale-normalized into [-1, 1] before assignment)."""
        kvq = KVQuantConfig(kv_bits=kv_bits, residual=residual)
        Hk, hd = 2, 8
        assert kvq.vec_d * kv_bits == 8 * residual
        assert kvq.idx_width(hd) == residual * (hd // kvq.vec_d)
        cb = kv_grid_codebooks(Hk, hd, kvq)
        assert cb.shape == (Hk, residual, 256, kvq.vec_d)
        x = jax.random.normal(KEY, (3, 7, Hk, hd), jnp.float32)
        idx, scale = kv_encode(x, cb, kvq.variant)
        assert idx.shape == (3, 7, Hk, kvq.idx_width(hd))
        assert idx.dtype == jnp.uint8 and scale.shape == (3, 7, Hk)
        xhat = kv_decode(idx, scale, cb)
        err = jnp.max(jnp.abs(xhat - x) / jnp.maximum(
            jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8))
        levels = int(round(256 ** (1.0 / kvq.vec_d)))
        # finest stage cell half-width, relative to the scale channel
        # (+eps: greedy residual assignment lands exactly on the bound)
        bound = (1.0 / (levels - 1)) * levels ** (1 - residual)
        assert float(err) <= bound * (1 + 1e-5) + 1e-6

    def test_config_validation(self):
        with pytest.raises(ValueError, match="kv_bits"):
            KVQuantConfig(kv_bits=3)
        with pytest.raises(ValueError, match="entries"):
            KVQuantConfig(entries=512)
        with pytest.raises(ValueError):
            KVQuantConfig(variant="nope")


# ------------------------------------------------------------- kernel level


def _kvq_operands(kvq, *, B=2, S=24, Hk=2, g=2, hd=8):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hk * g, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hk, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hk, hd), jnp.float32)
    cb_k = kv_grid_codebooks(Hk, hd, kvq)
    cb_v = kv_grid_codebooks(Hk, hd, kvq)
    k_idx, k_s = kv_encode(k, cb_k, kvq.variant)
    v_idx, v_s = kv_encode(v, cb_v, kvq.variant)
    lengths = jnp.array([S, S - 7], jnp.int32)
    return q, k_idx, v_idx, k_s, v_s, lengths, cb_k, cb_v


class TestKernel:
    @pytest.mark.parametrize("kv_bits,residual", [(4, 1), (4, 2), (2, 1)])
    def test_pallas_matches_dequant_oracle(self, kv_bits, residual):
        """The fused kernel (query/K-codebook table + in-kernel index
        gathers + post-softmax V reconstruction) reproduces
        dequantize-then-flash-decode."""
        kvq = KVQuantConfig(kv_bits=kv_bits, residual=residual)
        ops = _kvq_operands(kvq)
        ref = flash_decode_kvq_ref(*ops)
        out = flash_decode_kvq(*ops, block_s=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_partial_tail_block_masked(self):
        """lengths beyond the last full S-block: the online-softmax mask
        must zero pad positions, not just pad rows of the final block."""
        kvq = KVQuantConfig(kv_bits=4)
        q, k_idx, v_idx, k_s, v_s, _, cb_k, cb_v = _kvq_operands(kvq, S=24)
        lengths = jnp.array([1, 17], jnp.int32)
        ref = flash_decode_kvq_ref(q, k_idx, v_idx, k_s, v_s, lengths,
                                   cb_k, cb_v)
        out = flash_decode_kvq(q, k_idx, v_idx, k_s, v_s, lengths,
                               cb_k, cb_v, block_s=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_paged_matches_contiguous(self):
        """Scatter the index/scale planes into block arenas; the paged
        entry (uint8 gathers through the table) is bit-equivalent to the
        contiguous call, sentinel ids included."""
        kvq = KVQuantConfig(kv_bits=4)
        B, S, bs = 2, 24, 8
        q, k_idx, v_idx, k_s, v_s, lengths, cb_k, cb_v = _kvq_operands(
            kvq, B=B, S=S)
        W = S // bs
        NB = B * W  # sentinel == NB
        table = jnp.arange(B * W, dtype=jnp.int32).reshape(B, W)
        table = table.at[1, -1].set(NB)  # short row: last block unmapped

        def scatter(x):
            arena = jnp.zeros((NB + 1, bs) + x.shape[2:], x.dtype)
            return arena.at[:NB].set(
                x.reshape((B * W, bs) + x.shape[2:]))[:NB]

        lengths = jnp.array([S, bs], jnp.int32)
        out = flash_decode_kvq_paged(
            q, scatter(k_idx), scatter(v_idx), scatter(k_s), scatter(v_s),
            table, lengths, cb_k, cb_v, block_s=16, interpret=True)
        ref = flash_decode_kvq(q, k_idx, v_idx, k_s, v_s, lengths,
                               cb_k, cb_v, block_s=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)


class TestPlanBackends:
    def _spec(self):
        kvq = KVQuantConfig(kv_bits=4)
        return plan_mod.kvq_attention_spec(
            B=2, S=CAP, H=4, Hk=2, hd=8, idx_width=kvq.idx_width(8),
            entries=kvq.entries, x_dtype=jnp.float32, out_dtype=jnp.float32)

    def test_backend_selection_by_policy(self):
        """kind="kvq_attn" resolves to the dequantize oracle under jnp
        and to the fused kernel under impl="pallas" — cost ranking
        prefers the single-launch table+gather formulation."""
        spec = self._spec()
        assert plan_mod.plan(spec, PlanPolicy()).backend == "kvq_dequant_jnp"
        pl = plan_mod.plan(spec, PlanPolicy(impl="pallas", interpret=True))
        assert pl.backend == "kvq_flash_pallas"

    def test_execute_matches_direct_call(self):
        kvq = KVQuantConfig(kv_bits=4)
        ops = _kvq_operands(kvq, S=CAP)
        ref = flash_decode_kvq_ref(*ops)
        for policy in (PlanPolicy(), PlanPolicy(impl="pallas",
                                                interpret=True)):
            pl = plan_mod.plan(self._spec(), policy)
            out = pl.execute(ops, None)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- model level


KVQ_ARCHS = ["llama2_7b", "mixtral_8x22b", "deepseek_v2_lite"]


def _fp32_cfg(arch):
    return dataclasses.replace(get_smoke_config(arch), dtype="float32")


def _family_setup(arch, kvq):
    cfg = _fp32_cfg(arch)
    model = build_model(cfg)
    params = attach_kv_codebooks(model.init(KEY), cfg, kvq)
    return cfg, model, params, kv_codebook_tree(params)


def _prefill_pair(cfg, model, params, cbs, kvq, S):
    """One fp prefill -> (fp cache, KV-VQ-encoded cache), both padded to
    decode capacity."""
    window = cfg.sliding_window or cfg.local_window
    tokens = jax.random.randint(KEY, (1, S + 8), 0, cfg.vocab_size)
    rc_p = RunConfig(mode="prefill", remat=False, attn_chunk=8)
    _, fresh = model.prefill(params, {"tokens": tokens[:, :S]}, rc_p)
    enc = encode_prefill_cache(fresh, cbs, kvq)
    return (tokens, window, fresh, enc,
            pad_prefill_cache(fresh, CAP, window=window),
            pad_prefill_cache(enc, CAP, window=window))


@pytest.mark.parametrize("arch", KVQ_ARCHS)
def test_kvvq_decode_drift_vs_fp(arch):
    """Accuracy drift per family (dense/SWA/MLA): greedy decode over the
    4-bit VQ cache stays within a pinned max-logit deviation of the fp
    cache on a fixed prompt (observed ~0.8 on the smoke models; the
    bound is 3x slack, catching quantizer/kernel regressions, not noise).
    """
    kvq = KVQuantConfig(kv_bits=4)
    cfg, model, params, cbs = _family_setup(arch, kvq)
    S, N = 12, 3
    tokens, _, _, _, cont_fp, cont_q = _prefill_pair(
        cfg, model, params, cbs, kvq, S)
    rc_fp = RunConfig(mode="decode", remat=False)
    rc_q = RunConfig(mode="decode", remat=False, kv_vq=kvq)
    drift = 0.0
    for t in range(S, S + N):
        pos = jnp.full((1, 1), t, jnp.int32)
        lf, cont_fp = model.decode(params, tokens[:, t:t + 1], pos,
                                   cont_fp, rc_fp)
        lq, cont_q = model.decode(params, tokens[:, t:t + 1], pos,
                                  cont_q, rc_q)
        assert bool(jnp.all(jnp.isfinite(lq)))
        drift = max(drift, float(jnp.max(jnp.abs(lq - lf))))
    assert drift < 2.5, f"{arch}: 4-bit logit drift {drift} exceeds bound"


@pytest.mark.parametrize("arch", KVQ_ARCHS)
def test_kvvq_paged_decode_matches_contiguous(arch):
    """Paged KV-VQ decode (uint8 arenas + block tables) reproduces the
    contiguous VQ cache's logits for every family."""
    kvq = KVQuantConfig(kv_bits=4)
    cfg, model, params, cbs = _family_setup(arch, kvq)
    S, N = 12, 3
    tokens, window, _, enc, _, cont_q = _prefill_pair(
        cfg, model, params, cbs, kvq, S)
    meta = make_paging_config(model, 1, CAP, window=window, block_size=4,
                              kvq=kvq)
    paged = paging.init_paged_cache(model, 1, CAP, meta, kvq=kvq)
    pool = BlockPool(meta.num_blocks)
    row = np.asarray(pool.alloc(meta.blocks_per_slot), np.int32)
    paged = paging.write_prefill_into_blocks(
        paged, enc, 0, row, jnp.asarray(S, jnp.int32), meta, window=window)
    paged = paging.set_block_tables(paged, row[None])
    rc_q = RunConfig(mode="decode", remat=False, kv_vq=kvq)
    for t in range(S, S + N):
        pos = jnp.full((1, 1), t, jnp.int32)
        lc, cont_q = model.decode(params, tokens[:, t:t + 1], pos,
                                  cont_q, rc_q)
        lp, paged = model.decode(params, tokens[:, t:t + 1], pos,
                                 paged, rc_q)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lc),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["llama2_7b", "deepseek_v2_lite"])
def test_kvvq_index_arena_byte_identity(arch):
    """The paged uint8 index arenas, gathered through the block table,
    are byte-identical to the contiguous index cache — same codes, same
    scales, only the memory layout differs. (The quantizer runs before
    the layout split, so any divergence is a scatter/paging bug.)"""
    kvq = KVQuantConfig(kv_bits=4)
    cfg, model, params, cbs = _family_setup(arch, kvq)
    S = 12
    _, window, _, enc, _, cont_q = _prefill_pair(
        cfg, model, params, cbs, kvq, S)
    meta = make_paging_config(model, 1, CAP, window=window, block_size=4,
                              kvq=kvq)
    paged = paging.init_paged_cache(model, 1, CAP, meta, kvq=kvq)
    pool = BlockPool(meta.num_blocks)
    row = np.asarray(pool.alloc(meta.blocks_per_slot), np.int32)
    paged = paging.write_prefill_into_blocks(
        paged, enc, 0, row, jnp.asarray(S, jnp.int32), meta, window=window)

    checked = []

    def walk(pnode, cnode, path):
        if not isinstance(pnode, dict):
            return
        if "block_table" not in pnode:
            for k in pnode:
                walk(pnode[k], cnode[k], path + (k,))
            return
        for k, arena in pnode.items():
            if k in ("block_table", "len"):
                continue
            a = np.asarray(arena)          # (L, NB, bs, ...)
            cont = np.asarray(cnode[k])    # (L, 1, S_cap, ...)
            view = a[:, row].reshape((a.shape[0], CAP) + a.shape[3:])
            assert np.array_equal(view, cont[:, 0]), (path, k)
            checked.append((path, k, str(a.dtype)))

    walk(paged, cont_q, ())
    kinds = {dt for _, _, dt in checked}
    assert "uint8" in kinds and "bfloat16" in kinds  # indices AND scales


# -------------------------------------------------------------- engine level


@pytest.fixture(scope="module")
def setup():
    cfg = _fp32_cfg("llama2_7b")
    model = build_model(cfg)
    params = model.init(KEY)
    return cfg, model, params, RunConfig(mode="decode", remat=False,
                                         attn_chunk=16)


def _mixed_requests(cfg, lengths, max_new=6):
    rng = np.random.default_rng(7)
    reqs = []
    for i, L in enumerate(lengths):
        prompt = rng.integers(0, cfg.vocab_size, int(L)).astype(np.int32)
        sp = SamplingParams()
        if i % 3 == 1:
            sp = SamplingParams(greedy=False, temperature=0.8, top_k=20,
                                seed=100 + i)
        reqs.append(GenerationRequest(prompt=prompt, max_new_tokens=max_new,
                                      sampling=sp))
    return reqs


def _drain(eng, uids, limit=400):
    for _ in range(limit):
        eng.step()
        if all(eng.output(u) is not None and eng.output(u).finish_reason
               for u in uids):
            return [list(eng.output(u).tokens) for u in uids]
    raise AssertionError("engine did not drain")


def test_engine_kvvq_token_identity_mixed_workload(setup):
    """4-bit engine end-to-end on a mixed greedy/sampled workload:
    paged and contiguous arenas produce identical token streams (the
    acceptance gate — quantization happens before the layout split)."""
    cfg, model, params, rc = setup
    lengths = [5, 9, 3, 12]
    outs = {}
    for paged in (False, True):
        eng = Engine(model, params, rc,
                     EngineConfig(num_slots=2, max_len=CAP, kv_bits=4,
                                  paged=paged))
        uids = [eng.submit(r) for r in _mixed_requests(cfg, lengths)]
        outs[paged] = _drain(eng, uids)
    assert outs[False] == outs[True]
    assert all(len(t) > 0 for t in outs[False])


def test_engine_kvvq_2bit_runs(setup):
    """2-bit cache (vec_d=4 grid): engine completes and emits tokens —
    accuracy is not pinned at 2 bits, liveness and layout are."""
    cfg, model, params, rc = setup
    eng = Engine(model, params, rc,
                 EngineConfig(num_slots=2, max_len=CAP, kv_bits=2))
    uids = [eng.submit(r) for r in _mixed_requests(cfg, [4, 7], max_new=3)]
    toks = _drain(eng, uids)
    assert all(len(t) == 3 for t in toks)


def test_engine_kv_bits_validation(setup):
    cfg, model, params, rc = setup
    with pytest.raises(ValueError, match="kv_bits"):
        Engine(model, params, rc,
               EngineConfig(num_slots=1, max_len=CAP, kv_bits=3))


def test_engine_mla_int8_rejected():
    """int8 per-channel KV is a GQA layout; MLA latents only support
    fp16/fp32 or KV-VQ — the engine refuses the combination loudly."""
    cfg = _fp32_cfg("deepseek_v2_lite")
    model = build_model(cfg)
    params = model.init(KEY)
    rc = RunConfig(mode="decode", remat=False, attn_chunk=16)
    with pytest.raises(ValueError):
        Engine(model, params, rc,
               EngineConfig(num_slots=1, max_len=CAP, kv_bits=8))
