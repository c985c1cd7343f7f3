"""Paged KV-cache subsystem (serve/paging.py): block-pool invariants,
paged-vs-contiguous token/logit identity per family (int8 and SWA ring
wrap included), chunked prefill == one-shot prefill, out-of-blocks
preemption, mid-run snapshot/restore, and the KV memory gauges."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.models.common import RunConfig
from repro.serve import (BlockPool, Engine, EngineConfig, GenerationRequest,
                         SamplingParams, blocks_for_len, make_paging_config)
from repro.serve import paging
from repro.serve.api import chunk_spans
from repro.serve.kvcache import pad_prefill_cache

KEY = jax.random.PRNGKey(0)
CAP = 32


# ---------------------------------------------------------------- unit level


class TestGeometry:
    def test_effective_block_size_divisor(self):
        assert paging.effective_block_size(4, 32) == 4
        assert paging.effective_block_size(32, 32) == 32
        # gcd fallback when the request doesn't divide page_len
        assert paging.effective_block_size(12, 32) == 4
        assert paging.effective_block_size(7, 32) == 1
        with pytest.raises(ValueError):
            paging.effective_block_size(0, 32)

    def test_blocks_for_len_ceil_and_ring_cap(self):
        assert blocks_for_len(0, block_size=4, page_len=32) == 0
        assert blocks_for_len(1, block_size=4, page_len=32) == 1
        assert blocks_for_len(9, block_size=4, page_len=32) == 3
        assert blocks_for_len(-3, block_size=4, page_len=32) == 0
        # ring/SWA cap: a windowed cache wraps at page_len = window, so a
        # 1000-token prompt still needs only ceil(window / block_size)
        assert blocks_for_len(1000, block_size=4, page_len=32) == 8

    def test_make_paging_config_defaults_and_bounds(self):
        cfg = dataclasses.replace(get_smoke_config("llama2_7b"),
                                  dtype="float32")
        model = build_model(cfg)
        meta = make_paging_config(model, 3, CAP, block_size=4)
        assert meta.block_size == 4
        assert meta.page_len == CAP
        assert meta.blocks_per_slot == CAP // 4
        # default pool == contiguous worst case, but shared
        assert meta.num_blocks == 3 * meta.blocks_per_slot
        assert meta.sentinel == meta.num_blocks
        assert meta.bytes_per_block > 0
        # windowed: page_len snaps to the ring
        meta_w = make_paging_config(model, 2, 64, window=16, block_size=4)
        assert meta_w.page_len == 16 and meta_w.blocks_per_slot == 4
        with pytest.raises(ValueError, match="one full slot"):
            make_paging_config(model, 2, CAP, block_size=4,
                               num_blocks=CAP // 4 - 1)

    def test_chunk_spans_walk(self):
        assert chunk_spans(10, 4) == [(0, 4, 4), (4, 4, 4), (8, 2, 2)]
        assert chunk_spans(4, 8) == [(0, 4, 4)]
        # bucketed: each chunk pads to its own bucket
        assert chunk_spans(10, 4, buckets=(4, 8, 16)) == \
            [(0, 4, 4), (4, 4, 4), (8, 2, 4)]
        with pytest.raises(ValueError):
            chunk_spans(0, 4)
        with pytest.raises(ValueError):
            chunk_spans(4, 0)


class TestBlockPool:
    def test_lifo_deterministic(self):
        pool = BlockPool(4)
        assert pool.alloc(3) == [0, 1, 2]
        pool.free([1])
        assert pool.alloc(1) == [1]  # most recently freed comes back first
        pool.free([2, 0])
        assert pool.alloc(2) == [0, 2]

    def test_alloc_all_or_nothing(self):
        pool = BlockPool(3)
        assert pool.alloc(4) is None
        assert pool.free_count == 3  # refused alloc takes nothing
        got = pool.alloc(3)
        assert sorted(got) == [0, 1, 2] and pool.free_count == 0
        assert pool.alloc(1) is None
        assert pool.alloc(0) == []
        with pytest.raises(ValueError):
            pool.alloc(-1)

    def test_free_guards(self):
        pool = BlockPool(3)
        blocks = pool.alloc(2)
        pool.free(blocks)
        with pytest.raises(ValueError, match="double free"):
            pool.free([blocks[0]])
        with pytest.raises(ValueError, match="out of range"):
            pool.free([3])

    def test_state_restore_roundtrip(self):
        pool = BlockPool(5)
        pool.alloc(2)
        pool.free([0])
        state = pool.state()
        seq = [pool.alloc(1), pool.alloc(2)]
        fresh = BlockPool(5)
        fresh.restore(state)
        assert [fresh.alloc(1), fresh.alloc(2)] == seq  # exact layout replay
        with pytest.raises(ValueError, match="duplicate"):
            fresh.restore([1, 1])
        with pytest.raises(ValueError, match="out of range"):
            fresh.restore([7])


# --------------------------------------------------------------- model level


PAGED_ARCHS = ["llama2_7b", "mixtral_8x22b", "deepseek_v2_lite",
               "whisper_medium", "recurrentgemma_2b", "xlstm_125m",
               "llama_3_2_vision_11b"]


def _fp32_cfg(arch):
    return dataclasses.replace(get_smoke_config(arch), dtype="float32")


def _extras(cfg, B=1):
    ex = {}
    if cfg.family == "whisper":
        ex["frames"] = jax.random.normal(KEY, (B, 16, cfg.d_model),
                                         jnp.float32)
    if cfg.family == "vision":
        ex["image_embeds"] = jax.random.normal(KEY, (B, 8, cfg.d_model),
                                               jnp.float32)
    return ex


def _paged_after_prefill(model, fresh, true_len, *, cap, window=0,
                         block_size=4, kv_int8=False):
    """Build a 1-slot paged cache holding `fresh` (a B=1 prefill cache)."""
    meta = make_paging_config(model, 1, cap, window=window,
                              block_size=block_size, kv_int8=kv_int8)
    caches = paging.init_paged_cache(model, 1, cap, meta, kv_int8=kv_int8)
    pool = BlockPool(meta.num_blocks)
    row = np.asarray(pool.alloc(meta.blocks_per_slot), np.int32)
    caches = paging.write_prefill_into_blocks(
        caches, fresh, 0, row, jnp.asarray(true_len, jnp.int32), meta,
        window=window)
    caches = paging.set_block_tables(caches, row[None])
    return caches, meta, row


@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_paged_decode_matches_contiguous(arch):
    """Per-family logit identity: decoding over block arenas + tables
    reproduces the contiguous cache — gather view is shape-identical, so
    the same attention arithmetic runs on both."""
    cfg = _fp32_cfg(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    S, N = 12, 3
    tokens = jax.random.randint(KEY, (1, S + N), 0, cfg.vocab_size)
    ex = _extras(cfg)
    window = cfg.sliding_window or cfg.local_window
    _, fresh = model.prefill(
        params, {"tokens": tokens[:, :S], **ex},
        RunConfig(mode="prefill", remat=False, attn_chunk=8))
    cont = pad_prefill_cache(fresh, CAP, window=window)
    paged, _, _ = _paged_after_prefill(model, fresh, S, cap=CAP,
                                       window=window)
    rc_d = RunConfig(mode="decode", remat=False)
    for t in range(S, S + N):
        pos = jnp.full((1, 1), t, jnp.int32)
        lc, cont = model.decode(params, tokens[:, t:t + 1], pos, cont, rc_d)
        lp, paged = model.decode(params, tokens[:, t:t + 1], pos, paged, rc_d)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lc),
                                   rtol=1e-5, atol=1e-5)


def test_paged_decode_int8_kv_matches_contiguous():
    """int8 KV: decode quantizes the new K/V and scatters value + scale
    leaves through the table; logits match the contiguous int8 cache
    exactly (same quantizer, same storage values)."""
    cfg = _fp32_cfg("llama2_7b")
    model = build_model(cfg)
    params = model.init(KEY)
    cap = 16
    cont = model.init_cache(1, cap, kv_int8=True)
    meta = make_paging_config(model, 1, cap, block_size=4, kv_int8=True)
    paged = paging.init_paged_cache(model, 1, cap, meta, kv_int8=True)
    pool = BlockPool(meta.num_blocks)
    row = np.asarray(pool.alloc(meta.blocks_per_slot), np.int32)
    paged = paging.set_block_tables(paged, row[None])
    tokens = jax.random.randint(KEY, (1, 6), 0, cfg.vocab_size)
    rc_d = RunConfig(mode="decode", remat=False)
    for t in range(6):
        pos = jnp.full((1, 1), t, jnp.int32)
        lc, cont = model.decode(params, tokens[:, t:t + 1], pos, cont, rc_d)
        lp, paged = model.decode(params, tokens[:, t:t + 1], pos, paged, rc_d)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lc),
                                   rtol=1e-5, atol=1e-5)


def test_paged_swa_ring_wrap_matches_contiguous():
    """Prompt longer than the window: the prefill commit ring-converts
    before scattering, so the paged ring holds the same positions as the
    contiguous ring — and never needs more than ceil(window/bs) blocks."""
    cfg = _fp32_cfg("recurrentgemma_2b")  # local_window=32 in smoke
    model = build_model(cfg)
    params = model.init(KEY)
    window = cfg.local_window
    S, N, cap = window + 8, 3, 64  # prompt wraps the ring
    tokens = jax.random.randint(KEY, (1, S + N), 0, cfg.vocab_size)
    _, fresh = model.prefill(
        params, {"tokens": tokens[:, :S]},
        RunConfig(mode="prefill", remat=False, attn_chunk=8))
    cont = pad_prefill_cache(fresh, cap, window=window)
    paged, meta, _ = _paged_after_prefill(model, fresh, S, cap=cap,
                                          window=window)
    assert meta.page_len == window
    assert meta.blocks_per_slot == -(-window // meta.block_size)
    assert meta.blocks_for(10 * window) == meta.blocks_per_slot
    rc_d = RunConfig(mode="decode", remat=False)
    for t in range(S, S + N):
        pos = jnp.full((1, 1), t, jnp.int32)
        lc, cont = model.decode(params, tokens[:, t:t + 1], pos, cont, rc_d)
        lp, paged = model.decode(params, tokens[:, t:t + 1], pos, paged, rc_d)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lc),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["llama2_7b", "whisper_medium",
                                  "llama_3_2_vision_11b"])
def test_chunked_prefill_matches_one_shot(arch):
    """Chunk 1 commits via the prefill scatter, chunk 2 runs the forward
    continuation over a slot_view; the final logits and the next decode
    step match a one-shot prefill of the whole prompt."""
    cfg = _fp32_cfg(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    S, c1 = 12, 8
    tokens = jax.random.randint(KEY, (1, S + 1), 0, cfg.vocab_size)
    ex = _extras(cfg)
    rc_p = RunConfig(mode="prefill", remat=False, attn_chunk=8)

    logits_os, fresh_os = model.prefill(
        params, {"tokens": tokens[:, :S], **ex}, rc_p)
    cont = pad_prefill_cache(fresh_os, CAP)

    _, f1 = model.prefill(params, {"tokens": tokens[:, :c1], **ex}, rc_p)
    paged, meta, row = _paged_after_prefill(model, f1, c1, cap=CAP)
    view = paging.slot_view(paged, 0, row, c1, S - c1)
    batch = {"tokens": tokens[:, c1:S],
             "positions": c1 + jnp.arange(S - c1, dtype=jnp.int32)[None],
             **ex}
    logits_ch, new_view = model.forward(params, batch, rc_p, caches=view)
    paged = paging.merge_slot(paged, new_view, 0)
    np.testing.assert_allclose(np.asarray(logits_ch[:, -1]),
                               np.asarray(logits_os[:, -1]),
                               rtol=1e-4, atol=1e-4)
    pos = jnp.full((1, 1), S, jnp.int32)
    rc_d = RunConfig(mode="decode", remat=False)
    lc, _ = model.decode(params, tokens[:, S:S + 1], pos, cont, rc_d)
    lp, _ = model.decode(params, tokens[:, S:S + 1], pos, paged, rc_d)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lc),
                               rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------- engine level


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_smoke_config("llama2_7b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    rc = RunConfig(mode="decode", remat=False, attn_chunk=16)
    return cfg, model, params, rc


def _mixed_requests(cfg, lengths, max_new=8):
    rng = np.random.default_rng(7)
    reqs = []
    for i, L in enumerate(lengths):
        prompt = rng.integers(0, cfg.vocab_size, int(L)).astype(np.int32)
        if i % 3 == 1:
            sp = SamplingParams(greedy=False, temperature=0.8, top_k=20,
                                seed=100 + i)
        elif i % 3 == 2:
            sp = SamplingParams(greedy=False, top_p=0.9, seed=200 + i)
        else:
            sp = SamplingParams()
        reqs.append(GenerationRequest(prompt=prompt, max_new_tokens=max_new,
                                      sampling=sp))
    return reqs


def _run(model, params, rc, ecfg, reqs):
    eng = Engine(model, params, rc, ecfg)
    uids = [eng.submit(r) for r in reqs]
    while not eng.idle:
        eng.step()
    return eng, [eng.output(u) for u in uids]


def test_paged_engine_token_identical_beyond_contiguous_memory(setup):
    """ISSUE 8 acceptance: a mixed-sampling workload whose cumulative KV
    footprint exceeds the num_slots x max_len contiguous equivalent is
    served token-identically by the paged engine — block recycling covers
    what dedicated slots could not hold at once — with decode tracing
    exactly once and chunked prefill at most once per bucket."""
    cfg, model, params, rc = setup
    max_new = 8
    reqs = _mixed_requests(cfg, (12, 9, 6, 11, 5, 8), max_new=max_new)
    footprint = sum(len(r.prompt) + max_new - 1 for r in reqs)
    assert footprint > 2 * CAP  # exceeds the contiguous equivalent

    ecfg_c = EngineConfig(num_slots=2, max_len=CAP)
    ecfg_p = EngineConfig(num_slots=2, max_len=CAP, paged=True,
                          block_size=4, prefill_chunk=4)
    _, out_c = _run(model, params, rc, ecfg_c, reqs)
    eng_p, out_p = _run(model, params, rc, ecfg_p, reqs)
    for oc, op in zip(out_c, out_p):
        assert op.tokens == oc.tokens
        assert op.finish_reason == oc.finish_reason

    assert eng_p.trace_counts["decode"] == 1
    assert eng_p.trace_counts["prefill_chunk"] >= 1
    # chunks pad to <= prefill_chunk so every chunk lands in one bucket
    assert eng_p.trace_counts["prefill_chunk"] <= 1
    assert eng_p.trace_counts["prefill"] <= 2

    m = eng_p.metrics()
    assert m["prefill_chunks"] > 0
    assert m["tokens_generated"] == m["prefills"] + m["decode_slot_steps"]


def test_paged_engine_memory_gauges_drain(setup):
    """blocks_in_use / kv_bytes_in_use rise while serving and drain to
    zero at idle; peaks are sticky and byte-consistent with the pool."""
    cfg, model, params, rc = setup
    reqs = _mixed_requests(cfg, (10, 7, 5), max_new=6)
    ecfg = EngineConfig(num_slots=2, max_len=CAP, paged=True, block_size=4)
    eng = Engine(model, params, rc, ecfg)
    for r in reqs:
        eng.submit(r)
    saw_in_use = 0
    while not eng.idle:
        eng.step()
        saw_in_use = max(saw_in_use, eng.metrics()["blocks_in_use"])
    m = eng.metrics()
    assert saw_in_use > 0
    assert m["blocks_in_use"] == 0 and m["kv_bytes_in_use"] == 0
    assert m["blocks_free"] == eng.paging.num_blocks
    assert m["peak_blocks_in_use"] == saw_in_use
    assert m["peak_kv_bytes_in_use"] == \
        saw_in_use * eng.paging.bytes_per_block
    # contiguous engine reports its constant worst-case bytes instead
    eng_c, _ = _run(model, params, rc,
                    EngineConfig(num_slots=2, max_len=CAP), reqs)
    mc = eng_c.metrics()
    assert mc["kv_bytes_in_use"] > 0 and mc["blocks_in_use"] == 0


def test_out_of_blocks_preempts_youngest_and_stays_identical(setup):
    """A pool too small for the workload's peak forces decode-time
    preemption (youngest request back to the queue); the resumed request
    re-prefills prompt + generated prefix with its saved RNG key, so the
    final streams still match the contiguous engine token-for-token."""
    cfg, model, params, rc = setup
    max_new = 8
    reqs = _mixed_requests(cfg, (20, 16, 12, 8, 6, 4), max_new=max_new)
    ecfg_c = EngineConfig(num_slots=3, max_len=64)
    # W = 16; peak demand across 3 slots exceeds 17 blocks -> preemption
    ecfg_p = EngineConfig(num_slots=3, max_len=64, paged=True,
                          block_size=4, num_blocks=17)
    _, out_c = _run(model, params, rc, ecfg_c, reqs)
    eng_p, out_p = _run(model, params, rc, ecfg_p, reqs)
    assert eng_p.metrics()["preemptions"] >= 1
    for oc, op in zip(out_c, out_p):
        assert op.tokens == oc.tokens
        assert op.finish_reason == oc.finish_reason


def test_unholdable_pool_rejected_at_construction(setup):
    cfg, model, params, rc = setup
    with pytest.raises(ValueError, match="one full slot"):
        Engine(model, params, rc,
               EngineConfig(num_slots=2, max_len=CAP, paged=True,
                            block_size=4, num_blocks=3))


def test_snapshot_restore_mid_chunk_token_identical(setup):
    """Snapshot while chunked prefill + decode are in flight; a fresh
    engine restored from it finishes with byte-identical outputs — the
    pool free-list order rides the snapshot, so even the physical block
    layout replays."""
    cfg, model, params, rc = setup
    reqs = _mixed_requests(cfg, (12, 9, 6, 11), max_new=6)
    ecfg = EngineConfig(num_slots=2, max_len=CAP, paged=True,
                        block_size=4, prefill_chunk=4)
    eng = Engine(model, params, rc, ecfg)
    uids = [eng.submit(r) for r in reqs]
    for _ in range(3):  # stop mid-flight: chunked prefill still running
        eng.step()
    snap = eng.snapshot()
    assert snap.paged and snap.block_tables is not None
    while not eng.idle:
        eng.step()
    ref = [eng.output(u) for u in uids]

    eng2 = Engine(model, params, rc, ecfg)
    eng2.restore(snap)
    while not eng2.idle:
        eng2.step()
    for u, r in zip(uids, ref):
        out = eng2.output(u)
        assert out.tokens == r.tokens
        # in-flight-across-restore requests annotate their reason
        assert out.finish_reason.replace("-after-restore", "") == \
            r.finish_reason

    # geometry mismatches refuse loudly instead of corrupting the pool
    eng3 = Engine(model, params, rc, EngineConfig(num_slots=2, max_len=CAP))
    with pytest.raises(ValueError, match="paged"):
        eng3.restore(snap)


def test_serve_cache_specs_and_pspecs_paged():
    """launch/steps.serve_cache_specs produces the paged layout and
    runtime/sharding replicates arenas + tables (arena axis is the block
    pool, not batch)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.launch.steps import serve_cache_specs
    from repro.runtime import sharding as shd

    cfg = dataclasses.replace(get_smoke_config("llama2_7b"), dtype="float32")
    model = build_model(cfg)
    specs = serve_cache_specs(model, 2, CAP, paged=True, block_size=4)
    assert paging.is_paged(specs)
    cont = serve_cache_specs(model, 2, CAP)
    assert not paging.is_paged(cont)

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    pspecs = shd.cache_pspecs(specs, mesh)
    nodes = []

    def walk(node):
        if isinstance(node, dict):
            if "block_table" in node:
                nodes.append(node)
                return
            for v in node.values():
                walk(v)

    walk(pspecs)
    assert nodes
    for node in nodes:
        for name, spec in node.items():
            if name != "len":
                assert spec == P(*([None] * len(spec))), (name, spec)
