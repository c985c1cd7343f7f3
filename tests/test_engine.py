"""Serving-engine tests: continuous batching correctness, the typed
submit/step/stream surface, in-jit sampling/stopping, bucketed prefill
trace counts and metrics consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.models.common import RunConfig
from repro.serve import (Engine, EngineConfig, GenerationRequest,
                         SamplingParams, Scheduler)
from repro.serve.kvcache import pad_prefill_cache
from repro.serve.scheduler import QueueFull

KEY = jax.random.PRNGKey(0)


def _greedy_reference(model, params, prompt, max_new, rc, cap):
    """Sequential single-request greedy decode (the pre-redesign engine's
    exact-length prefill + host argmax)."""
    cfg = model.cfg
    logits, caches = model.prefill(
        params, {"tokens": jnp.asarray(prompt[None], jnp.int32)},
        rc.replace(mode="prefill"),
    )
    window = cfg.sliding_window or cfg.local_window
    caches = pad_prefill_cache(caches, cap, window=window)
    out = [int(np.argmax(np.asarray(logits[0, -1, :cfg.vocab_size])))]
    pos = len(prompt)
    while len(out) < max_new:
        tok = jnp.asarray([[out[-1]]], jnp.int32)
        logits, caches = model.decode(
            params, tok, jnp.asarray([[pos]], jnp.int32), caches,
            rc.replace(mode="decode"),
        )
        out.append(int(np.argmax(np.asarray(logits[0, 0, :cfg.vocab_size]))))
        pos += 1
    return out


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_smoke_config("llama2_7b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    rc = RunConfig(mode="decode", remat=False, attn_chunk=16)
    return cfg, model, params, rc


def test_continuous_batching_matches_sequential(setup):
    """generate() over the submit/step surface reproduces the
    pre-redesign greedy outputs token-for-token — bucketed prefill and
    in-jit argmax included."""
    cfg, model, params, rc = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9, 7, 4, 6)]
    max_new = 6
    ecfg = EngineConfig(num_slots=2, max_len=32)  # slots < requests: queueing
    eng = Engine(model, params, rc, ecfg)
    got = eng.generate(prompts, max_new)
    for uid, prompt in zip(got, prompts):
        ref = _greedy_reference(model, params, prompt, max_new, rc, 32)
        assert got[uid] == ref, (uid, got[uid], ref)


def test_scheduler_slot_lifecycle():
    s = Scheduler(num_slots=2)
    req = lambda n: GenerationRequest(prompt=np.ones(n, np.int32),
                                      max_new_tokens=4)
    u1 = s.submit(req(3))
    u2 = s.submit(req(4))
    u3 = s.submit(req(5))
    admitted = s.admit()
    assert len(admitted) == 2 and len(s.queue) == 1
    r = s.finish(admitted[0])
    assert r.uid == u1
    assert s.admit() == [admitted[0]]  # freed slot reused for u3
    assert not s.idle
    s.finish(0), s.finish(1)
    assert s.idle
    assert u2 != u3


def test_scheduler_admits_earliest_deadline_first():
    """EDF admission: the queued request with the nearest absolute
    deadline wins the free slot; no-deadline requests rank behind all
    deadlined ones, FIFO among themselves."""
    s = Scheduler(num_slots=1)
    req = lambda dl: GenerationRequest(prompt=np.ones(3, np.int32),
                                       max_new_tokens=2, deadline_s=dl)
    ua = s.submit(req(None))
    ub = s.submit(req(60.0))
    uc = s.submit(req(5.0))
    assert s.slots[s.admit()[0]].uid == uc  # tightest deadline first
    s.finish(0)
    assert s.slots[s.admit()[0]].uid == ub
    s.finish(0)
    assert s.slots[s.admit()[0]].uid == ua


def test_scheduler_admit_predicate_stops_without_bypass():
    """A can_admit refusal (the paged engine's block budget) stops the
    admission sweep instead of skipping to a smaller request behind the
    refused one — no head-of-line bypass, so large requests can't
    starve."""
    s = Scheduler(num_slots=2)
    big = GenerationRequest(prompt=np.ones(20, np.int32), max_new_tokens=2)
    small = GenerationRequest(prompt=np.ones(3, np.int32), max_new_tokens=2)
    s.submit(big), s.submit(small)
    admitted = s.admit(lambda tr: len(tr.request.prompt) < 10)
    assert admitted == [] and len(s.queue) == 2
    assert s.admit() and s.slots[0].request is big  # budget freed: FIFO


def test_scheduler_queue_bound():
    """The waiting queue is bounded: submit raises QueueFull at max_queue
    instead of growing the deque without limit."""
    s = Scheduler(num_slots=1, max_queue=2)
    req = GenerationRequest(prompt=np.ones(3, np.int32))
    s.submit(req), s.submit(req)
    with pytest.raises(QueueFull):
        s.submit(req)
    s.admit()  # one moves to a slot; queue has room again
    s.submit(req)


class _CountingModel:
    """Deterministic stub: next-token = (last_token + 1) % vocab. Lets the
    slot-retirement tests place a stop token mid-stream exactly and count
    batched decode steps. (The engine edge-pads bucketed prompts, so
    prefill's tokens[:, -1] stays the real last token.)"""

    def __init__(self, cfg):
        self.cfg = cfg

    def init_cache(self, slots, max_len):
        return {"state": jnp.zeros((1, slots, 1), jnp.float32)}

    def prefill(self, params, batch, rc):
        nxt = (batch["tokens"][:, -1] + 1) % self.cfg.vocab_size
        logits = jax.nn.one_hot(nxt, self.cfg.vocab_size)[:, None, :]
        return logits, {"state": jnp.zeros((1, 1, 1), jnp.float32)}

    def decode(self, params, tokens, positions, caches, rc):
        nxt = (tokens[:, 0] + 1) % self.cfg.vocab_size
        logits = jax.nn.one_hot(nxt, self.cfg.vocab_size)[:, None, :]
        return logits, caches


def _counting_engine(num_slots=2, max_len=64):
    cfg = dataclasses.replace(get_smoke_config("llama2_7b"), vocab_size=32)
    model = _CountingModel(cfg)
    eng = Engine(model, {}, RunConfig(mode="decode", remat=False),
                 EngineConfig(num_slots=num_slots, max_len=max_len))
    # count batched decode steps
    inner = eng._decode_fn
    calls = {"n": 0}

    def counted(*a, **kw):
        calls["n"] += 1
        return inner(*a, **kw)

    eng._decode_fn = counted
    return eng, calls


def _req(prompt_tok, max_new, eos=(), sampling=None):
    return GenerationRequest(prompt=np.array([prompt_tok], np.int32),
                             max_new_tokens=max_new, eos_ids=eos,
                             sampling=sampling or SamplingParams())


def _drain(eng):
    events = []
    while not eng.idle:
        events.extend(eng.step())
    return events


def test_slot_retires_in_same_step_as_eos():
    """Regression (slot-retirement bug): a request whose eos arrives
    mid-stream must free its slot in the step the token is generated —
    previously it occupied the slot for one extra batched decode step
    (with positions bumped for it anyway). eos is now PER-REQUEST
    (eos_ids), evaluated in-jit."""
    eng, calls = _counting_engine(num_slots=1)
    # prompt ends at 5 -> prefill emits 6; decode emits 7, 8, 9(eos)
    eng.submit(_req(5, 10, eos=(9,)))
    _drain(eng)
    out = eng.output(1)
    assert list(out.tokens) == [6, 7, 8, 9]
    assert out.finish_reason == "stop"
    # exactly 3 decode steps (7, 8, 9) — the old check-before-consume loop
    # needed a 4th step just to notice the eos
    assert calls["n"] == 3


def test_eos_slot_frees_for_queued_request_immediately():
    """With one slot and two requests, the freed slot admits the queued
    request on the tick right after eos — no dead step in between."""
    eng, calls = _counting_engine(num_slots=1)
    u1 = eng.submit(_req(6, 4, eos=(9,)))
    u2 = eng.submit(_req(20, 4, eos=(9,)))
    _drain(eng)
    # first: prefill 7, decode 8, 9(eos); second: prefill 21, decode 22..24
    assert list(eng.output(u1).tokens) == [7, 8, 9]
    assert list(eng.output(u2).tokens) == [21, 22, 23, 24]
    assert calls["n"] == 2 + 3  # no wasted step between the requests

    # a fresh engine serving only the second request needs the same 3
    # decode steps — the queued request paid zero extra latency
    eng2, calls2 = _counting_engine(num_slots=1)
    eng2.submit(_req(20, 4, eos=(9,)))
    _drain(eng2)
    assert calls2["n"] == 3


def test_eos_in_prefill_token_never_decodes():
    """A request whose very first (prefill-sampled) token is in its stop
    set — or whose budget is a single token — retires without any decode
    step."""
    eng, calls = _counting_engine()
    eng.submit(_req(8, 10, eos=(9,)))
    _drain(eng)
    assert list(eng.output(1).tokens) == [9]
    assert eng.output(1).finish_reason == "stop"
    assert calls["n"] == 0

    eng2, calls2 = _counting_engine()
    eng2.submit(_req(3, 1))
    _drain(eng2)
    assert list(eng2.output(1).tokens) == [4]
    assert eng2.output(1).finish_reason == "length"
    assert calls2["n"] == 0


def test_free_slots_fed_masked_tokens():
    """Free slots must not replay their stale last_token through decode:
    the engine masks them to token 0 / position 0."""
    eng, _ = _counting_engine(num_slots=2)
    seen = []
    inner = eng._decode_fn

    def spy(params, caches, tokens, positions, *rest):
        seen.append((np.asarray(tokens).ravel().copy(),
                     np.asarray(positions).ravel().copy()))
        return inner(params, caches, tokens, positions, *rest)

    eng._decode_fn = spy
    # slot 0 hits eos (9) in the second decode step; slot 1 keeps going
    eng.submit(_req(6, 6, eos=(9,)))
    eng.submit(_req(20, 6, eos=(9,)))
    _drain(eng)
    assert len(seen) == 5  # slot 1: 22, 23, 24, 25, 26
    # while slot 0 is live its lane carries the real last_token
    assert seen[0][0][0] == 7 and seen[1][0][0] == 8
    # after slot 0 retires, its lane must carry the masked 0 at position
    # 0 — never its stale eos token / bumped position
    for tok, pos in seen[2:]:
        assert tok[0] == 0 and pos[0] == 0, (tok, pos)


def test_concurrent_requests_finish_independently():
    """Two concurrent requests with different eos and temperature finish
    in their own correct step — stop sets and sampling params are
    per-slot device state, not engine globals."""
    eng, calls = _counting_engine(num_slots=2)
    # near-greedy sampled request: one-hot logits at temperature 0.01
    # concentrate all mass on the counting token
    sharp = SamplingParams(greedy=False, temperature=0.01, seed=3)
    ua = eng.submit(_req(5, 10, eos=(9,)))              # 6,7,8,9 -> stop @ 3
    ub = eng.submit(_req(20, 10, eos=(25,), sampling=sharp))  # 21..25 @ 4
    events = _drain(eng)
    a, b = eng.output(ua), eng.output(ub)
    assert list(a.tokens) == [6, 7, 8, 9] and a.finish_reason == "stop"
    assert list(b.tokens) == [21, 22, 23, 24, 25] and b.finish_reason == "stop"
    # b needed one more decode step than a; total steps = max chain
    assert calls["n"] == 4
    # terminal events carry each request's own final index: a at 3, b at 4
    term = {e.uid: e for e in events if e.done}
    assert term[ua].index == 3 and term[ub].index == 4


def test_decode_traces_once_mixed_sampling(setup):
    """The jitted decode step traces exactly ONCE across a mixed-sampling
    workload: greedy, temperature+top_k and top_p requests differ only in
    per-slot device data."""
    cfg, model, params, rc = setup
    eng = Engine(model, params, rc, EngineConfig(num_slots=2, max_len=32))
    rng = np.random.default_rng(2)
    p = lambda n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
    eng.submit(GenerationRequest(prompt=p(5), max_new_tokens=4))
    eng.submit(GenerationRequest(
        prompt=p(6), max_new_tokens=4,
        sampling=SamplingParams(greedy=False, temperature=0.7, top_k=8,
                                seed=1)))
    eng.submit(GenerationRequest(
        prompt=p(7), max_new_tokens=3, eos_ids=(2,),
        sampling=SamplingParams(greedy=False, top_p=0.9, seed=2)))
    _drain(eng)
    assert eng.trace_counts["decode"] == 1


def test_prefill_traces_once_per_bucket(setup):
    """Bucketed prefill: prompts pad to power-of-two buckets and the
    jitted prefill step retraces at most once per bucket (not once per
    prompt length). Counted via the engine's trace-counting harness."""
    cfg, model, params, rc = setup
    eng = Engine(model, params, rc, EngineConfig(num_slots=2, max_len=32))
    rng = np.random.default_rng(3)
    # lengths 3/5/6 -> bucket 8; 9/12 -> bucket 16: exactly two traces
    for n in (3, 5, 6, 9, 12):
        eng.submit(GenerationRequest(
            prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=2))
    _drain(eng)
    assert eng.trace_counts["prefill"] == 2
    # pre-planned per-bucket entries replaced the single prefill@cap
    # estimate: every bucket plan is at the exact padded execution M
    assert {"prefill@8", "prefill@16", "prefill@32"} <= set(eng.plans)
    assert "prefill@cap" not in eng.plans
    for m in (8, 16, 32):
        assert all(pl.spec.M == m for _p, pl in eng.plans[f"prefill@{m}"])


def test_metrics_consistent_with_stream_events(setup):
    """Engine.metrics() totals agree with the emitted StreamEvents."""
    cfg, model, params, rc = setup
    eng = Engine(model, params, rc,
                 EngineConfig(num_slots=2, max_len=32, max_queue=2))
    rng = np.random.default_rng(4)
    p = lambda n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
    eng.submit(GenerationRequest(prompt=p(5), max_new_tokens=3))
    eng.submit(GenerationRequest(
        prompt=p(6), max_new_tokens=4,
        sampling=SamplingParams(greedy=False, temperature=0.9, seed=5)))
    eng.submit(GenerationRequest(prompt=p(40), max_new_tokens=3))  # rejected
    events = _drain(eng)
    m = eng.metrics()
    token_events = [e for e in events if e.token is not None]
    terminal = [e for e in events if e.done]
    assert len(token_events) == m["tokens_generated"]
    assert m["finished"] == m["finished_stop"] + m["finished_length"]
    assert len(terminal) == m["finished"] + m["rejected"]
    assert m["submitted"] == 3 and m["admitted"] == 2 and m["rejected"] == 1
    assert m["tokens_generated"] == m["prefills"] + m["decode_slot_steps"]
    assert 0.0 < m["slot_occupancy"] <= 1.0


def test_submit_rejects_overlong_prompt_cleanly(setup):
    """A prompt longer than max_len used to die as a ValueError deep in
    kvcache._pad_time AFTER wasting prefill compute; it now rejects at
    submit() with a terminal RequestOutput and no compute."""
    cfg, model, params, rc = setup
    eng = Engine(model, params, rc, EngineConfig(num_slots=2, max_len=16))
    uid = eng.submit(GenerationRequest(
        prompt=np.arange(40).astype(np.int32) % cfg.vocab_size,
        max_new_tokens=4))
    out = eng.output(uid)
    assert out is not None and out.finish_reason == "rejected"
    assert out.tokens == ()
    assert eng.trace_counts["prefill"] == 0  # no compute spent
    ev = eng.step()
    assert [e for e in ev if e.uid == uid and e.done and e.token is None]
    # generate() stays loud on rejection (the old crash, but clean+early,
    # and validated BEFORE anything is enqueued)
    with pytest.raises(ValueError, match="unservable"):
        eng.generate([np.arange(40).astype(np.int32) % cfg.vocab_size], 4)


def test_submit_rejects_decode_budget_past_capacity(setup):
    """A full (non-windowed) cache also needs room for the decode writes:
    prompt_len + max_new_tokens - 1 past max_len would silently clamp the
    KV write slot — reject it at submit instead."""
    cfg, model, params, rc = setup
    eng = Engine(model, params, rc, EngineConfig(num_slots=1, max_len=16))
    prompt = np.arange(12).astype(np.int32) % cfg.vocab_size
    uid = eng.submit(GenerationRequest(prompt=prompt, max_new_tokens=8))
    assert eng.output(uid).finish_reason == "rejected"
    # the same prompt with a fitting budget is served: 12 + 5 - 1 = 16
    uid2 = eng.submit(GenerationRequest(prompt=prompt, max_new_tokens=5))
    _drain(eng)
    assert eng.output(uid2).finish_reason == "length"
    assert len(eng.output(uid2).tokens) == 5


def test_generate_partial_rejection_enqueues_nothing(setup):
    """generate() validates the whole batch before submitting: a raise on
    an unservable prompt must not leave the servable ones queued for a
    later call (stale compute + leaked outputs)."""
    cfg, model, params, rc = setup
    eng = Engine(model, params, rc, EngineConfig(num_slots=2, max_len=16))
    good = np.arange(4).astype(np.int32) % cfg.vocab_size
    bad = np.arange(40).astype(np.int32) % cfg.vocab_size
    with pytest.raises(ValueError, match="unservable"):
        eng.generate([good, bad], 4)
    assert eng.idle and len(eng.sched.queue) == 0
    m = eng.metrics()
    assert m["submitted"] == 0 and m["prefills"] == 0


def test_retained_outputs_bounded():
    """A long-running submit()/step() server that never reads outputs
    stays memory-bounded: finished outputs + event buffers evict FIFO
    past max_retained."""
    eng, _ = _counting_engine(num_slots=1)
    eng.ecfg.max_retained = 3
    uids = []
    for i in range(6):
        uids.append(eng.submit(_req(5, 2)))
        _drain(eng)
    assert all(eng.output(u) is None for u in uids[:3])
    assert all(eng.output(u) is not None for u in uids[3:])
    assert len(eng._outputs) == 3 and len(eng._buffers) == 3


def test_stream_iterator_delivers_all_tokens(setup):
    cfg, model, params, rc = setup
    eng = Engine(model, params, rc, EngineConfig(num_slots=2, max_len=32))
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    uid = eng.submit(GenerationRequest(prompt=prompt, max_new_tokens=4))
    evs = list(eng.stream(uid))
    assert [e.index for e in evs] == [0, 1, 2, 3]
    assert evs[-1].done and evs[-1].finish_reason == "length"
    out = eng.output(uid)
    assert tuple(e.token for e in evs) == out.tokens
    # matches greedy generate() on a fresh engine
    eng2 = Engine(model, params, rc, EngineConfig(num_slots=2, max_len=32))
    got = eng2.generate([prompt], 4)
    assert list(out.tokens) == list(got.values())[0]


def test_sampled_request_reproducible_and_different(setup):
    """Equal seed -> identical stream regardless of engine; different
    seed -> (almost surely) different stream. Greedy stays exact."""
    cfg, model, params, rc = setup
    prompt = np.arange(6).astype(np.int32) % cfg.vocab_size

    def run(seed):
        eng = Engine(model, params, rc, EngineConfig(num_slots=2, max_len=32))
        uid = eng.submit(GenerationRequest(
            prompt=prompt, max_new_tokens=6,
            sampling=SamplingParams(greedy=False, temperature=1.5, seed=seed)))
        _drain(eng)
        return eng.output(uid).tokens

    assert run(7) == run(7)
    assert run(7) != run(8)


def test_engine_vq_quantized(setup):
    """The engine runs end-to-end on EVA-quantized weights."""
    cfg, model, params, rc = setup
    qparams = model.quantize(params, method="synthetic", key=KEY)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
               for _ in range(3)]
    rc_vq = rc.replace_policy(vq_mode="eva")
    eng = Engine(model, qparams, rc_vq, EngineConfig(num_slots=3, max_len=24))
    got = eng.generate(prompts, 4)
    assert all(len(v) == 4 for v in got.values())
    # eva and dequant paths agree token-for-token
    eng2 = Engine(model, qparams, rc.replace_policy(vq_mode="dequant"),
                  EngineConfig(num_slots=3, max_len=24))
    got2 = eng2.generate(prompts, 4)
    assert list(got.values()) == list(got2.values())


def test_mixed_batch_poison_bystander_token_identity(setup):
    """A NaN/Inf-poisoned slot finishes ``finish_reason="error"`` while
    every bystander lane — greedy AND sampled — streams on BIT-IDENTICAL
    to a fault-free run: poison is additive per-lane data, so injection
    neither retraces the decode step nor perturbs neighbor lanes."""
    from repro.serve.resilience import FaultPlan, FaultSpec

    cfg, model, params, rc = setup
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 6, 7)]
    sampled = SamplingParams(greedy=False, temperature=1.3, seed=9)

    def run(fault_plan):
        eng = Engine(model, params, rc,
                     EngineConfig(num_slots=3, max_len=32,
                                  fault_plan=fault_plan))
        uids = [
            eng.submit(GenerationRequest(prompt=prompts[0],
                                         max_new_tokens=6)),
            eng.submit(GenerationRequest(prompt=prompts[1],
                                         max_new_tokens=6,
                                         sampling=sampled)),
            eng.submit(GenerationRequest(prompt=prompts[2],
                                         max_new_tokens=6)),
        ]
        _drain(eng)
        return eng, uids

    ref, runids = run(None)
    eng, uids = run(FaultPlan.scripted(
        FaultSpec("poison", tick=2, uid=3, mode="inf")))
    bad = eng.output(uids[2])
    assert bad.finish_reason == "error"
    # the poisoned request's pre-fault prefix matches the clean run
    assert bad.tokens == ref.output(runids[2]).tokens[: len(bad.tokens)]
    for i in (0, 1):
        assert eng.output(uids[i]).tokens == ref.output(runids[i]).tokens
        assert eng.output(uids[i]).finish_reason == "length"
    assert eng.trace_counts["decode"] == 1  # injection is data, not a retrace
    m = eng.metrics()
    assert m["errors"] == 1 and m["poisoned_slot_steps"] == 1
    assert m["tokens_generated"] == (
        m["prefills"] + m["decode_slot_steps"] - m["poisoned_slot_steps"])


def test_paged_admission_is_length_aware(setup):
    """Paged + full-cache engines admit by block consumption, not the
    worst-case ``prompt + max_new - 1 <= max_len`` reservation: a request
    whose nominal budget exceeds max_len is admitted, decodes to the
    capacity clamp and finishes with reason "length" — while the same
    request on a contiguous engine is rejected outright."""
    cfg, model, params, rc = setup
    prompt = np.arange(8, dtype=np.int32) % cfg.vocab_size
    req = lambda: GenerationRequest(prompt=prompt, max_new_tokens=64)

    contig = Engine(model, params, rc, EngineConfig(num_slots=1, max_len=32))
    uid = contig.submit(req())  # 8 + 64 - 1 = 71 > 32: the old rule fires
    assert contig.output(uid).finish_reason == "rejected"

    paged = Engine(model, params, rc,
                   EngineConfig(num_slots=1, max_len=32, paged=True,
                                num_blocks=8, block_size=8))
    uid = paged.submit(req())
    steps = 0
    while not paged.idle:
        paged.step()
        steps += 1
        assert steps < 200
    out = paged.output(uid)
    assert out.finish_reason == "length"
    # budget clamps to capacity: positions 8..31 leave room for 25 tokens
    assert len(out.tokens) == 32 - len(prompt) + 1
    ref = _greedy_reference(model, params, prompt, len(out.tokens), rc, 32)
    assert list(out.tokens) == ref


def test_paged_admission_still_rejects_oversized_prompt(setup):
    cfg, model, params, rc = setup
    paged = Engine(model, params, rc,
                   EngineConfig(num_slots=1, max_len=32, paged=True,
                                num_blocks=8, block_size=8))
    uid = paged.submit(GenerationRequest(
        prompt=np.zeros(40, np.int32), max_new_tokens=4))
    assert paged.output(uid).finish_reason == "rejected"


def test_logprobs_surface_in_events_and_output(setup):
    """SamplingParams.logprobs attaches the chosen-token logprob to every
    StreamEvent and the terminal RequestOutput; off by default."""
    cfg, model, params, rc = setup
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab_size
    eng = Engine(model, params, rc, EngineConfig(num_slots=2, max_len=32))
    u_on = eng.submit(GenerationRequest(
        prompt=prompt, max_new_tokens=4,
        sampling=SamplingParams(logprobs=True)))
    u_off = eng.submit(GenerationRequest(prompt=prompt, max_new_tokens=4))
    events = []
    while not eng.idle:
        events.extend(eng.step())
    on = [e for e in events if e.uid == u_on and e.token is not None]
    off = [e for e in events if e.uid == u_off and e.token is not None]
    assert len(on) == 4 and all(e.logprob is not None for e in on)
    # greedy picks the argmax: its logprob is the max, hence > log(1/V)
    assert all(e.logprob > -np.log(cfg.vocab_size) for e in on)
    assert all(e.logprob <= 0.0 for e in on)
    assert all(e.logprob is None for e in off)
    out = eng.output(u_on)
    assert len(out.logprobs) == 4
    np.testing.assert_allclose(out.logprobs, [e.logprob for e in on])
    assert eng.output(u_off).logprobs == ()


def test_eva_token_tiles_counter():
    """A 16-slot engine on the Pallas EVA path plans every decode VQ
    linear as one token tile of all 16 rows and says so in its metrics;
    on the jnp path no kernel tiles tokens and the counter reads 0."""
    from repro.core.plan import PlanPolicy

    cfg = get_smoke_config("minitron_4b")
    model = build_model(cfg)
    params = model.init_synthetic(KEY)
    for impl, tiles in (("pallas", 1), ("jnp", 0)):
        rc = RunConfig(mode="decode", remat=False, attn_chunk=64,
                       plan_policy=PlanPolicy(vq_mode="eva", impl=impl,
                                              interpret=True))
        eng = Engine(model, params, rc, EngineConfig(num_slots=16,
                                                     max_len=64))
        vq_plans = [pl for _path, pl in eng.plans["decode"]
                    if pl.spec.kind == "vq"]
        assert vq_plans
        if impl == "pallas":
            assert all(pl.config_dict["mt"] == 16
                       and pl.config_dict["token_tiles"] == 1
                       for pl in vq_plans)
        assert eng.metrics_counters.snapshot()["eva_token_tiles"] == tiles
