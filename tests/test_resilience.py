"""Serving-layer fault tolerance (serve/resilience.py + the engine/planner
wiring): deterministic fault injection at every boundary, NaN/Inf slot
quarantine, request deadlines, engine snapshot/restore (incl. the
CheckpointManager wire format), the serve restart controller and backend
quarantine + fallback chain in core/plan.py."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_smoke_config
from repro.core import plan as plan_mod
from repro.core.plan import (LinearSpec, MatmulPlan, PlanPolicy, Planner,
                             register_backend)
from repro.models import build_model
from repro.models.common import RunConfig
from repro.serve import (Engine, EngineConfig, GenerationRequest,
                         SamplingParams)
from repro.serve.api import RequestEvicted
from repro.serve.resilience import (BOUNDARIES, CircuitBreaker, FaultPlan,
                                    FaultSpec, InjectedFault,
                                    load_snapshot_arrays, save_snapshot,
                                    serve_with_restarts)

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Units: FaultPlan / CircuitBreaker
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_spec_validation(self):
        FaultSpec("poison", tick=0, mode="inf", times=2)
        with pytest.raises(ValueError, match="boundary"):
            FaultSpec("gc-pause", tick=0)
        with pytest.raises(ValueError, match="poison mode"):
            FaultSpec("poison", tick=0, mode="zero")
        with pytest.raises(ValueError, match="tick"):
            FaultSpec("decode", tick=-1)
        with pytest.raises(ValueError, match="times"):
            FaultSpec("decode", tick=0, times=0)

    def test_poll_fires_and_consumes(self):
        fp = FaultPlan.scripted(FaultSpec("decode", tick=2, times=2))
        assert fp.poll("decode", 0) is None          # not armed yet
        assert fp.poll("prefill", 3) is None         # wrong boundary
        assert fp.poll("decode", 3) is not None      # tick >= spec.tick
        assert fp.poll("decode", 3) is not None      # times=2: fires again
        assert fp.poll("decode", 4) is None          # budget exhausted
        assert fp.exhausted

    def test_uid_targeting(self):
        fp = FaultPlan.scripted(FaultSpec("poison", tick=0, uid=7))
        assert fp.poll("poison", 0, uid=3) is None   # wrong request
        assert fp.poll("poison", 0, uid=7) is not None
        # an untargeted spec matches any uid; an untargeted poll matches
        # any spec
        fp2 = FaultPlan.scripted(FaultSpec("poison", tick=0))
        assert fp2.poll("poison", 0, uid=42) is not None

    def test_seeded_plan_is_deterministic(self):
        a = FaultPlan.seeded(123, n_faults=4, max_tick=6, uids=(1, 2))
        b = FaultPlan.seeded(123, n_faults=4, max_tick=6, uids=(1, 2))
        assert a.faults == b.faults
        assert all(s.boundary in BOUNDARIES for s in a.faults)
        c = FaultPlan.seeded(124, n_faults=4, max_tick=6, uids=(1, 2))
        assert a.faults != c.faults


class TestCircuitBreaker:
    def test_trips_on_consecutive_only(self):
        br = CircuitBreaker(k=3)
        assert not br.record(True) and not br.record(True)
        assert not br.record(False)                  # clean step resets
        br.record(True), br.record(True)
        assert br.record(True) and br.tripped        # 3 consecutive

    def test_state_roundtrip_and_validation(self):
        with pytest.raises(ValueError, match="k must be"):
            CircuitBreaker(k=0)
        br = CircuitBreaker(k=2)
        br.record(True)
        br2 = CircuitBreaker(k=5)
        br2.restore(br.state())
        assert br2.record(True)                      # continues the streak


# ---------------------------------------------------------------------------
# Engine mechanics on the deterministic counting stub
# ---------------------------------------------------------------------------


class _CountingModel:
    """next-token = (last_token + 1) % vocab (see tests/test_engine.py)."""

    def __init__(self, cfg):
        self.cfg = cfg

    def init_cache(self, slots, max_len):
        return {"state": jnp.zeros((1, slots, 1), jnp.float32)}

    def prefill(self, params, batch, rc):
        nxt = (batch["tokens"][:, -1] + 1) % self.cfg.vocab_size
        return (jax.nn.one_hot(nxt, self.cfg.vocab_size)[:, None, :],
                {"state": jnp.zeros((1, 1, 1), jnp.float32)})

    def decode(self, params, tokens, positions, caches, rc):
        nxt = (tokens[:, 0] + 1) % self.cfg.vocab_size
        return jax.nn.one_hot(nxt, self.cfg.vocab_size)[:, None, :], caches


def _counting_engine(num_slots=2, max_len=64, **ecfg_kw):
    cfg = dataclasses.replace(get_smoke_config("llama2_7b"), vocab_size=64)
    return Engine(_CountingModel(cfg), {},
                  RunConfig(mode="decode", remat=False),
                  EngineConfig(num_slots=num_slots, max_len=max_len,
                               **ecfg_kw))


def _req(tok, n, eos=(), **kw):
    return GenerationRequest(prompt=np.array([tok], np.int32),
                             max_new_tokens=n, eos_ids=eos, **kw)


def _drain(eng):
    events = []
    while not eng.idle:
        events.extend(eng.step())
    return events


class TestNumericsQuarantine:
    def test_poisoned_request_errors_bystander_unaffected(self):
        fp = FaultPlan.scripted(FaultSpec("poison", tick=2, uid=1))
        eng = _counting_engine(fault_plan=fp)
        u1 = eng.submit(_req(5, 8))
        u2 = eng.submit(_req(20, 8))
        events = _drain(eng)
        bad, ok = eng.output(u1), eng.output(u2)
        assert bad.finish_reason == "error"
        assert bad.tokens == (6, 7, 8)               # tick0 prefill+decode, tick1
        assert ok.finish_reason == "length"
        assert ok.tokens == (21, 22, 23, 24, 25, 26, 27, 28)
        m = eng.metrics()
        assert m["errors"] == 1 and m["poisoned_slot_steps"] == 1
        assert m["tokens_generated"] == (m["prefills"] + m["decode_slot_steps"]
                                         - m["poisoned_slot_steps"])
        assert m["finished"] == (m["finished_stop"] + m["finished_length"]
                                 + m["errors"] + m["timeouts"])
        # the garbage token is SUPPRESSED: the terminal event carries
        # token=None, and no token-bearing event follows the fault
        term = [e for e in events if e.uid == u1][-1]
        assert term.token is None and term.finish_reason == "error"
        assert sum(e.token is not None
                   for e in events if e.uid == u1) == len(bad.tokens)

    def test_poisoned_prefill_never_activates_slot(self):
        fp = FaultPlan.scripted(FaultSpec("poison", tick=0, uid=1,
                                          mode="inf"))
        eng = _counting_engine()
        u1 = eng.submit(_req(5, 8))
        events = _drain(eng)
        del events
        # no fault plan on this engine: sanity check the scripted one
        eng2 = _counting_engine(fault_plan=fp)
        v1 = eng2.submit(_req(5, 8))
        events = _drain(eng2)
        out = eng2.output(v1)
        assert out.finish_reason == "error" and out.tokens == ()
        assert eng2.metrics()["tokens_generated"] == 0
        term = [e for e in events if e.uid == v1]
        assert len(term) == 1 and term[0].token is None
        assert term[0].finish_reason == "error"
        assert eng.output(u1).tokens == (6, 7, 8, 9, 10, 11, 12, 13)

    def test_breaker_trips_rejects_pending_and_submits(self):
        # one slot + per-admission poison: each tick admits one request,
        # poisons its prefill -> k consecutive poisoned steps
        fp = FaultPlan.scripted(FaultSpec("poison", tick=0, times=3))
        eng = _counting_engine(num_slots=1, fault_plan=fp, breaker_k=3)
        uids = [eng.submit(_req(5, 4)) for _ in range(5)]
        _drain(eng)
        assert [eng.output(u).finish_reason for u in uids] == (
            ["error"] * 3 + ["rejected"] * 2)
        assert not eng.healthy
        # new submits refuse while unhealthy
        u6 = eng.submit(_req(5, 4))
        assert eng.output(u6).finish_reason == "rejected"
        m = eng.metrics()
        assert m["errors"] == 3 and m["rejected"] == 3

    def test_clean_steps_reset_breaker(self):
        fp = FaultPlan.scripted(FaultSpec("poison", tick=0, uid=1),
                                FaultSpec("poison", tick=2, uid=3))
        eng = _counting_engine(num_slots=1, fault_plan=fp, breaker_k=2)
        uids = [eng.submit(_req(5, 2)) for _ in range(4)]
        _drain(eng)
        assert eng.healthy                           # never 2 in a row
        reasons = [eng.output(u).finish_reason for u in uids]
        assert reasons.count("error") == 2


class TestDeadlines:
    def test_queue_ttl_times_out_before_prefill(self):
        eng = _counting_engine(num_slots=1, queue_ttl_s=0.0)
        u1 = eng.submit(_req(5, 4))
        time.sleep(0.005)
        _drain(eng)
        out = eng.output(u1)
        assert out.finish_reason == "timeout" and out.tokens == ()
        assert eng.metrics()["prefills"] == 0        # no compute wasted
        assert eng.metrics()["timeouts"] == 1

    def test_deadline_expires_queued_request(self):
        eng = _counting_engine(num_slots=1)
        ua = eng.submit(_req(5, 6))
        ub = eng.submit(_req(7, 6, deadline_s=0.0))  # stuck behind ua
        time.sleep(0.005)
        _drain(eng)
        assert eng.output(ua).finish_reason == "length"
        assert eng.output(ub).finish_reason == "timeout"

    def test_deadline_frees_active_slot_mid_decode(self):
        eng = _counting_engine(num_slots=1, max_len=256)
        inner = eng._decode_fn

        def slow(*a, **kw):                          # ~5ms per decode step
            time.sleep(0.005)
            return inner(*a, **kw)

        eng._decode_fn = slow
        uid = eng.submit(_req(5, 200, deadline_s=0.05))
        _drain(eng)
        out = eng.output(uid)
        assert out.finish_reason == "timeout"
        assert 0 < len(out.tokens) < 200             # partial stream kept
        assert eng.metrics()["timeouts"] == 1

    def test_stream_delivers_timeout_terminal(self):
        eng = _counting_engine(num_slots=1, max_len=256)
        inner = eng._decode_fn

        def slow(*a, **kw):
            time.sleep(0.005)
            return inner(*a, **kw)

        eng._decode_fn = slow
        ua = eng.submit(_req(5, 200))
        eng.step()  # A takes the slot BEFORE B enters the EDF queue
        ub = eng.submit(_req(9, 4, deadline_s=0.02))
        evs = list(eng.stream(ub))
        assert len(evs) == 1 and evs[0].token is None
        assert evs[0].finish_reason == "timeout"

    def test_stream_stall_guard_is_wall_clock(self):
        """The old guard allowed 1,000,000 silent iterations; the new one
        raises once the stream makes no progress for stream_stall_s."""
        eng = _counting_engine(num_slots=1, stream_stall_s=0.0)
        eng.submit(_req(5, 50))
        ub = eng.submit(_req(9, 4))                  # queued behind slot 0
        with pytest.raises(RuntimeError, match="stalled"):
            next(iter(eng.stream(ub)))


class TestEvictedVsUnknown:
    def test_stream_distinguishes_evicted_from_unknown(self):
        eng = _counting_engine(num_slots=1)
        eng.ecfg.max_retained = 2
        uids = []
        for _ in range(4):
            uids.append(eng.submit(_req(5, 2)))
            _drain(eng)
        assert eng.evicted(uids[0]) and eng.evicted(uids[1])
        assert not eng.evicted(uids[3])
        assert not eng.evicted(999)                  # never issued
        with pytest.raises(RequestEvicted):
            next(iter(eng.stream(uids[0])))
        with pytest.raises(KeyError, match="unknown"):
            next(iter(eng.stream(999)))
        # RequestEvicted IS a KeyError: existing callers keep working
        assert issubclass(RequestEvicted, KeyError)

    def test_drained_stream_is_not_evicted(self):
        eng = _counting_engine(num_slots=1)
        uid = eng.submit(_req(5, 2))
        list(eng.stream(uid))                        # drains the buffer
        assert not eng.evicted(uid)                  # output still retained
        with pytest.raises(KeyError, match="already streamed"):
            next(iter(eng.stream(uid)))


class TestWatchdogWiring:
    def test_straggler_steps_reach_metrics(self):
        # threshold 0: every post-warmup decode step is a "straggler" —
        # pins the watchdog -> metrics wiring without timing flakiness
        eng = _counting_engine(num_slots=1, max_len=64,
                               straggler_threshold=0.0)
        eng.submit(_req(5, 30))
        _drain(eng)
        m = eng.metrics()
        assert m["straggler_steps"] > 0
        assert m["straggler_steps"] == len(eng.watchdog.straggler_steps)


class TestSnapshotRestore:
    def test_midstream_restore_is_token_identical(self):
        eng = _counting_engine()
        u1 = eng.submit(_req(5, 10))
        u2 = eng.submit(_req(20, 10))
        eng.step(), eng.step()
        snap = eng.snapshot()
        _drain(eng)
        ref1, ref2 = eng.output(u1).tokens, eng.output(u2).tokens

        eng2 = _counting_engine()
        eng2.restore(snap)
        _drain(eng2)
        assert eng2.output(u1).tokens == ref1
        assert eng2.output(u2).tokens == ref2
        # in flight across the restore: the annotated finish reason
        assert eng2.output(u1).finish_reason == "length-after-restore"
        assert eng2.metrics()["restores"] == 1

    def test_snapshot_does_not_alias_live_state(self):
        eng = _counting_engine()
        u1 = eng.submit(_req(5, 10))
        eng.step()
        snap = eng.snapshot()
        tick = snap.tick
        frozen = {p: (None if a is None else np.array(a, copy=True))
                  for p, a in snap.arrays.items()}
        _drain(eng)                                  # keep mutating
        assert snap.tick == tick
        for path, leaf in snap.arrays.items():
            if leaf is not None:
                np.testing.assert_array_equal(leaf, frozen[path])
        # restoring the untouched snapshot still resumes correctly
        eng2 = _counting_engine()
        eng2.restore(snap)
        _drain(eng2)
        assert eng2.output(u1).tokens == eng.output(u1).tokens

    def test_snapshot_geometry_mismatch_is_loud(self):
        snap = _counting_engine(num_slots=2).snapshot()
        with pytest.raises(ValueError, match="geometry"):
            _counting_engine(num_slots=3).restore(snap)

    def test_snapshot_roundtrips_through_checkpoint_manager(self, tmp_path):
        """EngineSnapshot array state reuses checkpoint/manager.py's
        path-flattened npz format: save_snapshot persists it atomically,
        load_snapshot_arrays reads back bit-identical leaves."""
        eng = _counting_engine()
        eng.submit(_req(5, 8))
        eng.step(), eng.step()
        snap = eng.snapshot()
        mgr = CheckpointManager(str(tmp_path / "snaps"), keep=2)
        save_snapshot(snap, mgr, step=snap.tick)
        assert mgr.latest_step() == snap.tick
        loaded = load_snapshot_arrays(mgr)
        want = {p: a for p, a in snap.arrays.items() if a is not None}
        assert set(loaded) == set(want)
        for path, arr in want.items():
            np.testing.assert_array_equal(loaded[path], arr)


class TestServeWithRestarts:
    # prefill faults fire at admissions: one slot + a short first request
    # puts the second admission (and the fault) at tick 2, with the
    # snapshot holding the victim still QUEUED. decode/sample faults hit
    # mid-flight slots, so the snapshot holds both requests ACTIVE and
    # their finish reasons carry the -after-restore annotation.
    @pytest.mark.parametrize("boundary,num_slots,budgets", [
        ("prefill", 1, (3, 8)),
        ("decode", 2, (8, 8)),
        ("sample", 2, (8, 8)),
    ])
    def test_crash_boundary_recovers_token_identically(self, boundary,
                                                       num_slots, budgets):
        ref = _counting_engine(num_slots=num_slots)
        refs = [ref.submit(_req(5, budgets[0])), ref.submit(_req(20, budgets[1]))]
        _drain(ref)

        fp = FaultPlan.scripted(FaultSpec(boundary, tick=2))
        eng, outs, stats = serve_with_restarts(
            lambda: _counting_engine(num_slots=num_slots, fault_plan=fp),
            [_req(5, budgets[0]), _req(20, budgets[1])])
        assert stats.restarts == 1 and stats.snapshots >= 2
        assert fp.exhausted                          # one shared plan instance
        for uid, ruid in zip(sorted(outs), refs):
            assert outs[uid].tokens == ref.output(ruid).tokens
            assert outs[uid].finish_reason.startswith("length")
        if boundary in ("decode", "sample"):
            # both requests were mid-flight at the restored snapshot
            assert all(o.finish_reason == "length-after-restore"
                       for o in outs.values())

    def test_gives_up_past_max_restarts(self):
        fp = FaultPlan.scripted(FaultSpec("decode", tick=0, times=10))
        with pytest.raises(RuntimeError, match="exceeded"):
            serve_with_restarts(lambda: _counting_engine(fault_plan=fp),
                                [_req(5, 8)], max_restarts=2)

    def test_no_event_delivered_twice(self):
        """snapshot_every=1 exactly-once: the crashed tick's events were
        never delivered and replay identically after restore — each
        (uid, index) pair appears exactly once across the run."""
        fp = FaultPlan.scripted(FaultSpec("sample", tick=3))
        seen = []

        def factory():
            eng = _counting_engine(fault_plan=fp)
            inner = eng.step

            def step():
                evs = inner()
                seen.extend((e.uid, e.index, e.token) for e in evs)
                return evs

            eng.step = step
            return eng

        _eng, outs, stats = serve_with_restarts(factory, [_req(5, 8)])
        assert stats.restarts == 1
        assert len(seen) == len(set(seen))
        assert outs[1].tokens == (6, 7, 8, 9, 10, 11, 12, 13)


# ---------------------------------------------------------------------------
# Real-model coverage: every fault boundary across the dense family and one
# recurrent family (xlstm exact-length prefill + recurrent cache trees)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def dense_setup():
    cfg = dataclasses.replace(get_smoke_config("llama2_7b"), dtype="float32")
    model = build_model(cfg)
    return cfg, model, model.init(KEY), RunConfig(mode="decode", remat=False,
                                                  attn_chunk=16)


@pytest.fixture(scope="module")
def recurrent_setup():
    cfg = dataclasses.replace(get_smoke_config("xlstm_125m"),
                              dtype="float32")
    model = build_model(cfg)
    return cfg, model, model.init(KEY), RunConfig(mode="decode", remat=False)


@pytest.fixture(params=["dense", "recurrent"])
def family_setup(request, dense_setup, recurrent_setup):
    return dense_setup if request.param == "dense" else recurrent_setup


@pytest.fixture(autouse=True)
def _clean_planner_quarantine():
    yield
    plan_mod.reset_quarantine()


def _family_engine(setup, fault_plan=None, num_slots=2, max_len=24):
    cfg, model, params, rc = setup
    return Engine(model, params, rc,
                  EngineConfig(num_slots=num_slots, max_len=max_len,
                               fault_plan=fault_plan))


def _family_reqs(cfg, n=2, max_new=4, seeds=(0,)):
    rng = np.random.default_rng(17)
    out = []
    for i in range(n):
        prompt = rng.integers(0, cfg.vocab_size, 5 + i).astype(np.int32)
        sampling = (SamplingParams(greedy=False, temperature=1.2,
                                   seed=seeds[i % len(seeds)])
                    if i % 2 else SamplingParams())
        out.append(GenerationRequest(prompt=prompt, max_new_tokens=max_new,
                                     sampling=sampling))
    return out


class TestEveryBoundaryPerFamily:
    def test_poison_quarantine_and_bystander_identity(self, family_setup):
        cfg = family_setup[0]
        reqs = _family_reqs(cfg, n=2, max_new=5, seeds=(3,))
        ref = _family_engine(family_setup)
        r1, r2 = ref.submit(reqs[0]), ref.submit(reqs[1])
        _drain(ref)

        fp = FaultPlan.scripted(FaultSpec("poison", tick=2, uid=1))
        eng = _family_engine(family_setup, fault_plan=fp)
        u1, u2 = eng.submit(reqs[0]), eng.submit(reqs[1])
        _drain(eng)
        assert eng.output(u1).finish_reason == "error"
        # the poisoned request streamed its pre-fault prefix faithfully
        assert eng.output(u1).tokens == ref.output(r1).tokens[
            : len(eng.output(u1).tokens)]
        # the bystander lane (a SAMPLED request — key streams are
        # per-slot) is bit-identical to the fault-free run
        assert eng.output(u2).tokens == ref.output(r2).tokens
        assert eng.output(u2).finish_reason == ref.output(r2).finish_reason
        assert eng.trace_counts["decode"] == 1       # poison is data

    @pytest.mark.parametrize("boundary", ["prefill", "decode", "sample"])
    def test_raise_boundaries_raise_injected_fault(self, family_setup,
                                                   boundary):
        cfg = family_setup[0]
        fp = FaultPlan.scripted(FaultSpec(boundary, tick=0))
        eng = _family_engine(family_setup, fault_plan=fp)
        eng.submit(_family_reqs(cfg, n=1)[0])
        with pytest.raises(InjectedFault) as e:
            _drain(eng)
        assert e.value.boundary == boundary
        assert fp.exhausted
        # recovery from a raise is snapshot/restore territory
        # (serve_with_restarts below), not in-place retry: the crashed
        # engine's state is torn by design

    def test_crash_recovery_is_token_identical(self, family_setup):
        """A scripted sample-boundary crash (device stepped, host did
        not — the torn-state case) recovers through serve_with_restarts
        with the full stream TOKEN-IDENTICAL to a fault-free run, for a
        greedy and a sampled request."""
        cfg = family_setup[0]
        reqs = _family_reqs(cfg, n=2, max_new=5, seeds=(5,))
        ref = _family_engine(family_setup)
        ruids = [ref.submit(r) for r in reqs]
        _drain(ref)

        fp = FaultPlan.scripted(FaultSpec("sample", tick=2))
        _eng, outs, stats = serve_with_restarts(
            lambda: _family_engine(family_setup, fault_plan=fp), reqs)
        assert stats.restarts == 1
        for uid, ruid in zip(sorted(outs), ruids):
            assert outs[uid].tokens == ref.output(ruid).tokens

    def test_backend_fault_recovers_and_counts(self, family_setup):
        cfg = family_setup[0]
        reqs = _family_reqs(cfg, n=1, max_new=4)
        ref = _family_engine(family_setup)
        r1 = ref.submit(reqs[0])
        _drain(ref)

        fp = FaultPlan.scripted(FaultSpec("backend", tick=1))
        eng = _family_engine(family_setup, fault_plan=fp)
        u1 = eng.submit(reqs[0])
        _drain(eng)
        # generation survived the backend failure and stayed exact
        assert eng.output(u1).tokens == ref.output(r1).tokens
        assert eng.metrics()["backend_fallbacks"] == 1
        stats = plan_mod.default_planner().backend_stats()
        assert sum(stats["failures"].values()) >= 1


class TestBackendFallbackVQ:
    def test_vq_engine_backend_fault_switches_token_identically(
            self, dense_setup):
        """A scripted backend fault on an EVA-quantized engine
        quarantines the PLANNED backend and re-plans through
        core/plan.py; the next eligible candidate
        (another EVA formulation, or ultimately the dequant jnp baseline
        — all token-exact) takes over and the stream stays identical."""
        cfg, model, params, rc = dense_setup
        qparams = model.quantize(params, method="synthetic", key=KEY)
        rc_vq = rc.replace_policy(vq_mode="eva")
        rng = np.random.default_rng(23)
        prompt = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
        req = GenerationRequest(prompt=prompt, max_new_tokens=5)

        ref = Engine(model, qparams, rc_vq,
                     EngineConfig(num_slots=1, max_len=24))
        r1 = ref.submit(req)
        _drain(ref)
        eva_chosen = sorted({pl.backend for _p, pl in ref.plans["decode"]
                             if pl.backend.startswith("eva_")})
        assert eva_chosen, "VQ decode plan should use an EVA backend"
        victim = eva_chosen[0]

        fp = FaultPlan.scripted(FaultSpec("backend", tick=1, backend=victim))
        eng = Engine(model, qparams, rc_vq,
                     EngineConfig(num_slots=1, max_len=24, fault_plan=fp))
        u1 = eng.submit(req)
        _drain(eng)
        assert eng.output(u1).tokens == ref.output(r1).tokens
        assert eng.metrics()["backend_fallbacks"] == 1
        # the failed backend is out of every re-planned decode leaf
        replanned = {pl.backend for _p, pl in eng.plans["decode"]}
        assert victim not in replanned
        stats = plan_mod.default_planner().backend_stats()
        assert victim in stats["quarantined"]
        assert stats["failures"][victim] == 1

    def test_all_eva_quarantined_degrades_to_dequant(self, dense_setup):
        """With EVERY eligible EVA backend quarantined the planner
        degrades the policy itself: vq_mode="eva" falls back to the
        dequant jnp baseline (token-exact vs EVA per the engine VQ
        equivalence test) instead of refusing to serve."""
        from repro.core.vq import VQWeight

        cfg, model, params, rc = dense_setup
        qparams = model.quantize(params, method="synthetic", key=KEY)
        vq = next(leaf for leaf in jax.tree_util.tree_leaves(
            qparams, is_leaf=lambda x: isinstance(x, VQWeight))
            if isinstance(leaf, VQWeight))
        spec = LinearSpec.for_vq(vq, M=2, x_dtype="float32",
                                 out_dtype="float32", in_mesh=False)
        policy = PlanPolicy(vq_mode="eva")
        pl = Planner(cooloff_s=60.0)
        matched = {be.name for be in Planner._match_all(spec, policy)}
        assert matched and all(b.startswith("eva_") for b in matched)
        for b in matched:
            pl.record_backend_failure(b)
        degraded = pl.plan(spec, policy)
        assert degraded.backend == "dequant_jnp"


class TestRecurrentRestore:
    def test_restore_preserves_recurrent_cache_structure(self,
                                                         recurrent_setup):
        """xlstm caches are nested tuple/dict trees; restore adopts the
        leaves under the LIVE engine's treedef (the path format collapses
        list-vs-tuple), so a restored engine decodes without retracing
        errors and stays token-identical."""
        cfg, model, params, rc = recurrent_setup
        rng = np.random.default_rng(29)
        prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
        mk = lambda: Engine(model, params, rc,
                            EngineConfig(num_slots=2, max_len=24))
        eng = mk()
        uid = eng.submit(GenerationRequest(prompt=prompt, max_new_tokens=8))
        eng.step(), eng.step(), eng.step()
        snap = eng.snapshot()
        _drain(eng)
        ref = eng.output(uid).tokens

        eng2 = mk()
        eng2.restore(snap)
        _drain(eng2)
        assert eng2.output(uid).tokens == ref
        assert jax.tree_util.tree_structure(
            eng2.caches) == jax.tree_util.tree_structure(eng.caches)


# ---------------------------------------------------------------------------
# The acceptance scenario: seeded mixed batch under the restart controller
# ---------------------------------------------------------------------------


class TestAcceptance:
    def test_mixed_batch_error_timeout_stop_after_restore(self, dense_setup):
        """One scripted plan drives a mixed batch: the poisoned request
        finishes "error", the expired one "timeout", the request that
        crosses an engine crash + restore finishes "stop-after-restore" —
        and both bystanders (one greedy, one sampled) stream tokens
        BIT-IDENTICAL to a fault-free run."""
        cfg, model, params, rc = dense_setup
        rng = np.random.default_rng(41)
        p = lambda n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        pa, pb, pc, pd, pe = p(5), p(6), p(4), p(7), p(5)
        sampled = SamplingParams(greedy=False, temperature=1.1, seed=13)

        # fault-free reference (no deadline, no eos: B runs to length)
        def submit_all(eng, with_faults):
            ua = eng.submit(GenerationRequest(prompt=pa, max_new_tokens=6))
            ub = eng.submit(GenerationRequest(
                prompt=pb, max_new_tokens=12,
                eos_ids=(b_eos,) if with_faults else ()))
            uc = eng.submit(GenerationRequest(
                prompt=pc, max_new_tokens=6,
                deadline_s=0.0 if with_faults else None))
            ud = eng.submit(GenerationRequest(prompt=pd, max_new_tokens=4))
            ue = eng.submit(GenerationRequest(prompt=pe, max_new_tokens=4,
                                              sampling=sampled))
            return ua, ub, uc, ud, ue

        b_eos = -1  # placeholder; reference ignores it
        ref = Engine(model, params, rc, EngineConfig(num_slots=4, max_len=32))
        ra, rb, rc_, rd, re_ = submit_all(ref, with_faults=False)
        _drain(ref)
        b_ref = ref.output(rb).tokens
        # choose B's stop token: a late token whose FIRST occurrence in
        # the stream is after the crash tick (so B is mid-flight at the
        # crash and stops only after the restore)
        b_idx = next(i for i in range(8, 12)
                     if b_ref[i] not in b_ref[:i])
        b_eos = int(b_ref[b_idx])

        fp = FaultPlan.scripted(
            FaultSpec("poison", tick=1, uid=1),      # A -> error
            FaultSpec("decode", tick=6),             # crash: only B active
        )

        def factory():
            return Engine(model, params, rc,
                          EngineConfig(num_slots=4, max_len=32,
                                       fault_plan=fp))

        # C's deadline_s=0.0 is already past at the first tick's sweep
        eng, outs, stats = serve_with_restarts(
            factory,
            [GenerationRequest(prompt=pa, max_new_tokens=6),
             GenerationRequest(prompt=pb, max_new_tokens=12,
                               eos_ids=(b_eos,)),
             GenerationRequest(prompt=pc, max_new_tokens=6, deadline_s=0.0),
             GenerationRequest(prompt=pd, max_new_tokens=4),
             GenerationRequest(prompt=pe, max_new_tokens=4,
                               sampling=sampled)])
        ua, ub, uc, ud, ue = sorted(outs)
        assert stats.restarts == 1
        # the three affected requests
        assert outs[ua].finish_reason == "error"
        assert outs[uc].finish_reason == "timeout"
        assert outs[ub].finish_reason == "stop-after-restore"
        assert outs[ub].tokens == b_ref[: b_idx + 1]
        # bystanders: bit-identical streams (greedy AND sampled lanes)
        assert outs[ud].tokens == ref.output(rd).tokens
        assert outs[ue].tokens == ref.output(re_).tokens
        assert outs[ud].finish_reason == "length"
        assert outs[ue].finish_reason == "length"
        # A's pre-fault prefix is the fault-free prefix
        assert outs[ua].tokens == ref.output(ra).tokens[
            : len(outs[ua].tokens)]
        m = eng.metrics()
        assert m["errors"] == 1 and m["timeouts"] == 1
        assert m["restores"] == 1 and m["snapshots"] >= 1
        assert m["finished"] == (m["finished_stop"] + m["finished_length"]
                                 + m["errors"] + m["timeouts"])


# ---------------------------------------------------------------------------
# Planner backend quarantine / fallback units (private Planner instances;
# synthetic backends match only a sentinel spec no real model produces)
# ---------------------------------------------------------------------------

_SENTINEL_N = 9973  # prime; no real layer width


def _synthetic_backend(name, fail):
    def matcher(s, p):
        return s.kind == "dense" and s.N == _SENTINEL_N

    def planner_fn(s, p):
        def run(x, w):
            if fail:
                raise RuntimeError(f"{name} exploded")
            return x @ w

        return MatmulPlan(name, s, p, (), run)

    return matcher, planner_fn


@pytest.fixture(scope="module")
def synthetic_backends():
    register_backend("t_cheap_flaky", *_synthetic_backend(
        "t_cheap_flaky", fail=True))
    register_backend("t_pricey_solid", *_synthetic_backend(
        "t_pricey_solid", fail=False))
    # the plan is the first match in registration order: put the pair
    # ahead of the built-in "fp", which matches every dense spec, so the
    # chain for the sentinel spec is flaky -> solid -> fp
    for name in ("t_pricey_solid", "t_cheap_flaky"):
        plan_mod._REGISTRY.move_to_end(name, last=False)
    return LinearSpec(M=4, K=8, N=_SENTINEL_N, kind="dense",
                      x_dtype="float32", out_dtype="float32")


class TestPlannerQuarantine:
    def test_execute_fallback_quarantines_and_reranks(self,
                                                      synthetic_backends):
        spec = synthetic_backends
        pl = Planner(cooloff_s=60.0)
        plan = pl.plan(spec, PlanPolicy())
        assert plan.backend == "t_cheap_flaky"       # first registered
        x = jnp.ones((4, 8), jnp.float32)
        w = jnp.ones((8, _SENTINEL_N), jnp.float32)
        out = plan.execute(x, w)                     # chains to a survivor
        assert out.shape == (4, _SENTINEL_N)
        stats = pl.backend_stats()
        assert stats["failures"]["t_cheap_flaky"] == 1
        assert stats["exec_fallbacks"] >= 1
        assert "t_cheap_flaky" in stats["quarantined"]
        # a fresh plan skips the quarantined backend entirely
        assert pl.plan(spec, PlanPolicy()).backend != "t_cheap_flaky"

    def test_cooloff_releases_quarantine(self, synthetic_backends):
        spec = synthetic_backends
        pl = Planner(cooloff_s=0.05)
        pl.record_backend_failure("t_cheap_flaky")
        assert pl.plan(spec, PlanPolicy()).backend != "t_cheap_flaky"
        time.sleep(0.06)
        # expiry releases the backend AND clears the cache, so the
        # recovered candidate is re-planned rather than shadowed
        assert pl.plan(spec, PlanPolicy()).backend == "t_cheap_flaky"
        assert pl.backend_stats()["quarantined"] == ()

    def test_all_quarantined_serves_as_last_resort(self, synthetic_backends):
        spec = synthetic_backends
        pl = Planner(cooloff_s=60.0)
        matched = {be.name for be in Planner._match_all(spec, PlanPolicy())}
        assert {"t_cheap_flaky", "t_pricey_solid", "fp"} <= matched
        for b in matched:
            pl.record_backend_failure(b)
        # policy is already the degraded jnp shape -> quarantine is
        # ignored rather than refusing to serve
        plan = pl.plan(spec, PlanPolicy())
        assert plan.backend in matched

    def test_reset_quarantine_clears_everything(self, synthetic_backends):
        spec = synthetic_backends
        pl = Planner(cooloff_s=60.0)
        pl.record_backend_failure("t_cheap_flaky")
        pl.reset_quarantine()
        stats = pl.backend_stats()
        assert stats == {"failures": {}, "quarantined": (),
                         "exec_fallbacks": 0}
        assert pl.plan(spec, PlanPolicy()).backend == "t_cheap_flaky"
