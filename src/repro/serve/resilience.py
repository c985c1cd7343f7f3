"""Serving-layer fault tolerance: deterministic fault injection, numerics
quarantine, engine snapshot/restore and the serve restart controller.

The PR-5 engine had no failure story: a NaN-poisoned slot streamed
garbage to its client, one exception inside ``step()`` killed every
in-flight request, and a wedged request held its slot forever. This
module supplies the pieces the engine (serve/engine.py) wires together:

  FaultPlan / FaultSpec : a SEEDABLE, scripted fault schedule injected
        via ``EngineConfig.fault_plan``. Faults fire at named engine
        boundaries (BOUNDARIES) at a scripted tick, optionally targeted
        at one request uid — so every recovery path is exercised by
        deterministic tier-1 tests, not hope. A plan is stateful (each
        spec fires ``times`` polls, then never again); share ONE plan
        instance across engine restarts or the fault re-fires forever.
  InjectedFault         : the exception scripted raise-faults throw.
  CircuitBreaker        : >= k CONSECUTIVE poisoned decode steps trip
        the engine unhealthy — pending requests are rejected cleanly
        and new submits refuse, instead of streaming garbage at line
        rate while every request "finishes" with an error.
  EngineSnapshot        : host-side serialized engine state — scheduler
        queue, tracked requests, per-slot KV caches, PRNG keys and
        sampling state. The array state is PATH-FLATTENED through
        checkpoint/manager.py's format, so a snapshot can be persisted
        with CheckpointManager (save_snapshot / load_snapshot) and a
        restored engine resumes mid-stream token-identically.
  serve_with_restarts   : the serving generalization of
        runtime/fault_tolerance.run_with_restarts — drive an engine to
        idle, snapshotting between ticks; on a step() crash build a
        fresh engine, restore the last snapshot and resume.

Nothing here imports serve/engine.py — the engine imports this module;
``serve_with_restarts`` takes an engine factory and stays duck-typed.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger(__name__)

# the named engine boundaries a FaultSpec can fire at:
#   prefill : raise InjectedFault in place of the admitted request's
#             prefill call (the request is back in the queue after a
#             snapshot restore)
#   decode  : raise InjectedFault before the batched decode step
#   sample  : raise InjectedFault AFTER the decode readback but before
#             host bookkeeping (the classic torn-state crash — only a
#             snapshot restore recovers it consistently)
#   poison  : add NaN/Inf ("mode") into the target slot's logits INSIDE
#             the jitted step — exercises the numerics quarantine
#   backend : simulate a planned backend failing at execute time —
#             exercises quarantine + the fallback chain in core/plan.py
BOUNDARIES = ("prefill", "decode", "sample", "poison", "backend")
POISON_MODES = ("nan", "inf")


class InjectedFault(RuntimeError):
    """A scripted fault fired by a FaultPlan at an engine boundary."""

    def __init__(self, boundary: str, tick: int, uid: Optional[int] = None):
        self.boundary = boundary
        self.tick = tick
        self.uid = uid
        at = f" uid={uid}" if uid is not None else ""
        super().__init__(f"injected {boundary} fault at tick {tick}{at}")


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One scripted fault.

    ``tick``  : first engine tick (0-based ``step()`` count) the spec is
                armed at — it fires on the first matching poll with
                ``tick >= spec.tick`` and keeps firing for ``times``
                polls (consecutive poisoned steps drive the breaker).
    ``uid``   : target request (None matches any request at a
                per-request boundary).
    ``mode``  : poison payload, "nan" | "inf" (poison boundary only).
    ``backend``: backend name to fail (backend boundary; None lets the
                engine pick its decode plan's chosen backend)."""

    boundary: str
    tick: int
    uid: Optional[int] = None
    mode: str = "nan"
    times: int = 1
    backend: Optional[str] = None

    def __post_init__(self):
        if self.boundary not in BOUNDARIES:
            raise ValueError(
                f"unknown fault boundary {self.boundary!r}; expected one of "
                f"{BOUNDARIES}")
        if self.mode not in POISON_MODES:
            raise ValueError(
                f"unknown poison mode {self.mode!r}; expected one of "
                f"{POISON_MODES}")
        if self.tick < 0 or self.times < 1:
            raise ValueError(
                f"tick must be >= 0 and times >= 1, got tick={self.tick} "
                f"times={self.times}")


class FaultPlan:
    """A deterministic, stateful schedule of FaultSpecs.

    The engine polls the plan at each boundary; a spec fires when the
    boundary matches, the engine tick has reached ``spec.tick``, its
    ``times`` budget is not exhausted, and the uid matches (a spec with
    ``uid=None`` matches any uid; a poll with ``uid=None`` matches any
    spec). Polls are deterministic in engine order, so a given request
    trace fires the same faults every run."""

    def __init__(self, faults: Iterable[FaultSpec] = ()):
        self.faults: Tuple[FaultSpec, ...] = tuple(faults)
        self._fired = [0] * len(self.faults)

    @classmethod
    def scripted(cls, *faults: FaultSpec) -> "FaultPlan":
        return cls(faults)

    @classmethod
    def seeded(cls, seed: int, *, boundaries: Sequence[str] = BOUNDARIES,
               n_faults: int = 3, max_tick: int = 8,
               uids: Sequence[int] = ()) -> "FaultPlan":
        """A pseudo-random scripted plan derived from ``seed`` — the same
        seed always yields the same spec list, so randomized fault tests
        stay reproducible (pin the seed, pin the failure)."""
        rng = np.random.default_rng(seed)
        specs = []
        for _ in range(n_faults):
            boundary = boundaries[int(rng.integers(len(boundaries)))]
            uid = (int(rng.choice(np.asarray(uids)))
                   if len(uids) and boundary in ("poison", "prefill") else None)
            specs.append(FaultSpec(
                boundary=boundary, tick=int(rng.integers(max_tick)),
                uid=uid, mode=POISON_MODES[int(rng.integers(2))]))
        return cls(specs)

    def poll(self, boundary: str, tick: int,
             uid: Optional[int] = None) -> Optional[FaultSpec]:
        """Fire-and-consume the first matching spec (None when nothing
        matches). Each successful poll consumes one of the spec's
        ``times``."""
        for i, spec in enumerate(self.faults):
            if spec.boundary != boundary or tick < spec.tick:
                continue
            if self._fired[i] >= spec.times:
                continue
            if spec.uid is not None and uid is not None and spec.uid != uid:
                continue
            self._fired[i] += 1
            log.warning("fault plan fired: %s (tick=%d uid=%s, %d/%d)",
                        spec.boundary, tick, uid, self._fired[i], spec.times)
            return spec
        return None

    @property
    def exhausted(self) -> bool:
        return all(f >= s.times for f, s in zip(self._fired, self.faults))


# ---------------------------------------------------------------------------
# Numerics circuit breaker
# ---------------------------------------------------------------------------


class CircuitBreaker:
    """Trips after ``k`` CONSECUTIVE poisoned engine steps.

    One poisoned slot is a per-request event (quarantined with
    ``finish_reason="error"``); k poisoned steps in a row mean the model
    or hardware is emitting garbage at line rate — the engine marks
    itself unhealthy, rejects pending requests and refuses new submits
    instead of erroring every request one slot at a time."""

    def __init__(self, k: int = 3):
        if k < 1:
            raise ValueError(f"breaker threshold k must be >= 1, got {k}")
        self.k = k
        self.consecutive = 0
        self.tripped = False

    def record(self, poisoned: bool) -> bool:
        """Record one engine step; returns the (possibly new) tripped
        state. A clean step resets the consecutive count."""
        if not self.tripped:
            self.consecutive = self.consecutive + 1 if poisoned else 0
            if self.consecutive >= self.k:
                self.tripped = True
                log.error("circuit breaker tripped: %d consecutive poisoned "
                          "steps", self.consecutive)
        return self.tripped

    def state(self) -> Tuple[int, int, bool]:
        return (self.k, self.consecutive, self.tripped)

    def restore(self, state: Tuple[int, int, bool]) -> None:
        self.k, self.consecutive, self.tripped = state


# ---------------------------------------------------------------------------
# Engine snapshot
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EngineSnapshot:
    """Host-side serialized engine state (see Engine.snapshot()).

    ``arrays`` is the PATH-FLATTENED array state (checkpoint/manager.py
    format): every per-slot KV-cache leaf, the PRNG keys and the
    per-slot sampling/stopping state live under ``/caches/...`` and
    ``/slots/...`` paths mapping to host numpy arrays. The request
    bookkeeping (scheduler queue + tracked requests, finished outputs,
    undrained event buffers) is deep-copied Python — a snapshot never
    aliases live engine state, so mutating the engine after
    ``snapshot()`` cannot corrupt it."""

    tick: int
    arrays: Dict[str, np.ndarray]
    uid_counter: int
    queue: List[Any]                  # TrackedRequest clones, FIFO order
    slots: List[Optional[Any]]        # TrackedRequest clones by slot index
    outputs: Dict[int, Any]           # uid -> RequestOutput (frozen)
    buffers: Dict[int, List[Any]]     # uid -> undrained StreamEvents
    pending: List[Any]
    retired: List[int]
    metrics: Dict[str, Any]
    breaker: Tuple[int, int, bool]
    num_slots: int
    max_len: int
    # ---- paged-KV state (serve/paging.py; defaults keep old snapshots
    # loadable by contiguous engines) ----
    paged: bool = False
    block_size: int = 0
    num_blocks: int = 0
    block_tables: Optional[np.ndarray] = None      # (num_slots, W) host copy
    pool_free: Optional[Tuple[int, ...]] = None    # BlockPool free list
    # per-slot owned block ids, allocation order (tuple of tuples)
    owned: Optional[Tuple[Tuple[int, ...], ...]] = None

    def checkpoint_state(self) -> Dict[str, Any]:
        """The array state as a CheckpointManager ``state`` group dict
        (save under one group; the Python bookkeeping is process-local
        and intentionally NOT persisted — cross-process replica failover
        is the ROADMAP item-2 seam this snapshot feeds)."""
        return {"engine_arrays": dict(self.arrays)}


def save_snapshot(snapshot: EngineSnapshot, manager: Any, step: int) -> None:
    """Persist the snapshot's array state through a CheckpointManager
    (checkpoint/manager.py) — same path-flattened npz format training
    checkpoints use."""
    manager.save(step, snapshot.checkpoint_state(), block=True)


def load_snapshot_arrays(manager: Any,
                         step: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Load a persisted snapshot's flat array state back from disk.

    ``manager.restore`` re-nests the saved tree (our "/caches/..." keys
    become path segments), so the group is re-flattened through the same
    path format to recover the EngineSnapshot.arrays keys exactly."""
    from repro.checkpoint import manager as ckpt_manager

    _, state = manager.restore(step)
    flat = ckpt_manager.flatten_with_paths(state["engine_arrays"])
    return {path: np.asarray(leaf) for path, leaf in flat}


# ---------------------------------------------------------------------------
# Serve restart controller
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ServeRestartStats:
    """What the restart controller did (mirrors runtime RestartStats)."""

    restarts: int = 0
    snapshots: int = 0
    resumed_tick: int = 0
    failures: List[str] = dataclasses.field(default_factory=list)


def serve_with_restarts(
    engine_factory: Callable[[], Any],
    requests: Sequence[Any],
    *,
    max_restarts: int = 3,
    snapshot_every: int = 1,
) -> Tuple[Any, Dict[int, Any], ServeRestartStats]:
    """Serve ``requests`` to completion under checkpoint-restart.

    The serving generalization of ``runtime.fault_tolerance.
    run_with_restarts``: build an engine, submit everything, then step to
    idle taking a host snapshot every ``snapshot_every`` ticks. When
    ``step()`` raises, a FRESH engine from ``engine_factory`` restores
    the last snapshot and resumes — token-identically, because the
    snapshot carries every per-slot PRNG key, KV cache and sampling
    state. Events of the crashed tick were never delivered, and restored
    ticks replay from un-delivered buffered state, so with
    ``snapshot_every=1`` no event is delivered twice.

    The factory must rebuild a compatible engine (same model/params/
    EngineConfig); pass the SAME FaultPlan instance through, or a
    scripted one-shot fault re-arms on every restart and the controller
    crash-loops to ``max_restarts``.

    Returns ``(engine, {uid: RequestOutput}, stats)``."""
    if snapshot_every < 1:
        raise ValueError(f"snapshot_every must be >= 1, got {snapshot_every}")
    stats = ServeRestartStats()
    eng = engine_factory()
    uids = [eng.submit(r) for r in requests]
    snap = eng.snapshot()
    stats.snapshots += 1
    since_snapshot = 0
    while not eng.idle:
        try:
            eng.step()
        except Exception as e:  # noqa: BLE001 - controller catches anything
            stats.restarts += 1
            stats.failures.append(f"{type(e).__name__}: {e}")
            if stats.restarts > max_restarts:
                raise RuntimeError(
                    f"exceeded {max_restarts} serve restarts; last: {e}"
                ) from e
            log.warning("engine step crashed (%s); restoring tick-%d "
                        "snapshot (restart %d/%d)", e, snap.tick,
                        stats.restarts, max_restarts)
            eng = engine_factory()
            eng.restore(snap)
            stats.resumed_tick = snap.tick
            since_snapshot = 0
            continue
        since_snapshot += 1
        if since_snapshot >= snapshot_every:
            snap = eng.snapshot()
            stats.snapshots += 1
            since_snapshot = 0
    outputs = {uid: eng.output(uid) for uid in uids}
    return eng, outputs, stats
