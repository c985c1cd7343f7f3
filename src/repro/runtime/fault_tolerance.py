"""Fault-tolerance machinery: step watchdog / straggler detection and the
checkpoint-restart driver loop.

At 1000+ nodes the failure model is: (a) hard node loss -> training raises
(collective timeout / data host gone) -> restart from the last committed
checkpoint, possibly at smaller world size (elastic.py); (b) stragglers ->
per-step wall time watchdog flags hosts whose step time exceeds
median * threshold so the scheduler can evict them.

This module is hardware-agnostic: failures are injected in tests through
the data pipeline (`fail_at`) and through a step callback.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional


@dataclasses.dataclass
class StragglerReport:
    step: int
    step_time: float
    median: float
    ratio: float
    is_straggler: bool


class StepWatchdog:
    """Tracks per-step wall time; flags stragglers vs the rolling median.

    On a real deployment each host feeds its own step times and the
    controller aggregates; here the same logic runs host-local. A server
    feeds it every decode step for as long as it runs, so it keeps only
    the latest ``window`` reports, and of the rest only the step numbers
    of the stragglers.
    """

    def __init__(self, window: int = 50, threshold: float = 2.0,
                 warmup_steps: int = 5):
        self.window: Deque[float] = deque(maxlen=window)
        self.threshold = threshold
        self.warmup_steps = warmup_steps
        self.reports: Deque[StragglerReport] = deque(maxlen=window)
        self._stragglers: List[int] = []
        self._t0: Optional[float] = None
        self._step = 0

    def start_step(self):
        self._t0 = time.monotonic()

    def end_step(self) -> StragglerReport:
        assert self._t0 is not None, "start_step not called"
        dt = time.monotonic() - self._t0
        self._t0 = None
        self._step += 1
        med = sorted(self.window)[len(self.window) // 2] if self.window else dt
        ratio = dt / max(med, 1e-9)
        is_straggler = (self._step > self.warmup_steps
                        and len(self.window) >= 5
                        and ratio > self.threshold)
        # stragglers don't poison the baseline window
        if not is_straggler:
            self.window.append(dt)
        rep = StragglerReport(self._step, dt, med, ratio, is_straggler)
        self.reports.append(rep)
        if is_straggler:
            self._stragglers.append(self._step)
        return rep

    @property
    def straggler_steps(self) -> List[int]:
        return list(self._stragglers)


@dataclasses.dataclass
class RestartStats:
    restarts: int = 0
    last_resume_step: int = 0
    failures: List[str] = dataclasses.field(default_factory=list)


def run_with_restarts(
    train_loop: Callable[[int], int],
    *,
    max_restarts: int = 3,
    on_failure: Optional[Callable[[Exception, int], int]] = None,
) -> RestartStats:
    """Drive `train_loop(start_step) -> last_step` with checkpoint-restart.

    `train_loop` must raise on failure and is expected to resume from the
    last committed checkpoint (it receives the resume step returned by
    `on_failure`, default: same step). Mirrors the controller loop a real
    cluster runs around the SPMD program.

    Only exceptions raised by `train_loop` itself count as training
    failures. An exception raised by the `on_failure` callback is a
    CONTROLLER bug, not a node loss: it propagates directly — unwrapped,
    not recorded in `failures`, and without Python's implicit
    "during handling of the above exception" chaining (the callback runs
    outside the except block), so callers can tell the two apart.
    `last_resume_step` is updated on every restart, callback or not.
    """
    stats = RestartStats()
    start_step = 0
    while True:
        try:
            train_loop(start_step)
            return stats
        except Exception as e:  # noqa: BLE001 - controller catches anything
            err = e
        stats.restarts += 1
        stats.failures.append(f"{type(err).__name__}: {err}")
        if stats.restarts > max_restarts:
            raise RuntimeError(
                f"exceeded {max_restarts} restarts; last: {err}"
            ) from err
        if on_failure is not None:
            # callback errors propagate from HERE, outside the except
            # block: no implicit exception chaining, no burned restart
            # recorded against the training loop
            start_step = on_failure(err, stats.restarts)
        stats.last_resume_step = start_step
