"""Engine observability: aggregate counters for the serving loop.

One ``EngineMetrics`` instance lives on each ``Engine``; the engine
increments it inline (submit / admit / prefill / decode / finish /
fault-recovery) and ``Engine.metrics()`` returns ``snapshot()`` — a
plain dict safe to log, JSON-serialize or emit as bench rows. The
invariants tests pin:

  tokens_generated == prefills + decode_slot_steps - poisoned_slot_steps
                      + extra_decode_tokens
                   == number of token-bearing StreamEvents

(``extra_decode_tokens`` is zero on non-speculative engines, so the
classic one-token-per-slot-step identity still holds there; on
speculative engines it counts the tokens emitted beyond the first in
each accepted draft window.)
  finished         == finished_stop + finished_length + errors + timeouts
  submitted        == admitted + rejected + still queued/running

The resilience counters (errors / timeouts / backend_fallbacks /
snapshots / restores / straggler_steps / poisoned_slot_steps) are pinned
consistent with emitted StreamEvents the same way the finish-reason
totals are: every "error"/"timeout" terminal event increments exactly
one counter here, every poisoned lane suppresses exactly one token
event."""
from __future__ import annotations

import dataclasses
import time
from typing import Dict


@dataclasses.dataclass
class EngineMetrics:
    num_slots: int
    submitted: int = 0
    admitted: int = 0
    rejected: int = 0
    finished: int = 0
    finished_stop: int = 0
    finished_length: int = 0
    errors: int = 0                  # numerics-quarantined requests
    timeouts: int = 0                # deadline_s / queue-TTL expiries
    prefills: int = 0
    prefill_prompt_tokens: int = 0
    prefill_chunks: int = 0          # chunked-prefill device calls (paged)
    preemptions: int = 0             # out-of-blocks decode evictions (paged)
    # KV memory gauges (paged engines update these on every block
    # alloc/free; contiguous engines set kv_bytes_in_use once at init)
    kv_bytes_in_use: int = 0
    blocks_in_use: int = 0
    blocks_free: int = 0
    peak_blocks_in_use: int = 0
    peak_kv_bytes_in_use: int = 0
    decode_steps: int = 0
    decode_slot_steps: int = 0       # active lanes summed over decode steps
    poisoned_slot_steps: int = 0     # lanes whose logits failed the finite check
    tokens_generated: int = 0
    # ---- speculative decoding (all zero when speculate_k == 0) ----
    drafted_tokens: int = 0          # K per speculating lane per decode step
    accepted_draft_tokens: int = 0   # drafts that matched the verify sample
    rejected_draft_tokens: int = 0   # drafted - accepted
    extra_decode_tokens: int = 0     # emissions beyond 1 per lane per step
    backend_fallbacks: int = 0       # planned-backend failures recovered by re-plan
    snapshots: int = 0
    restores: int = 0
    straggler_steps: int = 0         # watchdog-flagged slow decode steps
    # ---- expert layers (zero for dense models) ----
    # decode rows x top_k x MoE layers: the routes every decode step runs
    moe_routed_rows: int = 0
    # experts with at least one routed row, summed over each decode
    # step's MoE layers (counted in the decode program)
    moe_expert_visits: int = 0
    # most token tiles of any decode EVA kernel (set at construction): 1
    # when every decode row shares each index tile's handling, more when
    # the kernel's VMEM budget split the rows
    eva_token_tiles: int = 0
    queue_wait_s: float = 0.0        # summed over admitted requests
    prefill_s: float = 0.0           # summed wall time of prefill calls
    decode_s: float = 0.0            # summed wall time of batched decode steps
    started_at: float = dataclasses.field(default_factory=time.perf_counter)

    def count_finish(self, reason: str) -> None:
        self.finished += 1
        # a restore mid-flight annotates the reason but counts as its base
        base = reason.replace("-after-restore", "")
        if base == "stop":
            self.finished_stop += 1
        elif base == "length":
            self.finished_length += 1
        elif base == "error":
            self.errors += 1
        elif base == "timeout":
            self.timeouts += 1
        else:
            raise ValueError(f"not a finish reason for a served request: "
                             f"{reason!r}")

    @property
    def slot_occupancy(self) -> float:
        """Mean fraction of slots doing useful work per batched decode
        step — the paper's weight-tile amortization factor (§V-C)."""
        if self.decode_steps == 0:
            return 0.0
        return self.decode_slot_steps / (self.decode_steps * self.num_slots)

    @property
    def draft_acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verify pass accepted."""
        if self.drafted_tokens == 0:
            return 0.0
        return self.accepted_draft_tokens / self.drafted_tokens

    @property
    def decode_tokens_per_step(self) -> float:
        """Mean tokens emitted per active lane per decode step — 1.0
        without speculation, up to K+1 with it."""
        useful = self.decode_slot_steps - self.poisoned_slot_steps
        if useful <= 0:
            return 0.0
        return (useful + self.extra_decode_tokens) / useful

    @property
    def decode_tokens_per_s(self) -> float:
        if self.decode_s <= 0.0:
            return 0.0
        return self.decode_slot_steps / self.decode_s

    def state(self) -> Dict[str, float]:
        """The restorable counter fields (everything but the wall
        clock), as used by Engine.snapshot()/restore()."""
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self) if f.name != "started_at"}

    def restore(self, state: Dict[str, float]) -> None:
        for name, value in state.items():
            setattr(self, name, value)

    def snapshot(self) -> Dict[str, float]:
        out = self.state()
        out["uptime_s"] = time.perf_counter() - self.started_at
        out["slot_occupancy"] = self.slot_occupancy
        out["draft_acceptance_rate"] = self.draft_acceptance_rate
        out["decode_tokens_per_step"] = self.decode_tokens_per_step
        out["decode_tokens_per_s"] = self.decode_tokens_per_s
        return out

