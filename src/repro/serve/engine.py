"""Event-driven continuous-batching serving engine.

The EVA deployment shape (paper §V-C / Fig. 7(c)): prefill runs per-request
(INT8 GEMM path), decode runs as one batched step over all active slots so
every streamed weight-index tile is reused across requests. Slots free up
as requests finish and queued requests are admitted with a fresh prefill —
classic continuous batching, expressed with jit-stable shapes (fixed slot
count, fixed cache capacity).

Request-level surface (serve/api.py types):

  uid = engine.submit(GenerationRequest(...))   # admission-checked
  events = engine.step()                        # one tick -> StreamEvents
  for ev in engine.stream(uid): ...             # per-request iterator
  engine.generate(prompts, n)                   # greedy batch convenience
  engine.metrics()                              # counters snapshot

Sampling and stopping run INSIDE the jitted decode step with jit-stable
shapes: per-slot PRNG keys, temperature/top-k/top-p, stop-token sets and
budgets are all device arrays of fixed (num_slots, ...) shape, so a
mixed-sampling workload traces the decode step exactly ONCE and the host
loop only reads back a ``(next_tok, done_mask)`` pair.

Prefill is length-BUCKETED for attention families: prompts right-pad
(edge mode — the pad value is causally masked) to power-of-two buckets,
the true length rides along as a traced scalar, and the jitted prefill
step retraces at most once per bucket instead of once per prompt length.
MoE layers are dropless, so pad tokens route and compute but no real
row depends on them. Families whose prefill is not padding-invariant
(recurrent state integrates pad tokens: xlstm/rglru) run exact-length
prefill instead.

All caches are batched on axis 1 (axis 0 is the scanned layer/group axis),
so slot insertion is a tree-wide dynamic_update_slice at index b.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import time
from collections import deque
from typing import Any, Deque, Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import manager as ckpt_manager
from repro.core import plan as plan_mod
from repro.models.api import Model
from repro.models.common import RunConfig
from repro.runtime.fault_tolerance import StepWatchdog
from repro.serve import api
from repro.serve import paging
from repro.serve import speculative
from repro.serve.api import (GenerationRequest, RequestEvicted, RequestOutput,
                             SamplingParams, StreamEvent)
from repro.serve.kvcache import (cache_bytes, encode_prefill_cache,
                                 pad_prefill_cache,
                                 quantize_prefill_cache_int8)
from repro.serve.metrics import EngineMetrics
from repro.serve.resilience import (CircuitBreaker, EngineSnapshot, FaultPlan,
                                    InjectedFault)
from repro.serve.scheduler import QueueFull, Scheduler, TrackedRequest

log = logging.getLogger(__name__)

# families whose prefill output is invariant to causal right-padding
# (attention stacks; dropless MoE routes each token on its own);
# recurrent state (xlstm/rglru) integrates pad tokens, so those families
# prefill at exact prompt length
_BUCKETABLE_FAMILIES = ("dense", "moe", "whisper", "vision")


def _named_jit(impl, **bound):
    """``jax.jit`` of ``impl`` with ``bound`` keywords fixed, compiled
    under impl's own name: the module is ``jit_<name>`` and its ops'
    metadata reads ``jit(<name>)/...`` (a bare partial compiles as
    ``jit__unknown``). Dispatch still shows as ``PjitFunction(<name>)``."""
    fn = functools.partial(impl, **bound)
    fn.__name__ = impl.__name__
    return jax.jit(fn)


def _insert_slot(batched: Any, single: Any, b: int) -> Any:
    """Write a single-request cache (batch size 1 at axis 1) into slot b of
    the batched cache tree."""

    def one(dst, src):
        idx = [0] * dst.ndim
        idx[1] = b
        return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), tuple(idx))

    return jax.tree_util.tree_map(one, batched, single)


@dataclasses.dataclass
class EngineConfig:
    num_slots: int = 4
    max_len: int = 256
    max_queue: int = 256               # submit() rejects past this bound
    prefill_bucketing: bool = True     # pad prompts to power-of-two buckets
    min_prefill_bucket: int = 8
    # finished RequestOutputs (+ their undrained event buffers) retained
    # for output()/stream(); oldest evicted past this bound so a
    # long-running submit()/step() server stays memory-bounded
    max_retained: int = 1024
    # ---- resilience (serve/resilience.py) ----
    # queued requests older than this time out at the admission sweep
    # (finish_reason="timeout") — per-request deadline_s is checked too
    queue_ttl_s: Optional[float] = None
    # stream() raises RuntimeError after this long without yielding an
    # event (replaces the old 1,000,000-iteration guard with wall clock)
    stream_stall_s: float = 60.0
    # >= breaker_k CONSECUTIVE poisoned decode steps trip the engine
    # unhealthy: pending requests reject cleanly, submits refuse
    breaker_k: int = 3
    # decode steps slower than threshold x rolling median are stragglers
    # (runtime/fault_tolerance.StepWatchdog -> metrics straggler_steps)
    straggler_threshold: float = 3.0
    # scripted fault schedule for tests/chaos drills; None in production
    fault_plan: Optional[FaultPlan] = None
    # ---- paged KV memory (serve/paging.py) ----
    # paged=True swaps the per-slot contiguous cache for shared block
    # arenas + per-slot block tables: admission allocates blocks for the
    # prompt, decode grows one block at a time, finish recycles — so
    # memory tracks ACTUAL sequence lengths and an out-of-blocks decode
    # step preempts the youngest request back to the queue instead of
    # failing
    paged: bool = False
    block_size: int = 16               # tokens per block (gcd-snapped)
    # pool size; None -> num_slots * blocks_per_slot (contiguous parity)
    num_blocks: Optional[int] = None
    # chunked prefill: prompts longer than this admit as several engine
    # ticks (one bucketed chunk each) interleaved with decode; None
    # disables. Only effective for paged + bucketed attention families
    # with window == 0 and no MLA (the continuation path's support set)
    prefill_chunk: Optional[int] = None
    # ---- compressed KV (core/vq.py, serve/kvcache.py) ----
    # bits per stored KV channel: 16 = fp, 8 = int8 k_s/v_s layout,
    # 4/2 = KV-VQ (uint8 codebook indices; codebooks attach to params).
    # Prefill caches are encoded EXPLICITLY before slot insertion;
    # chunked prefill is gated off below 16 (the continuation path
    # cannot append into quantized leaves)
    kv_bits: int = 16
    # ---- speculative decoding (serve/speculative.py) ----
    # K > 0 turns every batched decode step into a K-draft verify
    # window: up to K+1 tokens emit per slot per step, streams stay
    # token-identical to K=0 (the acceptance rule replays the exact
    # sampling epilogue). Requires window == 0, the dense family and no
    # MLA.
    # Per-request opt-out: GenerationRequest.speculate=False
    speculate_k: int = 0


class Engine:
    def __init__(self, model: Model, params: Any, rc: RunConfig,
                 ecfg: EngineConfig, extras: Optional[Dict[str, Any]] = None):
        self.model = model
        self.params = params
        self.rc = rc
        self.ecfg = ecfg
        self.extras = extras or {}
        self.sched = Scheduler(ecfg.num_slots, max_queue=ecfg.max_queue)
        cfg = model.cfg
        self.window = cfg.sliding_window or cfg.local_window
        # expert layers of the decode step (0 for dense models), whose
        # routing the step reports (EngineMetrics.moe_*)
        self._moe_layers = (cfg.num_layers - cfg.first_dense_layers
                            if cfg.family == "moe" else 0)
        self.metrics_counters = EngineMetrics(num_slots=ecfg.num_slots)

        # ---- compressed KV layout (EngineConfig.kv_bits) ----
        if ecfg.kv_bits not in (16, 8, 4, 2):
            raise ValueError(
                f"kv_bits={ecfg.kv_bits} unsupported; expected 16/8/4/2")
        self.kvq = None
        self.kv_int8 = False
        if ecfg.kv_bits != 16 and cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"kv_bits={ecfg.kv_bits} requires an attention-cache "
                f"family (dense/moe), got {cfg.family!r}")
        if ecfg.kv_bits == 8:
            if getattr(cfg, "use_mla", False):
                raise ValueError(
                    "kv_bits=8 has no MLA latent layout; use 16 or the "
                    "KV-VQ 4/2-bit modes")
            self.kv_int8 = True
        elif ecfg.kv_bits in (4, 2):
            from repro.core.quantize import (attach_kv_codebooks,
                                             kv_codebook_tree)
            from repro.core.vq import KVQuantConfig

            self.kvq = KVQuantConfig(kv_bits=ecfg.kv_bits)
            try:  # keep calibrated codebooks when the caller attached them
                self._kv_cb = kv_codebook_tree(params)
            except ValueError:
                params = attach_kv_codebooks(params, cfg, self.kvq)
                self.params = params
                self._kv_cb = kv_codebook_tree(params)
            rc = rc.replace(kv_vq=self.kvq)
            self.rc = rc
        # only pass the quantized-cache kwargs when active: duck-typed
        # model stubs (and pre-kvq signatures) need not accept them
        self._cache_kw = {}
        if self.kv_int8:
            self._cache_kw["kv_int8"] = True
        if self.kvq is not None:
            self._cache_kw["kvq"] = self.kvq

        if ecfg.paged:
            self.paging: Optional[paging.PagingConfig] = \
                paging.make_paging_config(
                    model, ecfg.num_slots, ecfg.max_len, window=self.window,
                    block_size=ecfg.block_size, num_blocks=ecfg.num_blocks,
                    **self._cache_kw)
            self.caches = paging.init_paged_cache(
                model, ecfg.num_slots, ecfg.max_len, self.paging,
                **self._cache_kw)
            self.pool: Optional[paging.BlockPool] = \
                paging.BlockPool(self.paging.num_blocks)
            # host-side source of truth: per-slot block rows + owned ids;
            # the device mirror (set_block_tables) lags until _sync_tables
            self.tables = np.full(
                (ecfg.num_slots, self.paging.blocks_per_slot),
                self.paging.sentinel, np.int32)
            self._owned: List[List[int]] = [[] for _ in range(ecfg.num_slots)]
            self._tables_dirty = True
            self._update_kv_gauges()
        else:
            self.paging = None
            self.pool = None
            self.tables = None
            self._owned = []
            self._tables_dirty = False
            self.caches = paging.init_contiguous_cache(
                model, ecfg.num_slots, ecfg.max_len, **self._cache_kw)
            # contiguous allocation is worst-case and constant
            self.metrics_counters.kv_bytes_in_use = cache_bytes(self.caches)
            self.metrics_counters.peak_kv_bytes_in_use = \
                self.metrics_counters.kv_bytes_in_use

        B = ecfg.num_slots
        # per-slot decode state: every per-request sampling/stopping knob
        # is DATA of fixed shape, so the jitted decode step traces once
        self.positions = np.zeros((B,), np.int32)
        self.last_token = np.zeros((B,), np.int32)
        self.rng_keys = np.zeros((B, 2), np.uint32)
        self.temperature = np.ones((B,), np.float32)
        self.top_k = np.zeros((B,), np.int32)
        self.top_p = np.ones((B,), np.float32)
        self.greedy = np.ones((B,), bool)
        self.stop_ids = np.full((B, api.MAX_STOP_IDS), -1, np.int32)
        self.remaining = np.zeros((B,), np.int32)
        self.active = np.zeros((B,), bool)

        # ---- speculative decoding state (EngineConfig.speculate_k) ----
        self.spec_k = int(ecfg.speculate_k)
        if self.spec_k < 0:
            raise ValueError(f"speculate_k must be >= 0, got {self.spec_k}")
        if self.spec_k:
            if self.window != 0:
                raise ValueError(
                    "speculate_k > 0 requires a full (non-windowed) cache: "
                    "ring caches decode one token at a time")
            if cfg.family != "dense":
                raise ValueError(
                    f"speculate_k > 0 requires family='dense', got "
                    f"{cfg.family!r}")
            if getattr(cfg, "use_mla", False):
                raise ValueError(
                    "speculate_k > 0 is not supported with MLA decode")
            # per-slot successor table (the self-drafting n-gram model)
            # and opt-in mask — device args of the one traced decode
            # step, and part of the snapshot slot state
            self.succ = np.full((B, cfg.vocab_size), -1, np.int32)
            self.spec_on = np.ones((B,), bool)
            self._SLOT_STATE = Engine._SLOT_STATE + ("succ", "spec_on")

        # request-level bookkeeping; _retired drives FIFO eviction of
        # finished outputs/buffers past ecfg.max_retained
        self._outputs: Dict[int, RequestOutput] = {}
        self._buffers: Dict[int, Deque[StreamEvent]] = {}
        self._pending: List[StreamEvent] = []
        self._retired: Deque[int] = deque()

        # trace-counting harness: these tick only when jax (re)traces the
        # python body — tests pin decode==1 and prefill<=len(buckets)
        self.trace_counts = {"decode": 0, "prefill": 0, "prefill_chunk": 0}

        # resilience state: engine tick counter (FaultPlan schedule / the
        # snapshot resume point), numerics circuit breaker and the decode
        # step watchdog
        self._tick = 0
        self.fault_plan = ecfg.fault_plan
        self.breaker = CircuitBreaker(ecfg.breaker_k)
        self.watchdog = StepWatchdog(window=50,
                                     threshold=ecfg.straggler_threshold)

        self._bucketed = (ecfg.prefill_bucketing
                          and cfg.family in _BUCKETABLE_FAMILIES)
        self._buckets = (api.prefill_buckets(ecfg.max_len,
                                             ecfg.min_prefill_bucket)
                         if self._bucketed else ())
        # chunked prefill runs model.forward over a slot_view — supported
        # for the bucketable attention families with a full (non-ring)
        # cache and no MLA latent path (models/common.py gates the same)
        self._chunked = bool(
            ecfg.paged and ecfg.prefill_chunk and self._bucketed
            and self.window == 0 and not getattr(cfg, "use_mla", False)
            and ecfg.kv_bits == 16)  # continuations can't append quantized

        # Pre-plan at the exact execution shapes. Decode always runs at
        # M = num_slots tokens in flight; bucketed prefill runs at exactly
        # the bucket lengths — both warm the Planner cache before the
        # first trace (the traced steps then only hit it). Unbucketed
        # families keep the capacity-bound estimate for introspection.
        self.plans: Dict[str, Any] = {
            "decode": plan_mod.preplan_params(
                params, rc.policy, mode="decode", m=ecfg.num_slots,
                act_dtype=cfg.act_dtype),
        }
        if self._bucketed:
            per_bucket = plan_mod.preplan_prefill_buckets(
                params, rc.policy, buckets=self._buckets,
                act_dtype=cfg.act_dtype)
            for m, plans in per_bucket.items():
                self.plans[f"prefill@{m}"] = plans
        else:
            self.plans["prefill@cap"] = plan_mod.preplan_params(
                params, rc.policy, mode="prefill", m=ecfg.max_len,
                act_dtype=cfg.act_dtype)
        for phase, plans in self.plans.items():
            uniq: Dict[str, int] = {}
            rankings: Dict[str, int] = {}
            for _path, pl in plans:
                uniq[pl.describe()] = uniq.get(pl.describe(), 0) + 1
                rk = pl.describe_ranking()
                if rk:  # >1 eligible backend: show the predicted-time order
                    rankings[rk] = rankings.get(rk, 0) + 1
            for desc, count in sorted(uniq.items()):
                log.info("%s plan [%d leaves] %s", phase, count, desc)
            for rk, count in sorted(rankings.items()):
                log.info("%s ranking [%d leaves] %s", phase, count, rk)
        self.metrics_counters.eva_token_tiles = max(
            (pl.config_dict.get("token_tiles", 0)
             for _path, pl in self.plans["decode"]), default=0)

        self._jit_steps()
        # prefill extras (whisper frames / vision embeds), batched once
        self._extra_batch = {
            k: (v[None] if getattr(v, "ndim", 0) == 2 else v[:1])
            for k, v in self.extras.items()
        }

    # ------------------------------------------------------------ admission
    def _admission_error(self, request: GenerationRequest) -> Optional[str]:
        """Why this request can never be served on this engine (None when
        servable). Windowed caches wrap by design, so only the prompt must
        fit. Contiguous full caches also need room for every decode write
        (positions prompt_len .. prompt_len + max_new_tokens - 2) — past
        capacity the write would be dropped and the stream corrupted.
        PAGED full caches admit length-aware instead: memory is bounded
        by actual block consumption (free blocks at admission + growth /
        preemption during decode), so ``max_new_tokens`` is treated as a
        cap, not a reservation — the decode budget simply clamps to the
        remaining capacity at activation (finish_reason="length")."""
        if request.prompt_len > self.ecfg.max_len:
            return (f"prompt length {request.prompt_len} exceeds max_len "
                    f"{self.ecfg.max_len}")
        need = request.prompt_len + request.max_new_tokens - 1
        if self.window == 0 and need > self.ecfg.max_len:
            if self.paging is None:
                return (f"prompt_len + max_new_tokens - 1 = {need} exceeds "
                        f"the cache capacity max_len={self.ecfg.max_len}")
            need = self.ecfg.max_len  # paged: budget clamps at activation
        if self.paging is not None:
            peak = self.paging.blocks_for(need)
            if peak > self.paging.num_blocks:
                return (f"request needs {peak} KV blocks at peak, the pool "
                        f"only has {self.paging.num_blocks} "
                        f"(EngineConfig.num_blocks)")
        return None

    def submit(self, request: GenerationRequest) -> int:
        """Admission-checked submit. Unservable requests (over-long
        prompt, decode budget past cache capacity) and a full queue
        reject IMMEDIATELY with a clean terminal
        ``RequestOutput(finish_reason="rejected")`` — no prefill compute
        is spent and no deep shape error or silent cache clamp happens
        later."""
        if not isinstance(request, GenerationRequest):
            raise TypeError(
                f"submit() takes a GenerationRequest, got "
                f"{type(request).__name__}; use Engine.generate() for the "
                "prompt-list convenience path")
        if len(request.stop_set) > api.MAX_STOP_IDS:
            raise ValueError(
                f"request has {len(request.stop_set)} stop ids; the engine "
                f"supports at most {api.MAX_STOP_IDS} (api.MAX_STOP_IDS)")
        self.metrics_counters.submitted += 1
        if not self.healthy:
            return self._reject(
                request,
                f"engine unhealthy: circuit breaker tripped after "
                f"{self.breaker.consecutive} consecutive poisoned steps")
        why = self._admission_error(request)
        if why is not None:
            return self._reject(request, why)
        try:
            uid = self.sched.submit(request)
        except QueueFull as e:
            return self._reject(request, str(e))
        self._buffers[uid] = deque()
        return uid

    def _reject(self, request: GenerationRequest, why: str) -> int:
        uid = self.sched.next_uid()
        log.info("request %d rejected: %s", uid, why)
        self.metrics_counters.rejected += 1
        out = RequestOutput(uid=uid, tokens=(), finish_reason="rejected")
        self._outputs[uid] = out
        # the terminal event is delivered (and buffered) by the next step()
        self._buffers[uid] = deque()
        self._pending.append(StreamEvent(uid=uid, index=-1, token=None,
                                         finish_reason="rejected"))
        self._retain(uid)
        return uid

    def _retain(self, uid: int) -> None:
        """FIFO-bound the finished outputs + undrained event buffers: a
        long-running submit()/step() server that never reads them must
        not grow memory linearly in total requests served."""
        self._retired.append(uid)
        while len(self._retired) > self.ecfg.max_retained:
            old = self._retired.popleft()
            self._outputs.pop(old, None)
            self._buffers.pop(old, None)

    # ------------------------------------------------------------- prefill
    def _encode_cache(self, cache: Any) -> Any:
        """Bridge an fp prefill cache into the engine's compressed KV
        layout (kv_bits < 16) — the EXPLICIT quantization step before
        slot insertion / block writes; ``_insert_slot``'s astype would
        truncate rather than quantize. No-op at kv_bits=16. Runs inside
        the jitted prefill step."""
        if self.kvq is not None:
            return encode_prefill_cache(cache, self._kv_cb, self.kvq)
        if self.kv_int8:
            return quantize_prefill_cache_int8(cache)
        return cache

    def _prefill_impl(self, params, tokens, true_len, key, temperature,
                      top_k, top_p, greedy, poison, extras, *, rc):
        """Jitted per-request prefill: forward at the (bucket-)padded
        length, sample the first token from the logits at the TRUE last
        position, and convert the cache to decode capacity — all on
        device, one trace per bucket.

        ``poison`` is the fault-injection scalar (0.0 in production —
        adding it is a no-op): a scripted NaN/Inf rides into the logits
        here so the numerics quarantine is testable. ``bad`` (any
        non-finite in the sampled row) reads back with the token —
        no extra device sync."""
        self.trace_counts["prefill"] += 1
        batch = {"tokens": tokens}
        batch.update(extras)
        logits, cache = self.model.prefill(params, batch, rc)
        tok, bad, lp, new_key = self._sample_first(
            logits, true_len, key, temperature, top_k, top_p, greedy, poison)
        cache = self._encode_cache(cache)
        cache = pad_prefill_cache(cache, self.ecfg.max_len,
                                  window=self.window, true_len=true_len)
        return tok, bad, lp, new_key, cache

    def _sample_first(self, logits, true_len, key, temperature, top_k,
                      top_p, greedy, poison):
        """The prefill epilogue: sample the first token from the logits
        at the TRUE last position (padded ids sliced off, ``poison``
        added). Returns (token, bad, logprob, new_key)."""
        with jax.named_scope("lm_head"):
            last = jax.lax.dynamic_slice_in_dim(
                logits[0], true_len - 1, 1, axis=0)[0]
            last = last[: self.model.cfg.vocab_size][None]       # (1, V)
        with jax.named_scope("sample"):
            last = last + poison
            bad = ~jnp.all(jnp.isfinite(last.astype(jnp.float32)))
            tok, new_key = api.sample_tokens(
                last, key[None], temperature[None], top_k[None],
                top_p[None], greedy[None])
            lp = api.token_logprobs(last, tok)[0]
        return tok[0], bad, lp, new_key[0]

    def _paged_prefill_impl(self, params, caches, tokens, true_len, slot,
                            bt_row, key, temperature, top_k, top_p, greedy,
                            poison, extras, *, rc):
        """Jitted paged prefill (first/only chunk): same forward + sample
        as ``_prefill_impl``, but the fresh cache commits by scattering
        through ``slot``'s block-table row into the shared arenas
        (paging.write_prefill_into_blocks) instead of a contiguous slot
        insert. ``slot``/``bt_row``/``true_len`` are traced — one trace
        per bucket, shared by every slot."""
        self.trace_counts["prefill"] += 1
        batch = {"tokens": tokens}
        batch.update(extras)
        logits, fresh = self.model.prefill(params, batch, rc)
        tok, bad, lp, new_key = self._sample_first(
            logits, true_len, key, temperature, top_k, top_p, greedy, poison)
        caches = paging.write_prefill_into_blocks(
            caches, self._encode_cache(fresh), slot, bt_row, true_len,
            self.paging, window=self.window)
        return tok, bad, lp, new_key, caches

    def _prefill_chunk_impl(self, params, caches, tokens, hist, true_len,
                            slot, bt_row, key, temperature, top_k, top_p,
                            greedy, poison, extras, *, rc):
        """Jitted chunked-prefill CONTINUATION (``hist`` committed
        positions already in the slot's blocks): run model.forward in
        prefill mode over a single-slot view of the paged cache at
        absolute positions ``hist + [0, S)``; attention_fwd's paged
        continuation branch scatters the chunk's KV and attends over the
        gathered history. The sampled token only matters on the FINAL
        chunk (the engine discards it otherwise)."""
        self.trace_counts["prefill_chunk"] += 1
        S = tokens.shape[1]
        view = paging.slot_view(caches, slot, bt_row, hist, true_len)
        batch = {"tokens": tokens,
                 "positions": hist + jnp.arange(S, dtype=jnp.int32)[None]}
        batch.update(extras)
        logits, new_view = self.model.forward(params, batch, rc, caches=view)
        tok, bad, lp, new_key = self._sample_first(
            logits, true_len, key, temperature, top_k, top_p, greedy, poison)
        caches = paging.merge_slot(caches, new_view, slot)
        return tok, bad, lp, new_key, caches

    def _prefill_target(self, tr: TrackedRequest) -> int:
        """Positions to prefill before ``slot`` can (re)join decode: the
        prompt, plus the already-generated tokens minus one for a
        preempted request (the last generated token becomes the resume
        decode input, not cache history)."""
        if tr.preempted and tr.generated:
            return tr.prompt_len + len(tr.generated) - 1
        return tr.prompt_len

    def _chunk_len(self, tr: TrackedRequest) -> int:
        """Prompt positions the next prefill step of ``tr`` runs: the
        whole target, or (chunked prefill) the next chunk of it."""
        target = self._prefill_target(tr)
        if self._chunked and target > int(self.ecfg.prefill_chunk):
            return min(int(self.ecfg.prefill_chunk), target - tr.prefill_pos)
        return target

    def _prefill_tokens(self, tr: TrackedRequest) -> np.ndarray:
        seq = np.asarray(tr.request.prompt, np.int32)
        if tr.preempted and len(tr.generated) > 1:
            seq = np.concatenate(
                [seq, np.asarray(tr.generated[:-1], np.int32)])
        return seq

    def _prefill_one(self, slot: int, tr: TrackedRequest
                     ) -> "tuple[Optional[int], bool, bool]":
        """Advance the request in ``slot`` by one prefill step — the
        whole prompt in one call, or (chunked prefill) the next
        ``prefill_chunk``-sized piece. Returns ``(token, bad, final)``:

        * ``final=False`` — a non-final chunk committed; the slot stays
          occupied-but-inactive and the next tick continues.
        * ``bad=True`` — the sampled logits row failed the finite check:
          the slot is NOT activated and the caller quarantines.
        * ``token`` — the first sampled token on the final step, or None
          for non-final chunks and for preempted-request resumes (their
          re-sampled token is discarded; decode state restores from the
          eviction record instead, keeping the stream token-identical)."""
        if self.fault_plan is not None:
            spec = self.fault_plan.poll("prefill", self._tick, tr.uid)
            if spec is not None:
                raise InjectedFault("prefill", self._tick, tr.uid)
        poison = 0.0
        if self.fault_plan is not None:
            spec = self.fault_plan.poll("poison", self._tick, tr.uid)
            if spec is not None:
                poison = float("nan") if spec.mode == "nan" else float("inf")
        req = tr.request
        sp = req.sampling
        target = self._prefill_target(tr)
        pos0 = tr.prefill_pos
        c = self._chunk_len(tr)
        final = pos0 + c >= target
        chunk = self._prefill_tokens(tr)[pos0: pos0 + c]
        if self._bucketed:
            bucket = api.bucket_for(c, self._buckets)
            if bucket > c:
                # edge-pad: the value is causally masked for real rows,
                # and repeating the last token keeps stub models (that
                # read tokens[:, -1]) meaningful in tests
                chunk = np.pad(chunk, (0, bucket - c), mode="edge")
        key = jax.random.PRNGKey(sp.seed)
        sample_args = (
            jnp.asarray(key),
            jnp.asarray(sp.temperature, jnp.float32),
            jnp.asarray(sp.top_k, jnp.int32),
            jnp.asarray(sp.top_p, jnp.float32),
            jnp.asarray(sp.greedy),
            jnp.asarray(poison, jnp.float32), self._extra_batch,
        )
        toks_dev = jnp.asarray(chunk[None], jnp.int32)
        true_c = jnp.asarray(c, jnp.int32)
        if self.paging is None:
            tok, bad, lp, new_key, cache = self._prefill_fn(
                self.params, toks_dev, true_c, *sample_args)
        elif pos0 == 0:
            tok, bad, lp, new_key, new_caches = self._paged_prefill_fn(
                self.params, self.caches, toks_dev, true_c,
                jnp.asarray(slot, jnp.int32), jnp.asarray(self.tables[slot]),
                *sample_args)
        else:
            tok, bad, lp, new_key, new_caches = self._chunk_fn(
                self.params, self.caches, toks_dev,
                jnp.asarray(pos0, jnp.int32), true_c,
                jnp.asarray(slot, jnp.int32), jnp.asarray(self.tables[slot]),
                *sample_args)
            self.metrics_counters.prefill_chunks += 1
        tok, bad = int(tok), bool(bad)
        if bad:
            # quarantine: never activate the slot, never stream the
            # garbage token — the caller finishes with "error" (which
            # also recycles any blocks committed by earlier chunks)
            return tok, True, final
        if self.paging is None:
            self.caches = _insert_slot(self.caches, cache, slot)
        else:
            self.caches = new_caches
        tr.prefill_pos = pos0 + c
        if not final:
            return None, False, False

        # per-slot decode state for this request
        stop = sorted(req.stop_set)
        self.positions[slot] = target
        self.temperature[slot] = sp.temperature
        self.top_k[slot] = sp.top_k
        self.top_p[slot] = sp.top_p
        self.greedy[slot] = sp.greedy
        self.stop_ids[slot, :] = -1
        self.stop_ids[slot, : len(stop)] = stop
        self.active[slot] = True
        self._tables_dirty = self.paging is not None
        # paged full caches admit length-aware (_admission_error): the
        # decode budget clamps to the capacity left past the prompt
        budget = req.max_new_tokens
        if self.paging is not None and self.window == 0:
            budget = min(budget, self.ecfg.max_len - target + 1)
        if tr.preempted and tr.generated:
            # preemption resume: the re-sampled token is a duplicate of
            # history — restore the decode state saved at eviction so
            # the continuation is token-identical to an uninterrupted run
            self.last_token[slot] = tr.generated[-1]
            self.rng_keys[slot] = np.asarray(tr.resume_key)
            self.remaining[slot] = tr.resume_remaining
            tr.preempted = False
            self._prime_spec(slot, tr)
            return None, False, True
        tr.generated.append(tok)
        if sp.logprobs:
            tr.logprobs.append(float(lp))
        self.last_token[slot] = tok
        self.rng_keys[slot] = np.asarray(new_key)
        self.remaining[slot] = budget - 1
        self._prime_spec(slot, tr)
        return tok, False, True

    def _prime_spec(self, slot: int, tr: TrackedRequest) -> None:
        """(Re)prime the slot's speculative state at activation: the
        opt-in flag and the successor table, seeded from the full token
        history (prompt ++ generated — including the token prefill just
        sampled, so the last-prompt-token transition is known)."""
        if not self.spec_k:
            return
        self.spec_on[slot] = bool(tr.request.speculate)
        speculative.prime_successors(
            self.succ, slot,
            np.concatenate([np.asarray(tr.request.prompt, np.int32),
                            np.asarray(tr.generated, np.int32)]))

    # ------------------------------------------------------- paged KV blocks
    def _update_kv_gauges(self) -> None:
        m = self.metrics_counters
        used = self.pool.used_count
        m.blocks_in_use = used
        m.blocks_free = self.pool.free_count
        m.kv_bytes_in_use = used * self.paging.bytes_per_block
        m.peak_blocks_in_use = max(m.peak_blocks_in_use, used)
        m.peak_kv_bytes_in_use = max(m.peak_kv_bytes_in_use,
                                     m.kv_bytes_in_use)

    def _alloc_blocks(self, slot: int, n: int) -> bool:
        """Grow ``slot`` by ``n`` pool blocks (all-or-nothing)."""
        if n <= 0:
            return True
        blks = self.pool.alloc(n)
        if blks is None:
            return False
        start = len(self._owned[slot])
        self._owned[slot].extend(blks)
        self.tables[slot, start: start + len(blks)] = blks
        self._tables_dirty = True
        self._update_kv_gauges()
        return True

    def _free_blocks(self, slot: int) -> None:
        """Recycle every block ``slot`` owns and sentinel its table row."""
        if self._owned[slot]:
            self.pool.free(self._owned[slot])
            self._owned[slot] = []
        self.tables[slot, :] = self.paging.sentinel
        self._tables_dirty = True
        self._update_kv_gauges()

    def _sync_tables(self) -> None:
        """Push the host block tables to the device cache mirror before a
        batched decode step. Non-ACTIVE rows (free slots AND mid-prefill
        slots, which own blocks but must not receive interleaved decode
        writes) are masked to the sentinel, so the one traced decode step
        serves any live/dead/mid-prefill mix."""
        if self.paging is None or not self._tables_dirty:
            return
        masked = np.where(self.active[:, None], self.tables,
                          self.paging.sentinel).astype(np.int32)
        self.caches = paging.set_block_tables(self.caches, masked)
        self._tables_dirty = False

    def _preempt_victim(self) -> Optional[int]:
        """The youngest (highest-uid) active slot whose resume prefill
        still fits ``max_len`` — preempting it frees blocks NOW and the
        request remains servable later. None when nothing qualifies."""
        best = None
        for b in np.nonzero(self.active)[0]:
            tr = self.sched.slots[int(b)]
            resume = tr.prompt_len + max(0, len(tr.generated) - 1)
            if resume > self.ecfg.max_len:
                continue
            if best is None or tr.uid > self.sched.slots[best].uid:
                best = int(b)
        return best

    def _preempt(self, slot: int) -> None:
        """Evict ``slot`` mid-decode: save its decode state on the
        tracked request, recycle its blocks, and push it back to the
        QUEUE HEAD. It resumes by re-prefilling prompt ++ generated[:-1]
        and restoring the saved PRNG key/budget — token-identical to an
        uninterrupted run, just later."""
        tr = self.sched.slots[slot]
        tr.resume_key = np.array(self.rng_keys[slot], copy=True)
        tr.resume_remaining = int(self.remaining[slot])
        tr.preempted = True
        tr.prefill_pos = 0
        self.active[slot] = False
        self.sched.slots[slot] = None
        self.sched.queue.appendleft(tr)
        self._free_blocks(slot)
        self.metrics_counters.preemptions += 1
        log.info("request %d preempted out of slot %d (out of KV blocks); "
                 "re-queued at head with %d tokens generated",
                 tr.uid, slot, len(tr.generated))

    def _grow_decode_blocks(self) -> None:
        """Before a batched decode step, make sure every active slot owns
        blocks for the position(s) it is about to write — the next token
        plus, when the slot speculates, its K draft positions (draft KV
        past the slot's capacity drops harmlessly, so the lookahead caps
        at max_len / the table width). An exhausted pool preempts the
        youngest active request (possibly the one that needs the block)
        until the write fits."""
        for b in np.nonzero(self.active)[0]:
            b = int(b)
            k_ahead = self.spec_k if (self.spec_k and self.spec_on[b]) else 0
            while self.active[b]:
                want = min(int(self.positions[b]) + 1 + k_ahead,
                           self.ecfg.max_len)
                need = min(self.paging.blocks_for(want),
                           self.paging.blocks_per_slot)
                short = need - len(self._owned[b])
                if short <= 0 or self._alloc_blocks(b, short):
                    break
                victim = self._preempt_victim()
                if victim is None:  # pragma: no cover - defensive
                    raise RuntimeError(
                        "out of KV blocks with no preemptible request; "
                        "raise EngineConfig.num_blocks")
                self._preempt(victim)

    # -------------------------------------------------------------- decode
    def _decode_impl(self, params, caches, tokens, positions, keys,
                     temperature, top_k, top_p, greedy, stop_ids, remaining,
                     active, poison, *, rc):
        """Jitted batched decode step: model decode + in-jit per-slot
        sampling and stopping (serve/api.sample_and_stop). Every
        per-request knob is a fixed-shape device array -> ONE trace.

        ``poison`` (B,) is the fault-injection lane: all-zero in
        production (adding it is a no-op, and it is DATA — injecting a
        fault never retraces). ``bad`` flags lanes whose logits failed
        the all-finite check; it rides the existing readback, costing no
        extra device sync."""
        self.trace_counts["decode"] += 1
        stats: Dict[str, Any] = {}
        kw = {"stats": stats} if self._moe_layers else {}
        logits, new_caches = self.model.decode(
            params, tokens[:, None], positions[:, None], caches, rc, **kw)
        # named scopes (with models/common.py's kv_write, attend and
        # lm_head) let a profiler trace attribute device time by part
        with jax.named_scope("lm_head"):
            logits = logits[:, 0, : self.model.cfg.vocab_size]
        with jax.named_scope("sample"):
            logits = logits + poison[:, None]
            tok, done, bad, new_keys = api.sample_and_stop(
                logits, keys=keys, temperature=temperature, top_k=top_k,
                top_p=top_p, greedy=greedy, stop_ids=stop_ids,
                remaining=remaining, active=active)
            lp = api.token_logprobs(logits, tok)
        if self._moe_layers:
            # the step's expert visits ride the token readback: one int32
            # past the last slot's token
            tok = jnp.concatenate(
                [tok, stats["moe_expert_visits"].reshape(1).astype(tok.dtype)])
        return tok, done, bad, lp, new_keys, new_caches

    def _spec_decode_impl(self, params, caches, tokens, positions, succ,
                          keys, temperature, top_k, top_p, greedy, stop_ids,
                          remaining, active, spec_on, poison, *, rc):
        """Jitted SPECULATIVE batched decode step (speculate_k > 0):
        serve/speculative.spec_decode_step — K drafts from the per-slot
        successor tables verified in one model call, the acceptance rule
        replaying the exact sample_and_stop math per logit row. Same
        one-trace discipline as ``_decode_impl``: per-slot knobs, drafts
        and acceptance counts are all data."""
        self.trace_counts["decode"] += 1
        return speculative.spec_decode_step(
            self.model, params, caches, tokens, positions, succ, keys,
            temperature, top_k, top_p, greedy, stop_ids, remaining, active,
            spec_on, poison, rc=rc, k=self.spec_k)

    def _jit_steps(self) -> None:
        """Jit the serving steps, at construction and at backend-quarantine
        re-jit. The batched decode step is the speculative multi-token
        body or the classic single-token one, chosen here; the step shape
        never flips mid-serve."""
        impl = self._spec_decode_impl if self.spec_k else self._decode_impl
        self._decode_fn = _named_jit(impl, rc=self.rc.replace(mode="decode"))
        prefill_rc = self.rc.replace(mode="prefill")
        self._prefill_fn = _named_jit(self._prefill_impl, rc=prefill_rc)
        if self.ecfg.paged:
            self._paged_prefill_fn = _named_jit(self._paged_prefill_impl,
                                                rc=prefill_rc)
            self._chunk_fn = _named_jit(self._prefill_chunk_impl,
                                        rc=prefill_rc)

    def _prefill_step_events(self, slot: int,
                             events: List[StreamEvent]) -> bool:
        """Run one prefill step for ``slot`` (a whole prompt, one chunk,
        or a preemption-resume re-prefill) and translate the outcome into
        events + metrics. Returns True when the step poisoned.

        Counter discipline (keeps the EngineMetrics invariants exact):
        ``prefills`` ticks when a step emits a first token or poisons;
        non-final chunks tick ``prefill_chunks`` only, and a good
        preemption resume ticks neither (its token was already counted
        before eviction — ``preemptions`` observes the event)."""
        m = self.metrics_counters
        tr = self.sched.slots[slot]
        now = time.perf_counter()
        pos0 = tr.prefill_pos
        with jax.profiler.TraceAnnotation("engine.prefill", uid=tr.uid,
                                          tokens=self._chunk_len(tr)):
            tok, bad, final = self._prefill_one(slot, tr)
        dt = time.perf_counter() - now
        tr.prefill_s += dt
        m.prefill_s += dt
        m.prefill_prompt_tokens += tr.prefill_pos - pos0
        if bad:
            # numerics quarantine straight out of prefill: the garbage
            # token is suppressed, the request errors (and any blocks
            # committed by earlier chunks recycle via _finish_slot)
            m.prefills += 1
            m.poisoned_slot_steps += 1
            events.append(StreamEvent(tr.uid, 0, None, "error"))
            self._finish_slot(slot, "error")
            return True
        if not final:
            return False
        tr.decode_t0 = time.perf_counter()
        if tok is None:
            # preemption resume rejoins decode silently: its next token
            # continues the stream exactly where eviction cut it
            return False
        m.prefills += 1
        m.tokens_generated += 1
        # stop-set token straight out of prefill / effective budget of
        # one (max_new_tokens == 1, or a paged length-aware admission
        # whose clamped budget leaves no decode room): retire before the
        # request joins a decode batch at all
        reason = None
        if tok in tr.stop_set:
            reason = "stop"
        elif int(self.remaining[slot]) <= 0:
            reason = "length"
        lp = tr.logprobs[-1] if tr.request.sampling.logprobs else None
        events.append(StreamEvent(tr.uid, 0, tok, reason, logprob=lp))
        if reason is not None:
            self._finish_slot(slot, reason)
        return False

    # ---------------------------------------------------------------- step
    def _timeout_sweep(self) -> List[StreamEvent]:
        """Enforce per-request ``deadline_s`` and the engine queue TTL
        between steps: expired QUEUED requests time out before wasting a
        prefill; expired ACTIVE requests free their slot before another
        batched decode step is spent on them."""
        m = self.metrics_counters
        events: List[StreamEvent] = []
        now = time.perf_counter()
        ttl = self.ecfg.queue_ttl_s

        def dead_in_queue(tr: TrackedRequest) -> bool:
            return tr.expired(now) or (
                ttl is not None and now - tr.submit_t > ttl)

        for tr in self.sched.prune_queue(dead_in_queue):
            m.count_finish("timeout")
            # a preempted request waiting to resume may already hold
            # streamed tokens — the terminal output must carry them
            self._outputs[tr.uid] = RequestOutput(
                uid=tr.uid, tokens=tuple(tr.generated),
                logprobs=tuple(tr.logprobs),
                finish_reason="timeout", queue_wait_s=now - tr.submit_t)
            events.append(StreamEvent(tr.uid, -1, None, "timeout"))
            self._retain(tr.uid)
        for b in list(self.sched.active_slots()):
            tr = self.sched.slots[b]
            if tr.expired(now):
                events.append(
                    StreamEvent(tr.uid, len(tr.generated), None, "timeout"))
                self._finish_slot(b, "timeout")
        return events

    def step(self) -> List[StreamEvent]:
        """One engine tick: deadline/TTL sweep, admit+prefill queued
        requests, one batched decode step over active slots, retire
        finished requests. Returns the tick's StreamEvents (prefill
        tokens, decode tokens, pending rejections/timeouts).

        A request retires in the SAME step its stopping condition is met
        (stop-set token emitted / budget exhausted) — including straight
        out of prefill — so it never occupies a slot for an extra batched
        decode step. Free slots are masked out of the decode inputs
        (token 0 at position 0) instead of replaying stale state.

        Failure semantics: a lane whose logits fail the in-jit finite
        check is QUARANTINED — its garbage token is never streamed, the
        request finishes ``finish_reason="error"``, and the rest of the
        batch streams on untouched (poison is additive per-lane data, so
        bystander lanes are bit-identical to a fault-free run). ``k``
        consecutive poisoned steps trip the circuit breaker: pending
        requests are rejected and new submits refuse. A scripted
        ``backend`` fault quarantines the planned backend and re-plans
        (core/plan.py re-ranks; the next-cheapest candidate takes over).
        Exceptions out of ``step()`` (scripted prefill/decode/sample
        faults, real crashes) leave this tick's events undelivered —
        ``snapshot()``/``restore()`` (serve/resilience.py
        ``serve_with_restarts``) is the recovery path that resumes
        token-identically without double-delivering.

        Each tick writes host spans into a running profiler trace
        (``engine.step`` and, inside it, ``engine.admit``,
        ``engine.prefill`` and ``engine.decode.{upload, dispatch, wait,
        readback, emit}``); with no profiler running they are inactive."""
        with jax.profiler.StepTraceAnnotation("engine.step",
                                              step_num=self._tick):
            return self._step()

    def _step(self) -> List[StreamEvent]:
        m = self.metrics_counters
        tick = self._tick
        fp = self.fault_plan
        events: List[StreamEvent] = list(self._pending)
        self._pending.clear()

        events.extend(self._timeout_sweep())

        if fp is not None:
            backend_spec = fp.poll("backend", tick)
            if backend_spec is not None:
                self._fail_backend(backend_spec.backend)

        any_poisoned = False
        did_work = False

        # advance mid-prefill (chunked) slots one chunk each before
        # admitting more work: occupied-but-inactive marks mid-prefill
        for slot in self.sched.active_slots():
            if self.active[slot]:
                continue
            did_work = True
            any_poisoned |= self._prefill_step_events(slot, events)

        # paged admission reserves pool blocks for each candidate's full
        # prefill target; Scheduler.admit stops at the first refusal
        planned_free = self.pool.free_count if self.paging is not None else 0

        def can_admit(tr: TrackedRequest) -> bool:
            nonlocal planned_free
            if self.paging is None:
                return True
            need = self.paging.blocks_for(self._prefill_target(tr))
            if need > planned_free:
                return False
            planned_free -= need
            return True

        with jax.profiler.TraceAnnotation("engine.admit"):
            for slot in self.sched.admit(can_admit):
                tr = self.sched.slots[slot]
                did_work = True
                now = time.perf_counter()
                tr.queue_wait_s = now - tr.submit_t
                m.admitted += 1
                m.queue_wait_s += tr.queue_wait_s
                if self.paging is not None:
                    need = self.paging.blocks_for(self._prefill_target(tr))
                    ok = self._alloc_blocks(slot, need)
                    assert ok, ("can_admit reserved blocks the pool cannot "
                                "supply")
                any_poisoned |= self._prefill_step_events(slot, events)

        # every active slot must own blocks for the position this decode
        # step writes; an exhausted pool preempts the youngest request
        if self.paging is not None and np.any(self.active):
            self._grow_decode_blocks()

        active_idx = np.nonzero(self.active)[0]
        if active_idx.size:
            did_work = True
            self._sync_tables()
            if fp is not None and fp.poll("decode", tick) is not None:
                raise InjectedFault("decode", tick)
            poison = np.zeros((self.ecfg.num_slots,), np.float32)
            if fp is not None:
                for b in active_idx:
                    spec = fp.poll("poison", tick, self.sched.slots[b].uid)
                    if spec is not None:
                        poison[b] = (np.nan if spec.mode == "nan"
                                     else np.inf)
            t0 = time.perf_counter()
            self.watchdog.start_step()
            with jax.profiler.TraceAnnotation("engine.decode.upload"):
                dev_args = [
                    self.params, self.caches,
                    jnp.asarray(np.where(self.active, self.last_token, 0)),
                    jnp.asarray(np.where(self.active, self.positions, 0)),
                ]
                if self.spec_k:
                    dev_args.append(jnp.asarray(self.succ))
                dev_args += [
                    jnp.asarray(self.rng_keys),
                    jnp.asarray(self.temperature),
                    jnp.asarray(self.top_k),
                    jnp.asarray(self.top_p),
                    jnp.asarray(self.greedy),
                    jnp.asarray(self.stop_ids),
                    jnp.asarray(self.remaining),
                    jnp.asarray(self.active),
                ]
                if self.spec_k:
                    dev_args.append(jnp.asarray(self.spec_on))
                dev_args.append(jnp.asarray(poison))
            with jax.profiler.TraceAnnotation("engine.decode.dispatch"):
                *outs, self.caches = self._decode_fn(*dev_args)
            # the one wait for the device: the reads below find it done
            with jax.profiler.TraceAnnotation("engine.decode.wait"):
                jax.block_until_ready(outs)
            with jax.profiler.TraceAnnotation("engine.decode.readback"):
                if self.spec_k:
                    toks, lps, e_cnt, acc, done, bad, new_keys, new_succ = outs
                    toks = np.asarray(toks)                 # (B, K+1)
                    lps = np.asarray(lps)
                    e_cnt = np.asarray(e_cnt).astype(np.int32)
                    acc = np.asarray(acc)
                    self.succ = np.array(new_succ)
                else:
                    tok, done, bad, lp, new_keys = outs
                    toks = np.asarray(tok)
                    if self._moe_layers:
                        visits, toks = int(toks[-1]), toks[:-1]
                    toks = toks[:, None]                    # (B, 1)
                    lps = np.asarray(lp)[:, None]
                    acc = None
                done = np.asarray(done)
                bad = np.asarray(bad)
                # np.array (copy) — np.asarray of a device array is
                # read-only, and the next prefill writes per-slot keys in
                # place
                keys = np.array(new_keys)
            if not self.spec_k:
                e_cnt = (self.active & ~bad).astype(np.int32)
            rep = self.watchdog.end_step()
            if rep.is_straggler:
                m.straggler_steps += 1
            if fp is not None and fp.poll("sample", tick) is not None:
                # the classic torn-state crash: the device step already
                # ran, host bookkeeping has not — only a snapshot
                # restore recovers consistently
                raise InjectedFault("sample", tick)
            self.rng_keys = keys
            with jax.profiler.TraceAnnotation("engine.decode.emit"):
                n_bad = int(np.count_nonzero(bad))
                n_emit = int(e_cnt.sum())
                m.decode_steps += 1
                m.decode_slot_steps += int(active_idx.size)
                m.decode_s += time.perf_counter() - t0
                m.tokens_generated += n_emit
                m.extra_decode_tokens += (n_emit
                                          - (int(active_idx.size) - n_bad))
                m.poisoned_slot_steps += n_bad
                if self._moe_layers:
                    # every row of the step routes, active or not
                    m.moe_routed_rows += (self.ecfg.num_slots
                                          * self.model.cfg.top_k
                                          * self._moe_layers)
                    m.moe_expert_visits += visits
                if self.spec_k:
                    spec_lanes = self.active & ~bad & self.spec_on
                    n_spec = int(np.count_nonzero(spec_lanes))
                    n_acc = int(acc[spec_lanes].sum())
                    m.drafted_tokens += self.spec_k * n_spec
                    m.accepted_draft_tokens += n_acc
                    m.rejected_draft_tokens += self.spec_k * n_spec - n_acc
                any_poisoned = any_poisoned or n_bad > 0

                # only healthy lanes advance and emit; a poisoned lane's
                # token never reaches its stream. e_cnt is the per-lane
                # emission count (always 1 for non-speculative steps, up to
                # K+1 for accepted draft windows) — already zero for
                # inactive/bad lanes
                self.positions += e_cnt
                self.remaining -= e_cnt
                last_idx = np.maximum(e_cnt - 1, 0)
                new_last = toks[np.arange(toks.shape[0]), last_idx]
                self.last_token = np.where(e_cnt > 0, new_last,
                                           self.last_token)
                for b in active_idx:
                    tr = self.sched.slots[b]
                    if bad[b]:
                        events.append(StreamEvent(tr.uid, len(tr.generated),
                                                  None, "error"))
                        self._finish_slot(int(b), "error")
                        continue
                    n = int(e_cnt[b])
                    if n == 0:  # pragma: no cover - defensive
                        continue
                    want_lp = tr.request.sampling.logprobs
                    reason = None
                    if done[b]:
                        last_t = int(toks[b, n - 1])
                        reason = "stop" if last_t in tr.stop_set else "length"
                    base = len(tr.generated)
                    for j in range(n):
                        t = int(toks[b, j])
                        tr.generated.append(t)
                        lpj = None
                        if want_lp:
                            lpj = float(lps[b, j])
                            tr.logprobs.append(lpj)
                        events.append(StreamEvent(
                            tr.uid, base + j, t,
                            reason if j == n - 1 else None, logprob=lpj))
                    if reason is not None:
                        self._finish_slot(int(b), reason)

        if did_work:
            was_tripped = self.breaker.tripped
            if self.breaker.record(any_poisoned) and not was_tripped:
                events.extend(self._reject_pending_unhealthy())

        for ev in events:
            buf = self._buffers.get(ev.uid)
            if buf is not None:
                buf.append(ev)
        self._tick += 1
        return events

    def _reject_pending_unhealthy(self) -> List[StreamEvent]:
        """Circuit breaker just tripped: reject every queued request
        cleanly instead of leaving it waiting on an engine that will
        never serve it (in-flight slots keep draining)."""
        m = self.metrics_counters
        events: List[StreamEvent] = []
        for tr in self.sched.drain_queue():
            m.rejected += 1
            log.error("request %d rejected: engine unhealthy (circuit "
                      "breaker tripped)", tr.uid)
            self._outputs[tr.uid] = RequestOutput(
                uid=tr.uid, tokens=(), finish_reason="rejected")
            events.append(StreamEvent(tr.uid, -1, None, "rejected"))
            self._retain(tr.uid)
        return events

    def _fail_backend(self, name: Optional[str]) -> None:
        """A scripted backend fault fired: quarantine the named backend
        (default: the decode plan's chosen one) in the default planner
        and re-jit the stepped functions — the retrace re-enters
        core/plan.py's cost ranking, which now skips the quarantined
        backend and bakes the next-cheapest candidate in."""
        if name is None:
            name = self.plans["decode"][0][1].backend
        planner = plan_mod.default_planner()
        planner.record_backend_failure(name)
        self.metrics_counters.backend_fallbacks += 1
        log.warning("backend %r failed and was quarantined; re-planning "
                    "decode/prefill on the remaining candidates", name)
        self._jit_steps()
        self.plans["decode"] = plan_mod.preplan_params(
            self.params, self.rc.policy, mode="decode",
            m=self.ecfg.num_slots, act_dtype=self.model.cfg.act_dtype)

    def _finish_slot(self, slot: int, reason: str) -> TrackedRequest:
        tr = self.sched.finish(slot)
        self.active[slot] = False
        if self.paging is not None:
            self._free_blocks(slot)
        # a request that crossed a snapshot restore mid-flight finishes
        # with an annotated reason: the tokens are token-identical, the
        # client can still SEE that delivery crossed a failover
        if tr.restored and reason in ("stop", "length"):
            reason = f"{reason}-after-restore"
        self.metrics_counters.count_finish(reason)
        decode_s = (time.perf_counter() - tr.decode_t0
                    if len(tr.generated) > 1 else 0.0)
        self._outputs[tr.uid] = RequestOutput(
            uid=tr.uid, tokens=tuple(tr.generated),
            logprobs=tuple(tr.logprobs), finish_reason=reason,
            queue_wait_s=tr.queue_wait_s, prefill_s=tr.prefill_s,
            decode_s=decode_s)
        self._retain(tr.uid)
        return tr

    # ------------------------------------------------------------ streaming
    @property
    def idle(self) -> bool:
        return self.sched.idle and not self._pending

    @property
    def healthy(self) -> bool:
        """False once the numerics circuit breaker tripped: submits are
        refused and pending requests were rejected (the in-flight slots
        still drain)."""
        return not self.breaker.tripped

    def output(self, uid: int) -> Optional[RequestOutput]:
        """The terminal RequestOutput once ``uid`` finished (else None)."""
        return self._outputs.get(uid)

    def evicted(self, uid: int) -> bool:
        """True when ``uid`` WAS a real request whose retained output +
        event buffer have been FIFO-evicted past ``max_retained`` —
        distinct from a uid that was never issued (uids are dense and
        1-based, so the scheduler counter bounds the issued set)."""
        if not 1 <= uid <= self.sched.last_uid:
            return False
        if uid in self._outputs or uid in self._buffers:
            return False
        if any(tr.uid == uid for tr in self.sched.queue):
            return False
        if any(tr is not None and tr.uid == uid for tr in self.sched.slots):
            return False
        return True

    def stream(self, uid: int) -> Iterator[StreamEvent]:
        """Iterate ``uid``'s StreamEvents, driving ``step()`` as needed;
        ends after yielding the terminal event. Events for OTHER requests
        produced along the way stay buffered for their own streams.

        Raises ``RequestEvicted`` (a KeyError subclass) when the uid was
        served but its buffer was FIFO-evicted past ``max_retained``,
        plain ``KeyError`` when the uid was never issued or was already
        drained — callers can tell "read it sooner / raise max_retained"
        apart from "that uid never existed". A wall-clock stall guard
        (``EngineConfig.stream_stall_s``) bounds how long the stream
        drives an engine that makes no progress for this uid."""
        buf = self._buffers.get(uid)
        if buf is None:
            if self.evicted(uid):
                raise RequestEvicted(
                    f"request {uid} was served but its events were evicted "
                    f"past max_retained={self.ecfg.max_retained}; stream "
                    "promptly or raise EngineConfig.max_retained")
            if 1 <= uid <= self.sched.last_uid:
                raise KeyError(
                    f"request {uid} already streamed to completion")
            raise KeyError(f"unknown request uid {uid}")
        t_last = time.perf_counter()
        while True:
            while buf:
                ev = buf.popleft()
                t_last = time.perf_counter()
                yield ev
                if ev.done:
                    self._buffers.pop(uid, None)
                    return
            if self.idle:  # pragma: no cover - defensive
                raise RuntimeError(
                    f"engine idle but request {uid} never finished")
            self.step()
            if not buf and (time.perf_counter() - t_last
                            > self.ecfg.stream_stall_s):
                raise RuntimeError(
                    f"stream({uid}) stalled: no event for "
                    f"{self.ecfg.stream_stall_s:.1f}s "
                    f"(EngineConfig.stream_stall_s)")

    # ----------------------------------------------------- snapshot/restore
    _SLOT_STATE = ("positions", "last_token", "rng_keys", "temperature",
                   "top_k", "top_p", "greedy", "stop_ids", "remaining",
                   "active")

    def snapshot(self) -> EngineSnapshot:
        """Serialize the full engine state to host memory.

        Everything a resumed engine needs to continue TOKEN-IDENTICALLY
        mid-stream is captured: per-slot KV caches, PRNG keys, sampling/
        stopping state (path-flattened through checkpoint/manager.py's
        format, so the array state can also be persisted with
        CheckpointManager — serve/resilience.save_snapshot), the
        scheduler queue + tracked requests, finished outputs, undrained
        event buffers, metrics counters and the breaker. Nothing aliases
        live engine state — stepping after ``snapshot()`` cannot corrupt
        the snapshot."""
        m = self.metrics_counters
        m.snapshots += 1
        slot_state = {name: getattr(self, name) for name in self._SLOT_STATE}
        flat = ckpt_manager.flatten_with_paths(
            {"caches": self.caches, "slots": slot_state})
        arrays = {path: (np.array(leaf) if leaf is not None else None)
                  for path, leaf in flat}
        return EngineSnapshot(
            tick=self._tick,
            arrays=arrays,
            uid_counter=self.sched.last_uid,
            queue=[tr.clone() for tr in self.sched.queue],
            slots=[tr.clone() if tr is not None else None
                   for tr in self.sched.slots],
            outputs=dict(self._outputs),        # RequestOutput is frozen
            buffers={uid: list(b) for uid, b in self._buffers.items()},
            pending=list(self._pending),        # StreamEvent is frozen
            retired=list(self._retired),
            metrics=m.state(),
            breaker=self.breaker.state(),
            num_slots=self.ecfg.num_slots,
            max_len=self.ecfg.max_len,
            paged=self.paging is not None,
            block_size=self.paging.block_size if self.paging else 0,
            num_blocks=self.paging.num_blocks if self.paging else 0,
            **(paging.paged_state(self.tables, self.pool, self._owned)
               if self.paging is not None else {}),
        )

    def restore(self, snap: EngineSnapshot) -> None:
        """Adopt a snapshot: the engine resumes exactly at the
        snapshot's tick, mid-stream requests continue token-identically
        (their PRNG keys, KV caches and sampling state all came along).
        Requests in-flight across the restore are marked ``restored`` —
        they finish with a ``...-after-restore`` annotated reason."""
        if (snap.num_slots != self.ecfg.num_slots
                or snap.max_len != self.ecfg.max_len):
            raise ValueError(
                f"snapshot geometry (slots={snap.num_slots}, "
                f"max_len={snap.max_len}) does not match engine "
                f"(slots={self.ecfg.num_slots}, max_len={self.ecfg.max_len})")
        snap_paged = getattr(snap, "paged", False)
        if snap_paged != (self.paging is not None):
            raise ValueError(
                f"snapshot paged={snap_paged} does not match engine "
                f"paged={self.paging is not None}")
        if self.paging is not None and (
                snap.block_size != self.paging.block_size
                or snap.num_blocks != self.paging.num_blocks):
            raise ValueError(
                f"snapshot paging geometry (block_size={snap.block_size}, "
                f"num_blocks={snap.num_blocks}) does not match engine "
                f"(block_size={self.paging.block_size}, "
                f"num_blocks={self.paging.num_blocks})")
        tree = ckpt_manager.unflatten_from_paths(dict(snap.arrays))

        # adopt the cache leaves under THIS engine's pytree structure:
        # the path format collapses list-vs-tuple, so unflatten against
        # the live treedef (leaf order matches — both flatteners sort
        # dict keys and keep sequence order)
        t_leaves, treedef = jax.tree_util.tree_flatten(self.caches)
        r_leaves = jax.tree_util.tree_leaves(tree["caches"])
        if len(t_leaves) != len(r_leaves):
            raise ValueError(
                f"snapshot cache has {len(r_leaves)} leaves, engine cache "
                f"has {len(t_leaves)} — incompatible model/cache geometry")
        self.caches = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(r).astype(t.dtype)
                      for t, r in zip(t_leaves, r_leaves)])

        slot_state = tree["slots"]
        for name in self._SLOT_STATE:
            tmpl = getattr(self, name)
            setattr(self, name,
                    np.array(slot_state[name]).astype(tmpl.dtype))

        if self.paging is not None:
            self.tables = np.array(snap.block_tables, np.int32)
            self.pool.restore(snap.pool_free)
            self._owned = [list(o) for o in snap.owned]
            self._tables_dirty = True
            self._update_kv_gauges()

        self.sched.restore_state(snap.uid_counter, snap.queue, snap.slots)
        for tr in self.sched.slots:
            if tr is not None:
                tr.restored = True
        self._outputs = dict(snap.outputs)
        self._buffers = {uid: deque(b) for uid, b in snap.buffers.items()}
        self._pending = list(snap.pending)
        self._retired = deque(snap.retired)
        self.metrics_counters.restore(dict(snap.metrics))
        self.metrics_counters.restores += 1
        self.breaker.restore(snap.breaker)
        self._tick = snap.tick

    # ------------------------------------------------------------- metrics
    def metrics(self) -> Dict[str, float]:
        """Snapshot of the engine counters (serve/metrics.py)."""
        return self.metrics_counters.snapshot()

    # ---------------------------------------------------------- high level
    def generate(self, prompts: Sequence[np.ndarray], max_new_tokens: int,
                 sampling: Optional[SamplingParams] = None
                 ) -> Dict[int, List[int]]:
        """Convenience wrapper over submit/step: serve a batch of prompts
        to completion and return {uid: tokens} in submission order. The
        default sampling is greedy — token-for-token identical to the
        pre-redesign blocking engine. Rejected prompts raise (the typed
        submit() surface is the place to handle rejection gracefully)."""
        sampling = sampling or api.GREEDY
        reqs = [GenerationRequest(prompt=p, max_new_tokens=max_new_tokens,
                                  sampling=sampling) for p in prompts]
        # validate the whole batch BEFORE enqueueing anything: a partial
        # raise must not leave accepted prompts queued for a later call
        bad = {i: self._admission_error(r) for i, r in enumerate(reqs)}
        bad = {i: why for i, why in bad.items() if why is not None}
        if bad:
            raise ValueError(
                f"generate(): unservable prompt(s) {bad}; use submit() to "
                "handle rejection as data")
        guard = 0
        uids = []
        for r in reqs:
            # respect the bounded queue: drain instead of rejecting
            while len(self.sched.queue) >= self.sched.max_queue:
                self.step()
                guard += 1
                if guard > 100000:  # pragma: no cover
                    raise RuntimeError("engine did not converge")
            uids.append(self.submit(r))
        while not self.idle:
            self.step()
            guard += 1
            if guard > 100000:  # pragma: no cover
                raise RuntimeError("engine did not converge")
        results: Dict[int, List[int]] = {}
        for uid, req in zip(uids, reqs):
            out = self._outputs[uid]
            # the stopping condition is enforced in-jit; over-generation
            # would be an engine bug — assert the invariant rather than
            # silently truncating it away
            assert len(out.tokens) <= req.max_new_tokens, (
                f"request {uid} generated {len(out.tokens)} tokens, over "
                f"its max_new_tokens={req.max_new_tokens} budget")
            results[uid] = list(out.tokens)
            self._buffers.pop(uid, None)
        return results
