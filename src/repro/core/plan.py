"""Plan-once / execute-many dispatch for every model-layer matmul.

EVA's speedup comes from picking the right *formulation* per shape —
VQ-GEMM + structured lookup at small M, reconstruct-and-GEMM at large M,
the fused Pallas kernel on an accelerator, INT8 GEMM for prefill — and
then executing that frozen choice on every step (the VQ-LLM "select a
code variant per shape, execute the cached selection" structure). This
module is the selection layer:

  LinearSpec   : frozen, hashable description of one matmul site —
                 (M, K, N), weight kind (dense / int8 / vq), the VQ
                 geometry (C, V, 2^n, d, grouped splits), dtypes and the
                 mesh-context flag. Derived from ``(x, params)`` at trace
                 time; equal specs hash equal, so a spec is a cache key.
  PlanPolicy   : frozen, hashable execution policy (vq_mode, impl,
                 epilogue, block_v, int8_prefill, interpret). Statically
                 contradictory policies raise ValueError at construction.
  MatmulPlan   : the concrete executable: chosen backend plus every
                 resolved number (epilogue kind + block_v for jnp;
                 m/v/n tiles for the Pallas kernels — nothing re-derived
                 at execute time). ``plan.execute(x, leaf)`` runs it;
                 ``plan.describe()`` names it for logs.
  Planner      : LRU cache mapping (LinearSpec, PlanPolicy) -> MatmulPlan.
                 Same spec+policy returns the SAME plan object; inside a
                 jitted decode step the planner is only consulted while
                 tracing, never on the executed path.

Backends register via ``register_backend(name, matcher, planner_fn)``.
Selection is BY RULE: the plan is the first healthy (not quarantined)
backend whose matcher accepts (spec, policy), in registration order,
and the matches after it, in the same order, are its trace-time
fallback chain. Nothing is priced: where one backend should win only on
some shapes, its matcher declines the others (the fused EVA kernel
admits only shapes whose OC tile fits VMEM, leaving the rest to the
two-kernel split backend registered after it).

The pure-jnp formulations are registered here; the Pallas kernels
register themselves from ``kernels/*/ops.py`` (each owns its tile model)
and are imported lazily on first use, so ``core`` never imports kernel
modules at module scope.

Model layers (``models/common.py linear/grouped_linear``) fetch a plan
per call site instead of threading string knobs; ``eva_matmul`` /
``vq_matmul`` in ``core/ops.py`` remain as thin convenience wrappers
over ``Planner.plan(...).execute(...)``.
"""
from __future__ import annotations

import collections
import dataclasses
import importlib
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax.numpy as jnp

from repro.core import ops
from repro.core.ops import EPILOGUES
from repro.core.vq import VQWeight

log = logging.getLogger(__name__)

WEIGHT_KINDS = ("dense", "int8", "vq", "vq_grouped", "kvq_attn", "vq_logits")
VQ_MODES = ("none", "eva", "dequant")
IMPLS = ("jnp", "pallas")

# backends quarantined after a failure are retried after this cool-off;
# a transient failure (driver hiccup, OOM under pressure) recovers, a
# persistent one re-quarantines on the next attempt
DEFAULT_BACKEND_COOLOFF_S = 30.0


# ---------------------------------------------------------------------------
# Spec / policy
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LinearSpec:
    """Shape + weight-kind signature of one matmul site.

    ``kind`` is the *resolved* weight kind: "dense" (fp path), "int8"
    (a dense weight executed through the INT8 prefill GEMM), "vq",
    "vq_grouped" (VQ experts stacked on a leading axis, applied to rows
    sorted by expert — ``core/ops.ExpertRows``; M counts the layout's
    rows), or "kvq_attn" (a KV-VQ decode-attention site — see
    ``kvq_attention_spec`` for the field mapping). The VQ geometry
    fields are zero for non-VQ kinds. ``in_mesh``
    records whether the spec was derived inside an active mesh context
    (pjit/shard_map) — the SPMD-friendly flat epilogue is preferred
    there, exactly like the pre-plan string-knob behavior."""

    M: int
    K: int
    N: int
    kind: str                      # dense | int8 | vq
    x_dtype: str
    out_dtype: str
    C: int = 0
    V: int = 0
    k: int = 0                     # 2^n centroids per codebook
    d: int = 0
    splits: Tuple[int, ...] = ()   # grouped-family member widths
    in_mesh: bool = False

    def __post_init__(self):
        if self.kind not in WEIGHT_KINDS:
            raise ValueError(
                f"unknown weight kind {self.kind!r}; expected one of {WEIGHT_KINDS}")

    @classmethod
    def for_vq(cls, vq: VQWeight, *, M: int, x_dtype, out_dtype,
               in_mesh: Optional[bool] = None, kind: str = "vq"
               ) -> "LinearSpec":
        """Spec for a VQ weight leaf: geometry read off the ``VQWeight``
        (K/N/C/V/centroids/splits), ``M`` supplied by the call site.
        ``in_mesh=None`` auto-detects an active pjit/shard_map context."""
        k = vq.codebooks.shape[-1] if hasattr(vq.codebooks, "shape") else 2 ** vq.n
        return cls(
            M=int(M), K=vq.K, N=vq.N, kind=kind,
            x_dtype=jnp.dtype(x_dtype).name, out_dtype=jnp.dtype(out_dtype).name,
            C=vq.C, V=vq.V, k=int(k), d=vq.d, splits=tuple(vq.splits),
            in_mesh=ops._in_mesh_context() if in_mesh is None else in_mesh,
        )

    @classmethod
    def for_dense(cls, w, *, M: int, x_dtype, out_dtype, kind: str = "dense",
                  in_mesh: Optional[bool] = None) -> "LinearSpec":
        """Spec for a dense weight array ``w`` of shape (.., K, N);
        ``kind`` may be "int8" for the INT8 prefill GEMM path.

        Raises: ValueError (from __post_init__) on an unknown kind."""
        K, N = int(w.shape[-2]), int(w.shape[-1])
        return cls(
            M=int(M), K=K, N=N, kind=kind,
            x_dtype=jnp.dtype(x_dtype).name, out_dtype=jnp.dtype(out_dtype).name,
            in_mesh=ops._in_mesh_context() if in_mesh is None else in_mesh,
        )


@dataclasses.dataclass(frozen=True)
class PlanPolicy:
    """Execution policy for one matmul (the collapsed RunConfig knobs).

    ``vq_mode``  : "eva" | "dequant" | "none" ("none" resolves by run
                   mode: EVA in decode, the dequant baseline elsewhere).
    ``impl``     : "jnp" | "pallas".
    ``epilogue`` : "auto" or one of core/ops.EPILOGUES. Only the EVA jnp
                   backends consume it; impl="pallas" always runs the
                   fused tiled kernel and accepts only "auto".
    ``block_v``  : None (auto-sized) or a pinned v-block height — on jnp
                   only coherent with the v-blocked epilogues
                   ("blocked"/"recon"); on Pallas it pins the kernel's
                   v-tiles.
    ``int8_prefill`` : route dense prefill matmuls through the INT8 GEMM.
    ``interpret``    : Pallas interpret mode (CPU validation).

    Statically contradictory combinations raise ValueError here, so a
    bad policy is loud at construction (not at the first matmul).
    """

    vq_mode: str = "none"
    impl: str = "jnp"
    epilogue: str = "auto"
    block_v: Optional[int] = None
    int8_prefill: bool = False
    interpret: bool = False

    def __post_init__(self):
        if self.vq_mode not in VQ_MODES:
            raise ValueError(
                f"unknown vq_mode {self.vq_mode!r}; expected one of {VQ_MODES}")
        if self.impl not in IMPLS:
            raise ValueError(f"unknown impl {self.impl!r}; expected one of {IMPLS}")
        if self.epilogue not in EPILOGUES + ("auto",):
            raise ValueError(
                f"unknown epilogue {self.epilogue!r}; expected 'auto' or one "
                f"of {EPILOGUES}")
        if self.block_v is not None:
            if isinstance(self.block_v, bool) or not isinstance(self.block_v, int):
                raise ValueError(
                    f"block_v must be None ('auto') or an int, got {self.block_v!r}")
            if self.block_v <= 0:
                raise ValueError(f"block_v must be positive, got {self.block_v}")
            if self.impl == "jnp" and self.vq_mode != "dequant" \
                    and self.epilogue not in ("blocked", "recon"):
                # dequant is exempt: its jnp baseline has no epilogue and
                # documents block_v as ignored; on Pallas (any mode)
                # block_v pins the kernel's v-tiles
                raise ValueError(
                    f"explicit block_v={self.block_v} conflicts with epilogue="
                    f"{self.epilogue!r}; block_v only applies to the v-blocked "
                    "epilogues ('blocked', 'recon') on impl='jnp'")

    def resolve_vq_mode(self, mode: str) -> "PlanPolicy":
        """Resolve vq_mode="none" by run mode (decode -> EVA, else the
        dequant baseline — the historical linear() fallback)."""
        if self.vq_mode != "none":
            return self
        return dataclasses.replace(
            self, vq_mode="eva" if mode == "decode" else "dequant")


@dataclasses.dataclass(frozen=True)
class MatmulPlan:
    """A frozen, executable matmul choice.

    ``config`` holds every resolved number the backend needs (epilogue
    kind, block_v, kernel tiles, ...) — ``execute`` re-derives nothing.
    """

    backend: str
    spec: LinearSpec
    policy: PlanPolicy
    config: Tuple[Tuple[str, Any], ...]
    run: Callable[[Any, Any], Any]

    def execute(self, x, leaf):
        """Run the planned matmul. ``leaf`` is the weight leaf the spec
        was derived from (a VQWeight or a dense array)."""
        return self.run(x, leaf)

    @property
    def config_dict(self) -> Dict[str, Any]:
        """The frozen backend config as a plain dict (logging/tests)."""
        return dict(self.config)

    def describe(self) -> str:
        """One-line human summary: backend, shape and resolved config."""
        s = self.spec
        parts = [self.backend, f"M={s.M}", f"K={s.K}", f"N={s.N}"]
        if s.splits:
            parts.append(f"splits={len(s.splits)}")
        parts += [f"{k}={v}" for k, v in self.config]
        if self.policy.interpret:
            parts.append("interpret")
        return " ".join(parts)


def kvq_attention_spec(*, B: int, S: int, H: int, Hk: int, hd: int,
                       idx_width: int, entries: int,
                       x_dtype, out_dtype) -> LinearSpec:
    """Spec for a KV-VQ decode-attention site (kind="kvq_attn").

    Decode attention over a vector-quantized cache is a matmul-shaped
    site the planner resolves like any other: the field mapping is
    M=batch, K=cache length S, N=H*hd (the per-token attention output),
    C=Hk (kv heads), V=idx_width (uint8 indices per token per head),
    k=entries (codebook rows), d=hd. Backends registered from
    ``kernels/flash_decode/ops.py`` match on the kind: the fused Pallas
    kernel where its matcher admits the shape, the dequantize-jnp path
    otherwise.

    Args:
      B/S/H/Hk/hd: decode-attention geometry (static at trace time).
      idx_width: R*G uint8 indices per (token, head) — see
        core.vq.KVQuantConfig.idx_width.
      entries: codebook rows per stage (256).
      x_dtype/out_dtype: query/output dtypes.

    Returns: a hashable LinearSpec usable as a planner cache key.
    """
    return LinearSpec(
        M=int(B), K=int(S), N=int(H * hd), kind="kvq_attn",
        x_dtype=jnp.dtype(x_dtype).name, out_dtype=jnp.dtype(out_dtype).name,
        C=int(Hk), V=int(idx_width), k=int(entries), d=int(hd),
    )


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Backend:
    name: str
    matcher: Callable[[LinearSpec, PlanPolicy], bool]
    planner_fn: Callable[[LinearSpec, PlanPolicy], MatmulPlan]


_REGISTRY: "collections.OrderedDict[str, _Backend]" = collections.OrderedDict()
_REGISTRY_LOCK = threading.Lock()

# kernel wrapper modules that register Pallas backends on import; loaded
# lazily on the first plan() that can need them (impl="pallas", or a
# no-match retry) so pure-jnp workloads never import pallas
_KERNEL_BACKEND_MODULES = (
    "repro.kernels.fused_vq_matmul.ops",
    "repro.kernels.grouped_vq_matmul.ops",
    "repro.kernels.oc_lookup.ops",
    "repro.kernels.dequant_gemv.ops",
    "repro.kernels.int8_gemm.ops",
    "repro.kernels.flash_decode.ops",  # KV-VQ decode-attention backends
)
_kernels_loaded = False


def register_backend(name: str,
                     matcher: Callable[[LinearSpec, PlanPolicy], bool],
                     planner_fn: Callable[[LinearSpec, PlanPolicy], MatmulPlan],
                     ) -> None:
    """Register (or idempotently re-register) a matmul backend.

    ``matcher(spec, policy)`` says whether this backend can execute the
    site; ``planner_fn(spec, policy)`` freezes every tile size / epilogue
    choice into a MatmulPlan. Among the matching backends the first
    registered wins; the later ones are its fallback chain."""
    with _REGISTRY_LOCK:
        _REGISTRY[name] = _Backend(name, matcher, planner_fn)


def registered_backends() -> Tuple[str, ...]:
    """All registered backend names in registration order (kernel
    modules are imported first, so the tuple is complete)."""
    _ensure_kernel_backends()
    return tuple(_REGISTRY)


def _ensure_kernel_backends() -> None:
    global _kernels_loaded
    if _kernels_loaded:
        return
    for mod in _KERNEL_BACKEND_MODULES:
        importlib.import_module(mod)
    # only latch after every import succeeded — a transient failure must
    # stay loud and retryable, not silently de-register the Pallas backends
    _kernels_loaded = True


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------


CacheInfo = collections.namedtuple("CacheInfo", "hits misses currsize maxsize")


class Planner:
    """LRU-cached (LinearSpec, PlanPolicy) -> MatmulPlan resolver.

    Planning happens at Python/trace time only: a jitted decode step
    consults the planner while tracing and bakes ``plan.run`` into the
    program, so repeated executed steps never re-enter ``plan``.

    Selection is by rule: the first healthy backend whose matcher
    accepts the (spec, policy) pair, in registration order, is the plan;
    the other healthy matches, in the same order, back it up as a
    trace-time fallback chain."""

    def __init__(self, maxsize: int = 1024,
                 cooloff_s: float = DEFAULT_BACKEND_COOLOFF_S):
        self._cache: "collections.OrderedDict[Tuple[LinearSpec, PlanPolicy], MatmulPlan]" = (
            collections.OrderedDict())
        self._maxsize = maxsize
        self._lock = threading.Lock()
        self._hits = 0
        self._misses = 0
        # graceful degradation: backend name -> monotonic quarantine
        # expiry. A quarantined backend is skipped by planning until its
        # cool-off passes; both quarantine and release clear the plan
        # cache so re-planning actually changes the choice.
        self.cooloff_s = cooloff_s
        self._quarantine: Dict[str, float] = {}
        self._backend_failures: Dict[str, int] = collections.Counter()
        self._exec_fallbacks = 0

    # ---- backend quarantine (graceful degradation)
    def record_backend_failure(self, backend: str,
                               cooloff_s: Optional[float] = None) -> None:
        """Quarantine ``backend`` for ``cooloff_s`` (planner default when
        None): planning skips it until the cool-off expires, then it
        becomes a candidate again (transient failures recover). The plan
        cache is cleared so already-planned sites re-plan too."""
        with self._lock:
            self._backend_failures[backend] += 1
            self._quarantine[backend] = time.monotonic() + (
                self.cooloff_s if cooloff_s is None else cooloff_s)
            self._cache.clear()
        log.warning("backend %r quarantined for %.1fs (%d failures so far)",
                    backend, self.cooloff_s if cooloff_s is None else cooloff_s,
                    self._backend_failures[backend])

    def _active_quarantine(self) -> Tuple[str, ...]:
        """Currently-quarantined backend names; expired entries are
        released here (and the cache cleared, so the recovered backend
        is actually re-planned rather than shadowed by cached fallbacks)."""
        now = time.monotonic()
        with self._lock:
            expired = [b for b, t in self._quarantine.items() if now >= t]
            for b in expired:
                del self._quarantine[b]
            if expired:
                self._cache.clear()
            active = tuple(self._quarantine)
        for b in expired:
            log.info("backend %r released from quarantine (cool-off "
                     "expired); re-planning on next plan", b)
        return active

    def reset_quarantine(self) -> None:
        """Forget all quarantines + failure counts and clear the plan
        cache (tests around the GLOBAL default planner must call this to
        avoid cross-test contamination)."""
        with self._lock:
            self._quarantine.clear()
            self._backend_failures.clear()
            self._exec_fallbacks = 0
            self._cache.clear()

    def backend_stats(self) -> Dict[str, Any]:
        """Failure/fallback accounting: per-backend failure counts, the
        currently quarantined set and how many execute-time fallback
        switches the planned run chains performed."""
        with self._lock:
            failures = dict(self._backend_failures)
            fallbacks = self._exec_fallbacks
        return {"failures": failures,
                "quarantined": self._active_quarantine(),
                "exec_fallbacks": fallbacks}

    def plan(self, spec: LinearSpec, policy: PlanPolicy) -> MatmulPlan:
        """Resolve (spec, policy) to the first eligible MatmulPlan in
        registration order (LRU-cached; quarantined backends are skipped).

        Raises:
          ValueError: no registered backend matches the pair — or, on a
            jnp-policy miss, not even after lazily importing the kernel
            backend modules."""
        quarantined = self._active_quarantine()  # may purge + clear cache
        key = (spec, policy)
        with self._lock:
            hit = self._cache.get(key)
            if hit is not None:
                self._hits += 1
                self._cache.move_to_end(key)
                return hit
        # Load the Pallas kernel registrations only when they can be
        # needed: pure-jnp workloads must not pay (or depend on) the
        # pallas imports. A no-match retry covers custom late loads.
        if policy.impl == "pallas":
            _ensure_kernel_backends()
        matched = self._match_all(spec, policy)
        if not matched and not _kernels_loaded:
            _ensure_kernel_backends()
            matched = self._match_all(spec, policy)
        if not matched:
            raise ValueError(
                f"no registered backend matches spec={spec} policy={policy}; "
                f"registered: {tuple(_REGISTRY)}")
        if quarantined:
            healthy = tuple(be for be in matched
                            if be.name not in quarantined)
            if healthy:
                matched = healthy
            else:
                # every eligible backend is quarantined: degrade stepwise
                # — first to the plain jnp formulation of the same mode,
                # then (for EVA) to the dequant jnp baseline, which is
                # token-exact vs EVA and always available — rather than
                # refusing to serve
                degraded = dataclasses.replace(
                    policy, impl="jnp", epilogue="auto",
                    block_v=None, interpret=False)  # lint-ok: PlanPolicy field
                if degraded == policy and policy.vq_mode == "eva":
                    degraded = dataclasses.replace(degraded,
                                                   vq_mode="dequant")
                if degraded != policy:
                    log.warning(
                        "all matched backends %s quarantined for spec=%s; "
                        "degrading policy to %s",
                        tuple(be.name for be in matched), spec, degraded)
                    return self.plan(spec, degraded)
                # last resort: even the degraded jnp candidates are
                # quarantined — refusing to serve is worse than retrying
                # a possibly-recovered backend, so ignore the quarantine
                log.error(
                    "all backends quarantined even under the degraded jnp "
                    "policy for spec=%s; ignoring quarantine", spec)
        built = self._build(matched, spec, policy)
        with self._lock:  # (re-planning a raced key is harmless)
            self._misses += 1
            self._cache[key] = built
            while len(self._cache) > self._maxsize:
                self._cache.popitem(last=False)
        return built

    def _build(self, matched: Tuple[_Backend, ...], spec: LinearSpec,
               policy: PlanPolicy) -> MatmulPlan:
        """Build every matched candidate in registration order: the first
        is the plan, and the rest back it up through the fallback chain."""
        candidates = tuple(be.planner_fn(spec, policy) for be in matched)
        chosen = candidates[0]
        if len(candidates) == 1:
            return chosen
        return dataclasses.replace(chosen, run=self._chain_run(candidates))

    def _chain_run(self, candidates: Tuple[MatmulPlan, ...]
                   ) -> Callable[[Any, Any], Any]:
        """Bake the candidates into one run callable: when the chosen
        backend raises while the planned matmul is being BUILT
        (trace/lowering time — where Pallas kernel failures surface),
        the next candidate takes over in place, the failed
        backend is quarantined for the cool-off and the fallback is
        counted. Already-compiled executions never re-enter Python, so
        the chain costs nothing on the steady-state path."""

        def run(x, leaf):
            last_err: Optional[Exception] = None
            for cand in candidates:
                try:
                    return cand.run(x, leaf)
                except Exception as e:  # noqa: BLE001 - any backend fault
                    last_err = e
                    self.record_backend_failure(cand.backend)
                    with self._lock:
                        self._exec_fallbacks += 1
                    log.warning("planned backend %r failed at execute "
                                "(%s: %s); trying next candidate",
                                cand.backend, type(e).__name__, e)
            raise last_err

        return run

    @staticmethod
    def _match_all(spec: LinearSpec, policy: PlanPolicy
                   ) -> Tuple[_Backend, ...]:
        with _REGISTRY_LOCK:  # snapshot: register_backend may race
            backends = tuple(_REGISTRY.values())
        return tuple(be for be in backends if be.matcher(spec, policy))

    def cache_info(self) -> CacheInfo:
        """functools-style (hits, misses, currsize, maxsize) counters."""
        return CacheInfo(self._hits, self._misses, len(self._cache),
                         self._maxsize)

    def cache_clear(self) -> None:
        """Drop every cached plan and reset the hit/miss counters."""
        with self._lock:
            self._cache.clear()
            self._hits = 0
            self._misses = 0


_PLANNER = Planner()


def default_planner() -> Planner:
    """The process-global Planner every model-layer entry point uses."""
    return _PLANNER


def reset_quarantine() -> None:
    """Clear the DEFAULT planner's backend quarantine + failure stats
    (test hygiene: the default planner is process-global)."""
    _PLANNER.reset_quarantine()


def plan(spec: LinearSpec, policy: PlanPolicy) -> MatmulPlan:
    """Resolve (spec, policy) through the default planner's cache."""
    return _PLANNER.plan(spec, policy)


# ---------------------------------------------------------------------------
# Spec derivation + model-layer entry points
# ---------------------------------------------------------------------------


def plan_node(p: Any, x, *, mode: str, policy: PlanPolicy,
              out_dtype=None) -> MatmulPlan:
    """Plan one linear param node ({"w": ...}, {"vq": ...} or
    {"vql": ...}) for input
    ``x`` under run ``mode``. This is the single dispatch point used by
    ``models.common.linear`` — the weight-kind decision lives in the spec
    derivation, the formulation choice in the backend registry."""
    if "vq" in p and p["vq"].idx.ndim == 4:
        # experts stacked on a leading axis (idx (E, C, V, N)): one
        # grouped matmul over rows sorted by expert (x: ops.ExpertRows)
        spec = LinearSpec.for_vq(p["vq"], M=x.x.shape[0], x_dtype=x.x.dtype,
                                 out_dtype=out_dtype or x.x.dtype,
                                 kind="vq_grouped")
        return _PLANNER.plan(spec, policy.resolve_vq_mode(mode))
    out_dtype = out_dtype or x.dtype
    if "vq" in p:
        vq: VQWeight = p["vq"]
        spec = LinearSpec.for_vq(vq, M=x.size // vq.K, x_dtype=x.dtype,
                                 out_dtype=out_dtype)
        return _PLANNER.plan(spec, policy.resolve_vq_mode(mode))
    if "vql" in p:
        from repro.core import logits_vq as lvq  # local: lvq imports plan
        head = p["vql"]
        spec = lvq.vq_logits_spec(head, M=x.size // head.D, x_dtype=x.dtype,
                                  out_dtype=out_dtype)
        return _PLANNER.plan(spec, policy)
    w = p["w"]
    kind = "int8" if (mode == "prefill" and policy.int8_prefill) else "dense"
    spec = LinearSpec.for_dense(w, M=x.size // int(w.shape[-2]),
                                x_dtype=x.dtype, out_dtype=out_dtype,
                                kind=kind)
    return _PLANNER.plan(spec, policy)


def plan_vq(x, vq: VQWeight, policy: PlanPolicy, out_dtype=None) -> MatmulPlan:
    """Plan a bare VQ matmul (the eva_matmul / vq_matmul wrapper path)."""
    spec = LinearSpec.for_vq(vq, M=x.size // vq.K, x_dtype=x.dtype,
                             out_dtype=out_dtype or x.dtype)
    return _PLANNER.plan(spec, policy.resolve_vq_mode("decode"))


def preplan_params(params: Any, policy: PlanPolicy, *, mode: str, m: int,
                   act_dtype, planner: Optional[Planner] = None,
                   ) -> List[Tuple[Tuple[str, ...], MatmulPlan]]:
    """Walk a param tree and plan every linear leaf at batch size ``m``
    (tokens in flight), warming the planner cache before the first trace
    and returning (path, plan) pairs for logging/introspection.

    Expert leaves are left out: they run as grouped matmuls over the
    rows routed to them, planned on first trace — pre-planning is a
    warm-up plus a report, never a constraint."""
    planner = planner or _PLANNER
    out: List[Tuple[Tuple[str, ...], MatmulPlan]] = []

    def walk(node, path):
        if not isinstance(node, dict) or "experts" in path:
            return
        if "vq" in node:
            vq: VQWeight = node["vq"]
            spec = LinearSpec.for_vq(vq, M=m, x_dtype=act_dtype,
                                     out_dtype=act_dtype, in_mesh=False)
            out.append((path, planner.plan(spec, policy.resolve_vq_mode(mode))))
            return
        if "vql" in node:
            from repro.core import logits_vq as lvq
            spec = lvq.vq_logits_spec(node["vql"], M=m, x_dtype=act_dtype,
                                      out_dtype=jnp.float32)
            out.append((path, planner.plan(spec, policy)))
            return
        if "w" in node and hasattr(node["w"], "ndim") and node["w"].ndim >= 2:
            kind = "int8" if (mode == "prefill" and policy.int8_prefill) \
                else "dense"
            spec = LinearSpec.for_dense(node["w"], M=m, x_dtype=act_dtype,
                                        out_dtype=act_dtype, kind=kind,
                                        in_mesh=False)
            out.append((path, planner.plan(spec, policy)))
            return
        for key, sub in node.items():
            walk(sub, path + (key,))

    walk(params, ())
    return out


def preplan_prefill_buckets(params: Any, policy: PlanPolicy, *,
                            buckets: Tuple[int, ...], act_dtype,
                            planner: Optional[Planner] = None,
                            ) -> Dict[int, List[Tuple[Tuple[str, ...],
                                                      MatmulPlan]]]:
    """Plan every linear leaf at EACH prefill length bucket.

    The serving engine pads prompts to power-of-two buckets, so prefill
    executes at exactly these M values — unlike the old single
    capacity-bound ``prefill@cap`` estimate, every returned plan is the
    one the traced prefill step will fetch (regime choices like
    direct-vs-recon flip with M, so per-bucket planning is not just a
    warm-up: it is the report of what actually runs per bucket)."""
    return {
        m: preplan_params(params, policy, mode="prefill", m=m,
                          act_dtype=act_dtype, planner=planner)
        for m in buckets
    }


# ---------------------------------------------------------------------------
# jnp backend registrations (fp / int8 / dequant / EVA epilogues)
#
# The Pallas counterparts register from kernels/*/ops.py, each owning its
# tile model; these jnp formulations own the epilogue rule in
# core/ops.py (select_epilogue + the block sizing helpers), which are
# called from HERE only — model layers never re-derive a formulation.
# ---------------------------------------------------------------------------


def _plan_fp(spec: LinearSpec, policy: PlanPolicy) -> MatmulPlan:
    out_dt = jnp.dtype(spec.out_dtype)

    def run(x, w):
        if w.dtype != x.dtype:
            w = w.astype(x.dtype)
        return ops.fp_matmul(x, w, out_dtype=out_dt)

    return MatmulPlan("fp", spec, policy, (), run)


def _plan_int8_jnp(spec: LinearSpec, policy: PlanPolicy) -> MatmulPlan:
    out_dt = jnp.dtype(spec.out_dtype)

    def run(x, w):
        return ops.int8_matmul(x, w, out_dtype=out_dt)

    return MatmulPlan("int8_jnp", spec, policy, (), run)


def _plan_dequant_jnp(spec: LinearSpec, policy: PlanPolicy) -> MatmulPlan:
    out_dt = jnp.dtype(spec.out_dtype)

    def run(x, vq):
        return ops.dequant_matmul(x, vq, out_dtype=out_dt)

    return MatmulPlan("dequant_jnp", spec, policy, (), run)


def _is_eva_jnp(spec: LinearSpec, policy: PlanPolicy) -> bool:
    return (spec.kind == "vq" and policy.impl == "jnp"
            and policy.vq_mode in ("eva", "none"))


def _resolve_eva_epilogue(spec: LinearSpec, policy: PlanPolicy
                          ) -> Tuple[str, Optional[int]]:
    """Freeze (epilogue kind, block_v) for the jnp EVA backends. The only
    call site of core/ops.select_epilogue and the auto block sizers."""
    epi = policy.epilogue
    if epi == "auto":
        return ops.select_epilogue(spec.M, spec.V, spec.N, spec.C, spec.k,
                                   spec.d, distributed=spec.in_mesh)
    if epi == "blocked":
        if policy.block_v is not None:
            return "blocked", min(policy.block_v, spec.V)
        return "blocked", ops.auto_block_v(spec.M, spec.V, spec.N, spec.C,
                                           spec.k)
    if epi == "recon":
        if policy.block_v is not None:
            return "recon", min(policy.block_v, spec.V)
        return "recon", ops.auto_recon_block_v(spec.V, spec.N, spec.d)
    return epi, None


def _make_eva_jnp_planner(kind: str):
    def planner_fn(spec: LinearSpec, policy: PlanPolicy) -> MatmulPlan:
        resolved, bv = _resolve_eva_epilogue(spec, policy)
        assert resolved == kind, (resolved, kind)
        out_dt = jnp.dtype(spec.out_dtype)

        def run(x, vq):
            return ops.eva_epilogue_exec(x, vq, kind=kind, block_v=bv,
                                         out_dtype=out_dt)

        config = (("epilogue", kind),) + \
            ((("bv", bv),) if bv is not None else ())
        return MatmulPlan(f"eva_{kind}", spec, policy, config, run)

    return planner_fn


def _plan_grouped_jnp(kind: str):
    exec_fn = {"eva": ops.grouped_eva_matmul,
               "dequant": ops.grouped_dequant_matmul}[kind]

    def planner_fn(spec: LinearSpec, policy: PlanPolicy) -> MatmulPlan:
        out_dt = jnp.dtype(spec.out_dtype)

        def run(rows, vq):
            return exec_fn(rows, vq, out_dtype=out_dt)

        return MatmulPlan(f"grouped_{kind}_jnp", spec, policy, (), run)

    return planner_fn


def _register_jnp_backends() -> None:
    register_backend(
        "fp",
        lambda s, p: s.kind == "dense",
        _plan_fp,
    )
    register_backend(
        "int8_jnp",
        lambda s, p: s.kind == "int8" and p.impl == "jnp",
        _plan_int8_jnp,
    )
    register_backend(
        "dequant_jnp",
        lambda s, p: s.kind == "vq" and p.vq_mode == "dequant"
        and p.impl == "jnp",
        _plan_dequant_jnp,
    )
    register_backend(
        "grouped_eva_jnp",
        lambda s, p: s.kind == "vq_grouped" and p.impl == "jnp"
        and p.vq_mode in ("eva", "none"),
        _plan_grouped_jnp("eva"),
    )
    register_backend(
        "grouped_dequant_jnp",
        lambda s, p: s.kind == "vq_grouped" and p.vq_mode == "dequant",
        _plan_grouped_jnp("dequant"),
    )
    for kind in EPILOGUES:
        register_backend(
            f"eva_{kind}",
            lambda s, p, _kind=kind: _is_eva_jnp(s, p)
            and _resolve_eva_epilogue(s, p)[0] == _kind,
            _make_eva_jnp_planner(kind),
        )


_register_jnp_backends()
