#!/usr/bin/env python3
"""Serve Minitron-4B at its published widths on one TPU chip and check
what comes out.

    python chip_smoke.py [--seed 0]

Everything runs in this one process, on one chip, with random weights
made from ``--seed`` (2-bit EVA VQ weights built on the device, see
``Model.init_synthetic``):

1. serve 12 requests (prompts of 200-500 tokens, 32 new tokens each,
   greedy and seeded sampling mixed) through ``repro.launch.serve.serve``
   and ``Engine`` with 8 slots and a 2048-token contiguous cache, under
   ``PlanPolicy(vq_mode="eva", impl="pallas")``;
2. serve a shorter pass over a paged 4-bit KV-VQ cache
   (``paged=True, kv_bits=4``);
3. compare the last-position logits of one 500-token prompt, at prefill
   and over two cached decode steps, between the Pallas EVA path and the
   ``vq_mode="dequant"`` jnp oracle at highest matmul precision;
4. compare the Pallas kernels the Planner may pick but the passes need
   not run (split EVA, dequant GEMV, KV-VQ flash decode over a paged
   arena) with their jnp references at this model's shapes.

It fails (non-zero exit, no result line) on any device that is not a
TPU, when a request finishes other than ``stop``/``length``, when the
engine counts errors or backend fallbacks or the Planner counts backend
failures, when a decode VQ linear is not planned onto a compiled Pallas
EVA kernel, and when logits or kernel parity misses its tolerance. On success the
last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "minitron_4b"
EVA_PALLAS_BACKENDS = ("eva_fused_pallas", "eva_split_pallas")

# Parity tolerance: max |pallas - oracle| over the vocab, relative to the
# oracle's max |logit|. Both paths read the same VQ weights, but the EVA
# path sums C*d-term lookups in its own order in fp32 and writes bf16
# activations between layers, while the oracle rebuilds each weight and
# runs one fp32 dot; the bf16 rounding (2^-8 relative) of every layer's
# output therefore differs, and 32 layers compound it. The relative
# error measured at smoke widths is recorded in CHANGES.md; 5e-2 leaves
# headroom for depth while a wrong lookup (a misplaced index, table or
# scale) moves logits by their own magnitude.
PARITY_TOL = 5e-2
# One kernel against its jnp reference at highest precision, relative to
# the reference's max |value|. The kernels compute in fp32, but the KV-VQ
# wrapper's query/codebook einsum runs at the chip's default matmul
# precision (one bf16 pass, 2^-9 relative) before the softmax; a wrong
# index or table moves outputs by their own magnitude.
KERNEL_TOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Traffic of one smoke run."""
    requests: int = 12
    slots: int = 8
    max_len: int = 2048
    prompt_lens: Tuple[int, int] = (200, 500)
    max_new: int = 32
    paged_requests: int = 6
    paged_max_new: int = 16
    parity_prompt: int = 500


class CompileClock:
    """Seconds the XLA compiler runs, summed from JAX's backend-compile
    events (tracing and lowering nest across jit levels, so they are left
    out rather than counted twice)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def peak_bytes() -> Any:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def check_serving(out: Dict[str, Any], *, requests: int, interpret: bool
                  ) -> List[str]:
    """Everything that makes one serving pass a failure."""
    from repro.core import plan as plan_mod

    failures = []
    reasons = collections.Counter(o.finish_reason
                                  for o in out["outputs"].values())
    if sum(reasons.values()) != requests or set(reasons) - {"stop", "length"}:
        failures.append(f"finish reasons {dict(reasons)} for {requests} "
                        "requests")
    m = out["metrics"]
    if m["errors"] or m["backend_fallbacks"]:
        failures.append(f"engine errors={m['errors']} "
                        f"backend_fallbacks={m['backend_fallbacks']}")
    stats = plan_mod.default_planner().backend_stats()
    if stats["failures"] or stats["exec_fallbacks"]:
        failures.append(f"planner backend failures {stats}")
    vq = [(path, pl) for path, pl in out["plans"]["decode"]
          if pl.spec.kind == "vq"]
    if not vq:
        failures.append("no VQ linear in the decode plans")
    for path, pl in vq:
        if pl.backend not in EVA_PALLAS_BACKENDS \
                or pl.policy.interpret != interpret:
            failures.append(f"decode linear {path} planned as {pl.describe()}")
    return failures


def describe_plans(out: Dict[str, Any]) -> List[str]:
    """Every decode plan, and the backends of the prefill buckets."""
    lines = [f"  decode {'/'.join(path)}: {pl.describe()}"
             for path, pl in out["plans"]["decode"]]
    prefill = collections.Counter(
        pl.backend for phase, plans in out["plans"].items()
        if phase.startswith("prefill") for _path, pl in plans)
    buckets = [p.split("@")[1] for p in out["plans"] if "@" in p]
    lines.append(f"  prefill buckets {','.join(buckets)}: {dict(prefill)}")
    return lines


def serve_pass(name: str, *, smoke: bool, seed: int, interpret: bool,
               params: Any, requests: int, max_new: int, sizes: Sizes,
               clock: CompileClock, **engine_kw: Any) -> List[str]:
    from repro.launch.serve import serve

    before = clock.seconds
    out = serve(ARCH, smoke=smoke, requests=requests, max_new=max_new,
                prompt_len=sizes.prompt_lens[1],
                min_prompt_len=sizes.prompt_lens[0], num_slots=sizes.slots,
                max_len=sizes.max_len, impl="pallas", interpret=interpret,
                seed=seed, sample=True, params=params, **engine_kw)
    compile_s = clock.seconds - before
    m = out["metrics"]
    reasons = collections.Counter(o.finish_reason
                                  for o in out["outputs"].values())
    print(f"[{name}] plans:")
    print("\n".join(describe_plans(out)))
    print(f"[{name}] {requests} requests, {out['tokens']} tokens in "
          f"{out['wall_s']:.3f} s: {out['tok_per_s']:.3f} tok/s wall, "
          f"XLA compile {compile_s:.3f} s of it, "
          f"{out['tokens'] / max(out['wall_s'] - compile_s, 1e-9):.3f} "
          f"tok/s outside XLA compile")
    print(f"[{name}] finish {dict(reasons)}; errors={m['errors']} "
          f"backend_fallbacks={m['backend_fallbacks']} "
          f"decode_steps={m['decode_steps']} in {m['decode_s']:.3f} s, "
          f"prefills={m['prefills']} in {m['prefill_s']:.3f} s (both with "
          f"their compiles), peak_bytes_in_use={peak_bytes()}", flush=True)
    return [f"[{name}] {f}" for f in
            check_serving(out, requests=requests, interpret=interpret)]


def last_logits(model, params, tokens: np.ndarray, n_prompt: int,
                capacity: int, policy) -> List[np.ndarray]:
    """Last-position logits after prefilling ``tokens[:, :n_prompt]`` and
    after each cached decode step over the remaining tokens, with the
    cache padded to ``capacity``."""
    from repro.models.common import RunConfig
    from repro.serve.kvcache import pad_prefill_cache

    rc = RunConfig(mode="decode", remat=False, attn_chunk=64,
                   plan_policy=policy)
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, rc))
    decode = jax.jit(lambda p, t, pos, c: model.decode(p, t, pos, c, rc))
    logits, caches = prefill(params, jnp.asarray(tokens[:, :n_prompt]))
    caches = pad_prefill_cache(caches, capacity)
    out = [np.asarray(logits[:, -1], np.float32)]
    for t in range(n_prompt, tokens.shape[1]):
        pos = jnp.full((tokens.shape[0], 1), t, jnp.int32)
        logits, caches = decode(params, jnp.asarray(tokens[:, t:t + 1]),
                                pos, caches)
        out.append(np.asarray(logits[:, 0], np.float32))
    return out


def parity(model, params, *, seed: int, n_prompt: int, capacity: int,
           interpret: bool, decode_steps: int = 2
           ) -> Tuple[List[float], List[str]]:
    """Relative logits error of the Pallas EVA path against the dequant
    oracle at prefill and each decode step."""
    from repro.core.plan import PlanPolicy

    rng = np.random.default_rng(seed + 1)
    tokens = rng.integers(0, model.cfg.vocab_size,
                          (1, n_prompt + decode_steps)).astype(np.int32)
    got = last_logits(model, params, tokens, n_prompt, capacity, PlanPolicy(
        vq_mode="eva", impl="pallas", interpret=interpret))
    with jax.default_matmul_precision("highest"):
        want = last_logits(model, params, tokens, n_prompt, capacity,
                           PlanPolicy(vq_mode="dequant"))
    errs, failures = [], []
    for step, (g, w) in enumerate(zip(got, want)):
        scale = float(np.max(np.abs(w)))
        err = float(np.max(np.abs(g - w))) / max(scale, 1e-30)
        errs.append(err)
        if not (np.isfinite(g).all() and err <= PARITY_TOL):
            failures.append(f"[parity] step {step}: relative error {err} "
                            f"> {PARITY_TOL} (oracle max |logit| {scale})")
    return errs, failures


def kernel_parity(cfg, *, seed: int, sizes: Sizes, interpret: bool
                  ) -> Tuple[Dict[str, float], List[str]]:
    """Relative error of the Pallas kernels the Planner may pick but the
    serving passes need not run (the two-kernel EVA split, the dequant
    GEMV, the KV-VQ flash decode over a paged index arena) against their
    jnp references, at this model's grouped-QKV and attention shapes."""
    from repro.core.vq import KVQuantConfig, synthetic_vq
    from repro.kernels.dequant_gemv import dequant_gemv
    from repro.kernels.flash_decode import flash_decode_kvq_paged
    from repro.kernels.oc_lookup.ops import eva_split_matmul

    keys = jax.random.split(jax.random.PRNGKey(seed + 2), 11)
    K, B, S = cfg.d_model, sizes.slots, sizes.max_len
    splits = (cfg.q_dim, cfg.kv_dim, cfg.kv_dim)
    vq = synthetic_vq(keys[0], K, sum(splits), d=cfg.vq_d, n=cfg.vq_n,
                      C=cfg.vq_C, splits=splits)
    x = jax.random.normal(keys[1], (B, K), jnp.bfloat16)
    kvq = KVQuantConfig(kv_bits=4)
    Hk, hd = cfg.num_kv_heads, cfg.head_dim
    # the engine's default 16-token blocks, each slot's blocks scattered
    # over the arena by a random block table
    W = S // 16
    arena = (B * W, 16, Hk, kvq.idx_width(hd))
    cb = (Hk, kvq.residual, kvq.entries, kvq.vec_d)
    kv_args = (
        jax.random.normal(keys[2], (B, cfg.num_heads, hd), jnp.float32),
        jax.random.randint(keys[3], arena, 0, kvq.entries).astype(jnp.uint8),
        jax.random.randint(keys[4], arena, 0, kvq.entries).astype(jnp.uint8),
        jax.random.uniform(keys[5], arena[:3], jnp.float32, 0.5, 1.5),
        jax.random.uniform(keys[6], arena[:3], jnp.float32, 0.5, 1.5),
        jax.random.permutation(keys[7], B * W).reshape(B, W),
        jax.random.randint(keys[8], (B,), 1, S + 1),
        jax.random.normal(keys[9], cb, jnp.float32),
        jax.random.normal(keys[10], cb, jnp.float32))
    cases = {
        "eva_split_pallas": lambda pallas: eva_split_matmul(
            x, vq, use_pallas=pallas, interpret=interpret,
            out_dtype=jnp.float32),
        "dequant_pallas": lambda pallas: dequant_gemv(
            x, vq, use_pallas=pallas, interpret=interpret,
            out_dtype=jnp.float32),
        "flash_decode_kvq_paged": lambda pallas: flash_decode_kvq_paged(
            *kv_args, use_pallas=pallas, interpret=interpret),
    }
    errs, failures = {}, []
    for name, call in cases.items():
        got = np.asarray(call(True), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(call(False), np.float32)
        errs[name] = float(np.max(np.abs(got - want))
                           / max(float(np.max(np.abs(want))), 1e-30))
        if not errs[name] <= KERNEL_TOL:
            failures.append(f"[kernels] {name}: relative error "
                            f"{errs[name]} > {KERNEL_TOL}")
    return errs, failures


def run(*, smoke: bool, seed: int, interpret: bool, sizes: Sizes
        ) -> List[str]:
    """Serve both passes, check logits and kernel parity; returns the
    failures."""
    from repro.configs import get_config, get_smoke_config
    from repro.models.api import build_model

    clock = CompileClock()
    cfg = get_smoke_config(ARCH) if smoke else get_config(ARCH)
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = model.init_synthetic(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    print(f"[init] {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, params {n_bytes} bytes in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)

    failures: List[str] = []
    failures += serve_pass("contiguous", smoke=smoke, seed=seed,
                           interpret=interpret, params=params,
                           requests=sizes.requests, max_new=sizes.max_new,
                           sizes=sizes, clock=clock)
    failures += serve_pass("paged kv_bits=4", smoke=smoke, seed=seed + 1,
                           interpret=interpret, params=params,
                           requests=sizes.paged_requests,
                           max_new=sizes.paged_max_new, sizes=sizes,
                           clock=clock, paged=True, kv_bits=4)
    errs, f = parity(model, params, seed=seed, n_prompt=sizes.parity_prompt,
                     capacity=sizes.max_len, interpret=interpret)
    failures += f
    print(f"[parity] relative logits error vs dequant oracle (prefill, "
          f"decode 1, decode 2): {errs}; tolerance {PARITY_TOL}")
    errs, f = kernel_parity(cfg, seed=seed, sizes=sizes, interpret=interpret)
    failures += f
    print(f"[kernels] relative error vs jnp reference: {errs}; tolerance "
          f"{KERNEL_TOL}")
    print(f"[total] XLA compile {clock.seconds:.3f} s, "
          f"peak_bytes_in_use={peak_bytes()}", flush=True)
    return failures


def main(argv: Any = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found a {dev.platform} device "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    failures = run(smoke=False, seed=args.seed, interpret=False,
                   sizes=Sizes())
    if failures:
        print("\n".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
