"""Serving driver: quantize a model to the EVA representation and serve a
synthetic request stream through the request-level continuous-batching
engine (typed submit/step/stream surface, serve/api.py).

    PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --smoke \
        --requests 8 --max-new 16 --sample
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.plan import PlanPolicy
from repro.launch.compile_cache import use_compile_cache
from repro.models.api import build_model
from repro.models.common import RunConfig
from repro.serve import Engine, EngineConfig, GenerationRequest, SamplingParams


def serve(arch: str = "llama2-7b", *, smoke: bool = True, requests: int = 8,
          max_new: int = 16, prompt_len: int = 12, min_prompt_len: int = 4,
          num_slots: int = 4, max_len: Optional[int] = None,
          vq_mode: str = "eva", quantize: bool = True,
          impl: str = "jnp", interpret: bool = False, seed: int = 0,
          sample: bool = False, temperature: float = 0.8, top_k: int = 40,
          top_p: float = 0.95, eos: Any = None, params: Any = None,
          **engine_kw: Any) -> Dict[str, Any]:
    """Drive a synthetic trace through the engine. Prompt lengths are
    drawn from [min_prompt_len, prompt_len]; ``max_len`` defaults to the
    longest request. ``sample=True`` mixes sampled requests
    (temperature/top_k/top_p, per-request seeds) among the greedy ones;
    ``eos`` adds a per-request stop token. ``params`` reuses weights built
    once for several calls; ``engine_kw`` are further EngineConfig fields
    (``paged``, ``kv_bits``, ``fault_plan``, ...)."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    if params is None:
        # synthetic EVA weights are built on the device without dense
        # weight matrices, so a full-width model fits wherever its 2-bit
        # form does
        params = model.init_synthetic(key) if quantize else model.init(key)
    rc = RunConfig(mode="decode", remat=False, attn_chunk=64,
                   plan_policy=PlanPolicy(
                       vq_mode=vq_mode if quantize else "none", impl=impl,
                       interpret=interpret))
    ecfg = EngineConfig(num_slots=num_slots,
                        max_len=max_len or prompt_len + max_new + 8,
                        **engine_kw)
    extras = {}
    if cfg.family == "whisper":
        extras["frames"] = np.asarray(
            jax.random.normal(key, (16, cfg.d_model), jnp.float32))
    if cfg.family == "vision":
        extras["image_embeds"] = np.asarray(
            jax.random.normal(key, (8, cfg.d_model), jnp.float32))
    eng = Engine(model, params, rc, ecfg, extras=extras)
    rng = np.random.default_rng(seed)
    eos_ids = () if eos is None else (int(eos),)
    reqs = []
    for i in range(requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              rng.integers(min_prompt_len, prompt_len + 1))
        sp = SamplingParams() if not sample or i % 2 == 0 else SamplingParams(
            greedy=False, temperature=temperature, top_k=top_k, top_p=top_p,
            seed=i)
        reqs.append(GenerationRequest(prompt=prompt, max_new_tokens=max_new,
                                      sampling=sp, eos_ids=eos_ids))
    t0 = time.time()
    uids = [eng.submit(r) for r in reqs]
    events = []
    while not eng.idle:
        events.extend(eng.step())
    dt = time.time() - t0
    results = {u: list(eng.output(u).tokens) for u in uids}
    total_tokens = sum(len(v) for v in results.values())
    return {
        "results": results,
        "outputs": {u: eng.output(u) for u in uids},
        "events": events,
        "metrics": eng.metrics(),
        "plans": eng.plans,
        "wall_s": dt,
        "tokens": total_tokens,
        "tok_per_s": total_tokens / max(dt, 1e-9),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--vq-mode", default="eva", choices=["eva", "dequant"])
    ap.add_argument("--no-quantize", dest="quantize", action="store_false")
    ap.add_argument("--sample", action="store_true",
                    help="mix sampled requests among the greedy ones")
    ap.add_argument("--eos", type=int, default=None,
                    help="per-request stop token id")
    args = ap.parse_args()
    use_compile_cache()
    out = serve(args.arch, smoke=args.smoke, requests=args.requests,
                max_new=args.max_new, num_slots=args.slots,
                vq_mode=args.vq_mode, quantize=args.quantize,
                sample=args.sample, eos=args.eos)
    m = out["metrics"]
    print(f"served {len(out['results'])} requests, {out['tokens']} tokens, "
          f"{out['tok_per_s']:.1f} tok/s")
    print(f"engine: admitted={m['admitted']} rejected={m['rejected']} "
          f"finished={m['finished']} (stop={m['finished_stop']} "
          f"length={m['finished_length']}) decode_steps={m['decode_steps']} "
          f"occupancy={m['slot_occupancy']:.2f}")


if __name__ == "__main__":
    main()
