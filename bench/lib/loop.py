"""Build the system under test and drive it through one closed-loop run.

The window drives ``repro.serve.Engine`` (``submit``/``step``) built as
``repro.launch.serve.serve`` builds it: ``Model.init_synthetic`` from the
seed, ``RunConfig(mode="decode", attn_chunk=64)`` and the Pallas EVA
plan policy, with the engine settings of the traffic file.

Set-up warms every prefill bucket the mix can use that the first wave
does not, admits the first request of every client, runs the first
engine step (all first prefills and the first decode step) and collects
the garbage set-up left. The window
opens when set-up ends and closes at the end of the first ``step`` that
returns after ``seconds``. Every token event is stamped with the host
time at which the step that carried it returned (``step`` ends in a
device readback, so it is synchronous).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from bench.lib.traffic import Request, Traffic


@dataclasses.dataclass
class Sent:
    req: Request
    uid: int
    # when and whether inside the window the client sent it: what a
    # time-to-first-token reader of a prefill-heavy cell reads
    t_submit: float
    in_window: bool
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    finish: Optional[str] = None


@dataclasses.dataclass
class Step:
    t_end: float
    decode_contexts: List[int]   # per decode token: positions it attended


@dataclasses.dataclass
class Run:
    t_open: float = 0.0
    t_close: float = 0.0
    setup_s: float = 0.0
    sent: Dict[int, Sent] = dataclasses.field(default_factory=dict)
    steps: List[Step] = dataclasses.field(default_factory=list)
    counters_open: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters_close: Dict[str, float] = dataclasses.field(default_factory=dict)
    warmed_buckets: List[int] = dataclasses.field(default_factory=list)
    peak_bytes: Optional[int] = None
    # host clock at the end of each set-up phase, from process start
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def delta(self, name: str) -> float:
        return self.counters_close[name] - self.counters_open[name]


def model_config(conf: Dict[str, Any]):
    """The program's ModelConfig, with every size the configuration
    file states. Refuses what the program cannot run as stated."""
    from repro.configs import get_config

    c, s = conf["config"], conf["serving"]
    runs = {"hidden_act": "silu", "mlp": "gated", "normalization": "rmsnorm",
            "partial_rotary_factor": 1.0}
    for k, v in runs.items():
        if c[k] != v:
            raise ValueError(f"the program's dense block runs {k}={v!r}; "
                             f"the configuration states {c[k]!r}")
    base = get_config(conf["program_arch"])
    if base.family != conf["family"]:
        raise ValueError(f"{conf['program_arch']} is a {base.family!r} "
                         f"model, the configuration states {conf['family']!r}")
    return dataclasses.replace(
        base, num_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]), d_ff=int(c["intermediate_size"]),
        num_heads=int(c["num_attention_heads"]),
        num_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]), vocab_size=int(c["vocab_size"]),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        qkv_bias=bool(c["attention_bias"]), sliding_window=0,
        vq_C=int(s["vq_C"]), vq_d=int(s["vq_d"]), vq_n=int(s["vq_n"]),
        dtype=s["dtype"])


def build(conf: Dict[str, Any], mix: Dict[str, Any], seed: int, *,
          interpret: bool = False, phases: Optional[Dict[str, float]] = None,
          t_start: float = 0.0):
    """The engine for one run, its weights made on the device from the
    seed in one jitted call. ``phases`` gets the host clock (from
    ``t_start``) at which the weights and the engine were ready."""
    from repro.core.plan import PlanPolicy
    from repro.models.api import build_model
    from repro.models.common import RunConfig
    from repro.serve import Engine, EngineConfig

    model = build_model(model_config(conf))
    params = model.init_synthetic(jax.random.PRNGKey(int(seed) % 2 ** 32))
    jax.block_until_ready(params)
    if phases is not None:
        phases["weights"] = time.perf_counter() - t_start
    rc = RunConfig(mode="decode", remat=False, attn_chunk=64,
                   plan_policy=PlanPolicy(vq_mode="eva", impl="pallas",
                                          interpret=interpret))
    eng = Engine(model, params, rc, EngineConfig(**mix["engine"]))
    if phases is not None:
        phases["engine"] = time.perf_counter() - t_start
    return eng


def _request(r: Request):
    from repro.serve import GenerationRequest, SamplingParams

    sp = SamplingParams() if r.greedy else SamplingParams(
        greedy=False, temperature=r.temperature, top_k=r.top_k,
        top_p=r.top_p, seed=r.seed)
    return GenerationRequest(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                             sampling=sp)


def _span(name: str, on: bool):
    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


def drive(eng, traffic: Traffic, seconds: float, *, t_start: float,
          annotate: bool = False, on_open=None,
          phases: Optional[Dict[str, float]] = None) -> Run:
    """Set up, run one window of ``seconds``, and return what it saw.
    ``on_open`` runs just before the window opens (the profiler starts
    there); ``annotate`` writes host spans into the profiler's trace;
    ``phases`` are set-up phases already timed (``build``)."""
    from repro.serve import api

    run = Run(phases=dict(phases or {}))
    buckets = api.prefill_buckets(eng.ecfg.max_len, eng.ecfg.min_prefill_bucket)
    bucket_for = lambda n: api.bucket_for(n, buckets)  # noqa: E731
    first = [traffic.next() for _ in range(traffic.clients)]
    p_max = int(traffic.mix["prompt_len"]["max"])
    covered = {bucket_for(r.prompt.size) for r in first}
    for b in traffic.buckets(bucket_for):
        if b in covered:
            continue
        n = min(b, p_max)
        eng.submit(_request(dataclasses.replace(
            first[0], prompt=np.resize(first[0].prompt, n),
            max_new_tokens=1)))
        run.warmed_buckets.append(b)
    while not eng.idle:
        eng.step()
    run.phases["warm_buckets"] = time.perf_counter() - t_start

    def submit(r: Request, in_window: bool) -> None:
        with _span("bench.submit", annotate):
            t = time.perf_counter()
            uid = eng.submit(_request(r))
        run.sent[uid] = Sent(r, uid, t, in_window)

    def record(events, t: float) -> List[Sent]:
        step = Step(t, [])
        done = []
        for ev in events:
            s = run.sent.get(ev.uid)
            if s is None:
                continue
            if ev.token is not None:
                s.tokens.append(int(ev.token))
                s.times.append(t)
                if ev.index > 0:
                    step.decode_contexts.append(s.req.prompt.size + ev.index)
            if ev.finish_reason is not None:
                s.finish = ev.finish_reason
                done.append(s)
        run.steps.append(step)
        return done

    for r in first:
        submit(r, False)
    record(eng.step(), time.perf_counter())
    run.steps.clear()
    run.phases["first_wave"] = time.perf_counter() - t_start
    # set-up's garbage is collected in set-up: a full collection of
    # everything set-up left would otherwise fall inside the window
    gc.collect()
    if on_open is not None:
        on_open()
    run.counters_open = eng.metrics()
    run.t_open = time.perf_counter()
    run.setup_s = run.t_open - t_start
    with _span("bench.window", annotate):
        while True:
            with _span("bench.step", annotate):
                events = eng.step()
            t = time.perf_counter()
            done = record(events, t)
            if t - run.t_open >= seconds:
                break
            for _ in done:
                submit(traffic.next(), True)
    run.t_close = t
    run.counters_close = eng.metrics()
    return run
