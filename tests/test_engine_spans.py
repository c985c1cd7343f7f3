"""The serving engine's trace instrumentation at smoke widths on the CPU:
its jitted steps compile under their own names, with the named scopes by
which a profiler trace attributes device time, and each tick writes its
host spans into a running profiler trace."""
import dataclasses
import glob
import re

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.models.common import RunConfig
from repro.serve import Engine, EngineConfig, GenerationRequest

SCOPES = ("kv_write", "attend", "lm_head", "sample")
PHASES = ["engine.step", "engine.admit", "engine.prefill",
          "engine.decode.upload", "engine.decode.dispatch",
          "engine.decode.wait", "engine.decode.readback",
          "engine.decode.emit"]


@pytest.fixture(scope="module")
def engine():
    cfg = dataclasses.replace(get_smoke_config("llama2_7b"), dtype="float32")
    model = build_model(cfg)
    return Engine(model, model.init(jax.random.PRNGKey(0)),
                  RunConfig(mode="decode", remat=False, attn_chunk=16),
                  EngineConfig(num_slots=2, max_len=32))


def _request(n=5):
    return GenerationRequest(prompt=np.arange(1, n + 1, dtype=np.int32),
                             max_new_tokens=3)


def _drain(eng):
    for _ in range(50):
        if eng.idle:
            return
        eng.step()
    raise AssertionError("engine did not drain")


def test_steps_are_jitted_under_their_own_names(engine):
    assert engine._decode_fn.__name__ == "_decode_impl"
    assert engine._prefill_fn.__name__ == "_prefill_impl"


def test_decode_program_is_named_and_scoped(engine):
    seen = []
    inner = engine._decode_fn

    def spy(*args):
        seen.append(args)
        return inner(*args)

    engine._decode_fn = spy
    try:
        engine.submit(_request())
        _drain(engine)
    finally:
        engine._decode_fn = inner
    hlo = inner.lower(*seen[0]).compile().as_text()
    assert hlo.startswith("HloModule jit__decode_impl,")
    paths = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in SCOPES:
        assert any(scope in p.split("/") for p in paths), scope
    assert not any("<unknown>" in p for p in paths)


def test_one_traced_step_records_the_engine_spans(engine, tmp_path):
    from jax.profiler import ProfileData

    _drain(engine)
    uid = engine.submit(_request(6))
    tick = engine._tick
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        events = engine.step()
    finally:
        jax.profiler.stop_trace()
    assert [e.uid for e in events] == [uid, uid]  # prefill + decode token
    _drain(engine)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = [ln for p in ProfileData.from_file(path).planes
             for ln in p.lines
             if any(e.name == "engine.step" for e in ln.events)]
    assert len(lines) == 1
    evs = sorted(lines[0].events, key=lambda e: (e.start_ns, -e.duration_ns))
    spans = [e for e in evs if e.name.startswith("engine.")]
    assert [e.name for e in spans] == PHASES
    stats = {e.name: dict(e.stats) for e in spans}
    assert stats["engine.step"]["step_num"] == tick
    assert stats["engine.prefill"]["uid"] == uid
    assert stats["engine.prefill"]["tokens"] == 6
    # the prefill runs inside the admission, every span inside the step
    step, admit, prefill = spans[:3]
    end = lambda e: e.start_ns + e.duration_ns  # noqa: E731
    assert admit.start_ns <= prefill.start_ns and end(prefill) <= end(admit)
    assert all(end(e) <= end(step) for e in spans)
    # the decode program is still dispatched as PjitFunction(_decode_impl),
    # inside the dispatch span
    dispatch = spans[PHASES.index("engine.decode.dispatch")]
    assert any(e.name == "PjitFunction(_decode_impl)"
               and dispatch.start_ns <= e.start_ns < end(dispatch)
               for e in evs)
