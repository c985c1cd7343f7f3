"""The reduction of the program's own spans and named scopes
(bench/lib/spans.py), its readers and bench/tools/phases.py, on a
constructed trace with known answers, and the HLO op_name reader on a
trace recorded on the CPU."""
import importlib.util
import re

import pytest

import bench_smoke as smoke
from bench.lib import registry, report, spans, trace
from bench.lib.loop import Run
from bench.lib.trace import Event, Line, Plane, Summary

MS = 1e6  # ns

_spec = importlib.util.spec_from_file_location(
    "bench_phases", registry.BENCH / "tools" / "phases.py")
phases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(phases)


def _ev(name, start_ms, dur_ms, **stats):
    return Event(name, start_ms * MS, dur_ms * MS, stats)


def _hlo(name, shape="f32[16]", kind="fusion"):
    return f"%{name} = {shape}{{0}} {kind}(%p.1), calls=%c"


def _step(t, tick, prefill=False):
    """One engine tick starting at ``t`` ms: admit (with a prefill inside
    it when ``prefill``), then a decode step whose program runs on the
    device from t+4 to t+14 (see ``_planes``)."""
    evs = [_ev("engine.step", t, 20, step_num=tick),
           _ev("engine.admit", t, 2)]
    if prefill:
        evs.append(_ev("engine.prefill", t + 0.5, 1, uid=7, tokens=12))
    evs += [_ev("engine.decode.upload", t + 2, 1),
            _ev("engine.decode.dispatch", t + 3, 1),
            _ev("PjitFunction(_decode_impl)", t + 3.2, 0.5),
            _ev("engine.decode.wait", t + 4, 10),
            _ev("engine.decode.readback", t + 14, 3),
            _ev("engine.decode.emit", t + 17, 2)]
    return evs


def _planes():
    """A 40-ms window (10..50) holding two ticks (10..30, 30..50). Each
    decode program runs on the device from t+4 to t+14: a while op
    holding the kernel and the scoped ops, then the sampler."""
    host = Plane("/host:CPU", [Line("python3", [
        _ev("bench.window", 10, 40), _ev("bench.step", 10, 20),
        *_step(10, 1, prefill=True), _ev("bench.step", 30, 20),
        *_step(30, 2)])])
    ops, modules = [], []
    for t in (10, 30):
        ops += [
            _ev("%while.1 = (s32[]) while(%t)", t + 4, 6),
            _ev(_hlo("fused_vq_matmul.4", kind="custom-call"), t + 4, 2),
            _ev(_hlo("fusion.96"), t + 6, 1),          # kv_write
            _ev(_hlo("pad.64", kind="pad"), t + 7, 1),  # attend
            _ev(_hlo("flash_decode.6", kind="custom-call"), t + 8, 1),
            _ev(_hlo("fusion.7"), t + 10, 1),           # lm_head
            _ev(_hlo("sort.5", kind="sort"), t + 11, 2),  # sample
            _ev(_hlo("copy.48", kind="copy"), t + 13, 1),  # no metadata
        ]
        modules.append(_ev("jit__decode_impl(7)", t + 4, 10))
    dev = Plane("/device:TPU:0", [Line("XLA Modules", modules),
                                  Line("XLA Ops", ops)])
    return [host, dev]


OP_NAMES = {
    "while.1": "jit(_decode_impl)/while",
    "fused_vq_matmul.4":
        "jit(_decode_impl)/while/body/closed_call/jit(fused_vq_matmul)/"
        "pallas_call",
    "fusion.96": "jit(_decode_impl)/while/body/closed_call/kv_write/scatter",
    "pad.64": "jit(_decode_impl)/while/body/closed_call/attend/"
              "jit(flash_decode)/jit(_pad)/pad",
    "flash_decode.6": "jit(_decode_impl)/while/body/closed_call/attend/"
                      "jit(flash_decode)/pallas_call",
    "fusion.7": "jit(_decode_impl)/lm_head/dot_general",
    "sort.5": "jit(_decode_impl)/sample/jit(sort)/sort",
}
SCOPES = ("kv_write", "attend", "lm_head", "sample")


def _window(summary):
    run = Run(t_open=0.0, t_close=1.0)
    run.counters_open = {"decode_steps": 0, "decode_s": 0.0}
    run.counters_close = {"decode_steps": 2, "decode_s": 0.040}
    return report.Window("c", smoke.conf(), smoke.mix(), run,
                         {"bf16_flop_per_s": 197e12,
                          "hbm_bytes_per_s": 819e9}, summary)


def test_host_spans_are_summed_inside_the_window():
    s = Summary(_planes())
    assert spans.host_spans(s, "engine.decode.readback") == \
        [pytest.approx(0.003)] * 2
    assert spans.host_spans(s, "engine.prefill") == [pytest.approx(0.001)]
    assert spans.host_spans(s, "engine.no-such-span") == []
    planes = _planes()
    planes[0].lines[0].events.append(_ev("engine.decode.upload", 48, 4))
    planes[0].lines[0].events.append(_ev("engine.decode.upload", 60, 4))
    # clipped to the window's end at 50; the one after it is left out
    assert sorted(spans.host_spans(Summary(planes),
                                   "engine.decode.upload")) == \
        [pytest.approx(0.001)] * 2 + [pytest.approx(0.002)]


def test_phase_spans_leave_the_step_out():
    assert spans.is_phase("engine.admit")
    assert spans.is_phase("engine.decode.wait")
    assert not spans.is_phase("engine.step")
    assert not spans.is_phase("bench.step")


def test_idle_time_by_innermost_phase():
    s = Summary(_planes())
    idle = spans.idle_by_phase(s)
    # per tick the device idles 4 ms before the program (admit 2, of which
    # 1 inside the prefill on the first tick; upload 1; dispatch 1) and 6
    # after it (readback 3, emit 2, 1 under no phase span)
    assert idle["engine.prefill"] == pytest.approx(0.001)
    assert idle["engine.admit"] == pytest.approx(0.003)
    assert idle["engine.decode.upload"] == pytest.approx(0.002)
    assert idle["engine.decode.dispatch"] == pytest.approx(0.002)
    assert idle["engine.decode.readback"] == pytest.approx(0.006)
    assert idle["engine.decode.emit"] == pytest.approx(0.004)
    assert idle[None] == pytest.approx(0.002)
    assert "engine.decode.wait" not in idle
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s())


def test_the_engine_readers_on_the_constructed_trace():
    w = _window(Summary(_planes()))
    read = lambda n: registry.metric_reader(n).read(w)  # noqa: E731
    assert read("engine.upload_ms_per_decode_step") == pytest.approx(1.0)
    assert read("engine.readback_ms_per_decode_step") == pytest.approx(3.0)
    assert read("engine.unattributed_idle_share") == \
        pytest.approx(100.0 * 2 / 20)


def test_the_engine_readers_read_nothing_without_the_spans():
    """A program without the spans (the parent of this reduction) reads
    None, never raises: its traced run leaves the metrics out."""
    for name in ("engine.upload_ms_per_decode_step",
                 "engine.readback_ms_per_decode_step",
                 "engine.unattributed_idle_share"):
        assert registry.metric_reader(name).read(_window(None)) is None
        planes = _planes()
        planes[0].lines[0].events = [
            e for e in planes[0].lines[0].events
            if not e.name.startswith("engine.")]
        assert registry.metric_reader(name).read(
            _window(Summary(planes))) is None


def test_scopes_by_op_name_and_own_time():
    s = Summary(_planes())
    t = spans.scope_times(s, SCOPES, ["_decode_impl"], OP_NAMES,
                          kernels=("fused_vq_matmul",))
    assert t["fused_vq_matmul"] == pytest.approx(0.004)
    assert t["kv_write"] == pytest.approx(0.002)
    # flash_decode is a custom call too, but not one of the kernels given:
    # its path puts it under attend, beside the wrapper's pad
    assert t["attend"] == pytest.approx(0.004)
    assert t["lm_head"] == pytest.approx(0.002)
    assert t["sample"] == pytest.approx(0.004)
    # the while's own time (6 less the 5 of its body) and the copy
    # without metadata are under no scope
    assert t[None] == pytest.approx(0.002 + 0.002)
    assert sum(t.values()) == pytest.approx(s.program_s(["_decode_impl"]))
    # without the map, everything but the named kernels is under none
    bare = spans.scope_times(s, SCOPES, ["_decode_impl"], {},
                             kernels=("fused_vq_matmul",))
    assert set(bare) == {"fused_vq_matmul", None}
    assert spans.scope_times(s, SCOPES, ["_prefill_impl"], OP_NAMES) == {}


def test_a_scope_is_a_whole_path_component():
    assert spans.scope_of("jit(f)/while/body/attend/pad", SCOPES) == "attend"
    assert spans.scope_of("jit(f)/sample/jit(sort)/sort", SCOPES) == "sample"
    assert spans.scope_of("jit(f)/attend/x/kv_write/scatter", SCOPES) == \
        "kv_write"
    assert spans.scope_of("jit(f)/resample/sort", SCOPES) is None
    assert spans.scope_of("jit(sample)/sort", SCOPES) is None
    assert spans.scope_of("", SCOPES) is None


def test_phases_tool_on_the_constructed_trace():
    out = phases.attribute(Summary(_planes()), OP_NAMES)
    assert out["decode_programs"] == 2
    per = out["decode_ms_per_program"]
    assert per["kv_write"] + per["attend"] == pytest.approx(3.0)
    assert per["sample"] == pytest.approx(2.0)
    assert per["lm_head"] == pytest.approx(1.0)
    assert per["fused_vq_matmul"] == pytest.approx(2.0)
    assert out["covered"] == pytest.approx(16 / 20)
    assert out["idle_s"]["None"] == pytest.approx(0.002)
    # programs start when their dispatch span ends and end when their
    # wait span does
    assert out["launch_ms"] == pytest.approx(0.0)
    assert out["notice_ms"] == pytest.approx(0.0)
    assert [n for n, _ in out["unscoped"]] == [
        "while.1 [jit(_decode_impl)/while]", "copy.48 f32[16]"]


def test_kernel_wrappers_count_beside_their_kernel():
    ev = Event(_hlo("copy.97", kind="copy"), 0, 1)
    names = {"copy.97": "jit(_decode_impl)/while/body/closed_call/"
                        "jit(fused_vq_matmul)/transpose"}
    assert spans.label(ev, names, SCOPES, ("fused_vq_matmul",)) == \
        "fused_vq_matmul wrapper"
    assert spans.label(ev, names, SCOPES) is None


def test_hlo_op_names_from_a_recorded_cpu_trace(tmp_path):
    """The op_name of every instruction of a compiled module, read from
    the HLO protos in the trace file, matches the compiled HLO."""
    import functools

    import jax
    import jax.numpy as jnp

    def impl(x, *, k):
        with jax.named_scope("attend"):
            y = jnp.tanh(x @ x) * k
        with jax.named_scope("sample"):
            return jnp.sort(y, axis=-1)

    fn = functools.partial(impl, k=2.0)
    fn.__name__ = impl.__name__
    f = jax.jit(fn)
    x = jnp.ones((32, 32))
    hlo = f.lower(x).compile().as_text()
    f(x).block_until_ready()
    trace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    names = spans.hlo_op_names(trace.find_xplane(str(tmp_path)))["jit_impl"]
    want = dict(re.findall(r'%(\S+) = [^\n]*?op_name="([^"]*)"', hlo))
    assert want and names == {k: v for k, v in want.items() if v}
    assert {spans.scope_of(p, SCOPES) for p in names.values()} >= \
        {"attend", "sample"}
