"""The trace reduction on a constructed trace with known answers, and the
loader on a trace recorded on the CPU."""
import glob

import pytest

import bench_smoke as smoke
from bench.lib import report, trace
from bench.lib.loop import Run, Step
from bench.lib.trace import Event, Line, Plane, Summary

MS = 1e6  # ns


def _host(events):
    return Plane("/host:CPU", [Line("python3", events)])


def _device(name, ops, modules):
    return Plane(name, [Line("XLA Modules", modules), Line("XLA Ops", ops)])


def _op(name, start_ms, dur_ms):
    return Event(name, start_ms * MS, dur_ms * MS)


def _hlo(name, shape, kind="fusion"):
    return f"%{name} = {shape}{{1,0:T(8,128)}} {kind}(%p.1), calls=%c"


def _planes():
    host = _host([
        Event("bench.window", 10 * MS, 100 * MS),
        Event("bench.step", 10 * MS, 50 * MS),
        Event("PjitFunction(_decode_impl)", 12 * MS, 1 * MS),
        Event("bench.step", 60 * MS, 50 * MS),
        Event("PjitFunction(_prefill_impl)", 61 * MS, 1 * MS),
        Event("PjitFunction(_decode_impl)", 70 * MS, 1 * MS),
        Event("PjitFunction(convert)", 93 * MS, 1 * MS),
        Event("bench.submit", 106 * MS, 2 * MS),
    ])
    fused16 = _hlo("fused_vq_matmul.4", "f32[16,5120]", "custom-call")
    dev = _device("/device:TPU:0", [
        _op(_hlo("fusion.1", "f32[8]"), 5, 10),             # clipped to [10, 15]
        _op("%while.1 = (s32[], f32[16,3072]{1,0}) while(%t)", 20, 30),
        _op(fused16, 22, 20),                                # inside the while
        _op(_hlo("fusion.2", "bf16[16,3072]"), 42, 5),       # inside the while
        _op(_hlo("fused_vq_matmul.9", "f32[128,5120]", "custom-call"), 63, 5),
        _op(fused16, 72, 20),
        _op(_hlo("fusion.3", "f32[8]"), 95, 10),
        _op("late", 120, 5),                                 # after the window
    ], [
        Event("jit__unknown(7)", 18 * MS, 34 * MS),          # decode
        Event("jit__unknown(3)", 63 * MS, 6 * MS),           # prefill
        Event("jit__unknown(7)", 72 * MS, 20 * MS),          # decode
        Event("jit_convert(5)", 95 * MS, 10 * MS),
    ])
    return [host, dev]


def test_op_names():
    assert trace.op_name(_hlo("fusion.3", "bf16[16,3072]")) == \
        "fusion.3 bf16[16,3072]"
    assert trace.op_name("%while.1 = (s32[], f32[2]{0}) while(%t)") == \
        "while.1"
    assert trace.op_name("late") == "late"


def test_window_busy_and_idle():
    s = Summary(_planes())
    assert s.window_s == pytest.approx(0.100)
    # busy: [10,15] + [20,50] + [63,68] + [72,92] + [95,105]
    assert s.busy_s() == pytest.approx(0.070)
    # idle gaps: [15,20] [50,63] [68,72] [92,95] [105,110]; longest first,
    # each labelled with the innermost host span at its middle
    gaps = s.idle_gaps(10)
    assert [round(g[1], 6) for g in gaps] == [0.013, 0.005, 0.005, 0.004,
                                              0.003]
    assert gaps[0][0] == "bench.step"
    assert {g[0] for g in gaps if round(g[1], 6) == 0.005} == \
        {"bench.step", "bench.submit"}
    assert s.idle_gaps(1) == [gaps[0]]


def test_programs_are_named_by_their_dispatch():
    s = Summary(_planes())
    assert s.program_s(["_decode_impl"]) == pytest.approx(0.054)
    assert s.program_count(["_decode_impl"]) == 2
    assert s.program_s(["_prefill_impl"]) == pytest.approx(0.006)
    assert s.program_count(["_prefill_impl"]) == 1


def test_kernels_count_their_own_time_inside_a_program():
    s = Summary(_planes())
    assert s.kernel_s(["fused_vq_matmul"], ["_decode_impl"]) == \
        pytest.approx(0.040)
    assert s.kernel_s(["fused_vq_matmul"], ["_prefill_impl"]) == \
        pytest.approx(0.005)
    assert s.kernel_s(["oc_lookup"], ["_decode_impl"]) == 0.0


def test_top_ops_by_own_time_with_their_program():
    s = Summary(_planes())
    top = dict((name, t) for name, t in s.top_ops(10))
    assert top["_decode_impl/fused_vq_matmul.4 f32[16,5120]"] == \
        pytest.approx(0.040)
    assert top["_decode_impl/while.1"] == pytest.approx(0.005)
    assert top["fusion.1 f32[8]"] == pytest.approx(0.005)  # in no program
    assert s.top_ops(2) == [
        ["_decode_impl/fused_vq_matmul.4 f32[16,5120]", pytest.approx(0.040)],
        ["convert/fusion.3 f32[8]", pytest.approx(0.010)]]
    assert "late" not in top


def test_two_devices_are_averaged():
    planes = _planes()
    planes.append(_device("/device:TPU:1", [_op("x", 10, 100)], []))
    s = Summary(planes)
    assert s.busy_s() == pytest.approx((0.070 + 0.100) / 2)


def test_window_span_required():
    with pytest.raises(ValueError, match="bench.window"):
        Summary([_host([]), _device("/device:TPU:0", [], [])])


def _window(trace_summary, run=None, mix=None):
    conf = smoke.conf()
    run = run or Run(t_open=0.0, t_close=1.0)
    run.counters_open = {"decode_steps": 0, "decode_s": 0.0}
    run.counters_close = {"decode_steps": 2, "decode_s": 0.070}
    return report.Window("c", conf, mix or smoke.mix(), run,
                         {"bf16_flop_per_s": 197e12,
                          "hbm_bytes_per_s": 819e9}, trace_summary)


def test_readers_on_the_constructed_trace():
    from bench.lib import registry, work

    w = _window(Summary(_planes()))
    idle = registry.metric_reader("device_idle_share").read(w)
    assert idle == pytest.approx(30.0)
    host = registry.metric_reader("engine.host_ms_per_decode_step").read(w)
    assert host == pytest.approx((70.0 - 54.0) / 2)
    share = registry.metric_reader("eva_vq_roofline.decode").read(w)
    least = work.vq_least_seconds(w.shape, 2, w.peak)
    assert share == pytest.approx(100.0 * 2 * least / 0.040)


def test_roofline_is_100_when_the_kernel_takes_the_least_time():
    """A kernel that ran exactly its least time reads 100%, never more."""
    from bench.lib import registry, work

    w = _window(None)
    least = work.vq_least_seconds(w.shape, 2, w.peak)
    planes = _planes()
    dev = planes[1]
    dev.line("XLA Ops").events = [Event(
        _hlo("fused_vq_matmul.4", "f32[2,5120]", "custom-call"),
        20 * MS, least * 1e9)]
    dev.line("XLA Modules").events = [
        Event("jit__unknown(7)", 20 * MS, least * 1e9)]
    w.trace = Summary(planes)
    assert registry.metric_reader("eva_vq_roofline.decode").read(w) == \
        pytest.approx(100.0)


def test_readers_read_nothing_without_a_trace():
    from bench.lib import registry

    w = _window(None)
    for name in ("device_idle_share", "engine.host_ms_per_decode_step",
                 "eva_vq_roofline.decode"):
        assert registry.metric_reader(name).read(w) is None


def test_mfu_counts_active_rows_and_contexts():
    from bench.lib import registry, work

    run = Run(t_open=0.0, t_close=1.0)
    run.steps = [Step(0.5, [10, 20]), Step(1.0, [11])]
    w = _window(None, run)
    mfu = registry.metric_reader("decode_mfu").read(w)
    flops = work.decode_flops(w.shape, [10, 20, 11])
    assert mfu == pytest.approx(100.0 * flops / (0.070 * 197e12))


def test_load_reads_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    trace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    planes = trace.load(trace.find_xplane(str(tmp_path)))
    spans = [(ln, e) for p in planes for ln in p.lines for e in ln.events
             if e.name == trace.WINDOW_SPAN]
    assert len(spans) == 1 and spans[0][1].dur_ns > 0
    # the dispatch span that names a program shares the window's thread;
    # Python's function tracer is off
    names = {e.name for e in spans[0][0].events}
    assert "PjitFunction(<lambda>)" in names
    assert not any(n.startswith("$") for n in names)
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
