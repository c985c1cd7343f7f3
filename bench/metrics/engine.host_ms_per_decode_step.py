"""engine.host_ms_per_decode_step: host time of one batched decode step
outside its device program, in ms. The engine's summed decode wall time
over the window (``decode_s``, which ends in a device readback) less the
decode program's device time in the trace, per decode step."""
from bench.lib.report import DECODE_PROGRAMS


def read(w):
    steps = w.run.delta("decode_steps")
    if w.trace is None or steps <= 0:
        return None
    device = w.trace.program_s(DECODE_PROGRAMS)
    if device <= 0:
        return None
    return 1e3 * (w.run.delta("decode_s") - device) / steps
