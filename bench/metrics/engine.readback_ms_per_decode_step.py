"""engine.readback_ms_per_decode_step: host time the engine spends
reading a decode step's outputs back to the host once the device is done
(its ``engine.decode.readback`` span), per decode step in the traced
window, in ms."""
from bench.lib import spans


def read(w):
    if w.trace is None:
        return None
    t = spans.host_spans(w.trace, "engine.decode.readback")
    return 1e3 * sum(t) / len(t) if t else None
