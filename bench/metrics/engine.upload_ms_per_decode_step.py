"""engine.upload_ms_per_decode_step: host time the engine spends building
a decode step's device arguments (its ``engine.decode.upload`` span), per
decode step in the traced window, in ms."""
from bench.lib import spans


def read(w):
    if w.trace is None:
        return None
    t = spans.host_spans(w.trace, "engine.decode.upload")
    return 1e3 * sum(t) / len(t) if t else None
