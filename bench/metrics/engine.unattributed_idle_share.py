"""engine.unattributed_idle_share: the share of the traced window's
device idle time during which the host was inside no engine phase span
(``engine.admit``, ``engine.prefill``, ``engine.decode.*``; the
``engine.step`` around them does not count), in percent: idle time the
program's own spans cannot explain."""
from bench.lib import spans


def read(w):
    if w.trace is None or not any(
            spans.is_phase(e.name) for e in w.trace.host_line.events):
        return None
    idle = spans.idle_by_phase(w.trace)
    total = sum(idle.values())
    return 100.0 * idle.get(None, 0.0) / total if total > 0 else None
