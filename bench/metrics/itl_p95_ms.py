"""itl_p95_ms: 95th percentile of every gap between consecutive tokens
of one request, both returned inside the window (host clock)."""
from bench.lib.report import p95


def read(w):
    return p95(w.itl_ms())
