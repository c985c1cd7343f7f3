"""What one run saw, as the metric readers read it.

Each metric of `BENCHMARK.json` has a reader ``bench/metrics/<name>.py``
whose ``read(window)`` returns its value, or None where this run holds
nothing to read it from (the harness then leaves the metric out).
``Window`` is what they read: the run's host-clock record, the engine's
counters at the window's two ends, the trace summary of a traced run,
the configuration's shape and the chip's peaks.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np

from bench.lib import registry, work
from bench.lib.loop import Run
from bench.lib.trace import Summary

# the program's jitted serving steps, by the function that dispatches
# them (the host span ``PjitFunction(<name>)``), and its EVA kernels, by
# the HLO names of their custom calls in the device trace
DECODE_PROGRAMS = ("_decode_impl",)
EVA_KERNELS = ("fused_vq_matmul", "oc_lookup", "vq_gemm")


@dataclasses.dataclass
class Window:
    cell: str
    conf: Dict[str, Any]
    mix: Dict[str, Any]
    run: Run
    peak: Dict[str, Any]
    trace: Optional[Summary] = None

    @property
    def shape(self) -> work.Shape:
        return work.Shape(self.conf)

    def tokens(self) -> int:
        """Token-bearing events returned inside the window."""
        return sum(sum(1 for t in s.times if t > self.run.t_open)
                   for s in self.run.sent.values())

    def itl_ms(self) -> List[float]:
        """Every gap between consecutive tokens of one request, both
        returned inside the window."""
        out = []
        for s in self.run.sent.values():
            ts = [t for t in s.times if t > self.run.t_open]
            out += [1e3 * (b - a) for a, b in zip(ts, ts[1:])]
        return out


def p95(values: List[float]) -> Optional[float]:
    return float(np.percentile(values, 95)) if values else None


def read_metrics(window: Window, metrics: List[Dict[str, Any]]
                 ) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in metrics:
        value = registry.metric_reader(m["name"]).read(window)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
