#!/usr/bin/env python3
"""Attribute a kept trace's device time to the engine's phases and the
decode program's named scopes.

    python bench/tools/phases.py <trace dir> [--top 15]

The trace directory is one that ``measure(keep_trace=...)`` of
``bench/run.py`` kept (``bench/tools/calibrate.py --trace 1 --dump <dir>``
keeps it under ``<dir>/trace``). Prints one JSON object:

* ``idle_s``: the window's device idle seconds by the innermost engine
  phase span on the host at the time (``engine.admit``,
  ``engine.prefill``, ``engine.decode.*``; ``null`` for none);
* ``launch_ms``, ``notice_ms``: how far the decode programs start after
  their dispatch span ends and end before their wait span ends;
* ``decode_ms_per_program``: own device ms per decode program by EVA
  kernel, by its wrapper's layout copies and by named scope
  (``kv_write``, ``attend``, ``lm_head``, ``sample``; ``null`` for
  operations under none), with ``covered``, the share of the decode
  programs' own time those hold, and ``unscoped``, the operations under
  none with the most seconds.

The scopes are read from the HLO protos the trace keeps of each compiled
module (``bench/lib/spans.py::hlo_op_names``), which the benchmark's own
reduction, working from ``jax.profiler.ProfileData``, cannot see.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SCOPES = ("kv_write", "attend", "lm_head", "sample")


def _offsets(summary):
    """Median ms from the end of each ``engine.decode.dispatch`` span to
    the start of the decode program it dispatched (``launch_ms``), and
    from the program's end to the end of the ``engine.decode.wait`` span
    that waited for it (``notice_ms``): the k-th program in the window
    against the k-th spans. A negative one means the device clock runs
    behind the host's, which shifts the idle attribution by as much."""
    import statistics

    from bench.lib.report import DECODE_PROGRAMS

    def spans_named(name):
        return sorted((e for e in summary.host_line.events
                       if e.name == name and summary._inside(e)),
                      key=lambda e: e.start_ns)

    runs = sorted((e for e in summary._executions(summary.devices[0],
                                                  DECODE_PROGRAMS)
                   if summary.t0 <= e.start_ns < summary.t1),
                  key=lambda e: e.start_ns)
    out = {}
    for key, name, gap in (
            ("launch_ms", "engine.decode.dispatch",
             lambda sp, r: r.start_ns - sp.end_ns),
            ("notice_ms", "engine.decode.wait",
             lambda sp, r: sp.end_ns - r.end_ns)):
        pairs = list(zip(spans_named(name), runs))
        out[key] = statistics.median(gap(sp, r) for sp, r in pairs) * 1e-6 \
            if pairs else None
    return out


def attribute(summary, op_names, top: int = 15):
    """The JSON object described above, from a trace summary and the
    decode programs' instruction names mapped to their op_name paths."""
    from bench.lib import spans
    from bench.lib.report import DECODE_PROGRAMS, EVA_KERNELS
    from bench.lib.trace import op_name

    by_label = spans.scope_times(summary, SCOPES, DECODE_PROGRAMS, op_names,
                                 EVA_KERNELS)
    rest: collections.Counter = collections.Counter()
    for p in summary.devices:
        for ev, own in spans.program_ops(summary, p, DECODE_PROGRAMS):
            if spans.label(ev, op_names, SCOPES, EVA_KERNELS) is None:
                path = spans.scope_path(ev, op_names)
                name = op_name(ev.name)
                rest[f"{name} [{path}]" if path else name] += own
    k = len(summary.devices)
    total = sum(by_label.values())
    steps = summary.program_count(DECODE_PROGRAMS)
    ordered = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    return {
        "window_s": summary.window_s,
        "busy_s": summary.busy_s(),
        "idle_s": {str(n): v for n, v in ordered(spans.idle_by_phase(summary))},
        **_offsets(summary),
        "decode_programs": steps,
        "decode_ms_per_program": {str(n): 1e3 * v / steps
                                  for n, v in ordered(by_label)}
        if steps else {},
        "covered": 1 - by_label.get(None, 0.0) / total if total else None,
        "unscoped": [[n, ns / k * 1e-9] for n, ns in rest.most_common(top)],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args(argv)

    from bench.lib import spans, trace
    from bench.lib.report import DECODE_PROGRAMS

    path = trace.find_xplane(args.trace_dir)
    summary = trace.Summary(trace.load(path))
    modules = spans.hlo_op_names(path)
    op_names = {k: v for p in DECODE_PROGRAMS
                for k, v in modules.get(f"jit_{p}", {}).items()}
    print(json.dumps(attribute(summary, op_names, args.top), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
