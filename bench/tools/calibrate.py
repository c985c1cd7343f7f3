#!/usr/bin/env python3
"""Run one cell on several seeds in one process, reading the control
beside the program, to set the cell's limits of `correct`.

    python bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--control 1] [--trace 0|1] [--dump <dir>]

Each seed runs the same path as ``bench/run.py`` (set-up, window, check
against the reference) and prints one JSON line: the program's readings
and ``correct``, the metrics and the compile counts, and with
``--control 1`` the control's readings and its own ``correct`` under the
cell's limits (``control``), which has to be false on every seed. ``--dump`` keeps the first seed's trace there and writes
a listing of its planes, lines and heaviest device events beside it,
for reading the trace by hand. Like the benchmark, it refuses a device
that is not a TPU.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "bench"), str(ROOT / "src")]


def listing(trace_dir: str) -> str:
    from bench.lib import trace

    planes = trace.load(trace.find_xplane(trace_dir))
    out = []
    for p in planes:
        out.append(f"PLANE {p.name}")
        for ln in p.lines:
            out.append(f"  LINE {ln.name}: {len(ln.events)} events")
            tot = {}
            first = {}
            for e in ln.events:
                tot[e.name] = tot.get(e.name, 0.0) + e.dur_ns
                first.setdefault(e.name, e)
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:25]:
                e = first[name]
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in e.stats.items()}
                out.append(f"    {ns * 1e-6:12.3f} ms  {name[:120]}  {stats}")
    return "\n".join(out)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args()

    import run as bench_run

    from bench.lib import registry

    cell = registry.cell(args.workload)
    if bench_run.chips_or_none(int(cell["chips"])) is None:
        return 2
    t_start = T0
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        keep = None
        if args.dump and i == 0 and args.trace:
            keep = os.path.join(args.dump, "trace")
        result, lines = bench_run.measure(
            args.workload, seed, args.seconds, bool(args.trace),
            control=bool(args.control), keep_trace=keep, t_start=t_start)
        if keep:
            Path(args.dump, "listing.txt").write_text(listing(keep))
        sys.stderr.write("\n".join(lines) + "\n")
        print(json.dumps({"seed": seed, **result}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
