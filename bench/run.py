#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a `workloads` entry of BENCHMARK.json) names a configuration
(`bench/configs/<config>.json`) and a traffic mix
(`bench/traffic/<traffic>.json`). Weights and traffic come from
``--seed``. Set-up builds the weights on the device, the engine, warms
every program the window runs and admits every client's first request;
then the window measures ``--seconds``. With ``--trace 1`` the window
runs under the JAX profiler and the run reports the cell's per-layer
metrics instead of its end-to-end ones. Every run then checks what the
window served against the plain reference (bench/lib/check.py).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``checks``, each number compared beside its
limit; the same numbers close standard error. The run exits non-zero
and prints no result on a device that is not a TPU, or with fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_or_none(need: int):
    """The devices JAX found, or None (with the reason on stderr) when
    they are not TPUs or fewer than the cell needs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"bench/run.py: the cell needs {need} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        return None
    return devs


def measure(cell_name: str, seed: int, seconds: float, traced: bool, *,
            interpret: bool = False, conf=None, mix=None, limits=None,
            device_kind: str = None, t_start: float = None,
            control: bool = False, keep_trace: str = None,
            cache_dir: str = None):
    """One run of a cell, returning (result dict, stderr lines). The
    keyword arguments let a test or the calibration tool run the same
    path at small sizes on the CPU, judge the control by the cell's
    limits beside the program (``control``: the result's ``control``
    holds its readings, checks and ``correct``), keep the trace (``keep_trace``) or keep
    compiled programs elsewhere (``cache_dir``); the command line leaves
    them unset."""
    import gc

    import jax

    from bench.lib import check, compiles, registry, work
    from bench.lib import trace as trace_mod
    from bench.lib.loop import build, drive
    from bench.lib.report import DECODE_PROGRAMS, Window, read_metrics
    from bench.lib.traffic import Traffic

    t_start = T_START if t_start is None else t_start
    cell = registry.cell(cell_name)
    conf = conf or registry.config(cell["config"])
    mix = mix or registry.traffic(cell["traffic"])
    limits = limits or registry.limits(cell_name)
    dev = jax.devices()[0]
    pk = work.peak(device_kind or dev.device_kind)
    cache_dir = compiles.use_persistent_cache(cache_dir)
    clock, cache = compiles.watch()
    base = (clock.count, len(cache.misses), len(cache.not_written))

    phases = {"start": time.perf_counter() - t_start}
    eng = build(conf, mix, seed, interpret=interpret, phases=phases,
                t_start=t_start)
    traffic = Traffic(mix, seed, conf["config"]["vocab_size"])
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    marks = {}

    def on_open():
        if traced:
            trace_mod.start(trace_dir)
        marks["setup"] = (clock.count, len(cache.misses))

    run = drive(eng, traffic, seconds, t_start=t_start, annotate=traced,
                on_open=on_open, phases=phases)
    window_compiles = clock.count - marks["setup"][0]
    window_misses = cache.misses[marks["setup"][1]:]
    summary = None
    if traced:
        jax.profiler.stop_trace()
        summary = trace_mod.Summary(trace_mod.load(
            trace_mod.find_xplane(trace_dir)))
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run.peak_bytes = compiles.peak_bytes()
    window = Window(cell_name, conf, mix, run, pk, summary)
    metrics = read_metrics(window, registry.per_layer(cell_name) if traced
                           else registry.end_to_end(cell_name))

    attempted = len(run.sent)
    failed = sum(1 for s in run.sent.values()
                 if s.finish not in (None, "stop", "length"))
    tmix = mix["check"]
    picked = check.pick(run, seed, int(tmix["max_requests"]))
    ref_shape = (int(tmix["max_requests"]), int(mix["engine"]["max_len"]))
    # the program's state goes before the reference runs
    del eng, window
    gc.collect()
    held = compiles.live_bytes()
    ref = registry.reference(conf["family"])
    t_ref = time.perf_counter()
    gaps = check.gaps(ref, conf, seed, picked, ref_shape)
    readings = {**check.gap_readings(gaps),
                "compared_tokens": float(gaps.size),
                "failed_requests": float(failed)}
    correct, checks = check.judge(readings, limits["checks"])
    ref_s = time.perf_counter() - t_ref
    verdict = None
    if control:
        # the control in the program's place, judged by the same limits
        ctl = check.gaps(ref, conf, seed, picked, ref_shape, control=True)
        ctl_readings = {**check.gap_readings(ctl),
                        "compared_tokens": float(ctl.size),
                        "failed_requests": float(failed)}
        ctl_correct, ctl_checks = check.judge(ctl_readings, limits["checks"])
        verdict = {"correct": ctl_correct, "readings": ctl_readings,
                   "checks": ctl_checks}

    setup_misses = sorted(set(cache.misses[base[1]:marks["setup"][1]]))
    lines = [
        f"compile cache: {cache_dir}",
        "set-up phases (s from process start): " + ", ".join(
            f"{k} {v:.3f}" for k, v in run.phases.items()),
        f"set-up: {run.setup_s:.3f} s, {marks['setup'][0] - base[0]} "
        f"backend compiles, persistent-cache misses: {setup_misses}",
        f"entries not written: {sorted(set(cache.not_written[base[2]:]))}",
        f"window: {run.window_s:.3f} s, {len(run.steps)} steps, "
        f"{window_compiles} backend compiles, cache misses {window_misses}, "
        f"warmed buckets {run.warmed_buckets}",
    ]
    if summary is not None:
        lines.append(
            f"trace: {summary.program_count(DECODE_PROGRAMS):.0f} decode "
            f"programs, {run.delta('decode_steps'):.0f} decode steps")
    lines.append(
        f"reference over {len(picked)} requests, {gaps.size} tokens: "
        f"{ref_s:.3f} s, {held / 2 ** 30:.3f} GiB of arrays held before it")
    if verdict is not None:
        lines.append(f"control correct: {verdict['correct']}")
        lines += [f"control check {k}: {v['value']!r} {v['rule']} "
                  f"{v['limit']!r}" for k, v in verdict["checks"].items()]
    lines += [f"check {k}: {v['value']!r} {v['rule']} {v['limit']!r}"
              for k, v in checks.items()]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run.peak_bytes}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(10)}
    result["compiles"] = {"setup": marks["setup"][0] - base[0],
                          "setup_misses": setup_misses,
                          "window": window_compiles}
    result["readings"] = readings
    if verdict is not None:
        result["control"] = verdict
    result["checks"] = checks
    return result, lines


def main(argv=None) -> int:
    args = parse(argv)
    from bench.lib import registry

    cell = registry.cell(args.workload)
    if chips_or_none(int(cell["chips"])) is None:
        return 2
    result, lines = measure(args.workload, args.seed, args.seconds,
                            bool(args.trace))
    sys.stderr.write("\n".join(lines) + "\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
