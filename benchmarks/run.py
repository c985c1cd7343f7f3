"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. The analytic accelerator
model (accel_model.py) mirrors the paper's simulator; `measured/*` rows
are real wall-clock CPU executions of the JAX ops and carry the chosen
``plan=`` (core/plan.py MatmulPlan.describe()) per row.

``--json <path>`` additionally writes the rows machine-readably (the
``derived`` field parsed into key/value pairs — chosen plan, cost-model
terms, speedups, baseline timings) so the perf trajectory is tracked
across PRs and `core/calibrate.py` can fit the Planner's per-backend
time constants; ``--calibrate <path>`` runs that fit on the freshly
emitted rows and writes a versioned CALIBRATION.json. The `smoke`
module is the tiny-shape variant CI uses to gate the JSON schema
(benchmarks/schema.py) without paying full measured timings, e.g.

    python -m benchmarks.run measured --json BENCH_measured.json \
        --calibrate CALIBRATION.json
    python -m benchmarks.run smoke --json bench_smoke.json

Usage:
    python -m benchmarks.run                    # every module
    python -m benchmarks.run measured fig10     # just the named module(s)
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback
from typing import Any, Dict, List, Optional, Sequence

JSON_SCHEMA = "eva-bench-rows/v1"


def parse_derived(derived: str) -> Dict[str, Any]:
    """Parse a ';'-separated derived string into a dict: ``k=v`` pairs
    become fields (numeric where possible), bare text accumulates under
    "note"."""
    out: Dict[str, Any] = {}
    notes: List[str] = []
    for part in derived.split(";"):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            k, v = part.split("=", 1)
            try:
                out[k] = int(v)
            except ValueError:
                try:
                    out[k] = float(v)
                except ValueError:
                    out[k] = v
        else:
            notes.append(part)
    if notes:
        out["note"] = "; ".join(notes)
    return out


def write_json(path: str, rows: List[Dict[str, Any]],
               failures: Sequence[str]) -> None:
    with open(path, "w") as f:
        json.dump({"schema": JSON_SCHEMA, "rows": rows,
                   "failures": list(failures)}, f, indent=1)
        f.write("\n")


def main(argv: Optional[Sequence[str]] = None) -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        fig8_dse, fig10_decode, fig11_batch, fig12_e2e, fig14_spurious,
        measured, serve, smoke, tbl_iii_vq_configs, tbl_v_accuracy_proxy,
        tbl_viii_throughput, tbl_x_oc_advantage,
    )

    modules = [
        ("tbl_iii", tbl_iii_vq_configs),
        ("fig8", fig8_dse),
        ("tbl_viii", tbl_viii_throughput),
        ("fig10", fig10_decode),
        ("fig11", fig11_batch),
        ("fig12", fig12_e2e),
        ("fig14", fig14_spurious),
        ("tbl_x", tbl_x_oc_advantage),
        ("tbl_v", tbl_v_accuracy_proxy),
        ("measured", measured),
        ("serve", serve),
    ]
    known = {name for name, _ in modules} | {"smoke"}
    # tiny-shape CI smoke: only when named explicitly (not part of "all")
    smoke_mod = ("smoke", smoke)

    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("modules", nargs="*", metavar="MODULE",
                    help=f"module(s) to run (default all): {sorted(known)}")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (derived fields parsed)")
    ap.add_argument("--calibrate", default=None, metavar="PATH",
                    help="fit per-backend time constants from the emitted "
                         "rows and write a versioned CALIBRATION.json")
    args = ap.parse_args(list(argv) if argv is not None else None)

    selected = set(args.modules)
    unknown = selected - known
    if unknown:
        sys.exit(f"unknown benchmark module(s) {sorted(unknown)}; "
                 f"choose from {sorted(known)}")
    if "smoke" in selected:
        modules.append(smoke_mod)

    rows: List[Dict[str, Any]] = []
    current_module = [""]

    def report(name: str, us: float, derived: str = ""):
        print(f"{name},{us:.3f},{derived}", flush=True)
        rows.append({"module": current_module[0], "name": name,
                     "us_per_call": round(us, 3),
                     "derived": parse_derived(derived)})

    print("name,us_per_call,derived")
    failures = []
    for name, mod in modules:
        if selected and name not in selected:
            continue
        current_module[0] = name
        try:
            mod.run(report)
        except Exception as e:  # keep the harness running
            failures.append((name, e))
            report(f"{name}/ERROR", -1.0, f"{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if args.json:
        write_json(args.json, rows, [f"{n}: {e}" for n, e in failures])
    if args.calibrate:
        if failures:
            # never persist a fit from partial rows: a crashed module
            # would silently degrade every Planner loading the file
            print(f"calibration NOT written ({args.calibrate}): "
                  f"{len(failures)} module failure(s)", file=sys.stderr)
        else:
            from repro.core import calibrate as calibrate_mod

            # fit ONLY from the measured module's rows: smoke rows are
            # throwaway tiny-shape CI timings and must never overwrite a
            # valid calibration with under-sampled entries
            fit_rows = [r for r in rows if r.get("module") == "measured"]
            source = args.json or "benchmarks.run (unwritten rows)"
            calib = calibrate_mod.fit_calibration(
                {"schema": JSON_SCHEMA, "rows": fit_rows}, source=source)
            usable = sum(e.rows >= calibrate_mod.MIN_FIT_ROWS
                         for e in calib.backends.values())
            if not usable:
                print(f"calibration NOT written ({args.calibrate}): no "
                      f"backend reached {calibrate_mod.MIN_FIT_ROWS} "
                      "measured rows (run the `measured` module)",
                      file=sys.stderr)
            else:
                calibrate_mod.save_calibration(calib, args.calibrate)
                print(f"calibration: {len(calib.backends)} backends "
                      f"({usable} rankable) -> {args.calibrate}",
                      file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
