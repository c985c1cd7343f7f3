"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. The analytic accelerator
model (accel_model.py) mirrors the paper's simulator; `serve/*` rows
drive request traces through the serving engine.

``--json <path>`` additionally writes the rows machine-readably (the
``derived`` field parsed into key/value pairs) in the
``eva-bench-rows/v1`` format that benchmarks/schema.py checks, e.g.

    python -m benchmarks.run serve --json bench_serve.json

Usage:
    python -m benchmarks.run                    # every module
    python -m benchmarks.run serve fig10        # just the named module(s)
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
        serve, tbl_iii_vq_configs, tbl_v_accuracy_proxy,
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
        ("serve", serve),
    ]
    known = {name for name, _ in modules}

    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("modules", nargs="*", metavar="MODULE",
                    help=f"module(s) to run (default all): {sorted(known)}")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (derived fields parsed)")
    args = ap.parse_args(list(argv) if argv is not None else None)

    selected = set(args.modules)
    unknown = selected - known
    if unknown:
        sys.exit(f"unknown benchmark module(s) {sorted(unknown)}; "
                 f"choose from {sorted(known)}")

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
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
