"""Machine-readability gate for `eva-bench-rows/v1` bench JSON.

CI runs this check against a fresh `benchmarks/run.py serve --json`
emission, so the serving rows stay machine-readable across PRs — the
build fails on the first malformed row.

Validation is hand-rolled over the stdlib (the container pins its
packages; no jsonschema dependency):

  top level : {"schema": "eva-bench-rows/v1", "rows": [...],
               "failures": [str, ...]? }
  row       : {"module": str, "name": str, "us_per_call": number,
               "derived": dict}
  timed rows of the `serve` module (engine throughput traces; every row
  except harness-failure rows, name `*/ERROR`) must carry the engine
  totals in `derived`:
      tokens / tok_per_s / requests (non-negative numbers)

CLI (exit 1 on the first error, listing all of them):

    PYTHONPATH=src python -m benchmarks.schema bench_serve.json
"""
from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional, Sequence

SCHEMA = "eva-bench-rows/v1"

# serving-engine throughput rows must carry the engine totals — and the
# KV memory accounting (serve/paging.py gauges; a contiguous engine
# reports its constant worst-case kv_bytes_in_use and zero blocks) — so
# the serving trajectory stays machine-readable across PRs
SERVE_MODULES = ("serve",)
SERVE_FIELDS = ("tokens", "tok_per_s", "requests",
                "kv_bytes_in_use", "blocks_in_use", "blocks_free")

# the speculative-decoding trace row additionally pins its headline
# numbers so the multi-token-per-step trajectory is tracked across PRs
SPEC_ROW = "serve/spec_decode_trace"
SPEC_FIELDS = ("tokens_per_step", "acceptance_rate",
               "drafted", "accepted")


def _is_num(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def validate_rows(doc: Any) -> List[str]:
    """Every schema violation in `doc` (empty list == valid)."""
    errors: List[str] = []
    if not isinstance(doc, dict):
        return [f"top level must be an object, got {type(doc).__name__}"]
    if doc.get("schema") != SCHEMA:
        errors.append(f"schema must be {SCHEMA!r}, got {doc.get('schema')!r}")
    rows = doc.get("rows")
    if not isinstance(rows, list):
        return errors + ["rows must be a list"]
    failures = doc.get("failures", [])
    if not isinstance(failures, list) or \
            not all(isinstance(f, str) for f in failures):
        errors.append("failures must be a list of strings")

    for i, row in enumerate(rows):
        where = f"rows[{i}]"
        if not isinstance(row, dict):
            errors.append(f"{where}: must be an object")
            continue
        name = row.get("name")
        if not isinstance(name, str) or not name:
            errors.append(f"{where}: missing/empty name")
            name = ""
        where = f"rows[{i}] ({name})" if name else where
        if not isinstance(row.get("module"), str):
            errors.append(f"{where}: missing module")
        if not _is_num(row.get("us_per_call")):
            errors.append(f"{where}: us_per_call must be a number")
        derived = row.get("derived")
        if not isinstance(derived, dict):
            errors.append(f"{where}: derived must be an object")
            continue
        if row.get("module") in SERVE_MODULES \
                and not name.endswith("/ERROR"):
            for f in SERVE_FIELDS:
                v = derived.get(f)
                if not _is_num(v) or v < 0:
                    errors.append(
                        f"{where}: serve row needs non-negative "
                        f"derived.{f}, got {v!r}")
            if name == SPEC_ROW:
                for f in SPEC_FIELDS:
                    v = derived.get(f)
                    if not _is_num(v) or v < 0:
                        errors.append(
                            f"{where}: spec-decode row needs non-negative "
                            f"derived.{f}, got {v!r}")
    return errors


def validate_file(path: str) -> List[str]:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable ({e})"]
    return validate_rows(doc)


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    if not args:
        sys.exit("usage: python -m benchmarks.schema BENCH.json [...]")
    failed = False
    for path in args:
        errors = validate_file(path)
        if errors:
            failed = True
            print(f"{path}: {len(errors)} schema error(s)", file=sys.stderr)
            for e in errors:
                print(f"  {e}", file=sys.stderr)
        else:
            print(f"{path}: ok ({SCHEMA})")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
