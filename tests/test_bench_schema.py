"""benchmarks/schema.py: the eva-bench-rows/v1 gate CI runs against a
fresh `benchmarks/run.py serve --json` emission."""
import copy

from benchmarks import schema

SERVE_TOTALS = {"tokens": 18, "tok_per_s": 10.0, "requests": 3,
                "kv_bytes_in_use": 0, "blocks_in_use": 0, "blocks_free": 0}

VALID = {
    "schema": "eva-bench-rows/v1",
    "rows": [
        {"module": "fig10", "name": "fig10/decode", "us_per_call": 12.5,
         "derived": {"note": "analytic"}},
        {"module": "serve", "name": "serve/request_trace",
         "us_per_call": 100.0, "derived": dict(SERVE_TOTALS)},
        {"module": "serve", "name": "serve/ERROR", "us_per_call": -1.0,
         "derived": {"note": "ValueError:boom"}},
    ],
    "failures": ["serve: boom"],
}


def test_valid_doc_passes():
    assert schema.validate_rows(VALID) == []


def test_wrong_schema_version():
    doc = dict(VALID, schema="eva-bench-rows/v0")
    assert any("schema" in e for e in schema.validate_rows(doc))


def test_non_serve_modules_need_only_core_fields():
    doc = copy.deepcopy(VALID)
    doc["rows"][0]["derived"] = {}  # analytic fig10 rows carry nothing
    assert schema.validate_rows(doc) == []


def test_serve_row_missing_engine_totals_fails():
    for field in schema.SERVE_FIELDS:
        doc = copy.deepcopy(VALID)
        del doc["rows"][1]["derived"][field]
        errs = schema.validate_rows(doc)
        assert any(f"derived.{field}" in e for e in errs), field


def test_serve_error_rows_exempt_from_engine_totals():
    # the harness's failure rows carry only the exception text
    assert schema.validate_rows(copy.deepcopy(VALID)) == []


def test_malformed_rows_reported():
    doc = copy.deepcopy(VALID)
    doc["rows"][0].pop("us_per_call")
    doc["rows"].append("not a row")
    doc["rows"].append({"module": "fig10", "name": "fig10/x",
                        "us_per_call": 1.0, "derived": "not a dict"})
    errs = schema.validate_rows(doc)
    assert any("us_per_call" in e for e in errs)
    assert any("must be an object" in e for e in errs)
    assert any("derived must be an object" in e for e in errs)


def test_validate_file_reports_unreadable(tmp_path):
    errs = schema.validate_file(str(tmp_path / "missing.json"))
    assert errs and "unreadable" in errs[0]
    bad = tmp_path / "bad.json"
    bad.write_text("{")
    assert schema.validate_file(str(bad))


def test_spec_decode_row_requires_headline_fields():
    doc = copy.deepcopy(VALID)
    row = {"module": "serve", "name": "serve/spec_decode_trace",
           "us_per_call": 100.0,
           "derived": {"tokens": 80, "tok_per_s": 10.0, "requests": 2,
                       "kv_bytes_in_use": 0, "blocks_in_use": 0,
                       "blocks_free": 0, "tokens_per_step": 1.5,
                       "acceptance_rate": 0.18, "drafted": 168,
                       "accepted": 30}}
    doc["rows"].append(row)
    assert schema.validate_rows(doc) == []
    for field in schema.SPEC_FIELDS:
        broken = copy.deepcopy(doc)
        del broken["rows"][-1]["derived"][field]
        errs = schema.validate_rows(broken)
        assert any(f"derived.{field}" in e for e in errs), field


def test_other_serve_rows_exempt_from_spec_fields():
    doc = copy.deepcopy(VALID)
    doc["rows"].append(
        {"module": "serve", "name": "serve/request_trace",
         "us_per_call": 100.0,
         "derived": {"tokens": 18, "tok_per_s": 10.0, "requests": 3,
                     "kv_bytes_in_use": 0, "blocks_in_use": 0,
                     "blocks_free": 0}})
    assert schema.validate_rows(doc) == []
