"""The harness on the CPU: it finds every part of a cell by name, drives
the engine through a short window at smoke widths (Pallas in interpret
mode), and its command refuses a device that is not a TPU."""
import importlib.util
import json

import numpy as np
import pytest

import bench_smoke as smoke
from bench.lib import registry
from bench.lib.traffic import Traffic, wave_lengths

_spec = importlib.util.spec_from_file_location(
    "bench_run", registry.BENCH / "run.py")
bench_run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_run)


def test_every_cell_finds_its_files_by_name():
    b = registry.benchmark()
    assert b["command"] == ["python3", "bench/run.py"]
    for w in b["workloads"]:
        conf = registry.config(w["config"])
        assert conf["name"] == w["config"]
        mix = registry.traffic(w["traffic"])
        assert mix["name"] == w["traffic"]
        checks = registry.limits(w["name"])["checks"]
        assert any(k.endswith("logit_gap") and v["max"] > 0
                   for k, v in checks.items())
        assert registry.reference(conf["family"]).logits
        e2e = {m["name"] for m in registry.end_to_end(w["name"])}
        assert {"setup_s", "output_tok_s", "peak_hbm_gib"} <= e2e
        layer = registry.per_layer(w["name"])
        assert layer and all(m["moves"] in e2e for m in layer)
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(registry.metric_reader(m["name"]).read)


def test_config_files_state_their_cuts():
    listed = {c["name"]: c for c in registry.benchmark()["configs"]}
    files = sorted(registry.BENCH.glob("configs/*.json"))
    assert set(listed) <= {f.stem for f in files}
    for f in files:
        conf = registry.config(f.stem)
        changed = sorted(k for k, v in conf["published"].items()
                         if conf["config"].get(k) != v)
        assert changed == sorted(conf["reduced"])
        assert "assumed" in conf and "departures" in conf
        if f.stem in listed:
            assert sorted(listed[f.stem]["reduced"]) == changed
            assert listed[f.stem]["source"] == conf["source"]


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = registry.traffic("long-decode-16")
    a, b = Traffic(mix, 1, 1000), Traffic(mix, 2 ** 31 + 5, 1000)
    ra = [a.next() for _ in range(48)]
    rb = [b.next() for _ in range(48)]
    for w in range(3):
        sa = sorted((r.prompt.size, r.max_new_tokens) for r in ra[16 * w:16 * w + 16])
        sb = sorted((r.prompt.size, r.max_new_tokens) for r in rb[16 * w:16 * w + 16])
        assert sa == sb == sorted(wave_lengths(mix, w))
        assert sum(r.greedy for r in ra[16 * w:16 * w + 16]) == 8
    assert [r.prompt.size for r in ra] != [r.prompt.size for r in rb]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    assert all(lo <= r.prompt.size <= hi for r in ra)
    lo, hi = mix["output_len"]["min"], mix["output_len"]["max"]
    assert all(lo <= r.max_new_tokens <= hi for r in ra)
    again = Traffic(mix, 1, 1000)
    assert all(np.array_equal(r.prompt, again.next().prompt) for r in ra)


@pytest.fixture
def cache(tmp_path, monkeypatch):
    yield from smoke.isolated_cache(tmp_path, monkeypatch)


def test_a_short_window_at_smoke_widths(cache):
    result, lines = bench_run.measure(
        smoke.CELL, 2 ** 31 + 17, 1.0, False, interpret=True,
        conf=smoke.conf(), mix=smoke.mix(clients=2, outputs=(6, 10)),
        limits=smoke.limits(), device_kind="TPU v5 lite", cache_dir=cache)
    assert result["correct"], lines
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"output_tok_s", "itl_p95_ms",
                                      "peak_hbm_gib", "setup_s"} - (
        set() if result["device"]["memory_peak_bytes"] else {"peak_hbm_gib"})
    assert result["metrics"]["output_tok_s"]["value"] > 0
    assert result["compiles"]["window"] == 0
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert lines[-3].startswith("check max_logit_gap")
    json.dumps(result)


def test_command_refuses_a_cpu(capsys):
    assert bench_run.main(["--workload", smoke.CELL, "--seed", "1",
                           "--seconds", "1", "--trace", "0"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "cpu" in err


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        registry.cell("no-such-cell")
