"""The check of `correct` at smoke widths on the CPU: the program passes,
the float8 control fails, and a run whose timed path is broken
underneath comes out not correct, once for each fault a serving cell
on one chip can have (a decode step that returns its cache unchanged; a
token altered where it is produced). Also: the reference's weights,
made again from the seed by its own code, are the program's."""
import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_smoke as smoke
from bench.lib import registry
from repro.serve.engine import Engine

_spec = importlib.util.spec_from_file_location(
    "bench_run_check", registry.BENCH / "run.py")
bench_run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_run)

LIMIT = 0.1   # smoke widths: program 0.0013-0.0174, control 0.285-0.463


@pytest.fixture
def cache(tmp_path, monkeypatch):
    yield from smoke.isolated_cache(tmp_path, monkeypatch)


def _run(cache_dir, seed=7, control=False):
    return bench_run.measure(
        smoke.CELL, seed, 1.0, False, interpret=True, conf=smoke.conf(),
        mix=smoke.mix(clients=4, outputs=(16, 24)),
        limits=smoke.limits(LIMIT), device_kind="TPU v5 lite",
        control=control, cache_dir=cache_dir)


def test_program_passes_and_the_control_fails(cache):
    result, lines = _run(cache, control=True)
    r = result["readings"]
    assert result["correct"], lines
    assert r["compared_tokens"] >= 8
    # the control is judged by the harness, under the limits it was given
    ctl = result["control"]
    assert not ctl["correct"], lines
    assert ctl["checks"]["max_logit_gap"]["limit"] == LIMIT
    assert ctl["checks"]["max_logit_gap"]["value"] > LIMIT
    assert ctl["readings"]["compared_tokens"] == r["compared_tokens"]
    assert list(result)[-1] == "checks"
    assert "control correct: False" in lines


def test_a_decode_step_that_returns_its_cache_unchanged_fails(
        cache, monkeypatch):
    orig = Engine._decode_impl

    def stale(self, params, caches, *args, **kw):
        out = orig(self, params, caches, *args, **kw)
        return (*out[:-1], caches)

    monkeypatch.setattr(Engine, "_decode_impl", stale)
    result, lines = _run(cache)
    assert not result["correct"], lines
    assert result["checks"]["max_logit_gap"]["value"] > LIMIT


def test_a_token_altered_where_it_is_produced_fails(cache, monkeypatch):
    orig = Engine._decode_impl

    def altered(self, *args, **kw):
        tok, *rest = orig(self, *args, **kw)
        return ((tok + 1) % self.model.cfg.vocab_size, *rest)

    monkeypatch.setattr(Engine, "_decode_impl", altered)
    result, lines = _run(cache)
    assert not result["correct"], lines
    assert result["checks"]["max_logit_gap"]["value"] > LIMIT


def test_reference_weights_are_the_programs():
    """The reference rebuilds the synthetic weights from the seed with
    its own code; at smoke widths they equal the program's: indices,
scales, bias and embedding bit for bit, codebooks to one rounding."""
    from bench.lib.loop import model_config
    from repro.models.api import build_model

    conf = smoke.conf("qwen2-72b-pp4")
    ref = registry.reference(conf["family"])
    seed = 2 ** 31 + 3
    params = build_model(model_config(conf)).init_synthetic(
        jax.random.PRNGKey(seed % 2 ** 32))
    dims = ref.Dims.of(conf)
    key = ref.root_key(seed)
    keys = ref.layer_keys(dims, key)
    layers = params["layers"]
    leaves = [layers["attn"]["wqkv"], layers["attn"]["wo"],
              layers["mlp"]["gu"], layers["mlp"]["down"]]
    for f, ((K, N), leaf) in enumerate(zip(dims.families(), leaves)):
        vq = leaf["vq"]
        for layer in range(dims.L):
            k = keys[layer, f]
            idx = jax.random.randint(k, (dims.C, K // dims.d, N), 0,
                                     2 ** dims.n).astype(jnp.uint8)
            cb = jax.random.normal(k, (dims.C, dims.d, 2 ** dims.n)) \
                / np.sqrt(K * dims.C)
            np.testing.assert_array_equal(idx, vq.idx[layer])
            # XLA may turn the division by a constant into a product by
            # its reciprocal: one unit in the last place
            np.testing.assert_allclose(cb, vq.codebooks[layer], rtol=3e-7,
                                       atol=0)
        assert np.all(np.asarray(vq.scale) == 1.0)
    assert np.all(np.asarray(layers["attn"]["wqkv"]["b"]) == 0.0)
    tokens = jnp.arange(12, dtype=jnp.int32).reshape(2, 6)
    emb = ref._embed(key, tokens, dims=dims)
    want = jnp.take(params["embedding"]["emb"], tokens, axis=0)
    np.testing.assert_array_equal(emb, want.astype(jnp.float32))
