"""Smoke-width stand-ins for the benchmark's configuration and traffic
files, so the harness can be driven on the CPU in Pallas interpret mode."""
import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.lib import registry  # noqa: E402

CELL = "minitron-4b.long-decode"
SMALL = {"num_hidden_layers": 2, "hidden_size": 128, "intermediate_size": 384,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "vocab_size": 512}


def conf(name="minitron-4b", **over):
    c = copy.deepcopy(registry.config(name))
    c["config"].update(SMALL, **over)
    return c


def mix(clients=2, outputs=(6, 12)):
    m = copy.deepcopy(registry.traffic("long-decode-16"))
    m["clients"] = clients
    m["engine"] = {"num_slots": clients, "max_len": 48}
    m["prompt_len"] = {"dist": "log_uniform", "min": 8, "max": 24}
    m["output_len"] = {"dist": "log_uniform", "min": outputs[0],
                       "max": outputs[1]}
    m["check"] = {"max_requests": clients}
    return m


def limits(gap=0.05):
    return {"checks": {"max_logit_gap": {"max": gap},
                       "compared_tokens": {"min": 2},
                       "failed_requests": {"max": 0}}}


def isolated_cache(tmp_path, monkeypatch):
    """A persistent compile cache directory of one test's own, to hand to
    ``measure(cache_dir=...)``; the process gets its settings back
    afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    path = str(tmp_path / "jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
    cc.reset_cache()
    yield path
    for n, v in before.items():
        jax.config.update(n, v)
    cc.reset_cache()
