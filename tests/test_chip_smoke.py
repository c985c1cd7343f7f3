"""chip_smoke.py on the CPU: it refuses a device that is not a TPU, its
serving and parity phases pass at Minitron-4B's smoke widths in Pallas
interpret mode, and a scripted backend fault makes a serving pass fail
instead of passing."""
import importlib.util
import sys
from pathlib import Path

import pytest

from repro.core import plan as plan_mod
from repro.serve.resilience import FaultPlan, FaultSpec

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
sys.modules.setdefault("chip_smoke", chip_smoke)
_spec.loader.exec_module(chip_smoke)

SMALL = chip_smoke.Sizes(requests=3, slots=2, max_len=64, prompt_lens=(8, 24),
                         max_new=3, paged_requests=2, paged_max_new=2,
                         parity_prompt=24)


@pytest.fixture(autouse=True)
def _clean_planner():
    plan_mod.reset_quarantine()
    yield
    plan_mod.reset_quarantine()


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "cpu" in err and '"ok"' not in out


def test_phases_pass_at_smoke_widths_in_interpret_mode():
    assert chip_smoke.run(smoke=True, seed=0, interpret=True,
                          sizes=SMALL) == []


def test_backend_fault_fails_the_serving_pass():
    from repro.configs import get_smoke_config
    from repro.models.api import build_model

    import jax

    params = build_model(get_smoke_config(chip_smoke.ARCH)).init_synthetic(
        jax.random.PRNGKey(0))
    fault = FaultPlan.scripted(FaultSpec(boundary="backend", tick=1))
    failures = chip_smoke.serve_pass(
        "faulted", smoke=True, seed=0, interpret=True, params=params,
        requests=2, max_new=3, sizes=SMALL, clock=chip_smoke.CompileClock(),
        fault_plan=fault)
    assert any("backend_fallbacks=1" in f for f in failures), failures
    assert any("planner backend failures" in f for f in failures), failures


def test_compile_cache_placement(monkeypatch):
    """Unset: the checkout's .jax_cache. Set: JAX's own reading stands."""
    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.use_compile_cache()
        assert path == str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        jax.config.update("jax_compilation_cache_dir", before)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
