"""Distributed tests: sharding rule validity for every arch, plus a real
multi-device SPMD run in a subprocess (8 host devices) covering the
sharded train step, gradient compression over the 'pod' axis, and elastic
resharding.

The subprocess is required because XLA_FLAGS must be set before jax
initializes, and the main test process must keep 1 device (per the
assignment: smoke tests see one device).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import build_model

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _abstract_mesh():
    """Production-shaped AbstractMesh (no devices)."""
    from jax.sharding import AbstractMesh

    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestShardingRules:
    """Specs must be structurally valid and exactly divisible on the
    production mesh for every arch (checked abstractly, no devices)."""

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_param_specs_divisible(self, arch):
        from jax.sharding import PartitionSpec as P
        from repro.runtime.sharding import opt_pspecs, param_pspecs

        mesh = _abstract_mesh()
        model = build_model(get_config(arch))
        for quantized in (False, True):
            specs = model.param_specs(quantized=quantized)
            pspecs = param_pspecs(specs, mesh)
            flat_s, tdef = jax.tree_util.tree_flatten(
                pspecs, is_leaf=lambda x: isinstance(x, P))
            flat_p = tdef.flatten_up_to(specs)
            for spec, leaf in zip(flat_s, flat_p):
                if not isinstance(spec, P) or not hasattr(leaf, "shape"):
                    continue
                for dim, ax in zip(leaf.shape, tuple(spec)):
                    if ax is None:
                        continue
                    axes = ax if isinstance(ax, tuple) else (ax,)
                    total = int(np.prod([mesh.shape[a] for a in axes]))
                    assert dim % total == 0, (arch, leaf.shape, spec)
            if not quantized:
                ospecs = opt_pspecs(pspecs, specs, mesh)
                assert jax.tree_util.tree_structure(
                    ospecs, is_leaf=lambda x: isinstance(x, P)
                ) == jax.tree_util.tree_structure(
                    pspecs, is_leaf=lambda x: isinstance(x, P))

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_cache_specs_divisible(self, arch):
        from jax.sharding import PartitionSpec as P
        from repro.runtime.sharding import cache_pspecs

        mesh = _abstract_mesh()
        model = build_model(get_config(arch))
        cspecs = model.cache_specs(128, 32768)
        pspecs = cache_pspecs(cspecs, mesh)
        flat_s, tdef = jax.tree_util.tree_flatten(
            pspecs, is_leaf=lambda x: isinstance(x, P))
        flat_c = tdef.flatten_up_to(cspecs)
        for spec, leaf in zip(flat_s, flat_c):
            if not isinstance(spec, P):
                continue
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                total = int(np.prod([mesh.shape[a] for a in axes]))
                assert dim % total == 0, (arch, leaf.shape, spec)


_SUBPROC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map

    from repro.configs import get_smoke_config
    from repro.data import DataConfig, DataPipeline, global_batch_at
    from repro.launch.steps import make_train_step, train_shardings
    from repro.models import build_model
    from repro.models.common import RunConfig
    from repro.optim import AdamWConfig, adamw_init
    from repro.optim.compress import compress_psum, init_error_feedback
    from repro.runtime.sharding import to_named
    from repro.runtime.elastic import reshard_state

    out = {}
    assert len(jax.devices()) == 8

    # ---- sharded train step on a (pod=2, data=2, model=2) mesh ----
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_smoke_config("qwen3-0.6b")
    model = build_model(cfg)
    rc = RunConfig(mode="train", remat=True, attn_chunk=8)
    ocfg = AdamWConfig(lr=1e-3)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params, ocfg)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8)
    batch = {k: jnp.asarray(v) for k, v in global_batch_at(dcfg, 0).items()}
    step = make_train_step(model, ocfg, rc)
    in_sh, out_sh = train_shardings(model, mesh, params, opt, batch)
    with mesh:
        jitted = jax.jit(step, in_shardings=to_named(in_sh, mesh),
                         out_shardings=to_named(out_sh, mesh))
        p2, o2, metrics = jitted(params, opt, batch)
        # reference: unsharded single-device step
        p_ref, o_ref, m_ref = jax.jit(step)(params, opt, batch)
    out["sharded_loss"] = float(metrics["loss"])
    out["ref_loss"] = float(m_ref["loss"])
    diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
               for a, b in zip(jax.tree_util.tree_leaves(p2),
                                jax.tree_util.tree_leaves(p_ref)))
    out["param_diff"] = diff

    # ---- int8 EF gradient compression over the pod axis ----
    cmesh = jax.make_mesh((8,), ("pod",))
    g_global = jax.random.normal(jax.random.PRNGKey(1), (8, 64))

    def reduce_fn(g, e):
        red, new_e = compress_psum({"g": g}, {"g": e}, "pod")
        return red["g"], new_e["g"]

    sm = shard_map(reduce_fn, mesh=cmesh,
                   in_specs=(P("pod", None), P("pod", None)),
                   out_specs=(P("pod", None), P("pod", None)))
    ef = jnp.zeros((8, 64))
    red, ef = sm(g_global, ef)
    true_mean = jnp.mean(g_global, axis=0, keepdims=True)
    err1 = float(jnp.max(jnp.abs(red[0] - true_mean[0])))
    out["compress_err"] = err1
    out["compress_rel"] = err1 / float(jnp.max(jnp.abs(true_mean)))
    # error feedback guarantee: the CUMULATIVE applied update converges to
    # the cumulative true gradient (per-step error is bounded, residual
    # carried) -> relative error of the running mean shrinks ~ 1/k
    applied = red
    K = 8
    for _ in range(K - 1):
        red, ef = sm(g_global, ef)
        applied = applied + red
    cum_err = float(jnp.max(jnp.abs(applied[0] / K - true_mean[0])))
    out["compress_err_ef"] = cum_err
    out["ef_improves"] = cum_err < 0.5 * err1

    # ---- elastic restart: reshard onto a smaller mesh, same math ----
    mesh2 = jax.make_mesh((2, 2), ("data", "model"))
    params_host = jax.tree_util.tree_map(np.asarray, p2)
    opt_host = jax.tree_util.tree_map(
        lambda x: np.asarray(x) if hasattr(x, "shape") else x, o2)
    p3, o3 = reshard_state(params_host, opt_host, model, mesh2)
    batch2 = {k: jnp.asarray(v) for k, v in global_batch_at(dcfg, 1).items()}
    in_sh2, out_sh2 = train_shardings(model, mesh2, p3, o3, batch2)
    with mesh2:
        jit2 = jax.jit(step, in_shardings=to_named(in_sh2, mesh2),
                       out_shardings=to_named(out_sh2, mesh2))
        p4, o4, m4 = jit2(p3, o3, batch2)
    # reference continues on one device
    p_ref2, o_ref2, m_ref2 = jax.jit(step)(p_ref, o_ref, batch2)
    out["elastic_loss"] = float(m4["loss"])
    out["elastic_ref_loss"] = float(m_ref2["loss"])
    print("RESULT" + json.dumps(out))
""")


@pytest.mark.slow
class TestMultiDeviceSPMD:
    @pytest.fixture(scope="class")
    def result(self):
        env = dict(os.environ, PYTHONPATH=SRC, TF_CPP_MIN_LOG_LEVEL="2")
        proc = subprocess.run(
            [sys.executable, "-c", _SUBPROC_SCRIPT], env=env,
            capture_output=True, text=True, timeout=560,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][-1]
        return json.loads(line[len("RESULT"):])

    def test_sharded_step_matches_single_device(self, result):
        assert result["sharded_loss"] == pytest.approx(result["ref_loss"],
                                                       rel=2e-3)
        assert result["param_diff"] < 5e-3

    def test_gradient_compression(self, result):
        assert result["compress_rel"] < 0.05   # int8 quantization error
        assert result["ef_improves"]           # error feedback helps

    def test_elastic_restart(self, result):
        assert result["elastic_loss"] == pytest.approx(
            result["elastic_ref_loss"], rel=2e-3)
