"""Compile every Pallas kernel at Minitron-4B widths for a described TPU
v5e (no chip attached): the TPU compiler refuses here what interpret mode
cannot show — gathers it cannot lower, misaligned blocks, VMEM overruns.

Minitron-4B: d_model 3072, 24 heads (GQA kv=8, head_dim 128), d_ff 9216,
2-bit EVA weights (C=2, d=8, n=8). Decode linears run at M=8 slots, and
the EVA kernels also at M=16 (the long-decode cell's slots, one token
tile) and M=1 (one live request, unpadded); the int8 prefill GEMM at a
512-token bucket; decode attention at B=8 over a 2048-token cache. The
Qwen2-72B down projection (K=29568) compiles at M=16 in two token tiles
of 8, its OC scratch being too large for one. The grouped EVA kernel
compiles at DeepSeek-V2-Lite's expert widths for the expert layouts of
a 32-row decode step and a 128-token prefill bucket, and the VQ
dequantization kernel at its MLA ``wkv_b``.

The topology is described inside a module fixture (never at import:
only one process may load the TPU library, and pytest-xdist workers all
import this file), and JAX's persistent compilation cache is off around
these compiles (an entry written for a described chip cannot be read
back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.vq import KVQuantConfig, VQWeight

M_DECODE = 8
M_SLOTS = (1, 16)
M_PREFILL = 512
B, S, H, HK, HD = 8, 2048, 24, 8, 128
# (name, K, N, splits): the grouped QKV family, the o-projection, the
# grouped gate/up family and the down projection of one layer
LINEARS = [
    ("wqkv", 3072, 5120, (3072, 1024, 1024)),
    ("wo", 3072, 3072, ()),
    ("gu", 3072, 18432, (9216, 9216)),
    ("down", 9216, 3072, ()),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _vq(sharding, K, N, splits):
    return VQWeight(idx=_sds(sharding, (2, K // 8, N), jnp.uint8),
                    codebooks=_sds(sharding, (2, 8, 256), jnp.float32),
                    scale=_sds(sharding, (N,), jnp.float32),
                    K=K, N=N, d=8, n=8, splits=splits)


def _assert_kernel(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("name,K,N,splits", LINEARS)
def test_fused_vq_matmul_compiles(one_chip, name, K, N, splits):
    from repro.kernels.fused_vq_matmul import fused_vq_matmul

    x = _sds(one_chip, (M_DECODE, K), jnp.bfloat16)
    _assert_kernel(fused_vq_matmul.lower(x, _vq(one_chip, K, N, splits),
                                         out_dtype=jnp.bfloat16))


@pytest.mark.parametrize("name,K,N,splits", LINEARS)
def test_eva_split_compiles(one_chip, name, K, N, splits):
    """vq_gemm -> HBM output codebook -> oc_lookup."""
    from repro.kernels.oc_lookup.ops import eva_split_matmul

    x = _sds(one_chip, (M_DECODE, K), jnp.bfloat16)
    _assert_kernel(jax.jit(eva_split_matmul).lower(
        x, _vq(one_chip, K, N, splits)))


@pytest.mark.parametrize("M", M_SLOTS)
@pytest.mark.parametrize("name,K,N,splits", LINEARS)
def test_fused_vq_matmul_compiles_at_slots(one_chip, name, K, N, splits, M):
    from repro.kernels.fused_vq_matmul import fused_vq_matmul

    x = _sds(one_chip, (M, K), jnp.bfloat16)
    _assert_kernel(fused_vq_matmul.lower(x, _vq(one_chip, K, N, splits),
                                         out_dtype=jnp.bfloat16))


@pytest.mark.parametrize("M", M_SLOTS)
@pytest.mark.parametrize("name,K,N,splits", LINEARS)
def test_eva_split_compiles_at_slots(one_chip, name, K, N, splits, M):
    from repro.kernels.oc_lookup.ops import eva_split_matmul

    x = _sds(one_chip, (M, K), jnp.bfloat16)
    _assert_kernel(jax.jit(eva_split_matmul).lower(
        x, _vq(one_chip, K, N, splits)))


def test_fused_vq_matmul_compiles_in_two_token_tiles(one_chip):
    """Qwen2-72B's down projection at 16 slots: the OC of 16 rows
    (2 x 16 x 3712 x 256 fp32, 121.6 MB) exceeds the VMEM budget, so the
    tile model takes two token tiles of 8 rows."""
    from repro.kernels.fused_vq_matmul import fused_vq_matmul
    from repro.kernels.fused_vq_matmul.ops import select_fused_tiles

    K, N = 29568, 8192
    assert select_fused_tiles(16, K // 8, N, 2, 256)[0] == 8
    x = _sds(one_chip, (16, K), jnp.bfloat16)
    _assert_kernel(fused_vq_matmul.lower(x, _vq(one_chip, K, N, ()),
                                         out_dtype=jnp.bfloat16))


@pytest.mark.parametrize("name,K,N,splits", LINEARS[::3])
def test_dequant_gemv_compiles(one_chip, name, K, N, splits):
    from repro.kernels.dequant_gemv import dequant_gemv

    x = _sds(one_chip, (M_DECODE, K), jnp.bfloat16)
    _assert_kernel(dequant_gemv.lower(x, _vq(one_chip, K, N, splits)))


def test_vq_dequant_compiles(one_chip):
    """DeepSeek-V2-Lite's MLA wkv_b (kv_lora_rank 512, 16 heads x
    (128 + 128)), rebuilt on every decode step of the absorbed MLA."""
    from repro.kernels.vq_dequant import vq_dequant

    compiled = _assert_kernel(vq_dequant.lower(_vq(one_chip, 512, 4096, ())))
    assert "vq_dequant" in compiled.as_text()


def test_mla_wkv_b_kernel_compiles_on_a_mesh(topo, one_chip):
    """On four chips, with wkv_b's columns sharded over 'model', the
    absorbed MLA decode runs the kernel inside a shard_map on whole,
    replicated inputs: the TPU compiler refuses to partition a Mosaic
    kernel itself."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.plan import PlanPolicy
    from repro.models import common as cm

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    vq = VQWeight(
        idx=_sds(NamedSharding(mesh, P(None, None, "model")), (2, 64, 4096),
                 jnp.uint8),
        codebooks=_sds(NamedSharding(mesh, P()), (2, 8, 256), jnp.float32),
        scale=_sds(NamedSharding(mesh, P("model")), (4096,), jnp.float32),
        K=512, N=4096, d=8, n=8)
    rc = cm.RunConfig(mode="decode", plan_policy=PlanPolicy(
        vq_mode="eva", impl="pallas"))
    with mesh:
        compiled = jax.jit(lambda vq: cm._mla_wkv_b(
            {"wkv_b": {"vq": vq}}, rc, 512, 16, 128, 128)).lower(vq).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text


def test_int8_gemm_compiles(one_chip):
    from repro.kernels.int8_gemm import int8_matmul_kernel

    x = _sds(one_chip, (M_PREFILL, 3072), jnp.bfloat16)
    w = _sds(one_chip, (3072, 5120), jnp.bfloat16)
    _assert_kernel(int8_matmul_kernel.lower(x, w))


def test_flash_decode_compiles(one_chip):
    from repro.kernels.flash_decode import flash_decode

    q = _sds(one_chip, (B, 1, H, HD), jnp.bfloat16)
    kv = _sds(one_chip, (B, S, HK, HD), jnp.bfloat16)
    lens = _sds(one_chip, (B,), jnp.int32)
    _assert_kernel(flash_decode.lower(q, kv, kv, lens))


@pytest.mark.parametrize("kv_bits", [4, 2])
def test_flash_decode_kvq_compiles(one_chip, kv_bits):
    from repro.kernels.flash_decode import flash_decode_kvq

    kvq = KVQuantConfig(kv_bits=kv_bits)
    w = kvq.idx_width(HD)
    q = _sds(one_chip, (B, 1, H, HD), jnp.bfloat16)
    idx = _sds(one_chip, (B, S, HK, w), jnp.uint8)
    sc = _sds(one_chip, (B, S, HK), jnp.bfloat16)
    lens = _sds(one_chip, (B,), jnp.int32)
    cb = _sds(one_chip, (HK, kvq.residual, kvq.entries, kvq.vec_d),
              jnp.float32)
    _assert_kernel(flash_decode_kvq.lower(q, idx, idx, sc, sc, lens, cb, cb))


# DeepSeek-V2-Lite's routed experts: 64 of them stacked, top-6, d_model
# 2048, expert width 1408 (gate|up one grouped linear of N 2816). The
# layout holds every route plus at most a tile's padding per expert:
# 32 decode rows x 6 routes and a 128-token prefill bucket x 6 routes.
EXPERTS = 64
EXPERT_LINEARS = [("gu", 2048, 2816, (1408, 1408)), ("down", 1408, 2048, ())]


@pytest.mark.parametrize("routes", [32 * 6, 128 * 6])
@pytest.mark.parametrize("name,K,N,splits", EXPERT_LINEARS)
def test_grouped_vq_matmul_compiles(one_chip, name, K, N, splits, routes):
    from repro.core.ops import EXPERT_TILE, ExpertRows
    from repro.kernels.grouped_vq_matmul import grouped_vq_matmul

    R = -(-(routes + (EXPERT_TILE - 1) * EXPERTS) // EXPERT_TILE) \
        * EXPERT_TILE
    vq = VQWeight(idx=_sds(one_chip, (EXPERTS, 2, K // 8, N), jnp.uint8),
                  codebooks=_sds(one_chip, (EXPERTS, 2, 8, 256), jnp.float32),
                  scale=_sds(one_chip, (EXPERTS, N), jnp.float32),
                  K=K, N=N, d=8, n=8, splits=splits)
    rows = ExpertRows(_sds(one_chip, (R, K), jnp.bfloat16),
                      _sds(one_chip, (R // EXPERT_TILE,), jnp.int32),
                      _sds(one_chip, (), jnp.int32),
                      _sds(one_chip, (EXPERTS,), jnp.int32))
    compiled = _assert_kernel(grouped_vq_matmul.lower(
        rows, vq, out_dtype=jnp.bfloat16))
    assert "grouped_vq_matmul" in compiled.as_text()
