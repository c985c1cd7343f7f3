"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ops as core_ops
from repro.core.vq import synthetic_vq
from repro.kernels.dequant_gemv import dequant_gemv
from repro.kernels.fused_vq_matmul import fused_vq_matmul
from repro.kernels.int8_gemm import int8_matmul_kernel
from repro.kernels.oc_lookup import oc_lookup
from repro.kernels.vq_gemm import vq_gemm

KEY = jax.random.PRNGKey(0)

SHAPE_SWEEP = [
    # (K, N, M, d, n, C)
    (64, 128, 1, 8, 8, 1),       # paper decode: M=1
    (128, 384, 4, 8, 8, 2),      # multi-codebook
    (256, 256, 2, 8, 4, 3),
    (96, 80, 3, 8, 5, 2),        # non-divisible N vs block sizes
    (64, 512, 8, 4, 8, 1),       # d=4 (GPTVQ-4D config)
    (160, 100, 2, 8, 8, 4),      # C=4 (4-bit)
    (88, 130, 3, 8, 8, 2),       # V=11, N=130: pads BOTH v and n tiles
    (104, 52, 2, 8, 8, 1),       # V=13 odd vs block_v, N < block_n
    (128, 256, 16, 8, 8, 2),     # 16 decode slots: one token group
    (96, 130, 24, 8, 8, 2),      # a group of 16 tokens and one of 8
    (64, 70, 40, 8, 8, 1),       # two groups of 16 in a loop, one of 8
]


def _ids(shape):
    return "-".join(map(str, shape))


# The fused kernel's sweep: every SHAPE_SWEEP shape (ungrouped, every
# row in one token tile), a grouped family, and a VMEM budget so small
# that the token tile model splits M=20 into three tiles of 8 rows (the
# last padded): (K, N, M, d, n, C, splits, oc_budget)
FUSED_SWEEP = [pytest.param(*shape, (), None, id=_ids(shape))
               for shape in SHAPE_SWEEP] + [
    pytest.param(96, 96, 16, 8, 8, 2, (50, 26, 20), None, id="grouped-M16"),
    pytest.param(128, 96, 20, 8, 8, 2, (), 12 * 4 * 2 * 16 * 256,
                 id="three-token-tiles-M20"),
]

DTYPE_SWEEP = [jnp.float32, jnp.bfloat16]


def _mk(K, N, M, d, n, C, dtype, splits=()):
    vq = synthetic_vq(KEY, K, N, d=d, n=n, C=C, splits=splits)
    x = jax.random.normal(jax.random.fold_in(KEY, K * N + M), (M, K), dtype)
    return x, vq


@pytest.mark.parametrize("K,N,M,d,n,C", SHAPE_SWEEP)
@pytest.mark.parametrize("dtype", DTYPE_SWEEP)
def test_vq_gemm_kernel(K, N, M, d, n, C, dtype):
    x, vq = _mk(K, N, M, d, n, C, dtype)
    got = vq_gemm(x, vq.codebooks, interpret=True, block_mv=32)
    ref = vq_gemm(x, vq.codebooks, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("K,N,M,d,n,C", SHAPE_SWEEP)
def test_oc_lookup_kernel(K, N, M, d, n, C):
    x, vq = _mk(K, N, M, d, n, C, jnp.float32)
    O = vq_gemm(x, vq.codebooks, use_pallas=False)
    got = oc_lookup(O, vq.idx, vq.scale, interpret=True, block_v=4, block_n=64)
    ref = oc_lookup(O, vq.idx, vq.scale, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("K,N,M,d,n,C,splits,oc_budget", FUSED_SWEEP)
@pytest.mark.parametrize("dtype", DTYPE_SWEEP)
def test_fused_vq_matmul_kernel(K, N, M, d, n, C, splits, oc_budget, dtype):
    from repro.kernels.fused_vq_matmul.ops import select_fused_tiles

    x, vq = _mk(K, N, M, d, n, C, dtype, splits=splits)
    kw = {}
    if oc_budget is not None:
        mt, _, _ = select_fused_tiles(M, vq.V, N, C, 2 ** n, block_v=4,
                                      oc_budget=oc_budget)
        assert mt == 8 and -(-M // mt) == 3
        kw["m_tile"] = mt
    got = fused_vq_matmul(x, vq, interpret=True, block_v=4, block_n=64,
                          out_dtype=jnp.float32, **kw)
    ref = core_ops.eva_matmul(x, vq, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-3 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-3 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("K,N,M,d,n,C", SHAPE_SWEEP)
def test_dequant_gemv_kernel(K, N, M, d, n, C):
    x, vq = _mk(K, N, M, d, n, C, jnp.float32)
    got = dequant_gemv(x, vq, interpret=True, block_v=4, block_n=64,
                       out_dtype=jnp.float32)
    ref = core_ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("M,K,N", [(1, 128, 64), (8, 256, 128), (5, 96, 48)])
@pytest.mark.parametrize("dtype", DTYPE_SWEEP)
def test_int8_gemm_kernel(M, K, N, dtype):
    x = jax.random.normal(KEY, (M, K), dtype)
    w = jax.random.normal(jax.random.fold_in(KEY, 9), (K, N), jnp.float32) * 0.1
    got = int8_matmul_kernel(x, w, interpret=True, block_m=8, block_n=32,
                             block_k=64, out_dtype=jnp.float32)
    ref = core_ops.int8_matmul(x, w, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("K,N", [(88, 130), (104, 52), (80, 70)])
def test_kernel_wrappers_auto_tiles_on_odd_shapes(K, N):
    """Regression (odd-shape padding): every kernel wrapper with "auto"
    tile selection pads non-divisible V/N instead of tripping the
    kernels' V % block_v == 0 / N % block_n == 0 asserts."""
    x, vq = _mk(K, N, 3, 8, 8, 2, jnp.float32)
    ref = core_ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
    got_f = fused_vq_matmul(x, vq, interpret=True, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got_f), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    got_d = dequant_gemv(x, vq, interpret=True, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    O = vq_gemm(x, vq.codebooks, use_pallas=False)
    got_o = oc_lookup(O, vq.idx, vq.scale, interpret=True)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bv,bn", [(4, 64), (32, 512), (16, 48)])
def test_oc_and_dequant_kernels_pad_non_divisible_blocks(bv, bn):
    """Explicit block sizes that do NOT divide V/N (V=11 vs bv=4/32,
    N=130 vs bn=64/512/48) must be padded the way fused_vq_matmul pads."""
    x, vq = _mk(88, 130, 2, 8, 8, 2, jnp.float32)
    ref = core_ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
    got_o = oc_lookup(vq_gemm(x, vq.codebooks, use_pallas=False), vq.idx,
                      vq.scale, interpret=True, block_v=bv, block_n=bn)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    got_d = dequant_gemv(x, vq, interpret=True, block_v=bv, block_n=bn,
                         out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_kernel_equals_paper_formulation_end_to_end():
    """fused kernel == X @ dequant(I,B,s) — the full pipeline is exact."""
    x, vq = _mk(128, 96, 2, 8, 8, 2, jnp.float32)
    y_kernel = fused_vq_matmul(x, vq, interpret=True, block_v=8, block_n=32,
                               out_dtype=jnp.float32)
    y_dense = core_ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_dense),
                               rtol=1e-4, atol=1e-5)


def test_eva_matmul_pallas_dispatch():
    x, vq = _mk(64, 48, 2, 8, 4, 2, jnp.float32)
    got = core_ops.eva_matmul(x, vq, impl="pallas", interpret=True)
    ref = core_ops.eva_matmul(x, vq, impl="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_eva_split_matmul_two_kernel_pipeline():
    """The no-fusion formulation — vq_gemm materializes the OC buffer,
    oc_lookup gathers from it — equals the dequant oracle, including a
    grouped family (wider N in the lookup stage only) and odd V/N that
    pad against the kernel tiles."""
    from repro.kernels.oc_lookup.ops import eva_split_matmul

    for K, N, splits, M in ((128, 96, (), 2), (80, 70, (), 3),
                            (96, 96, (50, 26, 20), 1),
                            (96, 96, (50, 26, 20), 16)):
        x, vq = _mk(K, N, M, 8, 8, 2, jnp.float32, splits=splits)
        got = eva_split_matmul(x, vq, interpret=True, out_dtype=jnp.float32)
        ref = core_ops.dequant_matmul(x, vq, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
