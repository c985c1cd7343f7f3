"""The VQ dequantization kernel (kernels/vq_dequant) in interpret mode:
bit for bit ``core.vq.dequantize`` (the absorbed MLA decode that runs
it: tests/test_moe_dropless.py)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.vq import dequantize, synthetic_vq
from repro.kernels.vq_dequant import vq_dequant

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("K,N,C,d,n", [
    (512, 4096, 2, 8, 8),   # DeepSeek-V2-Lite's wkv_b: r 512, 16 x (128+128)
    (64, 256, 2, 8, 8),     # the smoke MLA wkv_b: r 64, 4 x (32+32)
    (64, 200, 2, 8, 8),     # N not a multiple of 128
    (96, 130, 3, 4, 6),     # three codebooks, d 4, 64 entries
])
def test_vq_dequant_equals_dequantize_bit_for_bit(K, N, C, d, n):
    vq = dataclasses.replace(
        synthetic_vq(KEY, K, N, d=d, n=n, C=C),
        scale=jax.random.uniform(jax.random.PRNGKey(1), (N,), minval=0.5,
                                 maxval=2.0))
    got = vq_dequant(vq, interpret=True)
    assert got.shape == (K, N) and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(dequantize(vq)))
