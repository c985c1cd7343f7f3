"""Pure-jnp oracle for the OC-lookup epilogue."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def oc_lookup_ref(O: jax.Array, I: jax.Array, scale: jax.Array) -> jax.Array:
    """O (C,M,V,k) fp32 (token-major), I (C,V,N) int, scale (N,) -> y
    (M,N) fp32."""
    g = jnp.take_along_axis(
        O, I[:, None, :, :].astype(jnp.int32), axis=3
    )  # (C, M, V, N)
    return g.sum(axis=(0, 2)) * scale[None, :].astype(jnp.float32)
