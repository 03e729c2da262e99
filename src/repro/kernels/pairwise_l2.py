"""Tiled pairwise squared-L2 distance kernel (Pallas TPU).

Backs the KNN baseline router and k-means model-embedding construction:
dist2[n, k] = ||x_n - c_k||^2 computed as x2 + c2 - 2 x.c with the cross
term on the MXU.

Grid: (N / block_n, K / block_k); the feature dimension is kept whole in
VMEM (d <= 1024 covers the 768-d embeddings; block_n=256, block_k=256 tiles
use ~1.5 MB). Squared norms are computed in-kernel, so the only HBM traffic
is the two operand tiles and the output tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pairwise_l2_kernel(x_ref, c_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)            # (bn, d)
    c = c_ref[...].astype(jnp.float32)            # (bk, d)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)    # (bn, 1)
    c2 = jnp.sum(c * c, axis=1)                   # (bk,)
    # Full float32 contraction: Mosaic's default rounds to bf16.
    cross = jnp.dot(x, c.T, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    d2 = x2 - 2.0 * cross + c2[None, :]
    out_ref[...] = jnp.maximum(d2, 0.0).astype(out_ref.dtype)


def pairwise_l2_pallas(
    x, c, *, block_n: int = 256, block_k: int = 256, interpret: bool = False
):
    """x (N, d), c (K, d) -> (N, K) squared distances. N, K pre-padded."""
    n, d = x.shape
    k = c.shape[0]
    assert n % block_n == 0 and k % block_k == 0, (n, k, block_n, block_k)
    return pl.pallas_call(
        _pairwise_l2_kernel,
        grid=(n // block_n, k // block_k),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, block_k), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(x, c)
