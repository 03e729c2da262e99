"""Fused single-head cross-attention routing-score kernel (Pallas TPU).

The paper's serving hot path: score a batch of query embeddings against the
model pool. One VMEM-resident pass per batch tile computes

    qp     = q @ Wq                       (768 -> d_latent)
    logits = qp @ K~^T / sqrt(d)          (K~ = model_emb @ Wk, precomputed)
    alpha  = softmax_K(logits)
    ctx    = alpha @ V~
    scores = ctx @ Wo + bo                ((B_tile, K) per-model scores)

TPU adaptation: the paper's latent d=20 and pool size K<=16 are far below
MXU/VPU tile granularity, so the wrapper (ops.py) zero-pads d_latent and K
to 128 lanes; padded K columns are masked to -inf before the softmax. One
batch tile (default 256 rows) keeps the whole working set
(256x768 q + 768x128 Wq + 3x128x128 pool mats ~ 1.2 MB fp32) comfortably in
the ~16 MB v5e VMEM while saturating the 128x128 MXU.

Grid: (B / block_b,). All operands are placed in VMEM via BlockSpecs; the
pool-side matrices are small and broadcast to every grid step.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
# Contract in full float32. Mosaic's default rounds f32 operands to bf16,
# which moved the scores by 4e-2 of their scale on a v5e against the
# float32 reference; the matmuls here are far too small for the extra
# MXU passes to matter.
_F32 = jax.lax.Precision.HIGHEST


def _router_xattn_kernel(
    q_ref,      # (block_b, dq)
    wq_ref,     # (dq, d_pad)
    kt_ref,     # (k_pad, d_pad)   projected model keys
    vt_ref,     # (k_pad, d_pad)   projected model values
    wo_ref,     # (d_pad, k_pad)
    bo_ref,     # (1, k_pad)
    kmask_ref,  # (1, k_pad)  1.0 for real models, 0.0 for padding
    out_ref,    # (block_b, k_pad)
    *,
    d_latent: int,
):
    q = q_ref[...].astype(jnp.float32)
    wq = wq_ref[...].astype(jnp.float32)
    qp = jnp.dot(q, wq, preferred_element_type=jnp.float32,
                 precision=_F32)                                  # (b, d_pad)

    kt = kt_ref[...].astype(jnp.float32)                          # (K, d_pad)
    scale = 1.0 / math.sqrt(d_latent)
    logits = jnp.dot(qp, kt.T, preferred_element_type=jnp.float32,
                     precision=_F32) * scale

    kmask = kmask_ref[0, :]                                       # (k_pad,)
    logits = jnp.where(kmask[None, :] > 0, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    alpha = e / jnp.sum(e, axis=-1, keepdims=True)                # (b, K)

    vt = vt_ref[...].astype(jnp.float32)
    ctx = jnp.dot(alpha, vt, preferred_element_type=jnp.float32,
                  precision=_F32)                                 # (b, d_pad)

    wo = wo_ref[...].astype(jnp.float32)
    scores = jnp.dot(ctx, wo, preferred_element_type=jnp.float32,
                     precision=_F32)
    out_ref[...] = (scores + bo_ref[0, :][None, :]).astype(out_ref.dtype)


def router_xattn_pallas(
    q, wq, kt, vt, wo, bo, kmask, *, d_latent: int, block_b: int = 256,
    interpret: bool = False,
):
    """Padded-shape kernel entry. q (B, dq); B % block_b == 0."""
    b, dq = q.shape
    k_pad, d_pad = kt.shape
    assert b % block_b == 0, (b, block_b)
    kernel = functools.partial(_router_xattn_kernel, d_latent=d_latent)
    return pl.pallas_call(
        kernel,
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, dq), lambda i: (i, 0)),
            pl.BlockSpec((dq, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((k_pad, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((k_pad, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((d_pad, k_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, k_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, k_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, k_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k_pad), jnp.float32),
        interpret=interpret,
    )(q, wq, kt, vt, wo, bo, kmask)
