"""jit'd public wrappers around the Pallas kernels.

Handle padding to TPU tile granularity (128 lanes), interpret mode on the
CPU, and un-padding of results. The rest of the codebase calls only these
entry points.

Profiling: the serving-hot entry points, ``router_xattn_pool`` and
``pairwise_l2``, read the program's one profiler slot
(:func:`repro.common.profile_slot.active`). With nothing installed (the
default) each call goes straight to the jit'd function: one ``None``
test, no annotation, clock read or sync. With a
:class:`repro.obs.profiling.LayerProfiler` installed
(:func:`repro.common.profile_slot.install`) each dispatch runs as the
span ``repro.kernels.router_xattn_pool`` or ``repro.kernels.pairwise_l2``
(arg ``n``, the batch rows) and blocks until the result is ready, so the
span covers the device work and not just the dispatch. The other
layers' spans, and the compiles the profiler charges to each, are listed
in :mod:`repro.obs.profiling`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common.profile_slot import active
from repro.kernels.pairwise_l2 import pairwise_l2_pallas
from repro.kernels.router_xattn import router_xattn_pallas

LANE = 128


def _interpret() -> bool:
    """Interpret mode on the CPU (the tests' platform), the compiled
    kernel on TPU; any other platform is an error, never a silent
    interpreter run."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels target TPU; refusing to run them in interpret "
        f"mode on platform {platform!r}")


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pool_projections(wk, wv, m_emb):
    """Pool-side K~ = m_emb Wk and V~ = m_emb Wv (fp32, (K, d)).

    Per-pool constants at serving time: compute once when the pool is
    (re)built and reuse across every score batch via
    :func:`router_xattn_pool`. Contracted in full float32, as the kernel
    is: XLA's default on TPU rounds the operands to bf16, which moved the
    kernel's scores by 5e-3 of their scale on a v5e.
    """
    m, f32 = m_emb.astype(jnp.float32), jax.lax.Precision.HIGHEST
    kt = jnp.matmul(m, wk.astype(jnp.float32), precision=f32)
    vt = jnp.matmul(m, wv.astype(jnp.float32), precision=f32)
    return kt, vt


def _xattn_padded(q, wq, kt, vt, wo, bo, *, block_b, interpret):
    """Pad to TPU tile granularity and invoke the Pallas kernel."""
    b, dq = q.shape
    k, d = kt.shape

    d_pad = _round_up(d, LANE)
    k_pad = _round_up(k, LANE)
    b_pad = _round_up(b, block_b)

    qp = jnp.pad(q, ((0, b_pad - b), (0, 0)))
    wq_p = jnp.pad(wq, ((0, 0), (0, d_pad - d)))
    kt_p = jnp.pad(kt, ((0, k_pad - k), (0, d_pad - d)))
    vt_p = jnp.pad(vt, ((0, k_pad - k), (0, d_pad - d)))
    wo_p = jnp.pad(wo, ((0, d_pad - d), (0, k_pad - k)))
    bo_p = jnp.pad(bo, (0, k_pad - k))[None, :]
    kmask = (jnp.arange(k_pad) < k).astype(jnp.float32)[None, :]

    out = router_xattn_pallas(
        qp, wq_p, kt_p, vt_p, wo_p, bo_p, kmask,
        d_latent=d, block_b=block_b, interpret=interpret,
    )
    return out[:b, :k]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def router_xattn(
    q, wq, wk, wv, wo, bo, m_emb, *, block_b: int = 256, interpret: bool = None
):
    """Fused routing scores: q (B, dq), m_emb (K, dm) -> (B, K) fp32.

    Pads d_latent and K to 128 lanes and B to the batch tile; the pool-side
    projections (K~ = m_emb Wk etc.) are tiny and computed outside the
    kernel (they are per-pool constants at serving time).
    """
    if interpret is None:
        interpret = _interpret()
    kt, vt = pool_projections(wk, wv, m_emb)
    return _xattn_padded(q, wq, kt, vt, wo, bo,
                         block_b=block_b, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def _router_xattn_pool_jit(
    q, wq, kt, vt, wo, bo, *, block_b: int = 256, interpret: bool = None
):
    if interpret is None:
        interpret = _interpret()
    return _xattn_padded(q, wq, kt, vt, wo, bo,
                         block_b=block_b, interpret=interpret)


def router_xattn_pool(
    q, wq, kt, vt, wo, bo, *, block_b: int = 256, interpret: bool = None
):
    """Fused routing scores against precomputed pool projections.

    The serving scheduler's hot path: K~/V~ from :func:`pool_projections`
    are computed once per pool and reused across every score micro-batch,
    so the per-batch work is only the query-side projection + attention.
    """
    prof = active()
    if prof is None:
        return _router_xattn_pool_jit(q, wq, kt, vt, wo, bo,
                                      block_b=block_b, interpret=interpret)
    with prof.span("repro.kernels.router_xattn_pool", n=int(q.shape[0])):
        out = _router_xattn_pool_jit(q, wq, kt, vt, wo, bo,
                                     block_b=block_b, interpret=interpret)
        jax.block_until_ready(out)
    return out


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "interpret"))
def _pairwise_l2_jit(
    x, c, *, block_n: int = 256, block_k: int = 256, interpret: bool = None
):
    if interpret is None:
        interpret = _interpret()
    n, d = x.shape
    k = c.shape[0]
    block_n = min(block_n, _round_up(n, 8))
    block_k = min(block_k, _round_up(k, 8))
    n_pad = _round_up(n, block_n)
    k_pad = _round_up(k, block_k)
    xp = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    cp = jnp.pad(c, ((0, k_pad - k), (0, 0)))
    out = pairwise_l2_pallas(
        xp, cp, block_n=block_n, block_k=block_k, interpret=interpret
    )
    return out[:n, :k]


def pairwise_l2(
    x, c, *, block_n: int = 256, block_k: int = 256, interpret: bool = None
):
    """Squared L2 distances x (N,d) vs c (K,d) -> (N,K) fp32."""
    prof = active()
    if prof is None:
        return _pairwise_l2_jit(x, c, block_n=block_n, block_k=block_k,
                                interpret=interpret)
    with prof.span("repro.kernels.pairwise_l2", n=int(x.shape[0])):
        out = _pairwise_l2_jit(x, c, block_n=block_n, block_k=block_k,
                               interpret=interpret)
        jax.block_until_ready(out)
    return out
