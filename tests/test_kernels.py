"""Pallas kernels: shape/dtype sweeps vs pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _mk(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("b", [1, 8, 100, 256, 300])
@pytest.mark.parametrize("k", [2, 5, 11])
def test_router_xattn_shape_sweep(b, k):
    keys = jax.random.split(jax.random.key(b * 31 + k), 7)
    dq, dm, d = 768, 20, 20
    q = _mk(keys[0], (b, dq), jnp.float32)
    m_emb = _mk(keys[1], (k, dm), jnp.float32)
    wq = _mk(keys[2], (dq, d), jnp.float32) * 0.05
    wk = _mk(keys[3], (dm, d), jnp.float32) * 0.3
    wv = _mk(keys[4], (dm, d), jnp.float32) * 0.3
    wo = _mk(keys[5], (d, k), jnp.float32) * 0.3
    bo = _mk(keys[6], (k,), jnp.float32) * 0.1
    out = ops.router_xattn(q, wq, wk, wv, wo, bo, m_emb, interpret=True)
    expect = ref.router_xattn_ref(q, wq, wk, wv, wo, bo, m_emb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d_latent", [4, 20, 64, 128])
def test_router_xattn_dtype_latent_sweep(dtype, d_latent):
    keys = jax.random.split(jax.random.key(d_latent), 7)
    b, k, dq, dm = 64, 5, 256, 20
    q = _mk(keys[0], (b, dq), dtype)
    m_emb = _mk(keys[1], (k, dm), jnp.float32)
    wq = _mk(keys[2], (dq, d_latent), jnp.float32) * 0.05
    wk = _mk(keys[3], (dm, d_latent), jnp.float32) * 0.3
    wv = _mk(keys[4], (dm, d_latent), jnp.float32) * 0.3
    wo = _mk(keys[5], (d_latent, k), jnp.float32) * 0.3
    bo = jnp.zeros((k,))
    out = ops.router_xattn(q, wq, wk, wv, wo, bo, m_emb, interpret=True)
    expect = ref.router_xattn_ref(q, wq, wk, wv, wo, bo, m_emb)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol)


def test_router_xattn_matches_predictor_module():
    """Kernel semantics == the core library's attention predictor."""
    from repro.core.predictors import PREDICTORS

    pred = PREDICTORS["attn"]
    params = pred.init(jax.random.key(0), 768, 5, 20)
    q = jax.random.normal(jax.random.key(1), (40, 768))
    m = jax.random.normal(jax.random.key(2), (5, 20))
    core = pred.apply(params, q, m)
    kern = ops.router_xattn(
        q, params["wq"], params["wk"], params["wv"], params["wo"],
        params["bo"], m, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(kern), np.asarray(core),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,k,d", [(8, 3, 16), (100, 20, 768), (256, 256, 64),
                                   (300, 37, 128), (1, 1, 8)])
def test_pairwise_l2_shape_sweep(n, k, d):
    keys = jax.random.split(jax.random.key(n * 7 + k), 2)
    x = _mk(keys[0], (n, d), jnp.float32)
    c = _mk(keys[1], (k, d), jnp.float32)
    out = ops.pairwise_l2(x, c, interpret=True)
    expect = ref.pairwise_l2_ref(x, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_l2_dtypes(dtype):
    keys = jax.random.split(jax.random.key(0), 2)
    x = _mk(keys[0], (64, 256), dtype)
    c = _mk(keys[1], (16, 256), dtype)
    out = ops.pairwise_l2(x, c, interpret=True)
    expect = ref.pairwise_l2_ref(x, c)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol)


def test_pairwise_l2_zero_distance_on_identical_rows():
    x = jnp.ones((8, 32))
    out = ops.pairwise_l2(x, x, interpret=True)
    assert float(jnp.abs(out).max()) < 1e-5


def test_pairwise_l2_matches_clustering_module():
    from repro.core.clustering import pairwise_sq_dists

    x = jax.random.normal(jax.random.key(5), (50, 96))
    c = jax.random.normal(jax.random.key(6), (7, 96))
    np.testing.assert_allclose(
        np.asarray(ops.pairwise_l2(x, c, interpret=True)),
        np.asarray(pairwise_sq_dists(x, c)),
        rtol=1e-4, atol=1e-4,
    )


@pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                ("tpu", False)])
def test_interpret_mode_follows_the_platform(monkeypatch, platform,
                                             interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert ops._interpret() is interpret


def test_no_interpret_fallback_on_other_platforms(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="target TPU"):
        ops._interpret()
