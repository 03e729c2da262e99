"""Compiles for one described TPU v5e chip (no chip attached).

The TPU compiler refuses what interpret mode accepts: tiles not aligned
to the hardware, kernels over their fast-memory budget, programs larger
than the chip's memory. These tests compile the serving path's kernels at
their serving widths, qwen3-0.6b's decode step and both benchmark
members' compiled decode loops at their published widths, for a
described v5e. The topology is described inside a fixture,
so no module import loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.model_repr import N_CLUSTERS
from repro.core.predictors import ATTN_LATENT
from repro.data.featurizer import EMB_DIM
from repro.kernels import ops
from repro.models import lm as lm_mod

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e, with the persistent compilation
    cache off: what is compiled for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("k", [2, 3])
def test_router_xattn_pool_compiles_at_serving_widths(one_chip, k):
    """Score batch 64 (padded to the 256-row tile), 768-d queries, latent
    20 and pool size K padded to 128 lanes."""
    args = (_sds((64, EMB_DIM), one_chip),
            _sds((EMB_DIM, ATTN_LATENT), one_chip),
            _sds((k, ATTN_LATENT), one_chip),
            _sds((k, ATTN_LATENT), one_chip),
            _sds((ATTN_LATENT, k), one_chip),
            _sds((k,), one_chip))
    compiled = _compile(
        functools.partial(ops.router_xattn_pool, interpret=False), *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_router_xattn_compiles_with_pool_projections(one_chip):
    """The unfused entry point (projections inside the jit) at K=3."""
    k = 3
    args = (_sds((64, EMB_DIM), one_chip),
            _sds((EMB_DIM, ATTN_LATENT), one_chip),
            _sds((N_CLUSTERS, ATTN_LATENT), one_chip),
            _sds((N_CLUSTERS, ATTN_LATENT), one_chip),
            _sds((ATTN_LATENT, k), one_chip),
            _sds((k,), one_chip),
            _sds((k, N_CLUSTERS), one_chip))
    compiled = _compile(functools.partial(ops.router_xattn, interpret=False),
                        *args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,c", [(64, 256), (512, 512)],
                         ids=["cache-probe", "radius-calibration"])
def test_pairwise_l2_compiles(one_chip, n, c):
    compiled = _compile(functools.partial(ops.pairwise_l2, interpret=False),
                        _sds((n, EMB_DIM), one_chip),
                        _sds((c, EMB_DIM), one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_decode_step_fits_one_chip(one_chip):
    """qwen3-0.6b's decode step at published widths (float32 parameters,
    a generate micro-batch of 8 with a 48-token prompt plus 8 new
    tokens) compiles for one v5e and fits its 16 GiB."""
    cfg = get_config("qwen3-0.6b")
    batch, max_len = 8, 48 + 8

    def on_chip(tree):
        return jax.tree.map(lambda a: _sds(a.shape, one_chip, a.dtype), tree)

    params = on_chip(lm_mod.abstract_params(cfg))
    caches = on_chip(lm_mod.abstract_caches(cfg, batch, max_len))
    compiled = _compile(functools.partial(lm_mod.apply_lm_decode, cfg),
                        params, _sds((batch, 1), one_chip, jnp.int32),
                        caches, _sds((), one_chip, jnp.int32))
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 3e9      # ~0.75 B float32 params
    assert total < V5E_HBM_BYTES, total


@pytest.mark.parametrize("name", ["qwen3-0.6b", "granite-moe-1b-a400m"])
def test_decode_loop_fits_one_chip(one_chip, name):
    """The compiled decode loop at published widths (float32 parameters and
    caches), a generate micro-batch of 8 at the top cache bucket of the
    benchmark's prompts (899 + 8 tokens, 1024 slots), 7 steps after the
    first token: the donated caches alias the returned ones, so one copy
    is live, and the program fits one v5e's 16 GiB."""
    cfg = get_config(name)
    batch, max_new = 8, 8
    max_len = lm_mod.cache_len(899, max_new)
    assert max_len == 1024

    def on_chip(tree):
        return jax.tree.map(lambda a: _sds(a.shape, one_chip, a.dtype), tree)

    params = on_chip(lm_mod.abstract_params(cfg))
    caches = on_chip(lm_mod.abstract_caches(cfg, batch, max_len))
    compiled = lm_mod.decode_loop.lower(
        params, _sds((batch, 1), one_chip, jnp.int32), caches,
        _sds((), one_chip, jnp.int32), cfg=cfg, max_new=max_new).compile()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(caches))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 3e9      # float32 parameters
    assert total < V5E_HBM_BYTES, total
