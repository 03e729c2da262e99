"""Pool-member weights made by the benchmark from the seed, and the
router checkpoint made from the configuration.

The program under test only receives these arrays; the plain reference
reads the same arrays. Each member's whole parameter tree is made on the
device in one jitted call, in float32 (the type the pool serves in), in
the layout the program's model code expects (taken from
``jax.eval_shape`` of its initializer, so nothing is allocated twice).

Values: RMSNorm scales are 1, the token table is N(0, 0.02^2), and every
matrix is N(0, 1/fan_in) with fan_in its second-to-last axis -- the usual
initialisation of a decoder LM, which keeps the residual stream and the
logits at unit scale through all layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A JAX key for any non-negative seed, also those above 2**32
    (``jax.random.key`` keeps only the low 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
        hi >>= 32
    return key


def _leaf_value(key, path, shape, dtype):
    names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    if len(shape) <= 1 or names[-1] == "scale":
        return jnp.ones(shape, dtype)
    if names[-1] == "table":
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    fan_in = shape[-2]
    return (jax.random.normal(key, shape, jnp.float32)
            / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)


def make_member_params(cfg, seed: int, member_index: int,
                       dtype=jnp.float32):
    """The member's parameter tree, made on the default device in one
    jitted call from ``(seed, member_index)``."""
    from repro.models import lm as lm_mod

    shapes = jax.eval_shape(
        lambda k: lm_mod.init_lm(k, cfg, dtype), jax.random.key(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = [_leaf_value(jax.random.fold_in(key, i), path, s.shape,
                              s.dtype)
                  for i, (path, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    key = jax.random.fold_in(seed_key(seed), member_index)
    return jax.jit(build)(key)


def make_router_params(router: dict, n_members: int, d_query: int):
    """The router checkpoint the deployment loads, made by the benchmark
    from the configuration's ``router`` entry, in the program's layout
    (``jax.eval_shape`` of its predictor initializers).

    Host float32 arrays, the same for every run: ``checkpoint_seed``
    draws them, so the lambda set against them holds on every machine.
    Every matrix is N(0, 1/fan_in); the query projections are scaled by
    ``query_scale`` (a unit-norm query would otherwise attend to every
    member alike), the quality head's output matrices by
    ``quality_head_scale``, and its bias is ``base_quality`` per
    member (every ensemble head alike, so heads differ by their matrices
    alone); the cost head's bias is 0 and the scaler
    (``cost_mu``, ``cost_sd``) turns its output into $ per request. Model
    embeddings (members x ``n_clusters``) are uniform in
    ``model_emb_range``, as per-cluster mean qualities.

    Returns ``(quality params, cost params, model embeddings, scaler)``."""
    import numpy as np

    from repro.core.predictors import PREDICTORS

    rng = np.random.default_rng(int(router["checkpoint_seed"]))
    k, c = n_members, int(router["n_clusters"])
    lo, hi = router["model_emb_range"]
    memb = rng.uniform(lo, hi, size=(k, c)).astype(np.float32)

    def head(kind, out_scale, bias):
        shapes = jax.eval_shape(
            lambda key: PREDICTORS[kind].init(key, d_query, k, c),
            jax.random.key(0))
        out = {}
        for name in sorted(shapes):
            shape = shapes[name].shape
            if name == "bo":
                v = np.broadcast_to(np.asarray(bias, np.float32), shape)
            else:
                v = rng.standard_normal(shape) / np.sqrt(shape[-2])
                if name == "wo":
                    v = v * out_scale
                elif name == "wq":
                    v = v * float(router["query_scale"])
            out[name] = np.array(v, np.float32)
        return out

    quality = head(router["quality_kind"], float(router["quality_head_scale"]),
                   router["base_quality"])
    cost = head(router["cost_kind"], 1.0, 0.0)
    scaler = {"mu": np.asarray(router["cost_mu"], np.float64),
              "sd": np.asarray(router["cost_sd"], np.float64)}
    return quality, cost, memb, scaler
