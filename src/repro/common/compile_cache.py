"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``repro.launch.serve``, ``repro.distributed.host``,
``chip_smoke.py``, ``benchmarks.run``) call :func:`enable_compile_cache`
from their ``main``; importing this module changes nothing.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def checkout_root() -> str:
    """The repository checkout this package was imported from."""
    import repro

    # __path__, not __file__: repro may load as a namespace package.
    return os.path.dirname(os.path.dirname(
        os.path.abspath(list(repro.__path__)[0])))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives in ``.jax_cache/`` at
    the checkout root: a fixed path, so a later process of the same
    checkout finds what an earlier one compiled.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = os.path.join(checkout_root(), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
