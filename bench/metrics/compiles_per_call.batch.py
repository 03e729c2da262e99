"""XLA backend compiles (jax.monitoring events) inside generate calls,
per call."""
from bench.readers import compiles_per_call as read  # noqa: F401
