"""Host milliseconds per scoring batch on the ensemble path (jnp, no
kernel), from the harness span around ``engine.score_emb_uncertainty``."""
from bench.readers import score_ms_per_batch as read  # noqa: F401
