"""Host milliseconds per scoring batch (the router kernel path), from
the harness span around ``engine.score_emb``."""
from bench.readers import score_ms_per_batch as read  # noqa: F401
