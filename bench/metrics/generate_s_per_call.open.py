"""Host seconds per generate micro-batch (prefill plus decode), from the
harness span around ``engine.generate_member``."""
from bench.readers import generate_s_per_call as read  # noqa: F401
