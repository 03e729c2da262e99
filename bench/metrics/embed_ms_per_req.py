"""Host milliseconds of the query embedder per text, from the harness
span around ``engine.embed``."""
from bench.readers import window_spans


def read(run):
    spans = window_spans(run, "embed")
    n = sum(s[3] for s in spans)
    return 1e3 * sum(b - a for _, a, b, _ in spans) / n if n else None
