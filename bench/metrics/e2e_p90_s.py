"""90th percentile of due-to-completion time over every request due in
the window."""
from bench.readers import latencies, percentile


def read(run):
    return percentile(latencies(run), 90)
