"""90th percentile of the time completed requests spent queued
(``Request.queued_s``, all legs), on the wall clock."""
import numpy as np

from bench.readers import percentile


def read(run):
    return percentile([r.queued_s for r in run.window.completed
                       if np.isfinite(r.queued_s)], 90)
