"""Arithmetic the metric readers share (``bench/metrics/<name>.py``).

Every reader takes the run record the harness builds and returns a
number, or None where its cell gives it nothing to read. Window times
are seconds on the window's wall clock; requests are timed from their
due time.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from bench.reference import for_member


def window_gens(run) -> List[dict]:
    """Generate calls that ended inside the window."""
    return [g for g in run.rec.gens if g["t1"] <= run.window.t_close]


def window_spans(run, name: str) -> List[tuple]:
    return [s for s in run.rec.spans
            if s[0] == name and s[2] <= run.window.t_close]


def latencies(run) -> np.ndarray:
    """Due-to-completion seconds of every request offered; a request
    that never completed counts as the time from its due time to the end
    of the run (it missed every limit)."""
    out = []
    for r in run.window.requests:
        done = r.status == "done" and np.isfinite(r.finish_s)
        out.append((r.finish_s if done else run.window.t_end)
                   - r.arrival_s)
    return np.asarray(out, np.float64)


def percentile(values, q: float) -> Optional[float]:
    values = np.asarray(values, np.float64)
    if values.size == 0:
        return None
    return float(np.percentile(values, q))


def generate_seconds(run) -> float:
    return sum(g["t1"] - g["t0"] for g in window_gens(run))


def step_mfu_pct(run) -> Optional[float]:
    """2 * N_active * (prompt + generated tokens) of every generate call
    in the window, over the calls' host-timed seconds at the chip's peak;
    N_active from each member's reference module."""
    gens = window_gens(run)
    secs = generate_seconds(run)
    if not gens or secs <= 0:
        return None
    active = [for_member(m).n_active(m) for m in run.members]
    flops = sum(2.0 * active[g["member"]]
                * (sum(len(p) for p in g["prompts"])
                   + sum(len(o) for o in g["outs"])) for g in gens)
    return 100.0 * flops / (secs * run.peaks["flops_per_s"])


def generate_s_per_call(run) -> Optional[float]:
    gens = window_gens(run)
    return generate_seconds(run) / len(gens) if gens else None


def compiles_per_call(run) -> Optional[float]:
    gens = window_gens(run)
    if not gens:
        return None
    n = sum(1 for t, _ in run.rec.compiles
            for g in gens if g["t0"] <= t <= g["t1"])
    return n / len(gens)


def score_ms_per_batch(run) -> Optional[float]:
    spans = window_spans(run, "score")
    if not spans:
        return None
    return 1e3 * sum(b - a for _, a, b, _ in spans) / len(spans)


def device_idle_pct(run) -> Optional[float]:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
