"""The program's own layer spans, as the benchmark reads them.

The program's wall-clock layer profiler
(``repro.obs.profiling.LayerProfiler``, installed through
``repro.common.profile_slot``) records spans named ``repro.<layer>.<what>``
(``repro.sched.round``, ``repro.engine.generate``, ``repro.lm.prefill``,
``repro.lm.decode``, ``repro.lm.decode_step``, ...) and charges each XLA
compile to the innermost span open when it ran (``compiles`` and
``compile_s`` args). Each span is also a ``jax.profiler`` annotation.
Two readings:

- a run record's ``program_spans``, the profiler's spans as ``(name, t0,
  t1, args)`` on the window's clock: the per-layer numbers below read the
  spans that end inside the window, and return None where a run has none
  (a program without the profiler, or a run that did not install it);
- :func:`collect`, the ``repro.*`` annotations of a profiler trace beside
  its device operations: every idle gap of the device named by the
  innermost program span over it (``compile in <span>`` where compiles
  cover most of it), and the device's busy seconds inside each span name.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce as tr

PROGRAM_PREFIX = "repro."
GENERATE = "repro.engine.generate"
SCORE = "repro.engine.score"
PREFILL = "repro.lm.prefill"
DECODE = "repro.lm.decode"


# -- spans on the window's clock -------------------------------------------

def window_spans(run, name: str) -> List[tuple]:
    """Program spans called ``name`` that ran inside the window."""
    spans = getattr(run, "program_spans", None) or []
    return [s for s in spans
            if s[0] == name and s[1] >= 0.0 and s[2] <= run.window.t_close]


def prefill_s_per_call(run) -> Optional[float]:
    """Seconds from prompt to first token, per generate call."""
    spans = window_spans(run, PREFILL)
    if not spans:
        return None
    return sum(b - a for _, a, b, _ in spans) / len(spans)


def decode_s_per_step(run) -> Optional[float]:
    """Seconds of the decode loop per step (``max_new - 1`` a call)."""
    spans = window_spans(run, DECODE)
    steps = sum(int(args["steps"]) for *_, args in spans)
    if not steps:
        return None
    return sum(b - a for _, a, b, _ in spans) / steps


def compile_pct(run, name: str) -> Optional[float]:
    """Share of the ``name`` spans' seconds spent in XLA compiles charged
    to them or to any span inside them."""
    outer = window_spans(run, name)
    secs = sum(b - a for _, a, b, _ in outer)
    if secs <= 0:
        return None
    spans = getattr(run, "program_spans", None) or []
    compile_s = sum(args.get("compile_s", 0.0)
                    for _, a, b, args in spans
                    if any(oa <= a and b <= ob for _, oa, ob, _ in outer))
    return 100.0 * compile_s / secs


def readings(run) -> Dict[str, Optional[float]]:
    """The four per-layer numbers of one run, by metric stem (a cell's
    metric adds ``.open`` or ``.batch``)."""
    return {"prefill_s_per_call": prefill_s_per_call(run),
            "decode_s_per_step": decode_s_per_step(run),
            "generate_compile_pct": compile_pct(run, GENERATE),
            "score_compile_pct": compile_pct(run, SCORE)}


# -- the same spans in a profiler trace --------------------------------------

Span = Tuple[float, float, str]


@dataclasses.dataclass
class ProgramTrace:
    """What one traced window says per program span."""

    window: tr.Interval
    n_devices: int
    idle: Dict[str, float]       # label -> idle seconds, mean over devices
    busy: Dict[str, float]       # span name -> device-busy seconds, mean


def parse(xplane_path: str):
    """(device op intervals per device, program spans, host compile
    intervals, the harness's ``bench.*`` spans) of one trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    devices: List[List[tr.Interval]] = []
    spans: List[Span] = []
    bench: List[Span] = []
    compiles: List[tr.Interval] = []
    for plane in pd.planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            devices.append([
                (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events])
        elif plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    a = e.start_ns * 1e-9
                    iv = (a, a + e.duration_ns * 1e-9)
                    if e.name.startswith(PROGRAM_PREFIX):
                        spans.append((*iv, e.name))
                    elif e.name.startswith(tr.SPAN_PREFIX):
                        bench.append((*iv, e.name[len(tr.SPAN_PREFIX):]))
                    elif e.name == tr.COMPILE_EVENT:
                        compiles.append(iv)
    if not devices:
        raise ValueError(f"{xplane_path}: no {tr.DEVICE_PREFIX}* plane")
    return devices, spans, compiles, bench


def _overlap(u: Sequence[tr.Interval], starts: Sequence[float], a: float,
             b: float) -> float:
    """Seconds of the sorted disjoint intervals ``u`` inside [a, b]."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    out = 0.0
    while i < len(u) and u[i][0] < b:
        out += max(0.0, min(u[i][1], b) - max(u[i][0], a))
        i += 1
    return out


def _innermost(g0: float, g1: float, spans: Sequence[Span]) -> str:
    """The shortest program span over more than half of [g0, g1]: spans
    nest, so that is the innermost one the gap lies in ("host" where
    none does)."""
    best, label = float("inf"), "host"
    for a, b, name in spans:
        if min(b, g1) - max(a, g0) > 0.5 * (g1 - g0) and b - a < best:
            best, label = b - a, name
    return label


def attribute(devices: Sequence[Sequence[tr.Interval]],
              spans: Sequence[Span], compiles: Sequence[tr.Interval],
              window: tr.Interval) -> ProgramTrace:
    """Name each idle gap of each device in ``window`` by the innermost
    program span over it, ``compile in <span>`` where host compiles cover
    more than half of it, and add up device-busy seconds per span name."""
    lo, hi = window
    spans = [(max(a, lo), min(b, hi), n) for a, b, n in spans
             if b > lo and a < hi]
    comp = tr.union(compiles)
    comp_starts = [a for a, _ in comp]
    idle: Dict[str, float] = {}
    busy: Dict[str, float] = {}
    for ivs in devices:
        u = tr.union(tr.clip(ivs, lo, hi))
        starts = [a for a, _ in u]
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            label = _innermost(g0, g1, spans)
            if _overlap(comp, comp_starts, g0, g1) > 0.5 * (g1 - g0):
                label = ("compile" if label == "host"
                         else f"compile in {label}")
            idle[label] = idle.get(label, 0.0) + (g1 - g0) / len(devices)
        for a, b, name in spans:
            busy[name] = (busy.get(name, 0.0)
                          + _overlap(u, starts, a, b) / len(devices))
    return ProgramTrace(window=window, n_devices=len(devices), idle=idle,
                        busy=busy)


def collect(xplane_path: str, window_span: Optional[str] = "window",
            window_s: Optional[float] = None) -> ProgramTrace:
    """The trace's program spans within the harness span
    ``bench.<window_span>`` (the whole trace where there is none), cut to
    its first ``window_s`` seconds where that is given."""
    devices, spans, compiles, bench = parse(xplane_path)
    window = next(((a, b) for a, b, n in bench if n == window_span), None)
    if window is None:
        edges = [x for ivs in devices for iv in ivs for x in iv]
        edges += [x for a, b, _ in spans + bench for x in (a, b)]
        window = (min(edges), max(edges))
    if window_s is not None:
        window = (window[0], min(window[1], window[0] + window_s))
    return attribute(devices, spans, compiles, window)


def line(pt: ProgramTrace) -> str:
    """One line: idle seconds by program span and device-busy seconds by
    span name, largest first."""
    def top(d):
        return ", ".join(f"{k} {v:.3f}" for k, v in
                         sorted(d.items(), key=lambda kv: -kv[1]))
    return (f"program spans: idle s by span: {top(pt.idle)}; device busy "
            f"s by span: {top(pt.busy)}")
