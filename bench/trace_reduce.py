"""From a profiler trace to the numbers the per-layer metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``:

- device planes are those named ``/device:TPU:<n>``; their ``XLA Ops``
  line holds one event per operation that ran (a Pallas kernel is a
  ``custom-call`` op named after its jitted function);
- the host plane ``/host:CPU`` holds, on its ``python`` line, the
  harness's ``jax.profiler.TraceAnnotation`` spans (``bench.<name>``) and
  on other lines the compiler's ``PJRT_Client_Compile`` events.

Host and device events share one time base, in nanoseconds from the start
of the trace (the device's may lead the host's by a millisecond, which
is far below the gaps this attributes).
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
COMPILE_EVENT = "PJRT_Client_Compile"

Interval = Tuple[float, float]   # seconds from the start of the trace


@dataclasses.dataclass
class Reduced:
    """What one traced window reduces to."""

    window: Interval
    n_devices: int
    busy_s: float                       # union of op intervals, per device
    ops: Dict[str, float]               # op name -> device seconds
    kernels: Dict[str, List[float]]     # custom-call op -> durations
    gaps: List[Tuple[float, str]]       # (idle seconds, what the host did)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def kernel_durations(self, prefix: str) -> List[float]:
        """Device seconds of each call of the custom-call ops (Pallas
        kernels) named ``<prefix>.<n>``, over all devices."""
        return [d for name, ds in self.kernels.items()
                if name.split(".")[0] == prefix for d in ds]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _label(t0: float, t1: float, spans: Sequence[Tuple[float, float, str]],
           compiles: Sequence[Interval]) -> str:
    """What the host was doing through most of the gap [t0, t1]."""
    def overlap(a, b):
        return max(0.0, min(b, t1) - max(a, t0))

    best, label, span_len = 0.0, "host", float("inf")
    comp = sum(overlap(a, b) for a, b in union(compiles))
    if comp > 0.5 * (t1 - t0):
        return "compile"
    for a, b, name in spans:
        o = overlap(a, b)
        # Prefer the innermost (shortest) span that covers the gap best.
        if o > best or (o == best and o > 0 and b - a < span_len):
            best, label, span_len = o, name, b - a
    return label


def reduce(xplane_path: str, window_span: Optional[str] = "window",
           window_s: Optional[float] = None) -> Reduced:
    """Busy time, per-op device time, and every idle gap
    named by what the host was doing, within the harness span
    ``bench.<window_span>`` (the whole trace where there is none), cut to
    its first ``window_s`` seconds where that is given."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    device_ops: List[List[Interval]] = []
    ops: Dict[str, float] = {}
    spans: List[Tuple[float, float, str]] = []
    compiles: List[Interval] = []
    raw = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ivs: List[Interval] = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        a = e.start_ns * 1e-9
                        raw.append((e.name, a, a + e.duration_ns * 1e-9))
                        ivs.append((a, a + e.duration_ns * 1e-9))
            device_ops.append(ivs)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    a = e.start_ns * 1e-9
                    b = a + e.duration_ns * 1e-9
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((a, b, e.name[len(SPAN_PREFIX):]))
                    elif e.name == COMPILE_EVENT:
                        compiles.append((a, b))
    if not device_ops:
        raise ValueError(f"{xplane_path}: no {DEVICE_PREFIX}* plane")
    window = next(((a, b) for a, b, name in spans if name == window_span),
                  None)
    spans = [sp for sp in spans if sp[2] != window_span]
    if window is None:
        lo = min((a for ivs in device_ops for a, _ in ivs), default=0.0)
        lo = min([lo] + [a for a, _, _ in spans])
        hi = max((b for ivs in device_ops for _, b in ivs), default=lo)
        hi = max([hi] + [b for _, b, _ in spans])
        window = (lo, hi)
    if window_s is not None:
        window = (window[0], min(window[1], window[0] + window_s))
    lo, hi = window
    kernels: Dict[str, List[float]] = {}
    for name, a, b in raw:
        if a < lo or b > hi:
            continue
        key = name.split(" = ")[0].lstrip("%")
        ops[key] = ops.get(key, 0.0) + b - a
        if " custom-call(" in name:
            kernels.setdefault(key, []).append(b - a)
    busy = 0.0
    gaps: List[Tuple[float, str]] = []
    for ivs in device_ops:
        u = union(clip(ivs, lo, hi))
        busy += sum(b - a for a, b in u)
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g1 - g0, _label(g0, g1, spans, compiles)))
    gaps.sort(key=lambda g: -g[0])
    return Reduced(window=window, n_devices=len(device_ops),
                   busy_s=busy / len(device_ops), ops=ops, kernels=kernels,
                   gaps=gaps)


def gap_totals(red: Reduced) -> List[Tuple[str, float]]:
    """Idle seconds per host activity, largest first, averaged over the
    devices."""
    out: Dict[str, float] = {}
    for s, label in red.gaps:
        out[label] = out.get(label, 0.0) + s / red.n_devices
    return sorted(out.items(), key=lambda kv: -kv[1])


def top_ops(red: Reduced, n: int = 10) -> List[Tuple[str, float]]:
    return sorted(red.ops.items(), key=lambda kv: -kv[1])[:n]
