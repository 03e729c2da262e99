"""The program's wall-clock layer profiler.

Installed through the one slot, :func:`repro.common.profile_slot.install`,
a :class:`LayerProfiler` receives a span from every instrumented layer:

==============================  ==========================================
span                            where (args)
==============================  ==========================================
``repro.sched.round``           ``MicroBatchScheduler.dispatch``
``repro.sched.cache_rung``      ``MicroBatchScheduler._cache_rung``
``repro.engine.embed``          ``RoutedEngine.embed`` (``n``)
``repro.engine.score``          ``RoutedEngine`` scoring (``n``, ``path``:
                                ``kernel``, ``jnp`` or ``ensemble``)
``repro.engine.generate``       ``RoutedEngine.generate_member``
                                (``member``, ``n``, ``length``,
                                ``max_new``)
``repro.lm.prefill``            ``greedy_generate``: prompt to first token,
                                closed once that token is ready
``repro.lm.decode``             ``greedy_generate``: the compiled decode
                                loop (``n``, ``steps``, ``cache_len``),
                                closed once the last token is ready
``repro.lm.decode_step``        inside it, the wait for the loop's steps
                                on the device (``steps``)
``repro.kernels.<kernel>``      ``router_xattn_pool``, ``pairwise_l2``
                                (``n``), closed once the result is ready
==============================  ==========================================

Each span

  * runs under a ``jax.profiler.TraceAnnotation`` of its name, so a
    ``jax.profiler`` trace holds it on the host plane, on the same time
    base as the device's operations;
  * is kept as ``(name, t0, t1, args)`` on ``time.perf_counter`` in
    :attr:`LayerProfiler.spans`, and in per-name call counts and
    latency :class:`Histogram` s (µs);
  * goes to a :class:`~repro.obs.trace.TraceRecorder` when one is given,
    in a :data:`~repro.obs.trace.WALL_CATS` category (``kernel`` for the
    kernels, ``layer`` for the rest), so the deterministic export and
    replay bit-identity are unaffected.

While installed the profiler also listens to JAX's monitoring events and
charges each XLA backend compile (count and seconds) and each persistent
compilation-cache hit to the innermost span open when it ran (spans are
opened on the serving thread, which is also the one that compiles), as
its ``compiles``, ``compile_s`` and ``cache_hits`` args, with
per-name totals. Dispatch compiles on the host, so a compile lands in
the span of the call that triggered it. Events outside every span are
totalled under :data:`UNSPANNED`.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List, Tuple

import jax
from jax import monitoring

from repro.serving.telemetry import Histogram

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
# Name the compile totals of events outside every span are kept under.
UNSPANNED = "(no span)"


class LayerProfiler:
    """Wall-clock spans of the program's layers and the compiles inside
    them; optionally feeds a tracer."""

    def __init__(self, tracer=None):
        self.tracer = tracer
        self.spans: List[Tuple[str, float, float, dict]] = []
        self.hists: Dict[str, Histogram] = {}
        self.calls: Dict[str, int] = {}
        self.compiles: Dict[str, int] = {}
        self.compile_s: Dict[str, float] = {}
        self.cache_hits: Dict[str, int] = {}
        self._open: List[Tuple[str, dict]] = []    # innermost last
        self._t0 = time.perf_counter()

    # -- the slot's hooks ----------------------------------------------------

    def attach(self) -> None:
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def detach(self) -> None:
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    # -- recording -----------------------------------------------------------

    @contextmanager
    def span(self, name: str, **args):
        """Time the body as span ``name`` (``with prof.span(
        "repro.engine.embed", n=8):``); yields its ``args`` dict."""
        self._open.append((name, args))
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield args
        finally:
            t1 = time.perf_counter()
            ann.__exit__(None, None, None)
            self._open.pop()
            self._record(name, t0, t1, args)

    def _charge(self, key: str, amount) -> None:
        name = UNSPANNED
        if self._open:
            name, args = self._open[-1]
            args[key] = args.get(key, 0) + amount
        totals = getattr(self, key)
        totals[name] = totals.get(name, 0) + amount

    def _on_duration(self, event, duration_secs, **_):
        if event == BACKEND_COMPILE:
            self._charge("compiles", 1)
            self._charge("compile_s", float(duration_secs))

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self._charge("cache_hits", 1)

    def _record(self, name, t0, t1, args):
        self.spans.append((name, t0, t1, args))
        h = self.hists.get(name)
        if h is None:
            h = self.hists[name] = Histogram()
            self.calls[name] = 0
        h.record((t1 - t0) * 1e6)
        self.calls[name] += 1
        if self.tracer is not None:
            cat = "kernel" if name.startswith("repro.kernels.") else "layer"
            self.tracer.span(name, cat, t0 - self._t0, t1 - self._t0,
                             args={"us": round((t1 - t0) * 1e6, 3), **args})

    # -- reporting -----------------------------------------------------------

    def totals(self) -> Dict[str, float]:
        """Compiles, their seconds and cache hits over every span and
        outside them."""
        return {"compiles": sum(self.compiles.values()),
                "compile_s": sum(self.compile_s.values()),
                "cache_hits": sum(self.cache_hits.values())}

    def summary(self) -> Dict[str, Dict]:
        out = {}
        for name in sorted(self.hists):
            h = self.hists[name]
            out[name] = {
                "calls": self.calls[name],
                "p50_us": h.percentile(50),
                "p99_us": h.percentile(99),
                "total_ms": h.total / 1e3,
                "compiles": self.compiles.get(name, 0),
                "compile_s": self.compile_s.get(name, 0.0),
            }
        return out

    def register_metrics(self, registry, prefix: str = "profile") -> None:
        """Expose per-span series on a MetricsRegistry (all wall-clock):
        calls, latency and compiles, labelled by ``span``."""
        for name in sorted(self.hists):
            labels = (("span", name),)
            registry.counter(f"{prefix}_calls_total", "spans closed",
                             labels=labels, wall=True,
                             fn=lambda n=name: self.calls[n])
            registry.histogram(f"{prefix}_latency_us",
                               "span wall latency (us)",
                               labels=labels, wall=True,
                               fn=lambda n=name: self.hists[n])
            registry.counter(f"{prefix}_compiles_total",
                             "XLA compiles charged to the span",
                             labels=labels, wall=True,
                             fn=lambda n=name: self.compiles.get(n, 0))

    def report(self) -> str:
        lines = ["layer profile:"]
        for name, s in self.summary().items():
            lines.append(
                f"  {name:<26s} calls {s['calls']:>6d}  p50 "
                f"{s['p50_us']:>11.1f}us  p99 {s['p99_us']:>11.1f}us  "
                f"total {s['total_ms']:.1f}ms  compiles {s['compiles']} "
                f"({s['compile_s']:.2f}s)")
        if len(lines) == 1:
            lines.append("  (no spans recorded)")
        return "\n".join(lines)
