"""Observability plane tests: trace recorder + validators, metrics
registry + exporters, layer profiler, and the traced serving scheduler
(single worker and shared-recorder multi-worker views).

The determinism contract under test everywhere: virtual-clock timestamps
and recorder-assigned trace keys only, wall-clock confined to WALL_CATS,
canonical JSON — so a seeded run's exported trace and deterministic
metrics snapshot are byte-identical across replays.
"""
import json

import numpy as np
import pytest

from repro.common import profile_slot
from repro.kernels import ops as kops
from repro.obs import (
    LayerProfiler,
    MetricsRegistry,
    MetricsServer,
    TraceRecorder,
    WALL_CATS,
    register_scheduler_metrics,
    request_trees,
    trace_summary,
    validate_chrome_trace,
    validate_span_tree,
)
from repro.serving import MicroBatchScheduler, Request, SchedulerConfig


def req(text="q", arrival=0.0, deadline=None, n_prompt=4, max_new=2):
    return Request(text=text, prompt=np.zeros(n_prompt, np.int32),
                   max_new=max_new, arrival_s=arrival, deadline_s=deadline)


class FakeMember:
    def __init__(self, name, cost_rate):
        self.name = name
        self.cost_rate = cost_rate


class FakeEngine:
    """Static-score engine (no router) — exercises the tracer's stub-engine
    guards alongside the span plumbing."""

    def __init__(self, cost_rates=(1.0, 10.0), quality=(0.5, 1.0)):
        self.pool = [FakeMember(f"m{i}", c) for i, c in enumerate(cost_rates)]
        self.quality = np.asarray(quality, np.float64)
        self.lam = 100.0

    def score_texts(self, texts):
        b = len(texts)
        s = np.tile(self.quality, (b, 1))
        c = np.tile([m.cost_rate for m in self.pool], (b, 1))
        return s, c

    def choose(self, s_hat, c_hat, lam=None):
        lam = self.lam if lam is None else lam
        return np.argmax(s_hat * np.exp(-c_hat / lam), axis=-1)

    def generate_member(self, mi, prompts, max_new=8):
        outs = [np.zeros(max_new, np.int32) for _ in prompts]
        return outs, self.pool[mi].cost_rate * len(prompts)


def run_traced_sched(n=12):
    rec = TraceRecorder(label="test")
    # Fixed virtual service times: with service_time=None the clock would
    # advance by measured wall time and the trace could not replay
    # bit-identically.
    sched = MicroBatchScheduler(
        FakeEngine(), SchedulerConfig(score_batch=4, max_batch=4),
        service_time=lambda kind, n_, wall: 1e-3,
        tracer=rec.scoped(0))
    reqs = [req(text=str(i), arrival=i * 1e-3) for i in range(n)]
    summary = sched.run_trace(reqs)
    return rec, sched, summary


# ---------------------------------------------------------------------------
# TraceRecorder
# ---------------------------------------------------------------------------

class TestTraceRecorder:
    def test_chrome_export_structure(self):
        rec = TraceRecorder(label="unit")
        rec.instant("admit", "queue", 0.001, key=rec.next_key())
        rec.span("request", "request", 0.001, 0.005, key=0,
                 args={"status": "done"})
        rec.span("score_batch", "sched", 0.002, 0.003)
        doc = rec.chrome_trace()
        assert validate_chrome_trace(doc) == []
        evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
        meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
        assert len(evs) == 3 and len(meta) == 1
        # ts in microseconds; request-scoped events on tid key+1, runtime
        # scope on tid 0.
        admit = next(e for e in evs if e["name"] == "admit")
        assert admit["ts"] == pytest.approx(1000.0) and admit["tid"] == 1
        batch = next(e for e in evs if e["name"] == "score_batch")
        assert batch["tid"] == 0
        root = next(e for e in evs if e["name"] == "request")
        assert root["dur"] == pytest.approx(4000.0)

    def test_wall_categories_excluded_from_deterministic_export(self):
        rec = TraceRecorder()
        rec.span("kernel:pairwise_l2", "kernel", 0.0, 0.1)
        rec.instant("admit", "queue", 0.0, key=rec.next_key())
        assert "kernel" in WALL_CATS
        names = {e["name"] for e in rec.chrome_trace()["traceEvents"]
                 if e.get("ph") != "M"}
        assert names == {"admit"}
        names_wall = {e["name"]
                      for e in rec.chrome_trace(include_wall=True)
                      ["traceEvents"] if e.get("ph") != "M"}
        assert "kernel:pairwise_l2" in names_wall

    def test_ensure_key_dense_admission_order(self):
        rec = TraceRecorder()
        reqs = [req(text=str(i)) for i in range(3)]
        assert [rec.ensure_key(r) for r in reqs] == [0, 1, 2]
        # Idempotent on re-sight (cascade re-admission).
        assert rec.ensure_key(reqs[1]) == 1
        assert rec._next_key == 3

    def test_canonical_json_byte_stable(self):
        def build():
            rec = TraceRecorder(label="x")
            rec.instant("a", "queue", 0.25, key=rec.next_key(),
                        args={"depth": 3})
            rec.span("b", "sched", 0.25, 0.5)
            return rec.to_json()
        assert build() == build()

    def test_scoped_views_share_one_log(self):
        rec = TraceRecorder()
        w0, w1 = rec.scoped(0), rec.scoped(1)
        k = rec.next_key()
        w0.instant("admit", "queue", 0.0, key=k)
        w1.span("leg", "request", 0.1, 0.2, key=k)
        doc = rec.chrome_trace()
        pids = {e["pid"] for e in doc["traceEvents"] if e.get("ph") != "M"}
        assert pids == {0, 1}
        # Both workers' events land in one request tree (same tid).
        trees = request_trees(doc)
        assert len(trees) == 1 and len(trees[k + 1]["events"]) == 2
        # Process metadata for both workers.
        meta_pids = {e["pid"] for e in doc["traceEvents"]
                     if e.get("ph") == "M"}
        assert meta_pids == {0, 1}

    def test_merge_rebases_keys(self):
        a, b = TraceRecorder(), TraceRecorder()
        ra, rb = req(text="a"), req(text="b")
        a.instant("admit", "queue", 0.0, key=a.ensure_key(ra))
        b.instant("admit", "queue", 0.0, key=b.ensure_key(rb))
        a.merge(b)
        keys = sorted(e[6] for e in a.events)
        assert keys == [0, 1]
        assert a._next_key == 2


# ---------------------------------------------------------------------------
# Validators
# ---------------------------------------------------------------------------

class TestValidators:
    def test_schema_catches_malformed_events(self):
        doc = {"traceEvents": [
            {"name": "x", "cat": "c", "ph": "X", "ts": 0.0, "pid": 0,
             "tid": 1},                                   # X without dur
            {"name": "y", "cat": "c", "ph": "Z", "ts": 0.0, "pid": 0,
             "tid": 0},                                   # unknown ph
            {"cat": "c", "ph": "i", "ts": 0.0, "pid": 0, "tid": 0},  # no name
        ]}
        problems = validate_chrome_trace(doc)
        assert len(problems) >= 3

    def test_span_tree_catches_leg_outside_root(self):
        rec = TraceRecorder()
        k = rec.next_key()
        rec.instant("admit", "queue", 0.0, key=k)
        rec.span("request", "request", 0.0, 0.1, key=k,
                 args={"status": "done", "legs": 1})
        rec.span("queue_wait", "queue", 0.0, 0.01, key=k, args={"leg": 1})
        rec.span("leg", "request", 0.5, 0.6, key=k,
                 args={"leg": 1, "member": "m0"})   # outside the root span
        assert validate_span_tree(rec.chrome_trace())

    def test_span_tree_accepts_wellformed(self):
        rec = TraceRecorder()
        k = rec.next_key()
        rec.instant("admit", "queue", 0.0, key=k)
        rec.span("queue_wait", "queue", 0.0, 0.01, key=k, args={"leg": 1})
        rec.span("leg", "request", 0.01, 0.05, key=k,
                 args={"leg": 1, "member": "m0"})
        rec.span("request", "request", 0.0, 0.05, key=k,
                 args={"status": "done", "legs": 1})
        assert validate_span_tree(rec.chrome_trace()) == []


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_owned_and_callback_metrics(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs_total", "requests")
        c.inc()
        c.inc(2)
        g = reg.gauge("depth", "queue depth")
        g.set(7)
        state = {"v": 3}
        cb = reg.gauge("live", "callback", fn=lambda: state["v"])
        snap = reg.snapshot()
        assert snap["reqs_total"]["value"] == 3.0
        assert snap["depth"]["value"] == 7.0
        assert snap["live"]["value"] == 3.0
        state["v"] = 9   # callbacks read live state at export time
        assert reg.snapshot()["live"]["value"] == 9.0
        with pytest.raises(TypeError):
            cb.set(1)

    def test_duplicate_registration_rejected(self):
        reg = MetricsRegistry()
        reg.counter("x", labels=(("worker", "0"),))
        reg.counter("x", labels=(("worker", "1"),))  # distinct labels: ok
        with pytest.raises(ValueError):
            reg.counter("x", labels=(("worker", "0"),))

    def test_deterministic_snapshot_excludes_wall(self):
        reg = MetricsRegistry()
        reg.counter("steady", "deterministic")
        reg.gauge("wall_g", "wall-clock", wall=True, fn=lambda: 1.0)
        full = reg.snapshot()
        det = reg.snapshot(deterministic=True)
        assert "wall_g" in full and "wall_g" not in det
        assert "steady" in det

    def test_histogram_snapshot_and_multigauge(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_s", "latency")
        for v in (0.01, 0.02, 0.04):
            h.observe(v)
        reg.multi_gauge("rate_by_leg", "per-rung", "leg",
                        fn=lambda: {"1": 0.5, "2": 0.25})
        snap = reg.snapshot()
        hs = snap["lat_s"]
        assert hs["count"] == 3 and hs["min"] == 0.01 and hs["max"] == 0.04
        assert hs["min"] <= hs["p50"] <= hs["max"]
        assert snap['rate_by_leg{leg="1"}']["value"] == 0.5
        assert snap['rate_by_leg{leg="2"}']["value"] == 0.25

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        reg.counter("reqs_total", "served requests",
                    labels=(("worker", "0"),)).inc(5)
        h = reg.histogram("lat_s", "latency")
        h.observe(0.01)
        h.observe(0.5)
        text = reg.prometheus()
        assert "# TYPE reqs_total counter" in text
        assert "# HELP reqs_total served requests" in text
        assert 'reqs_total{worker="0"} 5' in text
        assert "# TYPE lat_s histogram" in text
        assert "lat_s_count 2" in text
        assert "lat_s_sum 0.51" in text
        assert 'le="+Inf"} 2' in text
        # Buckets cumulative and ending at the total count.
        bucket_counts = [int(line.rsplit(" ", 1)[1])
                         for line in text.splitlines()
                         if line.startswith("lat_s_bucket")]
        assert bucket_counts == sorted(bucket_counts)
        assert bucket_counts[-1] == 2


# ---------------------------------------------------------------------------
# Traced scheduler (single worker)
# ---------------------------------------------------------------------------

class TestTracedScheduler:
    def test_span_tree_covers_every_request(self):
        rec, sched, summary = run_traced_sched(n=12)
        doc = rec.chrome_trace()
        assert validate_chrome_trace(doc) == []
        assert validate_span_tree(doc) == []
        summ = trace_summary(doc)
        assert summ["requests"] == 12
        assert summ["finalized"] == summary["completed"] == 12
        for t in request_trees(doc).values():
            assert t["root"] is not None
            assert t["root"]["args"]["status"] == "done"
            assert len(t["legs"]) == 1 and len(t["admits"]) == 1

    def test_replay_bit_identity(self):
        j1 = run_traced_sched(n=12)[0].to_json()
        j2 = run_traced_sched(n=12)[0].to_json()
        assert j1 == j2

    def test_untraced_run_has_no_tracer_state(self):
        sched = MicroBatchScheduler(
            FakeEngine(), SchedulerConfig(score_batch=4, max_batch=4))
        assert sched.tracer is None and sched.queue.tracer is None
        summary = sched.run_trace([req(text=str(i), arrival=i * 1e-3)
                                   for i in range(6)])
        assert summary["completed"] == 6

    def test_reject_and_expire_traced(self):
        rec = TraceRecorder()
        sched = MicroBatchScheduler(
            FakeEngine(),
            SchedulerConfig(score_batch=4, max_batch=4, queue_capacity=2),
            tracer=rec.scoped(0))
        # Burst of simultaneous arrivals against a 2-deep queue.
        reqs = [req(text=str(i), arrival=0.0) for i in range(5)]
        sched.run_trace(reqs)
        names = [e[0] for e in rec.events]
        assert names.count("reject") == 3
        # Rejected requests have no root span but are visible in the tree
        # grouping as reject-only leaves.
        doc = rec.chrome_trace()
        assert validate_span_tree(doc) == []

    def test_scheduler_metrics_match_telemetry(self):
        rec = TraceRecorder()
        reg = MetricsRegistry()
        sched = MicroBatchScheduler(
            FakeEngine(), SchedulerConfig(score_batch=4, max_batch=4),
            tracer=rec.scoped(0))
        register_scheduler_metrics(reg, sched)
        sched.run_trace([req(text=str(i), arrival=i * 1e-3)
                         for i in range(10)])
        snap = reg.snapshot(deterministic=True)
        assert snap["requests_completed_total"]["value"] == 10
        assert snap["queue_admitted_total"]["value"] == 10
        assert snap["e2e_latency_s"]["count"] == 10
        assert snap["spend_total"]["value"] == pytest.approx(
            sched.telemetry.total_spend)
        # Deterministic snapshot is replay-stable as JSON.
        assert json.loads(reg.to_json(deterministic=True)) == snap


# ---------------------------------------------------------------------------
# Kernel profiler
# ---------------------------------------------------------------------------

class TestKernelProfiler:
    def test_profiler_hooks_pairwise_l2(self):
        rec = TraceRecorder()
        prof = LayerProfiler(tracer=rec)
        profile_slot.install(prof)
        try:
            x = np.random.default_rng(0).normal(size=(8, 4)).astype(
                np.float32)
            c = np.random.default_rng(1).normal(size=(3, 4)).astype(
                np.float32)
            out = np.asarray(kops.pairwise_l2(x, c))
        finally:
            profile_slot.install(None)
        name = "repro.kernels.pairwise_l2"
        assert out.shape == (8, 3)
        assert prof.calls[name] == 1
        assert [(s[0], s[3]["n"]) for s in prof.spans] == [(name, 8)]
        assert prof.hists[name].count == 1
        # The span is wall-clock: kernel category, excluded by default.
        kernel_events = [e for e in rec.events if e[1] == "kernel"]
        assert len(kernel_events) == 1
        det = rec.chrome_trace()["traceEvents"]
        assert not any(e.get("cat") == "kernel" for e in det)
        summ = prof.summary()[name]
        assert summ["calls"] == 1 and summ["p50_us"] > 0
        assert name in prof.report()

    def test_uninstalled_profiler_is_passthrough(self):
        assert profile_slot.active() is None
        x = np.zeros((4, 4), np.float32)
        c = np.zeros((2, 4), np.float32)
        assert np.asarray(kops.pairwise_l2(x, c)).shape == (4, 2)

    def test_register_metrics(self):
        prof = LayerProfiler()
        with prof.span("repro.kernels.router_xattn_pool", n=64):
            pass
        reg = MetricsRegistry()
        prof.register_metrics(reg)
        snap = reg.snapshot()   # wall metrics: full snapshot only
        key = '{span="repro.kernels.router_xattn_pool"}'
        assert snap["profile_calls_total" + key]["value"] == 1
        assert snap["profile_compiles_total" + key]["value"] == 0
        assert snap["profile_latency_us" + key]["count"] == 1
        assert reg.snapshot(deterministic=True) == {}


class TestMetricsServer:
    """HTTP scrape endpoint over a live registry (ephemeral port)."""

    def _registry(self):
        reg = MetricsRegistry()
        reg.counter("reqs_total", "requests").inc(5)
        state = {"depth": 2}
        reg.gauge("queue_depth", "live depth", fn=lambda: state["depth"])
        return reg, state

    def _get(self, url):
        import urllib.request

        with urllib.request.urlopen(url, timeout=10.0) as resp:
            return resp.status, resp.headers.get("Content-Type"), \
                resp.read().decode()

    def test_prometheus_and_json_endpoints(self):
        reg, state = self._registry()
        with MetricsServer(reg) as srv:
            assert srv.port != 0          # ephemeral port was bound
            status, ctype, body = self._get(srv.url)
            assert status == 200 and ctype.startswith("text/plain")
            assert "# TYPE reqs_total counter" in body
            assert "reqs_total 5" in body
            # Gauges read their callbacks at scrape time.
            state["depth"] = 9
            _, _, body = self._get(srv.url)
            assert "queue_depth 9" in body
            status, ctype, body = self._get(
                f"http://127.0.0.1:{srv.port}/metrics.json")
            assert status == 200 and ctype == "application/json"
            assert json.loads(body)["reqs_total"]["value"] == 5.0
            assert srv.scrapes == 3

    def test_unknown_path_404(self):
        import urllib.error
        import urllib.request

        reg, _ = self._registry()
        with MetricsServer(reg) as srv:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=10.0)
            assert ei.value.code == 404
            assert srv.scrapes == 0

    def test_requires_registry_and_stop_idempotent(self):
        with pytest.raises(ValueError):
            MetricsServer(None)
        reg, _ = self._registry()
        srv = MetricsServer(reg)
        port = srv.start()
        assert srv.start() == port        # second start is a no-op
        srv.stop()
        srv.stop()                        # idempotent
