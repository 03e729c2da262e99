"""Deterministic CPU tests for the streaming serving runtime:
queue admission/backpressure, scheduler coalescing, budget governor,
traffic scenarios, and a small end-to-end simulated-traffic run.
"""
import numpy as np
import pytest

from repro.serving import (
    DONE,
    EXPIRED,
    REJECTED,
    SHED,
    AdmissionQueue,
    BudgetGovernor,
    Histogram,
    MicroBatchScheduler,
    Request,
    RoutedEngine,
    SchedulerConfig,
    TraceConfig,
    make_trace,
)


def req(text="q", arrival=0.0, deadline=None, n_prompt=4, max_new=2):
    return Request(text=text, prompt=np.zeros(n_prompt, np.int32),
                   max_new=max_new, arrival_s=arrival, deadline_s=deadline)


class FakeMember:
    def __init__(self, name, cost_rate):
        self.name = name
        self.cost_rate = cost_rate


class FakeEngine:
    """Quality/cost tables keyed by the first prompt char; counts generate
    calls so coalescing is observable. Reward semantics match the real
    engine (R2 argmax)."""

    def __init__(self, cost_rates=(1.0, 10.0), quality=(0.5, 1.0)):
        self.pool = [FakeMember(f"m{i}", c) for i, c in enumerate(cost_rates)]
        self.quality = np.asarray(quality, np.float64)
        self.lam = 100.0
        self.generate_log = []          # (member, batch_size)

    def score_texts(self, texts):
        b = len(texts)
        s = np.tile(self.quality, (b, 1))
        c = np.tile([m.cost_rate for m in self.pool], (b, 1))
        return s, c

    def choose(self, s_hat, c_hat, lam=None):
        lam = self.lam if lam is None else lam
        return np.argmax(s_hat * np.exp(-c_hat / lam), axis=-1)

    def generate_member(self, mi, prompts, max_new=8):
        self.generate_log.append((mi, len(prompts)))
        outs = [np.zeros(max_new, np.int32) for _ in prompts]
        return outs, self.pool[mi].cost_rate * len(prompts)


class TestAdmissionQueue:
    def test_fifo_admission_and_pop(self):
        q = AdmissionQueue(capacity=8)
        reqs = [req(text=str(i), arrival=float(i)) for i in range(5)]
        for i, r in enumerate(reqs):
            assert q.offer(r, now=float(i))
        assert q.depth == 5
        out = q.pop(3)
        assert [r.text for r in out] == ["0", "1", "2"]
        assert q.depth == 2

    def test_backpressure_rejects_when_full(self):
        q = AdmissionQueue(capacity=2)
        assert q.offer(req(), 0.0)
        assert q.offer(req(), 0.0)
        r3 = req()
        assert not q.offer(r3, 0.0)
        assert r3.status == REJECTED
        assert q.rejected == 1
        assert q.depth == 2

    def test_deadline_expiry(self):
        q = AdmissionQueue()
        r_live = req(deadline=10.0)
        r_dead = req(deadline=0.5)
        q.offer(r_live, 0.0)
        q.offer(r_dead, 0.0)
        dropped = q.expire(now=1.0)
        assert dropped == [r_dead]
        assert r_dead.status == EXPIRED
        assert q.depth == 1 and q.expired == 1

    def test_oldest_wait_tracks_head(self):
        q = AdmissionQueue()
        q.offer(req(), now=1.0)
        q.offer(req(), now=3.0)
        assert q.oldest_wait(5.0) == pytest.approx(4.0)


class TestSloClassShedding:
    def _mixed_queue(self, classes):
        q = AdmissionQueue()
        reqs = []
        for i, cls in enumerate(classes):
            r = req(text=str(i))
            r.slo_class = cls
            q.offer(r, 0.0)
            reqs.append(r)
        return q, reqs

    def test_sheds_only_the_lowest_class_present(self):
        q, reqs = self._mixed_queue([0, 1, 0, 2, 1])
        dropped = q.shed_lowest(1.0, alerts=("latency_p95",))
        assert [r.text for r in dropped] == ["0", "2"]
        assert all(r.status == SHED and r.finish_s == 1.0 for r in dropped)
        assert q.shed == 2 and q.depth == 3
        assert sorted(r.slo_class for r in q.peek_all()) == [1, 1, 2]
        # a second alert round now sheds class 1 — classes fall in order
        assert [r.slo_class for r in q.shed_lowest(2.0)] == [1, 1]

    def test_rescue_carrying_requests_never_shed(self):
        q, (r0, r1) = self._mixed_queue([0, 0])
        r1.best_output = np.zeros(2, np.int32)     # mid-cascade answer
        dropped = q.shed_lowest(1.0)
        assert dropped == [r0] and q.depth == 1
        assert r1.status != SHED

    def test_noop_on_empty_or_unsheddable_queue(self):
        assert AdmissionQueue().shed_lowest(0.0) == []
        q, (r0,) = self._mixed_queue([0])
        r0.best_output = np.zeros(1, np.int32)
        assert q.shed_lowest(0.0) == [] and q.shed == 0

    class _FiringSLO:
        """Stub tracker whose burn-rate alert is permanently firing."""

        tracer = None

        def __init__(self):
            self.observed = 0

        def firing(self):
            return ["latency_p95_burn"]

        def check(self, now, force=False):
            pass

        def observe_request(self, *a, **kw):
            self.observed += 1

    def test_scheduler_sheds_lowest_class_when_enforcing(self):
        eng = FakeEngine()
        slo = self._FiringSLO()
        sched = MicroBatchScheduler(
            eng, SchedulerConfig(score_batch=16, max_batch=16),
            service_time=lambda kind, n, wall: 1e-3, slo=slo)
        sched.slo_enforce = True
        for i, cls in enumerate([0, 1, 0, 1]):
            r = req(text=str(i))
            r.slo_class = cls
            sched.queue.offer(r, 0.0)
        served = sched.dispatch()
        # class-0 load shed before spending capacity on it; class 1 served
        assert sched.queue.shed == 2
        assert [r.slo_class for r in served] == [1, 1]
        assert all(r.status == DONE for r in served)
        # shed requests never feed the tracker (no self-amplified burn)
        assert slo.observed == 2

    def test_enforcement_defaults_off(self):
        eng = FakeEngine()
        slo = self._FiringSLO()
        sched = MicroBatchScheduler(
            eng, SchedulerConfig(score_batch=16, max_batch=16),
            service_time=lambda kind, n, wall: 1e-3, slo=slo)
        for i in range(3):
            sched.queue.offer(req(text=str(i)), 0.0)
        served = sched.dispatch()
        assert len(served) == 3 and sched.queue.shed == 0


class TestBudgetGovernor:
    def test_over_budget_tightens_lambda_proportionally(self):
        g = BudgetGovernor(budget=1.0, window_s=10.0, lam0=1.0, gain=1.0)
        g.record(5.0, now=0.0)
        lam1 = g.update(now=0.0)   # 5x over -> lambda shrinks 5x
        lam2 = g.update(now=0.1)
        assert lam1 == pytest.approx(0.2)
        assert lam2 == pytest.approx(0.04)
        assert g.tightened == 2

    def test_under_budget_relaxes_back_to_nominal_cap(self):
        g = BudgetGovernor(budget=1.0, window_s=1.0, lam0=2.0, decay=0.5)
        g.record(5.0, now=0.0)
        g.update(now=0.0)                 # tighten
        assert g.lam < 2.0
        # spend falls out of the window -> relax, but never above lam0
        for t in (5.0, 6.0, 7.0):
            g.update(now=t)
        assert g.lam == pytest.approx(2.0)
        assert g.relaxed >= 1

    def test_lambda_floor(self):
        g = BudgetGovernor(budget=1e-9, window_s=100.0, lam0=1.0,
                           lam_min=1e-3)
        g.record(1.0, now=0.0)
        for t in range(10):
            g.update(now=float(t) * 1e-3)
        assert g.lam == pytest.approx(1e-3)


class TestSchedulerCoalescing:
    def test_same_member_requests_land_in_one_generate_call(self):
        eng = FakeEngine()           # lam=100 -> everyone routes to m1
        sched = MicroBatchScheduler(
            eng, SchedulerConfig(score_batch=16, max_batch=16),
            service_time=lambda kind, n, wall: 1e-3)
        for i in range(6):
            sched.queue.offer(req(text=str(i)), 0.0)
        served = sched.dispatch()
        assert len(served) == 6
        assert eng.generate_log == [(1, 6)]
        assert all(r.status == DONE and r.member == 1 for r in served)

    def test_micro_batch_cap_splits_generate_calls(self):
        eng = FakeEngine()
        sched = MicroBatchScheduler(
            eng, SchedulerConfig(score_batch=16, max_batch=2),
            service_time=lambda kind, n, wall: 1e-3)
        for i in range(5):
            sched.queue.offer(req(text=str(i)), 0.0)
        sched.dispatch()
        assert eng.generate_log == [(1, 2), (1, 2), (1, 1)]

    def test_split_across_members(self):
        eng = FakeEngine()
        eng.lam = 3.0   # R2: m0 = .5*exp(-1/3) = .358 > m1 = 1*exp(-10/3) = .036
        sched = MicroBatchScheduler(
            eng, SchedulerConfig(score_batch=16, max_batch=16),
            service_time=lambda kind, n, wall: 1e-3)
        for i in range(4):
            sched.queue.offer(req(text=str(i)), 0.0)
        served = sched.dispatch()
        assert eng.generate_log == [(0, 4)]
        assert all(r.member == 0 for r in served)

    def test_wait_bound_float_rounding_still_dispatches(self):
        """Regression: admitted + max_wait can round to exactly `now`, making
        oldest_wait one ulp short of max_wait — must still dispatch (was a
        livelock in run_trace)."""
        eng = FakeEngine()
        sched = MicroBatchScheduler(
            eng, SchedulerConfig(score_batch=64, max_wait_s=0.05),
            service_time=lambda kind, n, wall: 1e-3)
        admitted = 0.16409982975992232      # from the original repro
        r = req()
        sched.clock.advance_to(admitted)
        sched.queue.offer(r, admitted)
        sched.clock.advance_to(admitted + 0.05)
        assert sched.queue.oldest_wait(sched.clock.now) <= 0.05
        assert sched.should_dispatch()

    def test_large_open_loop_trace_terminates(self):
        eng = FakeEngine()
        sched = MicroBatchScheduler(
            eng, SchedulerConfig(score_batch=64, max_batch=8,
                                 max_wait_s=0.05, queue_capacity=10_000),
            service_time=lambda kind, n, wall: 1e-3 * n)
        trace = make_trace(
            TraceConfig(kind="poisson", n_requests=2000, rate=400.0, seed=0),
            texts=["x"])
        summary = sched.run_trace(trace)
        assert summary["completed"] == 2000

    def test_scoring_is_one_batch(self):
        eng = FakeEngine()
        calls = []
        orig = eng.score_texts
        eng.score_texts = lambda texts: (calls.append(len(texts)),
                                         orig(texts))[1]
        sched = MicroBatchScheduler(
            eng, SchedulerConfig(score_batch=32, max_batch=4),
            service_time=lambda kind, n, wall: 1e-3)
        for i in range(12):
            sched.queue.offer(req(text=str(i)), 0.0)
        sched.dispatch()
        assert calls == [12]


class TestSchedulerGovernor:
    def test_tight_budget_shifts_traffic_to_cheap_member(self):
        """Quality favors the expensive member; a tight rolling budget must
        force the governor to reroute sustained traffic to the cheap one."""
        eng = FakeEngine(cost_rates=(1.0, 10.0), quality=(0.5, 1.0))
        gov = BudgetGovernor(budget=40.0, window_s=1e9, lam0=100.0,
                             decay=0.5)
        sched = MicroBatchScheduler(
            eng, SchedulerConfig(score_batch=4, max_batch=8, max_wait_s=0.01),
            governor=gov, service_time=lambda kind, n, wall: 1e-3)
        trace = [req(text=str(i), arrival=i * 0.001) for i in range(64)]
        sched.run_trace(trace)
        counts = sched.telemetry.member_counts
        assert counts[1] > 0           # started on the expensive member
        assert counts[0] > counts[1]   # governor shifted the bulk to cheap
        assert gov.lam < gov.lam0
        # lambda trace is monotone non-increasing until the shift happens
        lams = [l for _, l in sched.telemetry.lam_trace]
        assert lams[0] == gov.lam0 and min(lams) < gov.lam0

    def test_no_governor_keeps_engine_lambda(self):
        eng = FakeEngine()
        sched = MicroBatchScheduler(
            eng, SchedulerConfig(score_batch=4),
            service_time=lambda kind, n, wall: 1e-3)
        sched.queue.offer(req(), 0.0)
        sched.dispatch()
        assert sched.telemetry.lam_trace[0][1] == eng.lam


class TestTraffic:
    def test_trace_is_deterministic(self):
        cfg = TraceConfig(kind="poisson", n_requests=32, rate=100.0, seed=3)
        t1 = make_trace(cfg, texts=["a", "b", "c"])
        t2 = make_trace(cfg, texts=["a", "b", "c"])
        assert [r.arrival_s for r in t1] == [r.arrival_s for r in t2]
        assert [r.text for r in t1] == [r.text for r in t2]
        assert all(np.array_equal(a.prompt, b.prompt)
                   for a, b in zip(t1, t2))

    def test_arrivals_sorted_and_lengths_bounded(self):
        cfg = TraceConfig(kind="bursty", n_requests=64, rate=50.0, seed=0,
                          prompt_len_min=4, prompt_len_max=32)
        tr = make_trace(cfg, texts=["x"])
        arr = [r.arrival_s for r in tr]
        assert arr == sorted(arr)
        assert all(4 <= len(r.prompt) <= 32 for r in tr)

    def test_bursty_has_on_off_structure(self):
        cfg = TraceConfig(kind="bursty", n_requests=200, rate=50.0, seed=1,
                          burst_factor=20.0, on_mean_s=0.1, off_mean_s=1.0)
        gaps = np.diff([r.arrival_s for r in make_trace(cfg, texts=["x"])])
        # ON-phase gaps are tiny, OFF gaps huge: spread far beyond Poisson.
        assert gaps.max() > 20 * np.median(gaps)

    def test_drift_shifts_benchmark_mixture(self):
        texts = [f"t{i}" for i in range(400)]
        benchmarks = ["mmlu"] * 200 + ["mbpp"] * 200
        cfg = TraceConfig(kind="drift", n_requests=300, rate=100.0, seed=0)
        tr = make_trace(cfg, texts=texts, benchmarks=benchmarks)
        bench_of = dict(zip(texts, benchmarks))
        half = len(tr) // 2
        # group B = second half of the sorted benchmark names ("mmlu" here)
        late_b = np.mean([bench_of[t.text] == "mmlu" for t in tr[half:]])
        early_b = np.mean([bench_of[t.text] == "mmlu" for t in tr[:half]])
        assert late_b > early_b + 0.3

    def test_deadline_threads_through(self):
        cfg = TraceConfig(n_requests=8, rate=100.0, seed=0, deadline_s=0.5)
        tr = make_trace(cfg, texts=["x"])
        assert all(r.deadline_s == pytest.approx(r.arrival_s + 0.5)
                   for r in tr)


class TestTelemetry:
    def test_histogram_percentiles(self):
        h = Histogram()
        for v in np.linspace(0.001, 0.1, 1000):
            h.record(float(v))
        assert h.percentile(50) == pytest.approx(0.05, rel=0.15)
        assert h.percentile(99) == pytest.approx(0.1, rel=0.15)
        assert h.min == pytest.approx(0.001)
        assert h.count == 1000

    def test_run_trace_summary_accounts_everything(self):
        eng = FakeEngine()
        sched = MicroBatchScheduler(
            eng, SchedulerConfig(score_batch=8, max_batch=4, max_wait_s=0.005,
                                 queue_capacity=4),
            service_time=lambda kind, n, wall: 0.01)
        # Arrivals far faster than service -> some must be rejected.
        trace = [req(text=str(i), arrival=i * 1e-4) for i in range(40)]
        summary = sched.run_trace(trace)
        assert summary["completed"] + summary["rejected"] == 40
        assert summary["rejected"] > 0
        assert summary["total_spend"] > 0
        assert summary["max_queue_depth"] <= 4


class TestEndToEndSimulatedTraffic:
    @pytest.fixture(scope="class")
    def engine(self):
        from repro.launch.serve import build_routed_engine

        eng, data, te = build_routed_engine(
            ["qwen3-0.6b", "granite-3-8b"], seed=0, epochs=20,
            n_traffic=400)
        return eng, data, te

    def test_all_requests_complete(self, engine):
        eng, data, te = engine
        trace = make_trace(
            TraceConfig(kind="poisson", n_requests=12, rate=500.0, seed=0,
                        max_new=2, prompt_len_max=16, vocab=64),
            texts=[data.texts[i] for i in te])
        sched = MicroBatchScheduler(
            eng, SchedulerConfig(score_batch=16, max_batch=8))
        summary = sched.run_trace(trace)
        assert summary["completed"] == 12
        assert summary["rejected"] == 0 and summary["expired"] == 0
        assert all(r.status == DONE and r.output is not None
                   and len(r.output) == 2 for r in trace)
        assert summary["total_spend"] > 0
        counts = summary["per_member_counts"]
        assert sum(counts.values()) == 12

    def test_serve_entrypoint_backcompat(self, engine):
        """The one-shot RoutedEngine.serve path still works on the
        refactored stateless core (variable-length prompts included)."""
        import jax.numpy as jnp

        eng, data, te = engine
        texts = [data.texts[i] for i in te[:5]]
        prompts = jnp.zeros((5, 8), jnp.int32)
        res = eng.serve(texts, prompts, max_new=2)
        assert len(res["outputs"]) == 5
        assert all(o is not None and o.shape == (2,) for o in res["outputs"])
        assert res["per_member_counts"].sum() == 5


class TestPlatformDefaults:
    def _router(self):
        import jax

        from repro.core.predictors import PREDICTORS
        from repro.core.router import PredictiveRouter

        key = jax.random.key(0)
        qp = PREDICTORS["attn"].init(key, 8, 2, 4)
        cp = PREDICTORS["attn"].init(key, 8, 2, 4)
        return PredictiveRouter("attn", "attn", qp, cp,
                                np.ones((2, 4), np.float32))

    @pytest.mark.parametrize("platform,asked,used", [
        ("cpu", False, False), ("cpu", True, True),
        ("tpu", False, True), ("tpu", True, True)])
    def test_kernel_scoring_follows_the_platform(self, monkeypatch,
                                                 platform, asked, used):
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        eng = RoutedEngine(router=self._router(), pool=[],
                           use_pallas=asked)
        assert eng.use_pallas is used

    @pytest.mark.parametrize("full_width", [False, True])
    def test_build_pool_width(self, monkeypatch, full_width):
        """``full_width`` takes the published config; a stand-in keeps the
        test off a full-size model on the CPU."""
        import dataclasses

        from repro.configs import get_smoke_config
        from repro.launch import serve

        stand_in = dataclasses.replace(get_smoke_config("qwen3-0.6b"),
                                       name="published-stand-in")
        monkeypatch.setattr(serve, "get_config", lambda name: stand_in)
        (member,) = serve.build_pool(["qwen3-0.6b"], full_width=full_width)
        assert (member.cfg is stand_in) is full_width
        assert member.cost_rate == serve.arch_cost_rate(stand_in)
