"""Multi-worker serving plane: replay-merge determinism, stale-version
swap rejection across workers, crash/rejoin, shared budget ledger.

Workers here use stub pool members (no LM generation) and a hash-based
text embedder, so the whole module is CPU-fast; the real-engine path is
covered by benchmarks/distributed_bench.py and the serve driver.
"""
import dataclasses
import hashlib

import jax
import numpy as np
import pytest

from repro.core.predictors import PREDICTORS
from repro.core.router import PredictiveRouter
from repro.distributed import (
    Coordinator,
    PlaneEvent,
    ServingPlane,
    SharedBudgetLedger,
    SyncConfig,
    WorkerNode,
)
from repro.online import OnlineAdapter, OnlineUpdateConfig
from repro.serving import (
    MicroBatchScheduler,
    Request,
    RoutedEngine,
    SchedulerConfig,
    TraceConfig,
    default_service_model,
    make_trace,
)
from repro.serving.scheduler import SimClock

DQ, K, DM = 16, 2, 4
COSTS = (0.2, 1.0)
VOCAB = 32


def _text_emb(text: str) -> np.ndarray:
    h = int.from_bytes(hashlib.blake2s(text.encode(), digest_size=4).digest(),
                       "little")
    e = np.random.default_rng(h).normal(0, 1, DQ).astype(np.float32)
    return e / np.linalg.norm(e)


@dataclasses.dataclass
class StubEngine(RoutedEngine):
    """RoutedEngine with a cheap deterministic embedder (no featurizer)."""

    def embed(self, texts):
        return np.stack([_text_emb(t) for t in texts])


class StubGenMember:
    """Pool member whose generate is a constant-token stub."""

    def __init__(self, name, cost_rate):
        self.name, self.cost_rate = name, cost_rate

    def generate(self, prompts, max_new=8, attn_mask=None):
        return np.zeros((int(np.asarray(prompts).shape[0]), max_new),
                        np.int32)


def _truth(text: str, member: int) -> float:
    h = int.from_bytes(
        hashlib.blake2s(f"{text}|{member}".encode(),
                        digest_size=4).digest(), "little")
    return (h % 1000) / 999.0


def make_router(seed=0):
    rng = np.random.default_rng(seed)
    memb = rng.random((K, DM)).astype(np.float32)
    qp = PREDICTORS["attn"].init(jax.random.key(seed), DQ, K, DM)
    cp = {"w": np.zeros((DQ, K), np.float32),
          "b": np.asarray(COSTS, np.float32)}
    return PredictiveRouter("attn", "reg", qp, cp, memb, reward="R2")


def make_workers(n_workers=3, seed=0, update=None):
    """N workers sharing one router lineage + stub pool."""
    router = make_router(seed)
    pool = [StubGenMember(f"m{i}", c) for i, c in enumerate(COSTS)]
    workers = []
    for wid in range(n_workers):
        engine = StubEngine(router=router, pool=pool, lam=2.0)
        adapter = OnlineAdapter(
            engine, lambda req: _truth(req.text, req.member),
            config=update or OnlineUpdateConfig(min_buffer=8, batch_size=16),
            defer_updates=True, seed=seed + 7 * wid + 1)
        sched = MicroBatchScheduler(
            engine,
            SchedulerConfig(score_batch=8, max_batch=4, max_wait_s=0.005,
                            queue_capacity=64),
            clock=SimClock(), service_time=default_service_model(),
            adapter=adapter)
        workers.append(WorkerNode(wid, engine, sched, adapter))
    return workers


def make_trace_for(workers, n=48, seed=0, rate=2000.0):
    return make_trace(
        TraceConfig(kind="poisson", n_requests=n, rate=rate, seed=seed,
                    max_new=2, prompt_len_min=4, prompt_len_max=12,
                    vocab=VOCAB),
        texts=[f"query number {i} about topic {i % 7}" for i in range(40)],
    )


def feed_outcomes(worker, n=40, seed=0, now=0.0):
    """Directly observe synthetic outcomes (bypasses the scheduler)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        r = Request(text=f"direct {i}", prompt=np.zeros(1, np.int32))
        r.q_emb = rng.normal(0, 1, DQ).astype(np.float32)
        r.member = int(rng.integers(K))
        r.cost = COSTS[r.member]
        r.status = "done"
        reqs.append(r)
    worker.adapter.observe(reqs, now)


def _replay_tuples(buf):
    return [(q.tobytes(), m, s, c, t)
            for (q, m, s, c, t) in list(buf._recent) + buf._reservoir]


class TestReplayMerge:
    def test_merge_deterministic(self):
        """Two identically-fed planes produce bit-identical merged buffers
        and identical leader router parameters."""
        results = []
        for _ in range(2):
            workers = make_workers(3, seed=5)
            for w in workers:
                feed_outcomes(w, n=30, seed=50 + w.wid)
            coord = Coordinator(workers, SyncConfig(
                merge_per_worker=16, steps_per_sync=4, min_buffer=8, seed=5))
            router = coord.sync_round(1.0)
            assert router is not None
            results.append((
                _replay_tuples(coord.merge_replay),
                jax.tree.map(np.asarray, router.quality_params),
            ))
        assert results[0][0] == results[1][0]
        jax.tree.map(np.testing.assert_array_equal,
                     results[0][1], results[1][1])

    def test_merge_order_is_by_worker_id(self):
        """Gathered samples land in ascending-wid order regardless of the
        worker list's order."""
        workers = make_workers(3, seed=2)
        for w in workers:
            feed_outcomes(w, n=20, seed=20 + w.wid)
        coord_fwd = Coordinator(workers, SyncConfig(
            merge_per_worker=8, seed=2))
        coord_rev = Coordinator(list(reversed(make_workers(3, seed=2))),
                                SyncConfig(merge_per_worker=8, seed=2))
        for w in coord_rev.workers:
            feed_outcomes(w, n=20, seed=20 + w.wid)
        coord_fwd.merge_round(0.0)
        coord_rev.merge_round(0.0)
        assert (_replay_tuples(coord_fwd.merge_replay)
                == _replay_tuples(coord_rev.merge_replay))

    def test_broadcast_converges_all_workers(self):
        workers = make_workers(3, seed=0)
        for w in workers:
            feed_outcomes(w, n=30, seed=w.wid)
        coord = Coordinator(workers, SyncConfig(min_buffer=8))
        router = coord.sync_round(0.5)
        assert router is not None
        versions = {w.router_version for w in workers}
        assert versions == {router.version}


class TestStaleSwapRejection:
    def test_missed_version_cannot_roll_back(self):
        """A worker that already holds v2 rejects a delayed v1 broadcast
        (and the original v0) — publishing backwards is impossible."""
        workers = make_workers(2, seed=1)
        v0_router = workers[1].engine.router
        for w in workers:
            feed_outcomes(w, n=30, seed=w.wid + 3)
        coord = Coordinator(workers, SyncConfig(min_buffer=8))
        r1 = coord.sync_round(0.1)
        for w in workers:
            feed_outcomes(w, n=10, seed=w.wid + 9, now=0.2)
        r2 = coord.sync_round(0.2)
        assert r2.version > r1.version
        w = workers[1]
        assert w.router_version == r2.version
        rejected_before = w.swaps_rejected
        assert not w.publish(r1)           # delayed older broadcast
        assert not w.publish(v0_router)    # ancient version
        assert w.swaps_rejected == rejected_before + 2
        assert w.router_version == r2.version

    def test_rejection_counted_by_coordinator(self):
        workers = make_workers(2, seed=3)
        for w in workers:
            feed_outcomes(w, n=30, seed=w.wid)
        coord = Coordinator(workers, SyncConfig(min_buffer=8))
        r1 = coord.sync_round(0.1)
        coord.broadcast(r1)                # re-broadcast: stale everywhere
        assert coord.stats["stale_rejected"] == len(workers)


class TestPlaneCrashRejoin:
    def _run(self, events, n_workers=3, n=60):
        workers = make_workers(n_workers, seed=0)
        coord = Coordinator(workers, SyncConfig(
            sync_every_s=0.004, merge_per_worker=16, steps_per_sync=2,
            min_buffer=8, seed=0))
        plane = ServingPlane(workers, coord, events=events)
        trace = make_trace_for(workers, n=n)
        summary = plane.run_trace(trace)
        return workers, coord, plane, summary

    def test_all_requests_survive_a_crash(self):
        workers, coord, plane, summary = self._run(
            [PlaneEvent(0.008, "crash", 1)])
        assert summary["completed"] == 60
        assert plane.reassigned > 0
        alive = [w for w in workers if w.alive]
        assert {w.wid for w in alive} == {0, 2}
        assert len({w.router_version for w in alive}) == 1

    def test_rejoin_catches_up_to_current_version(self):
        workers, coord, plane, summary = self._run(
            [PlaneEvent(0.006, "crash", 1),
             PlaneEvent(0.02, "rejoin", 1)])
        assert summary["completed"] == 60
        assert all(w.alive for w in workers)
        versions = {w.router_version for w in workers}
        assert len(versions) == 1
        assert versions == {workers[0].router_version}
        assert workers[1].crashes == 1
        # the rejoined worker's replay was rebuilt empty at rejoin time
        # (whatever it holds accumulated after the rejoin)
        assert coord.stats["updates"] > 0

    def test_leader_crash_elects_next_and_recovers(self):
        """Crash the leader: the next-lowest wid takes over (fresh updater
        anchored on its broadcast-current router), updates keep flowing,
        and the old leader re-anchors on rejoin."""
        workers, coord, plane, summary = self._run(
            [PlaneEvent(0.006, "crash", 0),
             PlaneEvent(0.025, "rejoin", 0)])
        assert summary["completed"] == 60
        assert coord.stats["leader_changes"] >= 1
        assert coord.stats["updates"] > 0
        assert len({w.router_version for w in workers if w.alive}) == 1

    @pytest.mark.slow
    def test_four_worker_soak(self):
        """Nightly soak: 4 workers, a bigger trace, a mid-run crash and
        rejoin — versions converge, nothing is lost, updates keep flowing."""
        workers, coord, plane, summary = self._run(
            [PlaneEvent(0.01, "crash", 2),
             PlaneEvent(0.04, "rejoin", 2)],
            n_workers=4, n=400)
        assert summary["completed"] == 400
        assert len({w.router_version for w in workers}) == 1
        assert coord.stats["updates"] > 2
        assert coord.stats["stale_rejected"] == 0
        # every worker served a nontrivial share (round-robin + reassignment)
        for w in workers:
            assert w.telemetry.completed > 0


class TestSharedBudgetLedger:
    def test_spend_is_global(self):
        ledger = SharedBudgetLedger(budget=1.0, window_s=10.0, lam0=1.0)
        ledger.record(0.4, now=1.0)      # worker A's clock
        ledger.record(0.5, now=0.8)      # worker B lags slightly
        assert ledger.utilization(1.0) == pytest.approx(0.9)

    def test_controller_throttled_across_workers(self):
        ledger = SharedBudgetLedger(budget=0.1, window_s=10.0, lam0=1.0,
                                    update_min_interval_s=1.0)
        ledger.record(1.0, now=0.5)      # 10x over budget
        lam1 = ledger.update(0.6)        # controller steps
        lam2 = ledger.update(0.7)        # throttled: no second tightening
        lam3 = ledger.update(0.9)        # still inside min interval
        assert lam1 < 1.0
        assert lam2 == lam1 and lam3 == lam1
        assert ledger.throttled == 2
        lam4 = ledger.update(2.0)        # past the interval: steps again
        assert lam4 < lam1

    def test_monotone_time_keeps_window_sorted(self):
        ledger = SharedBudgetLedger(budget=1.0, window_s=1.0, lam0=1.0)
        ledger.record(0.3, now=5.0)
        ledger.record(0.3, now=4.0)      # out-of-order worker clock
        ts = [t for t, _ in ledger._events]
        assert ts == sorted(ts)
        # both events are inside the [hwm - window, hwm] window
        assert ledger.window_spend(5.0) == pytest.approx(0.6)


class TestPlacePool:
    """Each member's parameters go to its owning worker's device."""

    @staticmethod
    def _pool(n):
        import types

        return [types.SimpleNamespace(params={"w": np.ones(3, np.float32)})
                for _ in range(n)]

    def test_one_device_holds_everything(self):
        import jax

        from repro.distributed.shard import place_pool

        pool = self._pool(3)
        placed = place_pool(pool, 4, devices=jax.devices()[:1])
        assert [d.id for d in placed] == [jax.devices()[0].id] * 3
        assert all(m.params["w"].devices() == {placed[0]} for m in pool)

    def test_owners_devices_on_four(self):
        import os
        import subprocess
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = (
            "import types, numpy as np\n"
            "from repro.distributed.shard import place_pool\n"
            "for n_workers in (4, 2):\n"
            "    pool = [types.SimpleNamespace(params={'w': np.ones(3)})"
            " for _ in range(3)]\n"
            "    placed = place_pool(pool, n_workers)\n"
            "    print([d.id for d in placed],"
            " [next(iter(m.params['w'].devices())).id for m in pool])\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=os.pathsep.join(
                       [os.path.join(root, "src")]
                       + [p for p in [os.environ.get("PYTHONPATH")] if p]))
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             check=True, capture_output=True, text=True,
                             timeout=120).stdout.splitlines()
        assert out == ["[0, 1, 2] [0, 1, 2]", "[0, 1, 0] [0, 1, 0]"]
