"""Transport layer: codec round-trips (property + adversarial), fault
injection vs. version fencing, partitions + converge repair, leader
crash re-election with fenced catch-up, the real socket transport across
threads (nested RPC, follower->follower forwarding, shutdown, lost
controller), and sharded-pool generate dispatch over the wire.

Workers reuse the stub-engine recipe from test_distributed (duplicated
here — tests are standalone modules, not a package), so everything is
CPU-fast; real-process socket coverage lives in tools/distributed_smoke.
"""
import dataclasses
import hashlib
import threading

import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.predictors import PREDICTORS
from repro.core.router import PredictiveRouter
from repro.distributed import Coordinator, SyncConfig, WorkerNode
from repro.distributed import messages as M
from repro.distributed.messages import Message, decode, encode
from repro.distributed.shard import (
    PoolDispatcher,
    owned_members,
    owner_of,
)
from repro.distributed.transport import (
    FaultyTransport,
    LocalTransport,
    RpcStats,
    SocketTransport,
    TransportError,
)
from repro.obs import TraceRecorder, build_trace_doc, validate_span_tree
from repro.online import OnlineAdapter, OnlineUpdateConfig
from repro.serving import (
    MicroBatchScheduler,
    Request,
    RoutedEngine,
    SchedulerConfig,
    default_service_model,
)
from repro.serving.scheduler import SimClock
from repro.serving.telemetry import Telemetry

DQ, K, DM = 16, 2, 4
COSTS = (0.2, 1.0)


def _text_emb(text: str) -> np.ndarray:
    h = int.from_bytes(hashlib.blake2s(text.encode(), digest_size=4).digest(),
                       "little")
    e = np.random.default_rng(h).normal(0, 1, DQ).astype(np.float32)
    return e / np.linalg.norm(e)


@dataclasses.dataclass
class StubEngine(RoutedEngine):
    def embed(self, texts):
        return np.stack([_text_emb(t) for t in texts])


class StubGenMember:
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


def make_workers(n_workers=3, seed=0):
    router = make_router(seed)
    pool = [StubGenMember(f"m{i}", c) for i, c in enumerate(COSTS)]
    workers = []
    for wid in range(n_workers):
        engine = StubEngine(router=router, pool=pool, lam=2.0)
        adapter = OnlineAdapter(
            engine, lambda req: _truth(req.text, req.member),
            config=OnlineUpdateConfig(min_buffer=8, batch_size=16),
            defer_updates=True, seed=seed + 7 * wid + 1)
        sched = MicroBatchScheduler(
            engine,
            SchedulerConfig(score_batch=8, max_batch=4, max_wait_s=0.005,
                            queue_capacity=64),
            clock=SimClock(), service_time=default_service_model(),
            adapter=adapter)
        workers.append(WorkerNode(wid, engine, sched, adapter))
    return workers


def feed_outcomes(worker, n=40, seed=0, now=0.0):
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


def roundtrip(payload, kind="PING"):
    msg = Message(kind=kind, dst=3, src=1, seq=42, payload=payload)
    return decode(encode(msg)).payload


# ---------------------------------------------------------------------------
# Codec
# ---------------------------------------------------------------------------


class TestCodecRoundTrip:
    @settings(max_examples=40)
    @given(st.integers(-2**70, 2**70))
    def test_ints(self, n):
        assert roundtrip({"v": n})["v"] == n

    @settings(max_examples=40)
    @given(st.floats(-1e300, 1e300))
    def test_floats(self, x):
        got = roundtrip({"v": x})["v"]
        assert got == x and isinstance(got, float)

    @settings(max_examples=40)
    @given(st.text(max_size=40))
    def test_text(self, s):
        assert roundtrip({"v": s})["v"] == s

    @settings(max_examples=20)
    @given(st.lists(st.tuples(st.integers(-100, 100),
                              st.floats(-10.0, 10.0)),
                    max_size=6))
    def test_nested_containers(self, items):
        payload = {"items": items, "meta": {"n": len(items),
                                            "tags": ("a", "b")}}
        got = roundtrip(payload)
        assert got["items"] == items          # tuples stay tuples
        assert got["meta"] == {"n": len(items), "tags": ("a", "b")}

    def test_special_floats_and_bytes(self):
        p = roundtrip({"nan": float("nan"), "inf": float("inf"),
                       "ninf": float("-inf"), "blob": b"\x00\xffraw"})
        assert np.isnan(p["nan"])
        assert p["inf"] == float("inf") and p["ninf"] == float("-inf")
        assert p["blob"] == b"\x00\xffraw"

    def test_bool_none_set(self):
        p = roundtrip({"t": True, "f": False, "n": None, "s": {3, 1, 2}})
        assert p["t"] is True and p["f"] is False and p["n"] is None
        assert p["s"] == {1, 2, 3} and isinstance(p["s"], set)

    @pytest.mark.parametrize("arr", [
        np.arange(12, dtype=np.float32).reshape(3, 4) / 7,
        np.asarray([-2**40, 0, 2**40], np.int64),
        np.asarray([True, False, True]),
        np.asarray([np.nan, np.inf, -np.inf, 1.5], np.float64),
        np.zeros((0, 3), np.float32),
    ])
    def test_ndarray_exact(self, arr):
        got = roundtrip({"a": arr})["a"]
        assert got.dtype == arr.dtype and got.shape == arr.shape
        np.testing.assert_array_equal(got, arr)

    def test_non_contiguous_array_roundtrips(self):
        arr = np.arange(24, dtype=np.float32).reshape(4, 6)[:, ::2]
        np.testing.assert_array_equal(roundtrip({"a": arr})["a"], arr)

    def test_jax_array_degrades_to_numpy(self):
        arr = jax.numpy.arange(6, dtype=jax.numpy.float32)
        got = roundtrip({"a": arr})["a"]
        assert isinstance(got, np.ndarray)
        np.testing.assert_array_equal(got, np.arange(6, dtype=np.float32))

    def test_object_dtype_rejected(self):
        with pytest.raises(TypeError):
            encode(Message(kind="X", dst=0,
                           payload={"a": np.asarray([object()])}))

    def test_message_fields(self):
        msg = Message(kind=M.SYNC_STATUS, dst=2, src=7, seq=9000001,
                      reply_to=13, expect_reply=True, payload={"k": 1})
        got = decode(encode(msg))
        assert (got.kind, got.dst, got.src, got.seq) == \
            (M.SYNC_STATUS, 2, 7, 9000001)
        assert got.reply_to == 13 and got.expect_reply is True
        assert got.payload == {"k": 1}

    def test_bad_magic_rejected(self):
        buf = bytearray(encode(Message(kind="X", dst=0)))
        buf[0] ^= 0xFF
        with pytest.raises(ValueError):
            decode(bytes(buf))

    def test_version_mismatch_rejected(self):
        buf = bytearray(encode(Message(kind="X", dst=0)))
        buf[len(M.MAGIC)] = M.PROTOCOL_VERSION + 1
        with pytest.raises(ValueError):
            decode(bytes(buf))

    def test_truncated_frame_rejected(self):
        buf = encode(Message(kind="X", dst=0, payload={"a": 1}))
        with pytest.raises(ValueError):
            decode(buf[:-2])

    def test_router_adapter_roundtrip(self):
        router = make_router(4)
        got = roundtrip({"router": router})["router"]
        assert got.version == router.version
        assert got.quality_kind == router.quality_kind
        jax.tree.map(np.testing.assert_array_equal,
                     jax.tree.map(np.asarray, got.quality_params),
                     jax.tree.map(np.asarray, router.quality_params))
        np.testing.assert_array_equal(np.asarray(got.model_emb),
                                      np.asarray(router.model_emb))

    def test_request_adapter_roundtrip(self):
        r = Request(text="hello", prompt=np.arange(5, dtype=np.int32),
                    max_new=3, arrival_s=0.25)
        r.member, r.cost, r.status = 1, 0.5, "done"
        got = roundtrip({"req": r})["req"]
        assert got.text == "hello" and got.member == 1
        assert got.cost == 0.5 and got.status == "done"
        np.testing.assert_array_equal(got.prompt, r.prompt)

    def test_telemetry_adapter_roundtrip(self):
        tel = Telemetry(["m0", "m1"])
        got = roundtrip({"tel": tel})["tel"]
        assert isinstance(got, Telemetry)
        assert got.member_names == tel.member_names


class TestTraceContextFrames:
    """Protocol v2: frames optionally carry (trace_key, parent_span)."""

    def test_trace_context_roundtrip(self):
        msg = Message(kind=M.GENERATE, dst=1, src=0, seq=77,
                      trace_key=123, parent_span=4, payload={"x": 1})
        got = decode(encode(msg))
        assert got.trace_key == 123 and got.parent_span == 4
        assert got.payload == {"x": 1}

    def test_absent_trace_context_decodes_to_none(self):
        got = decode(encode(Message(kind=M.STEP, dst=1)))
        assert got.trace_key is None and got.parent_span is None

    def test_version_bumped_to_two(self):
        # The trace-context fields rode a frame version bump: a v1 peer
        # fails the version check up front instead of mis-parsing the new
        # fields. Simulated symmetrically — a v1 frame against this (v2)
        # decoder is the same fencing the old decoder applies to ours.
        assert M.PROTOCOL_VERSION == 2
        buf = bytearray(encode(Message(kind="X", dst=0, trace_key=5)))
        buf[len(M.MAGIC)] = 1
        with pytest.raises(ValueError):
            decode(bytes(buf))

    def test_rpc_span_kind_policy(self):
        # Real request/reply protocol legs trace; the hot NEXT_ACTION poll
        # and the obs drains themselves stay unspanned (they would dwarf
        # and recursively observe the traffic they measure).
        for kind in (M.GENERATE, M.STEP, M.SYNC_STATUS, M.LEDGER_OP,
                     M.ASSIGN, M.TICK, M.FINALIZE):
            assert kind in M.RPC_SPAN_KINDS
        for kind in (M.NEXT_ACTION, M.TRACE_REQ, M.TELEMETRY_REQ,
                     M.METRICS_REQ, M.HELLO, M.SHUTDOWN):
            assert kind not in M.RPC_SPAN_KINDS


class TestRpcTelemetry:
    def test_request_counts_latency_and_client_span(self):
        lt = LocalTransport()
        lt.bind(1, lambda msg: {"ok": 1})
        rec = TraceRecorder()
        lt.tracer = rec
        lt.now = 2.5
        lt.request(Message(kind=M.STEP, dst=1, payload={"t": 0.1}))
        s = lt.stats
        assert s.requests == {M.STEP: 1}
        assert s.peer_requests == {1: 1}
        assert s.in_flight == 0 and s.unreachable == 0
        assert s.latency[M.STEP].count == 1
        assert s.merged_latency().count == 1
        spans = [e for e in rec.events if e[0] == "rpc"]
        assert len(spans) == 1
        name, cat, ph, ts, dur, wid, key, args = spans[0]
        assert (cat, ph, wid, key) == ("rpc", "X", 0, None)
        assert ts == 2.5                      # virtual stamp, not wall
        assert args["side"] == "client" and args["peer"] == 1
        assert args["kind"] == M.STEP and args["rpc"] == 1

    def test_unspanned_kind_counts_but_emits_no_span(self):
        lt = LocalTransport()
        lt.bind(1, lambda msg: {})
        rec = TraceRecorder()
        lt.tracer = rec
        lt.request(Message(kind=M.NEXT_ACTION, dst=1))
        assert lt.stats.requests == {M.NEXT_ACTION: 1}
        assert not [e for e in rec.events if e[0] == "rpc"]

    def test_unreachable_failure_counted_no_span(self):
        lt = LocalTransport()
        rec = TraceRecorder()
        lt.tracer = rec
        with pytest.raises(TransportError):
            lt.request(Message(kind=M.STEP, dst=9))
        assert lt.stats.unreachable == 1
        assert lt.stats.requests == {}        # only completed RPCs count
        assert not rec.events                 # no span for a failed call

    def test_failure_classification(self):
        s = RpcStats()
        s.note_failure(TransportError("request to w1 timed out"))
        s.note_failure(TransportError("remote handler failed: boom"))
        s.note_failure(TransportError("no endpoint bound for wid 9"))
        assert (s.timeouts, s.errors, s.unreachable) == (1, 1, 1)

    def test_server_span_pairs_with_client_span(self):
        w = make_workers(1)[0]
        lt = LocalTransport()
        w.bind(lt)
        rec = TraceRecorder()
        lt.tracer = rec
        lt.trace_wid = 5                      # a distinct client process
        lt.now = 1.0
        w.scheduler.tracer = rec.scoped(0)
        lt.request(Message(kind=M.SYNC_STATUS, dst=0, src=5))
        spans = [e for e in rec.events if e[0] == "rpc"]
        sides = {e[7]["side"]: e for e in spans}
        assert set(sides) == {"client", "server"}
        assert sides["client"][7]["rpc"] == sides["server"][7]["rpc"]
        assert sides["client"][5] == 5 and sides["server"][5] == 0
        doc = build_trace_doc(rec.events)
        assert validate_span_tree(doc) == []

    def test_dangling_client_link_fails_validation(self):
        rec = TraceRecorder()
        rec.span("rpc", "rpc", 0.0, 0.1, wid=1,
                 args={"rpc": 99, "kind": M.STEP, "side": "client",
                       "peer": 0})
        errs = validate_span_tree(build_trace_doc(rec.events))
        assert errs and any("rpc" in e for e in errs)
        # An unmatched SERVER span is fine (the reply can be lost in
        # transit after the handler ran) — only client links must pair.
        rec2 = TraceRecorder()
        rec2.span("rpc", "rpc", 0.0, 0.1, wid=0,
                  args={"rpc": 99, "kind": M.STEP, "side": "server",
                        "peer": 1})
        assert validate_span_tree(build_trace_doc(rec2.events)) == []


# ---------------------------------------------------------------------------
# Local + faulty transports vs. version fencing
# ---------------------------------------------------------------------------


class TestLocalTransport:
    def test_request_reaches_bound_handler(self):
        lt = LocalTransport()
        lt.bind(1, lambda msg: {"echo": msg.payload["x"] + 1})
        rep = lt.request(Message(kind="PING", dst=1, payload={"x": 41}))
        assert rep.kind == M.ACK and rep.payload == {"echo": 42}

    def test_unbound_destination_raises(self):
        with pytest.raises(TransportError):
            LocalTransport().request(Message(kind="PING", dst=9))

    def test_handler_exception_propagates_raw(self):
        lt = LocalTransport()

        def boom(msg):
            raise KeyError("inner detail")

        lt.bind(0, boom)
        with pytest.raises(KeyError):
            lt.request(Message(kind="PING", dst=0))


class TestFaultInjection:
    def _bound_worker(self, **faults):
        w = make_workers(1)[0]
        ft = FaultyTransport(LocalTransport(), **faults)
        w.bind(ft)
        return w, ft

    def test_dropped_broadcasts_are_tolerated(self):
        w, ft = self._bound_worker(seed=0, drop=1.0)
        r2 = dataclasses.replace(w.engine.router, version=2)
        ft.send(Message(kind=M.ROUTER_BCAST, dst=0, payload={"router": r2}))
        assert ft.stats["dropped"] == 1
        assert w.router_version == 0          # lost, not applied
        # The reliable request path still works — and fencing lets a later
        # newer broadcast repair the miss.
        rep = ft.request(Message(kind=M.ROUTER_BCAST, dst=0,
                                 payload={"router": r2}))
        assert rep.payload["accepted"] and w.router_version == 2

    def test_duplicate_broadcast_applies_once(self):
        w, ft = self._bound_worker(seed=1, dup=1.0)
        r2 = dataclasses.replace(w.engine.router, version=2)
        ft.send(Message(kind=M.ROUTER_BCAST, dst=0, payload={"router": r2}))
        assert ft.stats["duplicated"] == 1
        assert w.router_version == 2
        assert w.swaps_accepted == 1 and w.swaps_rejected == 1

    def test_reordered_broadcasts_never_roll_back(self):
        for seed in range(6):                 # both flush orders occur
            w, ft = self._bound_worker(seed=seed, reorder=1.0)
            r1 = dataclasses.replace(w.engine.router, version=1)
            r2 = dataclasses.replace(w.engine.router, version=2)
            ft.send(Message(kind=M.ROUTER_BCAST, dst=0,
                            payload={"router": r1}))
            ft.send(Message(kind=M.ROUTER_BCAST, dst=0,
                            payload={"router": r2}))
            assert w.router_version == 0      # both held
            ft.flush()
            assert w.router_version == 2      # fencing beats delivery order


# ---------------------------------------------------------------------------
# Partition, converge repair, leader crash re-election
# ---------------------------------------------------------------------------


class PartitionedTransport(LocalTransport):
    """LocalTransport where a set of wids is unreachable."""

    def __init__(self):
        super().__init__()
        self.blocked = set()

    def _deliver(self, msg):
        if msg.dst in self.blocked:
            raise TransportError(f"w{msg.dst} partitioned")
        return super()._deliver(msg)


class TestPartitionAndElection:
    def _fleet(self, n=3, seed=0):
        workers = make_workers(n, seed=seed)
        pt = PartitionedTransport()
        for w in workers:
            w.bind(pt)
        coord = Coordinator(workers, SyncConfig(
            merge_per_worker=16, steps_per_sync=4, min_buffer=8, seed=seed),
            transport=pt)
        return workers, pt, coord

    def test_partition_during_sync_counts_unreachable(self):
        workers, pt, coord = self._fleet()
        for w in workers:
            feed_outcomes(w, n=30, seed=30 + w.wid)
        pt.blocked = {2}
        router = coord.sync_round(0.1)
        assert router is not None
        assert coord.stats["unreachable"] > 0
        assert workers[0].router_version == router.version
        assert workers[1].router_version == router.version
        assert workers[2].router_version == 0          # behind the wall

    def test_heal_then_converge_repairs_versions(self):
        workers, pt, coord = self._fleet()
        for w in workers:
            feed_outcomes(w, n=30, seed=30 + w.wid)
        pt.blocked = {2}
        router = coord.sync_round(0.1)
        pt.blocked = set()
        coord.converge()
        assert {w.router_version for w in workers} == {router.version}

    def test_converge_is_version_fenced(self):
        """catch_up on an already-current worker must not re-broadcast."""
        workers, pt, coord = self._fleet()
        for w in workers:
            feed_outcomes(w, n=30, seed=30 + w.wid)
        coord.sync_round(0.1)
        before = [(w.swaps_accepted, w.swaps_rejected) for w in workers]
        coord.converge()
        # Nobody re-receives the router they already hold.
        assert [(w.swaps_accepted, w.swaps_rejected)
                for w in workers] == before

    def test_leader_crash_reelection_and_fenced_catch_up(self):
        workers, pt, coord = self._fleet()
        for w in workers:
            feed_outcomes(w, n=30, seed=30 + w.wid)
        r1 = coord.sync_round(0.1)
        assert coord.leader is workers[0]
        # Leader crashes AND partitions away mid-run.
        workers[0].alive = False
        pt.blocked = {0}
        for w in workers[1:]:
            feed_outcomes(w, n=20, seed=90 + w.wid, now=0.2)
        r2 = coord.sync_round(0.2)
        assert r2 is not None and r2.version > r1.version
        assert coord.leader is workers[1]
        assert coord.stats["leader_changes"] >= 1
        assert workers[0].router_version == r1.version  # missed the epoch
        # Heal + catch up before marking alive (the plane's rejoin order:
        # the surviving leader is still authoritative while the returning
        # worker is down). The catch-up is version-fenced: it lands
        # exactly on the leader's version, and repeating it is a no-op.
        pt.blocked = set()
        coord.catch_up(workers[0])
        assert workers[0].router_version == r2.version
        before = (workers[0].swaps_accepted, workers[0].swaps_rejected)
        coord.catch_up(workers[0])
        assert (workers[0].swaps_accepted,
                workers[0].swaps_rejected) == before
        workers[0].alive = True
        assert coord.leader is workers[0]     # lowest alive id leads again


# ---------------------------------------------------------------------------
# Socket transport across real OS threads
# ---------------------------------------------------------------------------


def _start_follower(wid, port, handler, errors):
    """Connect + serve a follower SocketTransport on its own thread."""
    t = SocketTransport(wid, timeout=20.0)
    t.bind(wid, handler)
    ready = threading.Event()

    def run():
        try:
            t.connect(port, hello_payload={"pid": 1000 + wid})
            ready.set()
            t.serve_forever()
        except TransportError as exc:
            errors[wid] = exc
        finally:
            ready.set()
            t.close()

    th = threading.Thread(target=run, daemon=True)
    th.start()
    return t, th, ready


class TestSocketTransport:
    def test_hello_rpc_forwarding_and_shutdown(self):
        ctrl = SocketTransport(0, timeout=20.0)
        port = ctrl.listen()
        state = {"ticks": 0}
        ctrl.bind(0, lambda msg: {"ctrl": msg.payload.get("x", 0) * 10})
        errors = {}

        def w1_handler(msg):
            if msg.kind == "RELAY":
                # Nested RPC mid-handling: w1 -> w2 hops through the
                # controller while the controller itself is blocked
                # waiting on this very reply.
                rep = t1.request(Message(kind="PING", dst=2,
                                         payload={"x": msg.payload["x"]}))
                return {"via": rep.payload["sq"]}
            if msg.kind == "ASKCTRL":
                rep = t1.request(Message(kind="PING", dst=0,
                                         payload={"x": 7}))
                return {"ctrl": rep.payload["ctrl"]}
            state["ticks"] += 1
            return {}

        def w2_handler(msg):
            return {"sq": msg.payload["x"] ** 2}

        t1, th1, _ = _start_follower(1, port, w1_handler, errors)
        t2, th2, _ = _start_follower(2, port, w2_handler, errors)
        try:
            hellos = ctrl.accept(2, timeout=20.0)
            assert {w: h["pid"] for w, h in hellos.items()} == \
                {1: 1001, 2: 1002}

            # Direct RPC controller -> follower.
            rep = ctrl.request(Message(kind="PING", dst=2, payload={"x": 6}))
            assert rep.payload == {"sq": 36}
            # Nested follower -> follower (forwarded by the controller).
            rep = ctrl.request(Message(kind="RELAY", dst=1, payload={"x": 5}))
            assert rep.payload == {"via": 25}
            # Nested follower -> controller (serviced mid-roundtrip).
            rep = ctrl.request(Message(kind="ASKCTRL", dst=1))
            assert rep.payload == {"ctrl": 70}

            # One-way send is fire-and-forget; confirm via a later request.
            ctrl.send(Message(kind=M.TICK, dst=1))
            ctrl.request(Message(kind=M.TICK, dst=1))
            assert state["ticks"] == 2
        finally:
            for wid in (1, 2):
                try:
                    ctrl.request(Message(kind=M.SHUTDOWN, dst=wid))
                except TransportError:
                    pass
            th1.join(timeout=10.0)
            th2.join(timeout=10.0)
            ctrl.close()
        assert not th1.is_alive() and not th2.is_alive()
        assert errors == {}                   # clean SHUTDOWN, no degrade

    def test_remote_handler_error_surfaces_as_transport_error(self):
        ctrl = SocketTransport(0, timeout=20.0)
        port = ctrl.listen()
        errors = {}

        def bad_handler(msg):
            if msg.kind == "BOOM":
                raise ValueError("follower exploded")
            return {}

        t1, th1, _ = _start_follower(1, port, bad_handler, errors)
        try:
            ctrl.accept(1, timeout=20.0)
            with pytest.raises(TransportError, match="follower exploded"):
                ctrl.request(Message(kind="BOOM", dst=1))
            # The connection survives an application error.
            assert ctrl.request(Message(kind="OK", dst=1)).kind == M.ACK
        finally:
            try:
                ctrl.request(Message(kind=M.SHUTDOWN, dst=1))
            except TransportError:
                pass
            th1.join(timeout=10.0)
            ctrl.close()

    def test_lost_controller_raises_in_serve_forever(self):
        ctrl = SocketTransport(0, timeout=20.0)
        port = ctrl.listen()
        errors = {}
        t1, th1, ready = _start_follower(1, port, lambda msg: {}, errors)
        try:
            ctrl.accept(1, timeout=20.0)
            ready.wait(timeout=10.0)
            ctrl.drop_connection(1)
            th1.join(timeout=10.0)
            assert not th1.is_alive()
            assert isinstance(errors.get(1), TransportError)
        finally:
            ctrl.close()

    def test_connect_refused_after_retries(self):
        t = SocketTransport(3, timeout=1.0)
        sacrificial = SocketTransport(0, timeout=1.0)
        port = sacrificial.listen()
        sacrificial.close()                   # nobody listening any more
        t.CONNECT_RETRIES = 2
        with pytest.raises(TransportError):
            t.connect(port)


# ---------------------------------------------------------------------------
# Sharded pool dispatch
# ---------------------------------------------------------------------------


class TestPoolDispatch:
    def test_owner_layout_round_robin(self):
        assert [owner_of(mi, 2) for mi in range(4)] == [0, 1, 0, 1]
        assert owned_members(0, 5, 2) == [0, 2, 4]
        assert owned_members(1, 5, 2) == [1, 3]
        assert owned_members(2, 2, 3) == []   # more workers than members

    def _pair(self):
        workers = make_workers(2, seed=6)
        lt = LocalTransport()
        for w in workers:
            w.bind(lt)
        disp = PoolDispatcher(0, 2, workers[0].engine, lt)
        prompts = [np.arange(4, dtype=np.int32),
                   np.arange(7, dtype=np.int32) % 9]
        return workers, disp, prompts

    def test_remote_generate_matches_local(self):
        workers, disp, prompts = self._pair()
        want_outs, want_costs = workers[1].engine.generate_member(
            1, prompts, max_new=4)
        outs, costs = disp.generate_member(1, prompts, max_new=4)
        assert disp.stats == {"local": 0, "remote": 1}
        np.testing.assert_array_equal(np.asarray(costs),
                                      np.asarray(want_costs))
        for got, want in zip(outs, want_outs):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_owned_member_stays_local(self):
        workers, disp, prompts = self._pair()
        outs, costs = disp.generate_member(0, prompts, max_new=4)
        assert disp.stats == {"local": 1, "remote": 0}
        assert len(outs) == len(prompts) and costs.shape == (2,)

    def test_per_request_caps_cross_the_wire(self):
        workers, disp, prompts = self._pair()
        want_outs, want_costs = workers[1].engine.generate_member(
            1, prompts, max_new=4, max_new_per_req=[1, 3])
        outs, costs = disp.generate_member(1, prompts, max_new=4,
                                           max_new_per_req=[1, 3])
        np.testing.assert_array_equal(np.asarray(costs),
                                      np.asarray(want_costs))
        for got, want in zip(outs, want_outs):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_unreachable_owner_raises_transport_error(self):
        workers = make_workers(2, seed=6)
        lt = LocalTransport()
        workers[0].bind(lt)                   # w1 never binds
        disp = PoolDispatcher(0, 2, workers[0].engine, lt)
        with pytest.raises(TransportError):
            disp.generate_member(1, [np.arange(3, dtype=np.int32)])


class TestSocketRefusedOnTpu:
    def test_refuses_before_spawning_a_follower(self, monkeypatch, capsys):
        """A chip belongs to one process: on TPU the controller would hold
        it while its followers wait on its lock, so socket mode exits up
        front instead of starting them."""
        import subprocess

        from repro.launch import serve

        def no_spawn(*a, **kw):
            raise AssertionError("a follower process was started")

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(subprocess, "Popen", no_spawn)
        with pytest.raises(SystemExit) as exc:
            serve.main(["--workers", "2", "--transport", "socket"])
        assert exc.value.code == 2
        assert "already holds it" in capsys.readouterr().err
