"""Follower process entry point + controller-side worker proxy.

``python -m repro.distributed.host --wid W --port P --serve-argv JSON``
is what ``repro.launch.serve --transport socket`` launches for workers
1..N-1. The follower:

  1. dials the controller **first** (connect/retry/backoff) — the
     controller's ``accept`` returns as soon as the TCP handshakes land,
     and protocol frames simply queue in the socket buffers while step 2
     runs;
  2. re-parses the controller's forwarded serve argv and rebuilds the
     identical seeded serving context (pool init, predictor training,
     corpus split — every RNG derives from ``--seed``, so no parameters
     cross the wire);
  3. claims its pool shard (:func:`repro.distributed.shard.shard_pool`:
     mesh-sharded params for owned members, evicted otherwise) and
     installs a :class:`~repro.distributed.shard.PoolDispatcher` so legs
     for non-owned members hop to their owners;
  4. services protocol messages (``serve_forever``) until ``SHUTDOWN``.

Budget ops go through a :class:`~repro.distributed.ledger.LedgerClient`
to the controller's shared ledger; traces land in a process-local
recorder the controller collects via ``TRACE_REQ`` at end of run.

**Graceful degradation**: if the controller connection dies mid-run the
follower does not crash — it drains its remaining queued work locally
(:func:`drain_local`) under the last known router version and effective
lambda, stopping only if a leg needs an unreachable peer's pool shard.

:class:`RemoteWorkerProxy` is the other side: the controller's in-memory
stand-in for a follower, satisfying the plane/coordinator reporting
surface (``telemetry`` / ``router_version`` / ``clock`` / ``alive``) by
``TELEMETRY_REQ`` RPC with cached fallback, and mirroring step results
via ``observe_step`` so mid-run reads don't need extra round trips. It
deliberately has NO ``bind`` or ``scheduler`` attribute: the coordinator
then never binds it as a local endpoint, and the plane's SLO dedup
always forces the remote tracker's end-of-run check.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types
from typing import Optional

from repro.distributed import messages as M
from repro.distributed.messages import Message
from repro.distributed.transport import SocketTransport, TransportError
from repro.serving.telemetry import Telemetry


class RemoteWorkerProxy:
    """Controller-side mirror of a follower-process worker."""

    def __init__(self, wid: int, transport, *, member_names=(),
                 pid: int = -1):
        self.wid = int(wid)
        self.transport = transport
        self.pid = int(pid)
        self.alive = True
        self.clock = types.SimpleNamespace(now=0.0)
        self.served_count = 0
        # Cached fallbacks for a partitioned follower: reporting degrades
        # to the last mirrored values instead of raising mid-summary.
        self._telemetry = Telemetry(list(member_names))
        self._version = 0
        self.swaps_accepted = 0
        self.swaps_rejected = 0
        self.crashes = 0

    def observe_step(self, rep: dict) -> None:
        """Mirror a STEP reply — keeps clock/served fresh without RPC."""
        self.clock.now = max(self.clock.now, float(rep["now"]))
        self.served_count += int(rep["n_served"])

    def _refresh(self) -> None:
        try:
            rep = self.transport.request(
                Message(kind=M.TELEMETRY_REQ, dst=self.wid))
        except TransportError:
            return
        p = rep.payload
        self._telemetry = p["telemetry"]
        self._version = int(p["version"])
        self.swaps_accepted = int(p["swaps_accepted"])
        self.swaps_rejected = int(p["swaps_rejected"])
        self.crashes = int(p["crashes"])
        self.served_count = int(p["served"])
        self.clock.now = max(self.clock.now, float(p["now"]))

    @property
    def telemetry(self) -> Telemetry:
        self._refresh()
        return self._telemetry

    @property
    def router_version(self) -> int:
        self._refresh()
        return self._version


def drain_local(worker) -> int:
    """Follower-local degradation: serve out the backlog without a plane.

    Runs the worker's own step loop (arrivals -> queue -> dispatch) under
    the last broadcast router version; the LedgerClient governor has
    already degraded to its cached lambda. Stops early if a generate leg
    needs a pool shard owned by an unreachable peer. Returns requests
    served while degraded.
    """
    served = 0
    while True:
        t = worker.next_action_s()
        if t == float("inf"):
            break
        try:
            served += len(worker.step(t))
        except TransportError:
            break           # a leg needs an unreachable peer's shard
    return served


def run_follower(wid: int, port: int, serve_argv: list,
                 host: str = "127.0.0.1") -> int:
    """Build worker ``wid`` from the forwarded argv and serve the plane."""
    # Import here, not at module top: serve imports this module back for
    # RemoteWorkerProxy, and the follower only needs the heavy serving
    # stack after the connection is up anyway.
    from repro.distributed.ledger import LedgerClient
    from repro.distributed.shard import PoolDispatcher, shard_pool
    from repro.launch import serve

    args = serve.make_parser().parse_args(serve_argv)

    transport = SocketTransport(wid, timeout=600.0)
    transport.connect(port, host, hello_payload={"pid": os.getpid()})
    print(f"[w{wid}] pid {os.getpid()} connected to controller "
          f"{host}:{port}; building serving context", flush=True)

    ctx = serve.build_context(args)
    recorder = None
    if args.trace_out or args.trace_profile \
            or serve._streaming_requested(args):
        from repro.obs import TraceRecorder, TraceSampler

        sampler = None
        if args.trace_sample is not None:
            sampler = TraceSampler(args.trace_sample, seed=args.seed,
                                   head=args.trace_head)
        # key_base partitions the trace-key space per process so the
        # controller can absorb drained follower events verbatim.
        recorder = TraceRecorder(
            label=f"serve-{args.trace}-seed{args.seed}-w{wid}",
            sampler=sampler, max_buffered_per_worker=args.trace_cap,
            key_base=wid * 1_000_000)
    governor = None
    if args.budget > 0:
        governor = LedgerClient(transport, dst=0)
    slo = serve._make_slo(args, tracer=recorder)
    drift_proto = serve.build_drift_proto(args, ctx)
    worker = serve.build_plane_worker(args, ctx, wid, governor,
                                     drift_proto, recorder, slo)
    worker.recorder = recorder
    owned = shard_pool(worker.engine.pool, wid, args.workers)
    worker.scheduler.dispatcher = PoolDispatcher(
        wid, args.workers, worker.engine, transport)
    worker.bind(transport)
    # Fleet RPC observability: this follower's outbound RPCs (GENERATE
    # hops to shard owners, ledger ops) emit client-side rpc spans into
    # the local recorder, timestamped on the worker's virtual clock.
    if recorder is not None:
        transport.tracer = recorder
        transport.trace_wid = wid
    transport.now_fn = lambda: worker.clock.now
    # Federated metrics: a process-local registry (series labelled with
    # this wid) the controller scrapes via METRICS_REQ and merges into
    # its /metrics. The shared budget ledger is NOT registered here — it
    # lives in the controller's registry exactly once.
    if args.metrics_out or args.metrics_port is not None \
            or serve._streaming_requested(args):
        from repro.obs import (MetricsRegistry, register_scheduler_metrics,
                               register_slo_metrics,
                               register_transport_metrics)

        registry = MetricsRegistry()
        labels = (("worker", wid),)
        register_scheduler_metrics(registry, worker.scheduler, labels=labels)
        if slo is not None:
            register_slo_metrics(registry, slo,
                                 lambda: worker.clock.now, labels=labels)
        register_transport_metrics(registry, transport, labels=labels)
        worker.registry = registry
    print(f"[w{wid}] ready: router v{worker.router_version}, owns pool "
          f"members {owned}", flush=True)

    degraded_served = 0
    clean = True
    try:
        transport.serve_forever()
    except TransportError as exc:
        clean = False
        print(f"[w{wid}] controller lost ({exc}); degrading to "
              f"follower-local serving", flush=True)
        degraded_served = drain_local(worker)
        print(f"[w{wid}] degraded drain served {degraded_served} "
              f"requests", flush=True)
    finally:
        transport.close()

    disp = worker.scheduler.dispatcher
    print(f"[w{wid}] done: served {len(worker.served)} "
          f"(v{worker.router_version}, generate local/remote "
          f"{disp.stats['local']}/{disp.stats['remote']})", flush=True)
    if worker.scheduler.cascade is not None:
        print(f"[w{wid}] {worker.scheduler.cascade.report()}", flush=True)
    if worker.scheduler.semcache is not None:
        rep = worker.scheduler.semcache.report()
        print(f"[w{wid}] semcache: {rep['served']}/{rep['lookups']} served "
              f"(hit rate {rep['hit_rate']:.2f})  {rep['entries']} entries",
              flush=True)
    if worker.adapter is not None:
        print(f"[w{wid}] {worker.adapter.report()}", flush=True)
    return 0 if clean else 1


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--wid", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--serve-argv", required=True,
                    help="JSON list: the controller's serve argv, "
                         "re-parsed to rebuild identical seeded state")
    a = ap.parse_args(argv)
    serve_argv = json.loads(a.serve_argv)
    if not isinstance(serve_argv, list):
        ap.error("--serve-argv must be a JSON list of strings")
    from repro.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    return run_follower(a.wid, a.port, [str(s) for s in serve_argv],
                        host=a.host)


if __name__ == "__main__":
    sys.exit(main())
