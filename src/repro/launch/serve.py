"""Streaming routed-serving driver: simulated open-loop traffic end to end.

    PYTHONPATH=src python -m repro.launch.serve --trace poisson --requests 200
    PYTHONPATH=src python -m repro.launch.serve --trace bursty --requests 200 \
        --budget 0.02 --budget-window 0.5 --lam 1.0
    PYTHONPATH=src python -m repro.launch.serve --trace drift --requests 400 \
        --workers 4 --online --crash-at 0.1 --rejoin-at 0.3
    PYTHONPATH=src python -m repro.launch.serve --trace poisson --requests 200 \
        --cascade --max-legs 3 --budget 0.02
    PYTHONPATH=src python -m repro.launch.serve --trace drift --requests 200 \
        --workers 2 --online --transport socket

``--cascade`` trains the deep-ensemble quality head and runs multi-leg
escalation (repro.cascade): answers that look inadequate against the next
cost-ladder rung's expected marginal reward are re-admitted at elevated
priority, every leg is charged to the budget ledger, and telemetry splits
quality/cost/latency by leg. ``--semcache`` adds a semantic answer cache
as rung 0 of that ladder: near-duplicate queries (see ``--trace neardup``)
are answered from cache when the rung-0 stop-vs-escalate decision — the
same expected-marginal-reward math as the cascade — says the cached
answer's risk-adjusted quality beats paying for generation. ``--save-router`` / ``--restore-router``
persist the trained router (params + version + cost-scaler meta); restored
routers score bitwise-identically.

Builds pool members at the reduced smoke widths by default (what the CPU
tests run) or, with ``--full-width``, at their published widths (what one
chip runs: ``chip_smoke.py``), trains the attention router on synthetic RouterBench traffic mapped
onto the pool, then replays a simulated traffic scenario (poisson / bursty /
drift) through the admission queue + continuous micro-batching scheduler,
reporting per-member counts, spend vs. budget, and latency percentiles.

``--workers N`` (N > 1) runs the multi-worker serving plane instead of the
single scheduler: N workers (each with its own engine replica, queue, and
virtual clock) share the pool and — with ``--budget`` — one global
SharedBudgetLedger; with ``--online`` the workers run follower adapters
and the coordinator periodically merges their replay buffers onto the
leader, runs the bounded update steps there, and broadcasts the versioned
router to every worker. ``--crash-at``/``--rejoin-at`` inject a worker
crash-and-rejoin scenario; ``--feedback-delay`` routes quality feedback
through the staged delayed-outcome path.

``--transport`` picks how the plane's message protocol is carried:
``local`` (default) delivers by reference in-process and replays
bit-identically; ``socket`` launches workers 1..N-1 as real OS processes
(``repro.distributed.host``) speaking length-prefixed TCP to this
controller process (worker 0, which is also the lowest-id leader), with
the LM pool sharded by ownership across the processes — each generate
leg runs on the member's owning worker. ``--metrics-port`` serves the
live metrics registry over localhost HTTP (``/metrics`` Prometheus text,
``/metrics.json`` canonical JSON) for the run's duration.

Every random path — pool init, synthetic traffic, router training, the
trace arrival/content sampling, and the prompt token RNG — derives from
``--seed``, so runs are reproducible end to end; socket-mode follower
processes rebuild identical engine/corpus/truth state by re-parsing the
controller's forwarded argv.
"""
from __future__ import annotations

import argparse
import os
import sys
import types

import jax
import numpy as np

from repro.common import tree_count
from repro.common.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.core import build_model_embeddings
from repro.core.router import PredictiveRouter
from repro.data import generate
from repro.models import lm as lm_mod
from repro.serving import (
    TRACE_KINDS,
    BudgetGovernor,
    MicroBatchScheduler,
    PoolMember,
    RoutedEngine,
    SchedulerConfig,
    SemanticCache,
    TraceConfig,
    arch_cost_rate,
    calibrate_radius,
    default_service_model,
    make_trace,
)
from repro.training import train_dual_predictors


def build_pool(names, seed: int = 0, full_width: bool = False):
    """Pool members at their published widths (``full_width``) or at the
    reduced smoke widths. Cost rates always come from the published
    configs: the economics the router must learn are those of the real
    architectures, not of the smoke-test stand-ins."""
    members = []
    for i, name in enumerate(names):
        cfg = get_config(name) if full_width else get_smoke_config(name)
        params = lm_mod.init_lm(jax.random.key(seed + i), cfg)
        members.append(PoolMember(
            name=name, cfg=cfg, params=params,
            quality_profile=None,
            cost_rate=arch_cost_rate(get_config(name)),
        ))
    return members


def pool_quality_columns(pool, data) -> list:
    """RouterBench quality columns for the pool members, matched by cost
    order (cheapest member <- cheapest API model, etc.)."""
    api_cost_order = np.argsort(data.cost.mean(0))          # cheap -> pricey
    member_rank = np.argsort(np.argsort([m.cost_rate for m in pool]))
    k_api, p = len(api_cost_order), len(pool)
    return [
        int(api_cost_order[int(round(member_rank[i] * (k_api - 1) / max(p - 1, 1)))])
        for i in range(p)
    ]


def synthetic_pool_traffic(pool, n: int = 1200, seed: int = 0):
    """Map synthetic RouterBench quality columns onto the pool members."""
    data = generate(n, seed=seed)
    quality = data.quality[:, pool_quality_columns(pool, data)]  # pool order
    cost = np.stack([np.full(n, m.cost_rate) for m in pool], axis=1)
    return data, quality, cost


def build_routed_engine(names, *, seed: int = 0, epochs: int = 120,
                        lam: float = 1.0, n_traffic: int = 1200,
                        use_pallas: bool = False, quality_kind: str = "attn",
                        restore_router: str = None, full_width: bool = False):
    """Pool + trained router + engine, all seeded. Returns (engine, data, te).

    ``quality_kind="attn-ens"`` trains the deep-ensemble quality head (the
    cascade path's uncertainty source). ``restore_router`` skips offline
    predictor training entirely and loads a checkpoint saved by
    ``--save-router`` instead (the pool and traffic corpus are still built
    — they are the serving substrate, not router state). ``full_width``
    builds the members at their published widths (see :func:`build_pool`).
    """
    pool = build_pool(names, seed=seed, full_width=full_width)
    data, quality, cost = synthetic_pool_traffic(pool, n=n_traffic, seed=seed)
    tr, va, te = data.split(seed=seed)
    if restore_router is not None:
        from repro.checkpoint import load_router

        router = load_router(restore_router, expect_pool_names=names)
        if router.n_members != len(pool):
            raise ValueError(
                f"checkpoint pool size {router.n_members} != "
                f"serving pool size {len(pool)}")
    else:
        memb, centers = build_model_embeddings(data.emb[tr], quality[tr],
                                               seed=seed)
        qp, cp, scaler, _ = train_dual_predictors(
            quality_kind, "attn", data.emb[tr], quality[tr], cost[tr], memb,
            q_emb_val=data.emb[va], quality_val=quality[va],
            cost_val=cost[va], epochs=epochs, seed=seed,
        )
        # Centroids ride on the router so online hot-added members can be
        # embedded per-cluster from live outcomes (repro.online.membership).
        router = PredictiveRouter(quality_kind, "attn", qp, cp, memb,
                                  reward="R2", cost_scaler=scaler,
                                  centroids=centers)
    engine = RoutedEngine(router=router, pool=pool, lam=lam,
                          use_pallas=use_pallas)
    return engine, data, te


def build_context(args):
    """Everything a serving process derives deterministically from argv.

    The controller and every socket-mode follower call this with the SAME
    parsed argv: the pool init, predictor training, corpus split, truth
    lookup, and the per-scheduler component factories are all seeded by
    ``--seed``, so each process reconstructs bitwise-identical router and
    pool state without shipping parameters over the wire.
    """
    names = args.pool.split(",")
    engine, data, te = build_routed_engine(
        names, seed=args.seed, epochs=args.epochs, lam=args.lam,
        use_pallas=args.pallas,
        quality_kind="attn-ens" if args.cascade else "attn",
        restore_router=args.restore_router, full_width=args.full_width)

    # Quality truth lookup (--online feedback and --cascade per-leg
    # observed quality), built once and shared by every consumer.
    qual_of_text = None
    if args.online or args.cascade:
        quality = data.quality[:, pool_quality_columns(engine.pool, data)]
        qual_of_text = {data.texts[i]: quality[i]
                        for i in range(len(data.texts))}

    def truth(req):
        return float(qual_of_text[req.text][req.member])

    def make_cascade(governor):
        """Fresh cascade coordinator bound to one scheduler's governor."""
        if not args.cascade:
            return None
        from repro.cascade import (
            CascadeConfig, CascadeCoordinator, CascadePolicy, cost_ladder,
        )

        policy = CascadePolicy(
            cost_ladder(engine.router),
            CascadeConfig(max_legs=args.max_legs, beta=args.cascade_beta,
                          margin=args.cascade_margin,
                          min_headroom=args.cascade_min_headroom),
            reward=engine.router.reward)
        # Observed leg quality: the synthetic RouterBench truth stands in
        # for the deployment's response evaluator.
        return CascadeCoordinator(policy, observed_quality=truth,
                                  governor=governor)

    def make_semcache():
        """Fresh rung-0 semantic cache (policy/drift hooks are wired by the
        scheduler from the cascade policy and the adapter's detector)."""
        if not args.semcache:
            return None
        radius = args.cache_radius
        if radius is None:
            tr, _, _ = data.split(seed=args.seed)
            radius = calibrate_radius(data.emb[tr])
            print(f"semcache radius calibrated to {radius:.4f} "
                  f"(training-split NN-distance quantile)")
        return SemanticCache(radius, cap=args.cache_cap)

    def make_feedback(seed):
        """(quality_feedback, feedback_source, stage) for one adapter."""
        if args.feedback_delay > 0:
            from repro.online import DelayedFeedback, OutcomeStage
            fb = DelayedFeedback(truth, args.feedback_delay,
                                 jitter_s=args.feedback_delay * 0.5,
                                 seed=seed)
            # Bound how long unresolved outcomes are held: well past the
            # worst-case delivery delay, but never forever.
            stage = OutcomeStage(timeout_s=20.0 * args.feedback_delay)
            return fb, fb, stage
        return truth, None, None

    return types.SimpleNamespace(
        names=names, engine=engine, data=data, te=te, truth=truth,
        make_cascade=make_cascade, make_semcache=make_semcache,
        make_feedback=make_feedback)


def build_drift_proto(args, ctx):
    """Fitted per-worker drift-detector prototype (None without --online).

    Per-worker detectors watch each worker's 1/N traffic share: smaller
    windows, alarms escalate to a leader burst. The bootstrap calibration
    is identical for every worker, so fit ONCE and deep-copy the fitted
    detector instead of paying N calibration passes (socket-mode followers
    refit from the same seeded inputs and land on the same state).
    """
    if not args.online:
        return None
    from repro.online import DriftDetector

    tr, _, _ = ctx.data.split(seed=args.seed)
    return DriftDetector(window=max(16, 48 // args.workers)).fit(
        ctx.data.emb[tr], ctx.engine.router.centroids)


def build_plane_worker(args, ctx, wid, governor, drift_proto, recorder, slo):
    """One plane worker node, identical whichever process builds it.

    ``governor`` is the shared ledger in-process, or a
    :class:`~repro.distributed.ledger.LedgerClient` in a socket-mode
    follower; ``recorder`` is the shared TraceRecorder in-process, or the
    follower's own per-process recorder.
    """
    from repro.distributed import WorkerNode
    from repro.serving.scheduler import SimClock

    weng = RoutedEngine(router=ctx.engine.router, pool=ctx.engine.pool,
                        lam=args.lam, use_pallas=args.pallas)
    adapter = None
    if args.online:
        import copy

        from repro.online import (
            ExplorationConfig, OnlineAdapter, OnlineUpdateConfig,
        )

        wseed = args.seed + 101 * wid + 1
        quality_feedback, feedback_source, stage = ctx.make_feedback(wseed)
        membership = None
        if args.refresh_established:
            from repro.online import MembershipTracker

            membership = MembershipTracker(
                weng, refresh_established=True)
        adapter = OnlineAdapter(
            weng, quality_feedback, governor=governor,
            config=OnlineUpdateConfig(
                update_every=args.online_update_every),
            exploration=ExplorationConfig(epsilon=args.epsilon,
                                          seed=wseed),
            drift=copy.deepcopy(drift_proto),
            feedback_source=feedback_source, stage=stage,
            membership=membership,
            defer_updates=True, seed=wseed,
        )
    sched = MicroBatchScheduler(
        weng,
        SchedulerConfig(score_batch=args.score_batch,
                        max_batch=args.max_batch,
                        max_wait_s=args.max_wait,
                        queue_capacity=args.queue_capacity),
        governor=governor, clock=SimClock(),
        service_time=None if args.wall_time else default_service_model(),
        adapter=adapter, cascade=ctx.make_cascade(governor),
        semcache=ctx.make_semcache(),
        tracer=recorder.scoped(wid) if recorder is not None else None,
        slo=slo,
    )
    sched.slo_enforce = args.slo_class > 0
    return WorkerNode(wid, weng, sched, adapter)


def _streaming_requested(args) -> bool:
    return (args.scrape_every is not None or args.trace_sample is not None
            or args.trace_cap is not None or args.obs_dir is not None)


def _setup_obs(args):
    """(recorder, registry, profiler, flusher) from the obs flags.

    All default to None — the runtime's tracer branches then cost nothing.
    Streaming mode (any of ``--scrape-every/--trace-sample/--trace-cap/
    --obs-dir``) builds the recorder with the sampler/cap installed and an
    :class:`ObsFlusher` over the segment directory; with no
    ``--scrape-every`` the flusher still applies sampling, in one
    final-only flush. ``--trace-profile`` additionally installs the layer
    profiler in the program's one slot (removed again by
    :func:`_save_obs`).
    ``--metrics-port`` forces the registry on so the HTTP endpoint has
    something to scrape.
    """
    recorder = registry = profiler = flusher = None
    streaming = _streaming_requested(args)
    label = f"serve-{args.trace}-seed{args.seed}"
    if args.trace_out or args.trace_profile or streaming:
        from repro.obs import TraceRecorder, TraceSampler

        sampler = None
        if args.trace_sample is not None:
            sampler = TraceSampler(args.trace_sample, seed=args.seed,
                                   head=args.trace_head)
        recorder = TraceRecorder(
            label=label, sampler=sampler,
            max_buffered_per_worker=args.trace_cap)
    if args.metrics_out or args.metrics_port is not None or streaming:
        from repro.obs import MetricsRegistry

        registry = MetricsRegistry()
    if streaming:
        from repro.obs import ObsFlusher

        obs_dir = args.obs_dir or f"obs_{args.trace}_seed{args.seed}"
        args.obs_dir = obs_dir
        flusher = ObsFlusher(
            obs_dir, recorder=recorder, registry=registry,
            scrape_every_s=args.scrape_every, label=label,
            include_wall=args.trace_profile,
            deterministic_metrics=not args.trace_profile)
    if args.trace_profile:
        from repro.common import profile_slot
        from repro.obs import LayerProfiler

        profiler = LayerProfiler(tracer=recorder)
        profile_slot.install(profiler)
    return recorder, registry, profiler, flusher


def _save_obs(args, recorder, registry, profiler, flusher=None,
              now: float = 0.0):
    """Write the observability artifacts and uninstall the profiler.

    ``now`` is the run's final virtual time — it stamps the flusher's
    last segment and manifest. In streaming mode ``--trace-out`` becomes
    the concatenation of the rotated segments (still one valid,
    replay-stable Chrome trace — minus sampled-out request trees).
    """
    if profiler is not None:
        from repro.common import profile_slot

        profile_slot.install(None)
        print(profiler.report())
        if registry is not None:
            profiler.register_metrics(registry)
    if flusher is not None:
        flusher.finalize(now)
        stats = recorder.drop_stats
        print(f"obs segments written to {args.obs_dir} "
              f"({flusher.seq} flushes, peak {recorder.peak_buffered} "
              f"buffered events, {stats['requests_sampled_out']} trees "
              f"sampled out, {stats['requests_shed']} shed)")
        if args.trace_out:
            import json as _json

            from repro.obs import concat_dir

            doc = concat_dir(args.obs_dir)
            with open(args.trace_out, "w") as f:
                f.write(_json.dumps(doc, sort_keys=True,
                                    separators=(",", ":")))
            print(f"concatenated trace written to {args.trace_out}")
    elif recorder is not None and args.trace_out:
        recorder.save(args.trace_out, include_wall=args.trace_profile)
        print(f"trace written to {args.trace_out} "
              f"({recorder.n_events} events)")
    if registry is not None and args.metrics_out:
        if args.metrics_out.endswith((".prom", ".txt")):
            registry.save_prometheus(args.metrics_out)
        else:
            # Deterministic snapshot unless the operator opted wall-clock
            # data in — replays of a seeded run then produce identical
            # bytes, same contract as the trace.
            registry.save(args.metrics_out,
                          deterministic=not args.trace_profile)
        print(f"metrics snapshot written to {args.metrics_out} "
              f"({len(registry)} series)")


def _make_slo(args, tracer=None):
    """SLO tracker from the --slo-* flags (None when none are set)."""
    from repro.obs import build_slo_tracker

    return build_slo_tracker(
        tracer=tracer, p95_target_s=args.slo_p95,
        miss_rate_budget=args.slo_miss_rate,
        quality_floor=args.slo_quality_floor,
        spend_per_window=args.slo_spend, window_s=args.slo_window)


def _print_slo(slo, now: float) -> None:
    if slo is None:
        return
    firing = slo.firing()
    burns = {name: f"{b['long']:.2f}x"
             for name, b in slo.burn_rates(now).items()}
    print(f"slo: {slo.alerts_total} alert transitions  "
          f"firing {firing if firing else 'none'}  long-window burn "
          + "  ".join(f"{k}={v}" for k, v in burns.items()))


def make_parser() -> argparse.ArgumentParser:
    """The serve argv schema — shared with ``repro.distributed.host``,
    which re-parses the controller's forwarded argv to rebuild identical
    serving state in each follower process."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pool", default="qwen3-0.6b,granite-moe-1b-a400m,granite-3-8b")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--trace", default="poisson", choices=TRACE_KINDS)
    ap.add_argument("--rate", type=float, default=400.0,
                    help="mean arrivals per virtual second")
    ap.add_argument("--lam", type=float, default=1.0,
                    help="nominal willingness-to-pay")
    ap.add_argument("--budget", type=float, default=0.0,
                    help="$ budget per rolling window (0 disables governor)")
    ap.add_argument("--budget-window", type=float, default=0.5,
                    help="governor window, virtual seconds")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds pool init, traffic, training, trace and prompts")
    ap.add_argument("--epochs", type=int, default=80)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait", type=float, default=0.05)
    ap.add_argument("--score-batch", type=int, default=64)
    ap.add_argument("--queue-capacity", type=int, default=512)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline, virtual seconds after arrival")
    ap.add_argument("--full-width", action="store_true",
                    help="build pool members at their published widths "
                         "instead of the reduced smoke configs")
    ap.add_argument("--pallas", action="store_true",
                    help="on the CPU, score through the fused Pallas "
                         "router_xattn kernel in interpret mode (on TPU "
                         "the kernel always scores)")
    ap.add_argument("--wall-time", action="store_true",
                    help="advance the virtual clock by measured wall time "
                         "instead of the deterministic service model")
    ap.add_argument("--online", action="store_true",
                    help="online adaptation: replay-buffered outcome "
                         "feedback, drift detection, exploration, and "
                         "incremental router updates during serving")
    ap.add_argument("--cascade", action="store_true",
                    help="cascade routing: train the deep-ensemble quality "
                         "head and escalate inadequate answers up the cost "
                         "ladder (multi-leg requests, cumulative-cost "
                         "budget accounting)")
    ap.add_argument("--max-legs", type=int, default=3,
                    help="cascade: max legs per request")
    ap.add_argument("--cascade-beta", type=float, default=1.0,
                    help="cascade: optimism width on untried rungs "
                         "(x ensemble std)")
    ap.add_argument("--cascade-margin", type=float, default=0.0,
                    help="cascade: required expected marginal reward to "
                         "escalate")
    ap.add_argument("--cascade-min-headroom", type=float, default=0.0,
                    help="cascade: budget headroom in [0,1] below which "
                         "escalation is blocked (0 disables the gate; "
                         "needs --budget to have any effect)")
    ap.add_argument("--semcache", action="store_true",
                    help="semantic answer cache as cascade rung 0: "
                         "embedding-keyed reuse of finalized answers for "
                         "near-duplicate queries, stop-vs-escalate decided "
                         "by the same expected-marginal-reward policy as "
                         "the cascade ladder")
    ap.add_argument("--cache-radius", type=float, default=None,
                    help="semcache: L2 match radius in embedding space "
                         "(default: calibrated from the training split's "
                         "nearest-neighbour distance quantile)")
    ap.add_argument("--cache-cap", type=int, default=256,
                    help="semcache: max entries (LRU eviction past it)")
    ap.add_argument("--save-router", default=None, metavar="PATH",
                    help="persist the trained router (params + version + "
                         "cost-scaler meta) after offline training")
    ap.add_argument("--restore-router", default=None, metavar="PATH",
                    help="load a --save-router checkpoint instead of "
                         "training (restored scores are bitwise-identical)")
    ap.add_argument("--refresh-established", action="store_true",
                    help="online: EMA outcome-driven embedding refresh for "
                         "graduated (established) pool members under drift")
    ap.add_argument("--online-update-every", type=int, default=32,
                    help="outcomes between scheduled incremental updates")
    ap.add_argument("--epsilon", type=float, default=0.05,
                    help="exploration rate at full budget headroom")
    ap.add_argument("--feedback-delay", type=float, default=0.0,
                    help="virtual seconds between completion and quality "
                         "feedback (staged delayed-outcome path; 0 = "
                         "feedback at completion time)")
    ap.add_argument("--workers", type=int, default=1,
                    help="N>1 runs the multi-worker serving plane "
                         "(repro.distributed) with leader/follower sync")
    ap.add_argument("--transport", default="local",
                    choices=["local", "socket"],
                    help="plane message transport: local = in-process "
                         "by-reference delivery (bit-identical seeded "
                         "replays); socket = workers 1..N-1 as real OS "
                         "processes over length-prefixed TCP, with the LM "
                         "pool sharded by ownership across the processes")
    ap.add_argument("--sync-every", type=float, default=0.05,
                    help="virtual seconds between replay-merge/broadcast "
                         "sync rounds (multi-worker only)")
    ap.add_argument("--crash-at", type=float, default=None,
                    help="crash --crash-worker at this virtual time "
                         "(multi-worker only)")
    ap.add_argument("--rejoin-at", type=float, default=None,
                    help="rejoin the crashed worker at this virtual time")
    ap.add_argument("--crash-worker", type=int, default=1,
                    help="worker id for the crash/rejoin scenario")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the run's "
                         "per-request spans (deterministic: bit-identical "
                         "across replays of the same seed)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a metrics snapshot at end of run "
                         "(.prom/.txt -> Prometheus text exposition, "
                         "else canonical JSON)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve the live metrics registry over localhost "
                         "HTTP for the run's duration (/metrics Prometheus "
                         "text, /metrics.json canonical JSON; 0 picks an "
                         "ephemeral port)")
    ap.add_argument("--trace-profile", action="store_true",
                    help="profile the scheduler, engine, LM and kernel "
                         "layers (wall clock, with the XLA compiles each "
                         "triggers) and include the wall-clock spans/"
                         "metrics in the artifacts — the outputs are then "
                         "NOT replay-stable")
    ap.add_argument("--scrape-every", type=float, default=None,
                    metavar="VIRT_S",
                    help="streaming obs: flush completed trace spans and a "
                         "metrics scrape to rotating segments every this "
                         "many virtual seconds (bounds recorder memory)")
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="segment directory for streaming obs (default "
                         "obs_<trace>_seed<seed> when streaming is on)")
    ap.add_argument("--trace-sample", type=float, default=None,
                    metavar="RATE",
                    help="deterministic per-request trace sampling rate in "
                         "[0,1]; anomalous requests (escalations, expiries, "
                         "rescues) are always kept")
    ap.add_argument("--trace-head", type=int, default=8,
                    help="always keep the first N request trees regardless "
                         "of --trace-sample")
    ap.add_argument("--trace-cap", type=int, default=None, metavar="N",
                    help="hard per-worker buffered-event cap: new request "
                         "trees are shed (with drop accounting) past it")
    ap.add_argument("--slo-p95", type=float, default=None, metavar="VIRT_S",
                    help="SLO: p95 e2e latency target (error budget 5%%)")
    ap.add_argument("--slo-miss-rate", type=float, default=None,
                    metavar="FRAC",
                    help="SLO: allowed deadline-miss fraction")
    ap.add_argument("--slo-quality-floor", type=float, default=None,
                    help="SLO: per-request quality floor (error budget 10%%)")
    ap.add_argument("--slo-spend", type=float, default=None, metavar="USD",
                    help="SLO: $ spend allowed per --slo-window")
    ap.add_argument("--slo-window", type=float, default=0.25,
                    metavar="VIRT_S",
                    help="SLO compliance window, virtual seconds (the "
                         "burn-rate alert pairs it with a window/12 short "
                         "window)")
    ap.add_argument("--slo-class", type=int, default=0, metavar="K",
                    help="SLO-class-aware admission enforcement: assign "
                         "each trace request a class in [0, K) (round-"
                         "robin over arrival order; higher = more "
                         "important) and, while any --slo-* burn-rate "
                         "alert fires, shed the queue's lowest class at "
                         "dispatch time (0 disables)")
    return ap


def main(argv=None, *, devices=None):
    """Run one serve invocation; returns the run's summary dict.

    ``devices`` (programmatic callers only) are the devices an in-process
    plane spreads its pool members over; default: every local device.
    """
    ap = make_parser()
    args = ap.parse_args(argv)
    # Socket mode forwards the raw argv to follower processes, which
    # re-parse it to rebuild identical seeded state.
    raw_argv = list(sys.argv[1:]) if argv is None else list(argv)
    if (args.crash_at is not None and args.rejoin_at is not None
            and args.rejoin_at <= args.crash_at):
        ap.error(f"--rejoin-at ({args.rejoin_at}) must be after "
                 f"--crash-at ({args.crash_at})")
    if args.transport == "socket":
        if args.workers < 2:
            ap.error("--transport socket needs --workers >= 2")
        if args.crash_at is not None and args.crash_worker == 0:
            ap.error("--transport socket pins the controller (and leader) "
                     "to worker 0; crash a follower instead")
        if jax.default_backend() == "tpu":
            ap.error("--transport socket starts follower processes that "
                     "each need the chip, but this controller process "
                     "already holds it (a chip belongs to one process); "
                     "use --transport local")
    enable_compile_cache()

    ctx = build_context(args)
    pool_desc = [_describe_member(m) for m in ctx.engine.pool]
    print("pool: " + "  ".join(pool_desc))
    if args.save_router:
        from repro.checkpoint import save_router

        save_router(args.save_router, ctx.engine.router,
                    pool_names=ctx.names)
        print(f"router checkpoint saved to {args.save_router} "
              f"(v{ctx.engine.router.version}, "
              f"{ctx.engine.router.quality_kind}/"
              f"{ctx.engine.router.cost_kind})")

    trace = make_trace(
        TraceConfig(
            kind=args.trace, n_requests=args.requests, rate=args.rate,
            seed=args.seed, max_new=args.max_new, deadline_s=args.deadline,
            prompt_len_max=48,
            vocab=min(m.cfg.vocab_size for m in ctx.engine.pool),
        ),
        texts=[ctx.data.texts[i] for i in ctx.te],
        benchmarks=[ctx.data.benchmark[i] for i in ctx.te],
    )
    if args.slo_class > 0:
        # Deterministic class assignment (arrival order) — followers see
        # the classes via the ASSIGN codec, not by re-deriving them.
        for i, r in enumerate(trace):
            r.slo_class = i % args.slo_class

    obs = _setup_obs(args)
    mserver = None
    if args.metrics_port is not None:
        from repro.obs import MetricsServer

        mserver = MetricsServer(obs[1], port=args.metrics_port)
        print(f"metrics endpoint: http://127.0.0.1:{mserver.start()}"
              f"/metrics")
    try:
        if args.workers > 1:
            if args.transport == "socket":
                return _run_plane_socket(args, ctx, trace, obs, raw_argv,
                                         mserver=mserver)
            summary = _run_plane(args, ctx, trace, obs, devices)
        else:
            summary = _run_solo(args, ctx, trace, obs)
        # In-process runs hold every request: its generated tokens (or
        # None if it never completed), in trace order.
        summary["pool"] = pool_desc
        summary["outputs"] = [None if r.output is None
                              else np.asarray(r.output).tolist()
                              for r in trace]
        summary["peak_bytes_in_use"] = _peak_memory()
        return summary
    finally:
        if mserver is not None:
            mserver.stop()


def _describe_member(m) -> str:
    """One member's widths as built, with its parameter count and dtype."""
    c = m.cfg
    moe = f" {c.n_experts} experts top-{c.top_k}" if c.n_experts else ""
    dtype = jax.tree.leaves(m.params)[0].dtype
    return (f"{m.name} d_model {c.d_model} x {c.n_layers} layers vocab "
            f"{c.vocab_size}{moe} ({tree_count(m.params)} {dtype} params)")


def _peak_memory() -> dict:
    """Peak bytes in use per local device, where the backend reports it
    (printed; the CPU backend reports nothing)."""
    peaks = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks[d.id] = int(stats["peak_bytes_in_use"])
            print(f"peak device memory {d.platform}:{d.id} "
                  f"{peaks[d.id]} bytes")
    return peaks


def _run_solo(args, ctx, trace, obs):
    """Single-scheduler path (``--workers 1``)."""
    recorder, registry, profiler, flusher = obs
    engine, data = ctx.engine, ctx.data

    governor = None
    if args.budget > 0:
        governor = BudgetGovernor(args.budget, args.budget_window,
                                  lam0=args.lam)

    adapter = None
    if args.online:
        from repro.online import (
            DriftDetector, ExplorationConfig, OnlineAdapter,
            OnlineUpdateConfig,
        )

        # Quality feedback: the synthetic RouterBench truth stands in for
        # user ratings / auto-eval (the held-out split is what the trace
        # samples its texts from).
        quality_feedback, feedback_source, stage = ctx.make_feedback(args.seed)
        tr, _, _ = data.split(seed=args.seed)
        drift = DriftDetector(window=48).fit(
            data.emb[tr], engine.router.centroids)
        membership = None
        if args.refresh_established:
            from repro.online import MembershipTracker

            membership = MembershipTracker(
                engine, refresh_established=True)
        adapter = OnlineAdapter(
            engine, quality_feedback, governor=governor,
            config=OnlineUpdateConfig(
                update_every=args.online_update_every),
            exploration=ExplorationConfig(epsilon=args.epsilon,
                                          seed=args.seed),
            drift=drift, feedback_source=feedback_source, stage=stage,
            membership=membership,
            seed=args.seed,
        )

    cascade = ctx.make_cascade(governor)
    semcache = ctx.make_semcache()
    slo = _make_slo(args, tracer=recorder)
    sched = MicroBatchScheduler(
        engine,
        SchedulerConfig(score_batch=args.score_batch,
                        max_batch=args.max_batch,
                        max_wait_s=args.max_wait,
                        queue_capacity=args.queue_capacity),
        governor=governor,
        service_time=None if args.wall_time else default_service_model(),
        adapter=adapter, cascade=cascade, semcache=semcache,
        tracer=recorder.scoped(0) if recorder is not None else None,
        slo=slo, flusher=flusher,
    )
    sched.slo_enforce = args.slo_class > 0
    if registry is not None:
        from repro.obs import (
            register_governor_metrics, register_scheduler_metrics,
            register_slo_metrics, register_stream_metrics,
        )

        register_scheduler_metrics(registry, sched)
        if governor is not None:
            register_governor_metrics(registry, governor,
                                      lambda: sched.clock.now)
        if slo is not None:
            register_slo_metrics(registry, slo, lambda: sched.clock.now)
        if flusher is not None:
            register_stream_metrics(registry, flusher)
    summary = sched.run_trace(trace)

    print(f"trace={args.trace} requests={args.requests} seed={args.seed}")
    print(sched.telemetry.report(summary.get("duration_s")))
    if cascade is not None:
        print(cascade.report())
    if semcache is not None:
        rep = semcache.report()
        print(f"semcache: {rep['served']} served / {rep['lookups']} lookups "
              f"(hit rate {rep['hit_rate']:.2f})  "
              f"{rep['fallthroughs']} fallthroughs  "
              f"{rep['stale_hits']} stale  {rep['evicted']} evicted  "
              f"{rep['invalidations']} invalidated  "
              f"{rep['entries']} entries")
    if adapter is not None:
        print(adapter.report())
    if governor is not None:
        g = governor.summary(sched.clock.now)
        print(f"budget ${g['budget_per_window']:.4f}/{args.budget_window}s "
              f"window  spend ${g['total_spend']:.6f}  "
              f"final lambda {g['lam']:.3g} (nominal {g['lam0']:.3g})  "
              f"tightened x{int(g['tightened'])} relaxed x{int(g['relaxed'])}")
    _print_slo(slo, sched.clock.now)
    _save_obs(args, recorder, registry, profiler, flusher,
              now=sched.clock.now)
    return summary


def _run_plane(args, ctx, trace, obs, devices=None):
    """Multi-worker path over LocalTransport: N in-process workers.

    Each pool member's parameters sit on its owning worker's device
    (:func:`repro.distributed.shard.place_pool` over ``devices``).
    """
    from repro.distributed import (
        Coordinator, PlaneEvent, ServingPlane, SharedBudgetLedger,
        SyncConfig,
    )
    from repro.distributed.shard import place_pool

    recorder, registry, profiler, flusher = obs
    # One fleet-level SLO tracker: every worker's finalized requests feed
    # the same rolling windows (they tolerate cross-worker time skew).
    slo = _make_slo(args, tracer=recorder)
    governor = None
    if args.budget > 0:
        governor = SharedBudgetLedger(args.budget, args.budget_window,
                                      lam0=args.lam)

    placed = place_pool(ctx.engine.pool, args.workers, devices)
    print("pool placement: " + "  ".join(
        f"{m.name}->{d.platform}:{d.id}"
        for m, d in zip(ctx.engine.pool, placed)))
    drift_proto = build_drift_proto(args, ctx)
    workers = [
        build_plane_worker(args, ctx, wid, governor, drift_proto,
                           recorder, slo)
        for wid in range(args.workers)
    ]

    from repro.online import OnlineUpdateConfig
    coord = Coordinator(workers, SyncConfig(
        sync_every_s=args.sync_every, seed=args.seed,
        update=OnlineUpdateConfig(update_every=args.online_update_every)))
    events = []
    if args.crash_at is not None:
        events.append(PlaneEvent(args.crash_at, "crash", args.crash_worker))
        if args.rejoin_at is not None:
            events.append(
                PlaneEvent(args.rejoin_at, "rejoin", args.crash_worker))
    plane = ServingPlane(workers, coord, events=events, tracer=recorder,
                         flusher=flusher)
    if registry is not None:
        from repro.obs import (
            register_plane_metrics, register_slo_metrics,
            register_stream_metrics,
        )

        register_plane_metrics(registry, plane)
        if slo is not None:
            register_slo_metrics(
                registry, slo,
                lambda: max(w.clock.now for w in plane.workers.values()))
        if flusher is not None:
            register_stream_metrics(registry, flusher)
    summary = plane.run_trace(trace)
    summary["pool_device"] = {m.name: d.id
                              for m, d in zip(ctx.engine.pool, placed)}

    print(f"trace={args.trace} requests={args.requests} seed={args.seed} "
          f"workers={args.workers}")
    print(plane.report(summary.get("duration_s")))
    if args.cascade:
        for w in sorted(workers, key=lambda w: w.wid):
            print(f"w{w.wid} {w.scheduler.cascade.report()}")
    if args.semcache:
        for w in sorted(workers, key=lambda w: w.wid):
            rep = w.scheduler.semcache.report()
            print(f"w{w.wid} semcache: {rep['served']}/{rep['lookups']} "
                  f"served (hit rate {rep['hit_rate']:.2f})  "
                  f"{rep['entries']} entries")
    if args.online:
        for w in sorted(workers, key=lambda w: w.wid):
            print(f"w{w.wid} {w.adapter.report()}")
    if governor is not None:
        now = max(w.clock.now for w in workers)
        g = governor.summary(now)
        print(f"shared budget ${g['budget_per_window']:.4f}/"
              f"{args.budget_window}s window  spend ${g['total_spend']:.6f}  "
              f"final lambda {g['lam']:.3g} (nominal {g['lam0']:.3g})  "
              f"tightened x{int(g['tightened'])} relaxed x{int(g['relaxed'])} "
              f"throttled x{governor.throttled}")
    t_end = max(w.clock.now for w in workers)
    _print_slo(slo, t_end)
    _save_obs(args, recorder, registry, profiler, flusher, now=t_end)
    return summary


def _run_plane_socket(args, ctx, trace, obs, raw_argv, mserver=None):
    """Multi-worker path over SocketTransport: real OS processes.

    This process is worker 0 AND the controller AND (by lowest-id
    election) the leader — the coordinator's updater reads the leader's
    engine directly, so leader/controller co-location is what lets socket
    mode run leader updates without shipping optimizer state over the
    wire. Workers 1..N-1 are ``repro.distributed.host`` subprocesses:
    each rebuilds identical seeded serving state from the forwarded argv,
    claims its pool shard (mesh-sharded parameters for owned members,
    evicted otherwise), and services protocol messages over
    length-prefixed TCP. Generate legs for a member the executing worker
    does not own hop to the owner as ``GENERATE`` messages; follower
    budget ops flow to the controller's shared ledger as ``LEDGER_OP``.
    """
    import json
    import os
    import subprocess

    from repro.distributed import (
        Coordinator, PlaneEvent, PoolDispatcher, ServingPlane,
        SharedBudgetLedger, SocketTransport, SyncConfig, TransportError,
        owner_of,
    )
    from repro.distributed import messages as M
    from repro.distributed.host import RemoteWorkerProxy
    from repro.distributed.messages import Message
    from repro.distributed.shard import shard_pool

    recorder, registry, profiler, flusher = obs
    slo = _make_slo(args, tracer=recorder)
    governor = None
    if args.budget > 0:
        governor = SharedBudgetLedger(args.budget, args.budget_window,
                                      lam0=args.lam)

    # Long conn timeout: follower processes connect BEFORE building their
    # engines, so frames queue in TCP buffers while training runs — the
    # first real exchange can lag the connect by minutes on a cold CPU.
    transport = SocketTransport(0, timeout=600.0)
    port = transport.listen()
    # Followers must import repro the same way this process did, even when
    # the driver was launched by path (no PYTHONPATH in the environment).
    import repro

    env = dict(os.environ)
    # __path__ (not __file__): repro is a plain src-layout package dir and
    # may be imported as a namespace package, where __file__ is None.
    src_root = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env["PYTHONPATH"] = (src_root + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else src_root)
    procs = [
        subprocess.Popen([sys.executable, "-m", "repro.distributed.host",
                          "--wid", str(wid), "--port", str(port),
                          "--serve-argv", json.dumps(raw_argv)],
                         env=env)
        for wid in range(1, args.workers)
    ]
    try:
        hellos = transport.accept(args.workers - 1, timeout=120.0)
        drift_proto = build_drift_proto(args, ctx)
        w0 = build_plane_worker(args, ctx, 0, governor, drift_proto,
                                recorder, slo)
        w0.ledger = governor        # follower LEDGER_OP messages land here
        shard_pool(w0.engine.pool, 0, args.workers)
        w0.scheduler.dispatcher = PoolDispatcher(0, args.workers,
                                                 w0.engine, transport)
        w0.bind(transport)
        names = [m.name for m in ctx.engine.pool]
        proxies = [
            RemoteWorkerProxy(wid, transport, member_names=names,
                              pid=int(hellos[wid].get("pid", -1)))
            for wid in range(1, args.workers)
        ]
        workers = [w0] + proxies
        pids = {0: os.getpid()}
        pids.update({p.wid: p.pid for p in proxies})
        print(f"socket plane: controller pid {pids[0]} port {port}  "
              + "  ".join(f"w{p.wid}:pid{p.pid}" for p in proxies))
        print("pool ownership: " + "  ".join(
            f"{names[mi]}->w{owner_of(mi, args.workers)}"
            for mi in range(len(names))))

        from repro.online import OnlineUpdateConfig
        coord = Coordinator(workers, SyncConfig(
            sync_every_s=args.sync_every, seed=args.seed,
            update=OnlineUpdateConfig(
                update_every=args.online_update_every)),
            transport=transport)
        events = []
        if args.crash_at is not None:
            events.append(
                PlaneEvent(args.crash_at, "crash", args.crash_worker))
            if args.rejoin_at is not None:
                events.append(
                    PlaneEvent(args.rejoin_at, "rejoin", args.crash_worker))
        # Fleet-wide obs drain, called by the plane at sync boundaries
        # (and once more after the run): incremental follower trace
        # segments are absorbed verbatim (keys pre-partitioned by the
        # followers' key_base), and follower registries are scraped over
        # METRICS_REQ so the live /metrics endpoint federates the fleet.
        # RPCs happen HERE, on the plane loop — never on the HTTP scrape
        # thread (the socket protocol is single-threaded lockstep).
        fleet_prom = {}

        def fleet_drain(now, force=False):
            for p in proxies:
                try:
                    if recorder is not None:
                        rep = transport.request(Message(
                            kind=M.TRACE_REQ, dst=p.wid,
                            payload={"force": bool(force)}))
                        recorder.absorb(
                            [tuple(e) for e in rep.payload["events"]])
                    if registry is not None:
                        rep = transport.request(Message(
                            kind=M.METRICS_REQ, dst=p.wid))
                        text = rep.payload.get("prom", "")
                        if text:
                            fleet_prom[p.wid] = text
                            if mserver is not None:
                                mserver.update_fleet(p.wid, text)
                except TransportError:
                    continue

        plane = ServingPlane(workers, coord, events=events, tracer=recorder,
                             flusher=flusher,
                             fleet_drain=(fleet_drain
                                          if recorder is not None
                                          or registry is not None
                                          else None))
        if registry is not None:
            from repro.obs import (
                register_plane_metrics, register_slo_metrics,
                register_stream_metrics,
            )

            register_plane_metrics(registry, plane)
            if slo is not None:
                register_slo_metrics(
                    registry, slo,
                    lambda: max(w.clock.now
                                for w in plane.workers.values()))
            if flusher is not None:
                register_stream_metrics(registry, flusher)
        summary = plane.run_trace(trace)
        summary["transport"] = "socket"
        summary["pids"] = pids
        summary["pool_owner"] = {
            names[mi]: owner_of(mi, args.workers)
            for mi in range(len(names))}

        # Final force-drain: whatever the incremental sync-boundary drains
        # have not collected yet (open trees, post-FINALIZE spans, the
        # last metrics state) is absorbed now so --trace-out and the
        # fleet exposition cover the whole run.
        if recorder is not None or registry is not None:
            fleet_drain(None, force=True)

        print(f"trace={args.trace} requests={args.requests} "
              f"seed={args.seed} workers={args.workers} transport=socket")
        print(plane.report(summary.get("duration_s")))
        # Only w0's serving components live in this process; each follower
        # prints its own cascade/semcache/adapter lines at shutdown.
        if args.cascade and w0.scheduler.cascade is not None:
            print(f"w0 {w0.scheduler.cascade.report()}")
        if args.semcache and w0.scheduler.semcache is not None:
            rep = w0.scheduler.semcache.report()
            print(f"w0 semcache: {rep['served']}/{rep['lookups']} "
                  f"served (hit rate {rep['hit_rate']:.2f})  "
                  f"{rep['entries']} entries")
        if args.online and w0.adapter is not None:
            print(f"w0 {w0.adapter.report()}")
        if governor is not None:
            now = max(w.clock.now for w in workers)
            g = governor.summary(now)
            print(f"shared budget ${g['budget_per_window']:.4f}/"
                  f"{args.budget_window}s window  "
                  f"spend ${g['total_spend']:.6f}  "
                  f"final lambda {g['lam']:.3g} (nominal {g['lam0']:.3g})  "
                  f"tightened x{int(g['tightened'])} "
                  f"relaxed x{int(g['relaxed'])} "
                  f"throttled x{governor.throttled}")
        t_end = max(w.clock.now for w in workers)
        _print_slo(slo, t_end)
        _save_obs(args, recorder, registry, profiler, flusher, now=t_end)
        if args.metrics_out and registry is not None and fleet_prom:
            from repro.obs import merge_prom_texts

            fleet_path = args.metrics_out + ".fleet.prom"
            own = registry.prometheus(
                deterministic=not args.trace_profile)
            with open(fleet_path, "w") as f:
                f.write(merge_prom_texts(
                    [own] + [fleet_prom[w] for w in sorted(fleet_prom)]))
            print(f"fleet metrics exposition written to {fleet_path} "
                  f"({1 + len(fleet_prom)} registries)")
        for p in proxies:
            try:
                transport.send(Message(kind=M.SHUTDOWN, dst=p.wid))
            except TransportError:
                pass
        return summary
    finally:
        transport.close()
        for pr in procs:
            try:
                pr.wait(timeout=60)
            except Exception:
                pr.kill()


if __name__ == "__main__":
    main()
