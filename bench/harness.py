"""Build one cell from its files, drive the program through a measured
window on the wall clock, and check what it produced.

A cell (``BENCHMARK.json`` workload) names a configuration file
(``bench/configs/<config>.json``: the pool, router, scheduler, cache and
cascade settings and the limits of the comparison), the members' files
(``bench/members/<name>.json``: published sizes) and a traffic mix
(``bench/traffic/<traffic>.json``). Metrics are read by one reader file
each (``bench/metrics/<metric>.py``). Nothing here names a cell.

The program's own parts serve: ``RoutedEngine``, ``PredictiveRouter``
loaded with the benchmark's router checkpoint, ``SemanticCache`` with
its calibrated radius, ``CascadeCoordinator`` and ``MicroBatchScheduler``
driven with a wall clock and ``service_time=None``. The harness owns the
load loop, the member weights and the router checkpoint
(``bench/weights.py``) and thin wrappers
around the engine's ``embed``, ``score_emb``/``score_emb_uncertainty``,
``choose`` and ``generate_member`` that time each call on the host clock,
put a ``jax.profiler.TraceAnnotation`` around it, and keep what it
returned for the comparison. Each wrapped call ends in host numpy, so it
waits for the device.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import time
import types
from collections import deque
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import traffic as traffic_mod

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# Open loop: requests still queued this long after the window closed
# count as failed (a run has to end within its time limit).
DRAIN_LIMIT_S = 120.0
CLOSED_LOOP_BLOCKS = 8


def bench_dir(root: str) -> str:
    return os.path.join(root, "bench")


@dataclasses.dataclass
class CellSpec:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    mix: dict
    members: List[dict]
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(root: str, workload: str) -> CellSpec:
    """Everything the files say about one workload."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    mix = traffic_mod.load_mix(
        os.path.join(bench_dir(root), "traffic", f"{w['traffic']}.json"))
    members = []
    for name in config["members"]:
        with open(os.path.join(bench_dir(root), "members",
                               f"{name}.json")) as f:
            members.append(json.load(f))
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return CellSpec(workload, w["config"], w["traffic"], int(w["chips"]),
                    config, mix, members, e2e, per_layer)


def load_reader(root: str, metric: str) -> Callable:
    path = os.path.join(bench_dir(root), "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class WallClock:
    """The scheduler's clock on ``perf_counter``: ``now`` is seconds
    since :meth:`start`, ``advance`` is a no-op (time passes by itself)
    and ``advance_to`` sleeps."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def start(self) -> None:
        self.t0 = time.perf_counter()

    @property
    def now(self) -> float:
        return time.perf_counter() - self.t0

    def advance(self, dt: float) -> None:
        pass

    def advance_to(self, t: float) -> None:
        dt = t - self.now
        if dt > 0:
            time.sleep(dt)


class Recorder:
    """Host spans, compile events and what the wrapped calls returned.

    Times are seconds on the window's clock."""

    def __init__(self, clock: WallClock):
        self.clock = clock
        self.spans: List[tuple] = []          # (name, t0, t1, n)
        self.compiles: List[tuple] = []       # (t, seconds)
        self.scores: List[dict] = []
        self.gens: List[dict] = []
        self._text_of: Dict[bytes, str] = {}

    def on_duration(self, event, duration_secs, **_):
        if event == BACKEND_COMPILE:
            self.compiles.append((self.clock.now, float(duration_secs)))

    @contextlib.contextmanager
    def span(self, name: str, n: int = 0):
        import jax

        t0 = self.clock.now
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.spans.append((name, t0, self.clock.now, n))

    def instrument(self, engine) -> None:
        """Wrap the engine instance's per-layer entry points."""
        embed, score = engine.embed, engine.score_emb
        score_u, choose = engine.score_emb_uncertainty, engine.choose
        gen = engine.generate_member

        @functools.wraps(embed)
        def embed_w(texts):
            with self.span("embed", len(texts)):
                out = np.asarray(embed(texts))
            for t, row in zip(texts, out):
                self._text_of[row.tobytes()] = t
            return out

        def texts_of(q_emb):
            return [self._text_of[np.asarray(r).tobytes()] for r in q_emb]

        @functools.wraps(score)
        def score_w(q_emb):
            with self.span("score", len(q_emb)):
                s, c = score(q_emb)
                s, c = np.asarray(s), np.asarray(c)
            self.scores.append(dict(texts=texts_of(q_emb), s=s,
                                    s_std=np.zeros_like(s), c=c,
                                    t=self.clock.now, kernel=True))
            return s, c

        @functools.wraps(score_u)
        def score_u_w(q_emb):
            with self.span("score", len(q_emb)):
                s, sd, c = (np.asarray(a) for a in score_u(q_emb))
            self.scores.append(dict(texts=texts_of(q_emb), s=s, s_std=sd,
                                    c=c, t=self.clock.now, kernel=False))
            return s, sd, c

        @functools.wraps(choose)
        def choose_w(s_hat, c_hat, lam=None):
            out = np.asarray(choose(s_hat, c_hat, lam))
            last = self.scores[-1] if self.scores else None
            if last is not None and last["s"] is s_hat:
                last["choices"] = out.copy()
            return out

        @functools.wraps(gen)
        def gen_w(member_idx, prompts, max_new=8, max_new_per_req=None):
            t0 = self.clock.now
            with self.span("generate", len(prompts)):
                outs, costs = gen(member_idx, prompts, max_new=max_new,
                                  max_new_per_req=max_new_per_req)
                outs = [np.asarray(o) for o in outs]
            self.gens.append(dict(
                member=int(member_idx),
                prompts=[np.asarray(p) for p in prompts], outs=outs,
                t0=t0, t1=self.clock.now))
            return outs, costs

        engine.embed, engine.score_emb = embed_w, score_w
        engine.score_emb_uncertainty, engine.choose = score_u_w, choose_w
        engine.generate_member = gen_w


def _corpus(mix: dict, config: dict):
    """Texts of the mix's tasks from the RouterBench corpus the config
    names, in corpus order and without repeats, with their quality rows."""
    from repro.data import generate

    c = config["traffic_corpus"]
    data = generate(int(c["n"]), seed=int(c["seed"]), embed=False)
    tasks = set(mix["tasks"])
    texts, rows = [], {}
    for i, (t, b) in enumerate(zip(data.texts, data.benchmark)):
        if b in tasks and t not in rows:
            texts.append(t)
            rows[t] = i
    return data, texts, rows


def build(spec: CellSpec, seed: int, *, full_width: bool = True,
          seconds: float = 10.0, mix_overrides: Optional[dict] = None):
    """Set-up: weights, router, engine, cache, cascade and requests."""
    import jax
    import jax.numpy as jnp

    from bench.reference.router import EMB_DIM
    from bench.weights import make_member_params, make_router_params
    from repro.cascade import (CascadeConfig, CascadeCoordinator,
                               CascadePolicy, cost_ladder)
    from repro.configs import get_config, get_smoke_config
    from repro.core.router import PredictiveRouter
    from repro.data import generate
    from repro.launch import serve
    from repro.serving import (PoolMember, RoutedEngine, SemanticCache,
                               arch_cost_rate, calibrate_radius)

    config = spec.config
    mix = {**spec.mix, **(mix_overrides or {})}
    cfgs, weights, pool = [], [], []
    for i, m in enumerate(spec.members):
        name = m["program_config"]
        cfg = dataclasses.replace(
            (get_config if full_width else get_smoke_config)(name),
            **m.get("program_overrides", {}))
        params = make_member_params(cfg, seed, i)
        cfgs.append(cfg)
        weights.append(params)
        pool.append(PoolMember(name=name, cfg=cfg, params=params,
                               quality_profile=None,
                               cost_rate=arch_cost_rate(get_config(name))))
    jax.block_until_ready(weights)

    # The router is a checkpoint the deployment loads, made by the
    # benchmark from the configuration (lambda was set against it); the
    # program and the reference read the same arrays.
    r = config["router"]
    qp, cp, memb, scaler = make_router_params(r, len(pool), EMB_DIM)
    router_ref = dict(quality=copy.deepcopy(qp), cost=copy.deepcopy(cp),
                      model_emb=memb.copy(), scaler=copy.deepcopy(scaler),
                      quality_kind=r["quality_kind"])
    router = PredictiveRouter(
        r["quality_kind"], r["cost_kind"], jax.tree.map(jnp.asarray, qp),
        jax.tree.map(jnp.asarray, cp), memb, reward=r["reward"],
        cost_scaler=scaler)
    engine = RoutedEngine(router=router, pool=pool, lam=float(r["lam"]))

    corpus, texts, rows = _corpus(mix, config)
    # The cache radius comes from the program's own calibration over the
    # first texts of a RouterBench training split (the reference derives
    # its own from the same texts, see ``correct.reference_radius``).
    rc = config["radius_corpus"]
    radius_data = generate(int(rc["n"]), seed=int(rc["seed"]), embed=False)
    tr = radius_data.split(seed=int(rc["seed"]))[0][:int(rc["sample"])]
    radius_texts = [radius_data.texts[i] for i in tr]
    semcache = None
    if config.get("semcache"):
        semcache = SemanticCache(
            calibrate_radius(np.asarray(engine.embed(radius_texts))),
            cap=int(config["semcache"]["cap"]))
    cascade = None
    if config.get("cascade"):
        c = config["cascade"]
        qual = corpus.quality[:, serve.pool_quality_columns(pool, corpus)]

        def truth(req):
            return float(qual[rows[req.text]][req.member])

        policy = CascadePolicy(
            cost_ladder(router),
            CascadeConfig(max_legs=int(c["max_legs"]), beta=float(c["beta"]),
                          margin=float(c["margin"])),
            reward=router.reward)
        cascade = CascadeCoordinator(policy, observed_quality=truth)

    vocab = min([int(mix["token_vocab"])] + [c.vocab_size for c in cfgs])
    if mix["loop"] == "open":
        drafts = traffic_mod.open_loop(mix, texts, seed, seconds, vocab)
    else:
        drafts = traffic_mod.closed_loop(mix, texts, seed,
                                         CLOSED_LOOP_BLOCKS, vocab)
    ref_members = [m if full_width else _smoke_member(m, c)
                   for m, c in zip(spec.members, cfgs)]
    return types.SimpleNamespace(
        spec=spec, mix=mix, seed=seed, engine=engine, semcache=semcache,
        cascade=cascade, drafts=drafts, weights=weights,
        router_ref=router_ref, ref_members=ref_members,
        train_texts=radius_texts, cfgs=cfgs)


def _smoke_member(member: dict, cfg) -> dict:
    """The member's reference sizes at the program's reduced smoke widths
    (CPU rehearsal only)."""
    conf = dict(member["config"])
    conf.update(hidden_size=cfg.d_model, num_hidden_layers=cfg.n_layers,
                num_attention_heads=cfg.n_heads,
                num_key_value_heads=cfg.n_kv_heads,
                head_dim=cfg.resolved_head_dim, vocab_size=cfg.vocab_size,
                intermediate_size=cfg.d_ff_expert or cfg.d_ff)
    if cfg.n_experts:
        conf.update(num_local_experts=cfg.n_experts,
                    num_experts_per_tok=cfg.top_k)
    return {**member, "config": conf}


def check_sizes(built) -> None:
    """At published widths the program's configs must hold the sizes the
    members' files state (the reference runs from the files)."""
    for m, cfg in zip(built.ref_members, built.cfgs):
        c = m["config"]
        got = dict(hidden_size=cfg.d_model, num_hidden_layers=cfg.n_layers,
                   num_attention_heads=cfg.n_heads,
                   num_key_value_heads=cfg.n_kv_heads,
                   vocab_size=cfg.vocab_size,
                   head_dim=cfg.resolved_head_dim)
        want = dict(c, head_dim=c.get("head_dim")
                    or c["hidden_size"] // c["num_attention_heads"])
        bad = {k: (v, want[k]) for k, v in got.items() if want[k] != v}
        if bad:
            raise ValueError(f"{m['name']}: program sizes differ from the "
                             f"published ones: {bad}")


def warm_up(built) -> None:
    """One scoring batch and one generate micro-batch per member at the
    cell's largest shapes, through the engine (the program's
    ``greedy_generate`` still compiles its scan bodies at every call)."""
    eng, mix = built.engine, built.mix
    sched = built.spec.config["scheduler"]
    texts = [d.text for d in built.drafts[:sched["score_batch"]]]
    q = np.asarray(eng.embed(texts))
    if built.cascade is not None:
        s, _, c = eng.score_emb_uncertainty(q)
    else:
        s, c = eng.score_emb(q)
    eng.choose(s, c)
    hi = int(mix["prompt_len"][1])
    prompts = [d.prompt for d in built.drafts[:sched["max_batch"]]]
    prompts[0] = np.resize(prompts[0], hi)
    for mi in range(len(eng.pool)):
        eng.generate_member(mi, prompts, max_new=int(mix["max_new"]))


def _request(d):
    from repro.serving.queue import Request

    return Request(text=d.text, prompt=d.prompt, max_new=d.max_new,
                   arrival_s=d.due_s)


@dataclasses.dataclass
class Window:
    requests: list          # every request offered
    t_close: float          # window length on its clock
    t_end: float            # end of the drive (after the drain)
    completed: list
    failed: int


def drive_open(sched, clock: WallClock, rec: Recorder, drafts,
               seconds: float, on_close: Callable[[], None]) -> Window:
    """Offer each request at its due time; dispatch by the scheduler's
    own policy; drain after the window."""
    reqs = [_request(d) for d in drafts]
    pending = deque(sorted(reqs, key=lambda r: r.arrival_s))
    max_wait = sched.config.max_wait_s
    closed = False
    clock.start()
    while True:
        now = clock.now
        if not closed and now >= seconds:
            closed = True
            on_close()
        while pending and pending[0].arrival_s <= now:
            sched.queue.offer(pending.popleft(), now)
        if not pending and sched.queue.depth == 0:
            break
        if now > seconds + DRAIN_LIMIT_S:
            break
        if sched.should_dispatch(flush=not pending):
            with rec.span("dispatch"):
                sched.dispatch()
            continue
        nxt = []
        if pending:
            nxt.append(pending[0].arrival_s)
        if sched.queue.depth:
            nxt.append(sched.queue.peek_all()[0].admitted_s + max_wait)
        if not closed:
            nxt.append(seconds)
        with rec.span("wait"):
            clock.advance_to(min(nxt))
    if not closed:
        on_close()
    completed = [r for r in reqs if r.status == "done"]
    return Window(reqs, seconds, clock.now, completed,
                  len(reqs) - len(completed))


def drive_closed(sched, clock: WallClock, rec: Recorder, drafts,
                 clients: int, seconds: float,
                 on_close: Callable[[], None]) -> Window:
    """Keep ``clients`` requests outstanding until the window closes at
    ``seconds``; the dispatch round in flight then runs to its end (its
    work counts in the window pro rata, see ``readers``)."""
    source = iter(drafts)
    reqs = []

    def offer():
        d = next(source, None)
        if d is None:
            raise RuntimeError("the closed-loop mix ran out of requests; "
                               "raise CLOSED_LOOP_BLOCKS")
        r = _request(d)
        r.arrival_s = clock.now
        reqs.append(r)
        sched.queue.offer(r, clock.now)

    clock.start()
    for _ in range(clients):
        offer()
    max_wait = sched.config.max_wait_s
    while clock.now < seconds:
        if sched.should_dispatch():
            with rec.span("dispatch"):
                served = sched.dispatch()
            if clock.now < seconds:
                for _ in served:
                    offer()
            continue
        if sched.queue.depth == 0:
            break
        with rec.span("wait"):
            clock.advance_to(min(seconds, sched.queue.peek_all()[0].admitted_s
                                 + max_wait))
    on_close()
    completed = [r for r in reqs if r.status == "done"]
    failed = sum(r.status in ("rejected", "expired", "shed") for r in reqs)
    return Window(reqs, seconds, clock.now, completed, failed)


@contextlib.contextmanager
def no_cache_writes():
    """The drive writes nothing to JAX's persistent compilation cache.

    The program compiles inside the window (eager ``greedy_generate``
    recompiles at every call, and every new padded batch shape compiles
    anew). Programs slower than the cache's threshold would otherwise be
    written by one run and read by the next, so each window would meet a
    warmer cache than the one before it and latencies would drift down
    over a check's runs. With writes off every window meets the cache as
    set-up left it, and pays the program's compiles in full."""
    import jax

    key = "jax_persistent_cache_min_compile_time_secs"
    old = getattr(jax.config, key)
    jax.config.update(key, float("inf"))
    try:
        yield
    finally:
        jax.config.update(key, old)


def peak_bytes() -> int:
    import jax

    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks, default=0)


def window_counts(rec: Recorder, win: Window) -> str:
    """What the window held: dispatch rounds, scored batches and generate
    calls that ended inside it (and all of them, drain included),
    requests completed by the close and in all, cascade legs after the
    first, and cache-served requests."""
    def n(name):
        spans = [s for s in rec.spans if s[0] == name]
        inside = sum(s[2] <= win.t_close for s in spans)
        return f"{inside}/{len(spans)}"

    by_close = sum(r.finish_s <= win.t_close for r in win.completed)
    later_legs = sum(max(r.leg - 1, 0) for r in win.requests)
    hits = sum(r.leg == 0 for r in win.completed)
    return (f"in window/all: rounds {n('dispatch')}, scored batches "
            f"{n('score')}, generate calls {n('generate')}, completed "
            f"{by_close}/{len(win.completed)}, escalated legs {later_legs}, "
            f"cache hits {hits}")


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, peaks: dict,
             full_width: bool = True, mix_overrides: Optional[dict] = None,
             controls=(), check: bool = True,
             on_window: Optional[Callable] = None) -> dict:
    """One run: the result's fields, ready for the JSON line.

    ``t_start`` is the process's start on ``perf_counter``; ``peaks`` the
    chip's row of ``bench/peaks.json``. ``controls`` (names from
    ``correct.CONTROLS``) also reads each control on the same window,
    under ``"control"`` (limit setting, see ``bench/limits.py``; the
    benchmark's runs read none). A sweep
    (``bench/sweep.py``) passes ``check=False`` to skip the comparison and
    reads the window through ``on_window``."""
    import jax
    from jax import monitoring

    from bench import correct
    from bench import trace_reduce
    from repro.common.compile_cache import enable_compile_cache
    from repro.serving import MicroBatchScheduler, SchedulerConfig

    enable_compile_cache()
    spec = load_cell(root, workload)
    built = build(spec, seed, full_width=full_width, seconds=seconds,
                  mix_overrides=mix_overrides)
    if full_width:
        check_sizes(built)
    warm_up(built)

    clock = WallClock()
    rec = Recorder(clock)
    rec.instrument(built.engine)
    sched = MicroBatchScheduler(
        built.engine, SchedulerConfig(**spec.config["scheduler"]),
        clock=clock, service_time=None, cascade=built.cascade,
        semcache=built.semcache)
    trace_dir = os.path.join(root, "bench_out", "trace",
                             f"{workload}-{seed}")
    window_span = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window_span = rec.span("window")
        window_span.__enter__()

    def on_close():
        if window_span is not None:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    monitoring.register_event_duration_secs_listener(rec.on_duration)
    setup_s = time.perf_counter() - t_start
    try:
        with no_cache_writes():
            if built.mix["loop"] == "open":
                win = drive_open(sched, clock, rec, built.drafts, seconds,
                                 on_close)
            else:
                win = drive_closed(sched, clock, rec, built.drafts,
                                   int(built.mix["clients"]), seconds,
                                   on_close)
    finally:
        monitoring.unregister_event_duration_listener(rec.on_duration)
    if on_window is not None:
        on_window(win)
    peak = peak_bytes()
    cache_report = (built.semcache.report() if built.semcache is not None
                    else None)
    # Free the program's state before the reference runs; the weights
    # stay: they are the benchmark's own and the reference reads them.
    built.engine = sched = None
    built.semcache = built.cascade = None
    gc.collect()

    compare = functools.partial(
        correct.compare, members=built.ref_members, weights=built.weights,
        router=built.router_ref, lam=float(spec.config["router"]["lam"]),
        limits=spec.config["limits"], scores=rec.scores, gens=rec.gens,
        completed=win.completed, corpus_texts=built.train_texts, seed=seed)
    t_check = time.perf_counter()
    numbers = compare() if check else []
    control_numbers = {c: compare(control=c) for c in controls}
    check_s = time.perf_counter() - t_check

    reduced = None
    if trace:
        reduced = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir),
                                      window_s=seconds)
    run = types.SimpleNamespace(
        spec=spec, seed=seed, seconds=seconds, setup_s=setup_s, window=win,
        rec=rec, cache=cache_report, trace=reduced, members=built.ref_members,
        peaks=peaks, router_shapes=dict(
            d_query=built.router_ref["quality"]["wq"].shape[0],
            latent=built.router_ref["quality"]["wq"].shape[1],
            members=built.router_ref["model_emb"].shape[0]))
    dev = jax.devices()[0]
    metrics = {}
    for m in (spec.per_layer if trace else spec.end_to_end):
        value = load_reader(root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct.all_ok(numbers),
           "attempted": len(win.requests), "failed": win.failed,
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = {
            "device_ops": [list(kv) for kv in trace_reduce.top_ops(reduced)],
            "idle_gaps": [list(kv)
                          for kv in trace_reduce.gap_totals(reduced)[:10]]}
    if control_numbers:
        out["control"] = {k: correct.as_dict(v)
                          for k, v in control_numbers.items()}
    out["compared"] = correct.as_dict(numbers)
    out["_lines"] = ([f"bench: setup {setup_s:.1f} s, window {seconds:g} s, "
                      f"drain {win.t_end - win.t_close:.1f} s, check "
                      f"{check_s:.1f} s; {window_counts(rec, win)}"]
                     + correct.summary_lines(numbers))
    return out
