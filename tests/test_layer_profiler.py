"""The layer profiler and its one slot: spans nest and take the compiles
of the innermost span open; with nothing installed the instrumented sites
make the plain call; with it installed generate is split into prefill and
decode without changing a token, and a serving round records every
layer's span."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import profile_slot
from repro.configs import get_smoke_config
from repro.core.model_repr import N_CLUSTERS
from repro.core.predictors import PREDICTORS
from repro.core.router import PredictiveRouter
from repro.data.featurizer import EMB_DIM
from repro.models import lm as lm_mod
from repro.obs import LayerProfiler, TraceRecorder
from repro.serving import (MicroBatchScheduler, PoolMember, Request,
                           RoutedEngine, SchedulerConfig, SemanticCache)

MAX_NEW = 4
LAYER_SPANS = {"repro.sched.round", "repro.sched.cache_rung",
               "repro.engine.embed", "repro.engine.score",
               "repro.engine.generate", "repro.lm.prefill",
               "repro.lm.decode", "repro.lm.decode_step"}


@pytest.fixture
def installed():
    prof = LayerProfiler(tracer=TraceRecorder())
    profile_slot.install(prof)
    try:
        yield prof
    finally:
        profile_slot.install(None)


@pytest.fixture(scope="module")
def member():
    cfg = dataclasses.replace(get_smoke_config("qwen3-0.6b"), n_layers=2)
    params = lm_mod.init_lm(jax.random.key(0), cfg)
    return PoolMember(name=cfg.name, cfg=cfg, params=params,
                      quality_profile=None, cost_rate=1e-4)


def _prompt(b=2, s=12, vocab=64):
    return jnp.asarray(np.random.default_rng(0).integers(
        1, vocab, (b, s)), jnp.int32)


def _scheduler(member):
    key = jax.random.key(1)
    qp = PREDICTORS["attn"].init(key, EMB_DIM, 1, N_CLUSTERS)
    cp = PREDICTORS["attn"].init(jax.random.fold_in(key, 1), EMB_DIM, 1,
                                 N_CLUSTERS)
    router = PredictiveRouter("attn", "attn", qp, cp,
                              np.ones((1, N_CLUSTERS), np.float32))
    engine = RoutedEngine(router=router, pool=[member])
    return MicroBatchScheduler(
        engine, SchedulerConfig(score_batch=8, max_batch=4),
        semcache=SemanticCache(1e-6, cap=8))


def _requests(n=3):
    rng = np.random.default_rng(2)
    return [Request(text=f"question {i} about routing",
                    prompt=rng.integers(1, 64, 6 + i).astype(np.int32),
                    max_new=MAX_NEW, arrival_s=0.0) for i in range(n)]


def test_spans_nest_and_a_compile_goes_to_the_innermost():
    prof = LayerProfiler()
    f = jax.jit(lambda x: jnp.sin(x) * 3.0)
    profile_slot.install(prof)
    try:
        with prof.span("outer", tag=1) as outer:
            with prof.span("inner") as inner:
                # A fresh shape, from host memory: one compile, no other.
                jax.block_until_ready(f(np.ones((3, 11), np.float32)))
            with prof.span("again") as again:
                jax.block_until_ready(f(np.ones((3, 11), np.float32)))
    finally:
        profile_slot.install(None)
    assert inner["compiles"] == 1 and inner["compile_s"] > 0
    assert "compiles" not in outer and "compiles" not in again
    assert prof.compiles == {"inner": 1}
    assert prof.totals()["compiles"] == 1
    (n1, a1, b1, _), (n2, a2, b2, _), (n3, a3, b3, args3) = prof.spans
    assert (n1, n2, n3) == ("inner", "again", "outer")
    assert a3 <= a1 <= b1 <= a2 <= b2 <= b3 and args3 == {"tag": 1}
    # Uninstalled, the profiler no longer hears compiles.
    jax.block_until_ready(f(np.ones((5, 13), np.float32)))
    assert prof.totals()["compiles"] == 1


def test_uninstalled_sites_make_the_plain_call(member, monkeypatch):
    assert profile_slot.active() is None

    def forbidden(*a, **k):
        raise AssertionError("instrumentation ran with nothing installed")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    monkeypatch.setattr(jax, "block_until_ready", forbidden)
    toks = lm_mod.greedy_generate(member.cfg, member.params, _prompt(),
                                  MAX_NEW)
    assert toks.shape == (2, MAX_NEW)
    sched = _scheduler(member)
    reqs = _requests()
    summary = sched.run_trace(reqs)
    assert summary["completed"] == len(reqs)


def test_profiled_generate_is_bitwise_equal_and_split(member, installed):
    prompt = _prompt()
    profile_slot.install(None)
    plain = np.asarray(lm_mod.greedy_generate(member.cfg, member.params,
                                              prompt, MAX_NEW))
    profile_slot.install(installed)
    profiled = np.asarray(lm_mod.greedy_generate(member.cfg, member.params,
                                                 prompt, MAX_NEW))
    np.testing.assert_array_equal(profiled, plain)
    names = [s[0] for s in installed.spans]
    assert names.count("repro.lm.prefill") == 1
    assert names.count("repro.lm.decode") == 1
    # One compiled loop runs every step: one wait for its steps, no span
    # a step.
    assert names.count("repro.lm.decode_step") == 1
    by = {}
    for name, a, b, args in installed.spans:
        by.setdefault(name, []).append((a, b, args))
    (pa, pb, pargs), = by["repro.lm.prefill"]
    (da, db, dargs), = by["repro.lm.decode"]
    assert pargs["n"] == 2 and pargs["length"] == 12
    assert dargs["steps"] == MAX_NEW - 1 and pb <= da
    assert dargs["cache_len"] == lm_mod.cache_len(12, MAX_NEW) == 256
    (sa, sb, sargs), = by["repro.lm.decode_step"]
    assert sargs == {"steps": MAX_NEW - 1} and da <= sa <= sb <= db


def test_a_profiled_round_records_every_layer(member, installed):
    sched = _scheduler(member)
    reqs = _requests()
    summary = sched.run_trace(reqs)
    assert summary["completed"] == len(reqs)
    assert LAYER_SPANS <= set(installed.calls)
    rounds = [(a, b) for n, a, b, _ in installed.spans
              if n == "repro.sched.round"]
    gens = [(a, b, args) for n, a, b, args in installed.spans
            if n == "repro.engine.generate"]
    assert all(any(ra <= a <= b <= rb for ra, rb in rounds)
               for a, b, _ in gens)
    assert all(args["member"] == member.name and args["max_new"] == MAX_NEW
               for *_, args in gens)
    score = [args for n, *_, args in installed.spans
             if n == "repro.engine.score"]
    assert score and all(args["path"] == "jnp" for args in score)
    # Every span also went to the tracer, in a wall-clock category.
    cats = {e[1] for e in installed.tracer.events}
    assert cats == {"layer"}
    assert len(installed.tracer.events) == len(installed.spans)
