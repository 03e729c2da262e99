"""A whole run of one cell on the CPU at the program's smoke widths,
through the harness's internal entry (``bench/run.py`` itself refuses the
CPU): the result's schema, the metric arithmetic, the comparison with the
plain references, the control that has to fail it, and faults planted in
the timed path that have to fail it too."""
import os
import time

import numpy as np
import pytest

from bench import correct, harness
from bench.reference import for_member

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

WORKLOAD = "pool2-route.mcq"
SEED = 2**33 + 2**31 + 77
SECONDS = 4.0
# Smoke widths, so no peak is reached; the numbers only feed arithmetic.
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MIX = {"rate_per_s": 2.0}


def _run(mix=MIX, seconds=SECONDS, **kw):
    wins = []
    res = harness.run_cell(ROOT, WORKLOAD, SEED, seconds, False,
                           t_start=time.perf_counter(), peaks=PEAKS,
                           full_width=False, mix_overrides=mix,
                           on_window=wins.append, **kw)
    return res, wins[0]


@pytest.fixture(scope="module")
def drive():
    """The rehearsal, and the profiler installed at each generate call of
    its warm-up and drive."""
    from repro.common import profile_slot
    from repro.serving import engine

    installed = []
    real = engine.RoutedEngine.generate_member

    def generate_member(self, *args, **kw):
        installed.append(profile_slot.active())
        return real(self, *args, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine.RoutedEngine, "generate_member", generate_member)
        res, win = _run(controls=["bfloat16"])
    return res, win, installed


@pytest.fixture(scope="module")
def rehearsal(drive):
    return drive[:2]


def test_an_untraced_run_installs_no_profiler(drive):
    res, win, installed = drive
    # One warm-up call per member, then the window's calls.
    assert len(installed) > 2
    assert set(installed) == {None}


def test_result_has_the_result_line_keys(rehearsal):
    res, win = rehearsal
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-2:] == ["compared", "_lines"]
    assert set(res["metrics"]) == {"setup_s", "e2e_p50_s", "e2e_p90_s"}
    assert all(m["unit"] == "s" for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert res["attempted"] == len(win.requests) == 8
    assert res["failed"] == 0


def test_the_program_compares_correct(rehearsal):
    res, _ = rehearsal
    assert res["correct"] is True
    for name, n in res["compared"].items():
        assert n["value"] <= n["limit"], name
    assert len(res["_lines"]) == len(res["compared"]) + 1
    assert [ln.split()[0] for ln in res["_lines"][1:]] == list(
        res["compared"])


def test_latency_percentiles_are_of_every_request_due(rehearsal):
    res, win = rehearsal
    lat = [r.finish_s - r.arrival_s for r in win.requests]
    assert res["metrics"]["e2e_p50_s"]["value"] == pytest.approx(
        np.percentile(lat, 50))
    assert res["metrics"]["e2e_p90_s"]["value"] == pytest.approx(
        np.percentile(lat, 90))
    assert res["metrics"]["setup_s"]["value"] > 0


def test_the_control_fails_the_comparison(rehearsal):
    res, _ = rehearsal
    control = res["control"]["bfloat16"]
    assert any(n["value"] > n["limit"] for n in control.values())


def test_active_parameters_follow_the_published_sizes():
    spec = harness.load_cell(ROOT, WORKLOAD)
    qwen, granite = spec.members
    # Qwen3-0.6B: 28 x (attention 1024x(2048+1024+1024) + 2048x1024, SwiGLU
    # 3x1024x3072) + LM head 1024 x 151936.
    assert for_member(qwen).n_active(qwen) == 28 * (
        1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024
        + 3 * 1024 * 3072) + 1024 * 151936
    # granite: 24 x (attention: 16 query heads of 64 and 8 key/value
    # heads, so 3 x 1024^2 + router 1024x32 + 8 experts of 3 x 1024 x 512)
    # + head 1024 x 49155.
    assert for_member(granite).n_active(granite) == 24 * (
        3 * 1024 * 1024 + 1024 * 32 + 8 * 3 * 1024 * 512) + 1024 * 49155


def _alter_first_token(monkeypatch):
    from repro.serving import engine

    real = engine.PoolMember.generate

    def generate(self, prompts, max_new=8, attn_mask=None):
        toks = np.array(real(self, prompts, max_new, attn_mask))
        toks[0, 0] = (toks[0, 0] + 1) % self.cfg.vocab_size
        return toks

    monkeypatch.setattr(engine.PoolMember, "generate", generate)


def _shift_scores(monkeypatch):
    from repro.serving import engine

    real = engine.RoutedEngine._scores

    def scores(self, q_emb):
        s, c = real(self, q_emb)
        return np.asarray(s) * 1.01, c

    monkeypatch.setattr(engine.RoutedEngine, "_scores", scores)


@pytest.mark.parametrize("plant", [_alter_first_token, _shift_scores],
                         ids=["token_altered", "score_altered"])
def test_a_fault_in_the_timed_path_fails_the_comparison(plant, monkeypatch):
    plant(monkeypatch)
    res, _ = _run()
    assert res["correct"] is False
    assert not correct.all_ok([correct.Number(k, v["value"], v["limit"])
                               for k, v in res["compared"].items()])


def _alter_cached_answer(monkeypatch):
    from repro.serving import semcache

    real = semcache.SemanticCache.admit

    def admit(self, emb, *, output, **kw):
        wrong = np.array(output)
        wrong[0] += 1
        return real(self, emb, output=wrong, **kw)

    monkeypatch.setattr(semcache.SemanticCache, "admit", admit)


@pytest.mark.parametrize("plant", [None, _alter_cached_answer],
                         ids=["program", "cached_answer_altered"])
def test_cache_hits_are_held_to_the_answer_generated(plant, monkeypatch):
    """A text repeated after its first copy was answered is served from
    the semantic cache (at these sizes a generate call takes seconds on
    the CPU, hence the longer window); a hit whose answer is not the one
    generated for its text fails the comparison."""
    if plant is not None:
        plant(monkeypatch)
    res, win = _run(mix={"rate_per_s": 0.4, "repeat_frac": 0.5,
                         "hot_set": 1}, seconds=20.0)
    assert sum(r.leg == 0 for r in win.completed) > 0
    mismatch = res["compared"]["cache_mismatch"]["value"]
    assert (mismatch == 0) is (plant is None)
    assert res["correct"] is (plant is None)
