"""The program's layer spans as the benchmark reads them: the per-layer
numbers on a synthetic run record, gap naming on synthetic intervals, a
recorded v5e trace with ``repro.*`` spans (``record_program_trace.py``),
and a CPU rehearsal of a traced ``harness.run_cell``, which installs the
profiler."""
import os
import time
import types

import jax
import pytest

from bench import harness
from bench import program_spans as ps

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "program_trace.xplane.pb")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _reader(metric):
    return harness.load_reader(ROOT, metric)


def _record(spans, close=10.0):
    return types.SimpleNamespace(
        program_spans=spans, window=types.SimpleNamespace(t_close=close))


# Two generate calls and two scoring batches inside a 10 s window, one
# call cut by the close and one span from before the window opened.
SPANS = [
    ("repro.engine.score", -2.0, -1.0, {"n": 8, "path": "kernel",
                                        "compile_s": 9.0, "compiles": 1}),
    ("repro.kernels.router_xattn_pool", 0.6, 0.8,
     {"n": 64, "compiles": 1, "compile_s": 0.2}),
    ("repro.engine.score", 0.5, 0.9, {"n": 64, "path": "kernel"}),
    ("repro.lm.prefill", 1.1, 2.1, {"n": 8, "length": 800, "compiles": 2,
                                    "compile_s": 0.6}),
    *[("repro.lm.decode_step", 2.1 + 0.6 * i, 2.6 + 0.6 * i,
       {"i": i, "compiles": 1, "compile_s": 0.3}) for i in range(3)],
    ("repro.lm.decode", 2.1, 3.9, {"n": 8, "steps": 3}),
    ("repro.engine.generate", 1.0, 4.0, {"member": "qwen3-0.6b", "n": 8,
                                         "length": 800, "max_new": 4,
                                         "compiles": 1, "compile_s": 0.5}),
    ("repro.engine.score", 4.5, 4.6, {"n": 64, "path": "kernel"}),
    ("repro.lm.prefill", 5.1, 5.6, {"n": 2, "length": 300}),
    ("repro.lm.decode", 5.6, 6.9, {"n": 2, "steps": 3}),
    ("repro.engine.generate", 5.0, 7.0, {"member": "granite-moe-1b-a400m",
                                         "n": 2, "length": 300,
                                         "max_new": 4}),
    ("repro.lm.prefill", 9.0, 11.0, {"n": 8, "length": 800}),
]

EXPECTED = {
    "prefill_s_per_call": (1.0 + 0.5) / 2,
    "decode_s_per_step": (1.8 + 1.3) / 6,
    # 0.5 on generate itself, 0.6 on its prefill, 3 x 0.3 on its steps,
    # over 3 + 2 s of generate.
    "generate_compile_pct": 100.0 * (0.5 + 0.6 + 0.9) / 5.0,
    # 0.2 s in the kernel span inside the first batch, over 0.4 + 0.1 s.
    "score_compile_pct": 100.0 * 0.2 / 0.5,
}


@pytest.mark.parametrize("cell", ["open", "batch"])
@pytest.mark.parametrize("stem", sorted(EXPECTED))
def test_readings_on_a_synthetic_record(stem, cell):
    # A cell's metric is <stem>.<cell>; both read the same arithmetic.
    got = _reader(f"{stem}.{cell}")(_record(SPANS))
    assert got == pytest.approx(EXPECTED[stem])


def test_no_spans_read_nothing():
    # A program without the profiler, or a run that did not install it.
    for rec in (types.SimpleNamespace(window=types.SimpleNamespace(
            t_close=10.0)), _record([])):
        assert {_reader(f"{stem}.{cell}")(rec) for stem in EXPECTED
                for cell in ("open", "batch")} == {None}


def test_gaps_go_to_the_innermost_span_and_to_its_compiles():
    spans = [(0.0, 10.0, "repro.sched.round"),
             (1.0, 9.0, "repro.engine.generate"),
             (1.0, 3.0, "repro.lm.prefill"),
             (3.0, 9.0, "repro.lm.decode"),
             (3.0, 5.2, "repro.lm.decode_step"),
             (5.2, 9.0, "repro.lm.decode_step")]
    device = [(0.0, 1.0), (2.5, 3.1), (5.5, 6.0), (9.0, 12.0)]
    # Compiles under the prefill's gap and the second step's.
    compiles = [(1.0, 2.4), (5.3, 5.5), (6.1, 8.0)]
    pt = ps.attribute([device], spans, compiles, (0.0, 12.0))
    assert pt.idle == pytest.approx({
        "compile in repro.lm.prefill": 1.5,          # [1.0, 2.5]
        # [3.1, 5.5]: mostly the first step's, compiles 0.2 of 2.4 s.
        "repro.lm.decode_step": 2.4,
        "compile in repro.lm.decode_step": 3.0,      # [6.0, 9.0]
    })
    assert pt.busy["repro.lm.prefill"] == pytest.approx(0.5)
    assert pt.busy["repro.lm.decode"] == pytest.approx(0.1 + 0.5)
    assert pt.busy["repro.sched.round"] == pytest.approx(1.0 + 0.6 + 0.5
                                                         + 1.0)
    assert sum(pt.idle.values()) + 5.1 == pytest.approx(12.0)
    assert "round" in ps.line(pt) and "compile in" in ps.line(pt)


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(DATA):
        pytest.fail(f"missing recorded trace {DATA}")
    return ps.collect(DATA)


def test_recorded_program_spans_share_the_device_clock(recorded):
    # Two generate calls (prefill + decode), one kernel scoring pass.
    for name in ("repro.engine.generate", "repro.lm.prefill",
                 "repro.lm.decode", "repro.engine.score"):
        assert recorded.busy.get(name, 0.0) > 0.0, name
    assert (recorded.busy["repro.engine.generate"]
            >= recorded.busy["repro.lm.decode"])
    assert recorded.busy["repro.engine.score"] >= recorded.busy.get(
        "repro.kernels.router_xattn_pool", 0.0)
    window = recorded.window[1] - recorded.window[0]
    assert 0.2 < window < 60.0
    assert sum(recorded.idle.values()) < window


def test_recorded_idle_time_names_program_spans_and_compiles(recorded):
    labels = set(recorded.idle)
    # The first call compiles its prefill and its decode steps on the
    # host while the device waits; the sleep lies outside every span.
    assert any(k.startswith("compile in repro.lm.") for k in labels)
    assert recorded.idle["host"] == pytest.approx(0.2, abs=0.05)


def test_profiled_rehearsal_reads_every_layer_and_stays_correct(
        reader_runs):
    # Compile anew whatever an earlier test in this process compiled, so
    # the window's compile shares read as in a fresh process.
    jax.clear_caches()
    res = harness.run_cell(
        ROOT, "pool2-route.mcq", 2**33 + 2**31 + 78, 4.0, True,
        t_start=time.perf_counter(),
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        full_width=False, mix_overrides={"rate_per_s": 2.0})
    # Tokens made with the profiler on still match the reference.
    assert res["correct"] is True
    run = reader_runs[-1]
    assert run.program_spans
    # At smoke widths on the CPU a generate call outlasts the window: read
    # every span of the drive, through the eight metric readers.
    whole = _record(run.program_spans, close=run.window.t_end)
    readings = {f"{stem}.{cell}": _reader(f"{stem}.{cell}")(whole)
                for stem in EXPECTED for cell in ("open", "batch")}
    assert all(v is not None and v > 0 for v in readings.values()), \
        readings
    assert readings["generate_compile_pct.open"] <= 100
    assert all(readings[f"{stem}.open"] == readings[f"{stem}.batch"]
               for stem in EXPECTED)
    names = {s[0] for s in run.program_spans}
    assert {"repro.sched.round", "repro.engine.embed", "repro.engine.score",
            "repro.engine.generate", "repro.lm.prefill", "repro.lm.decode",
            "repro.lm.decode_step"} <= names
