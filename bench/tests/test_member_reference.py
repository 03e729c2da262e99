"""A pool member joins the benchmark through files of its own: its file
under ``bench/members`` names its reference module under ``"reference"``,
and every part of the harness that needs the member's sizes or its
reference goes through that module (``bench.reference.for_member``).

The rehearsal here gives the members of ``pool2-route.mcq`` a reference
module that no file of the benchmark names, registered for the test
alone, which wraps ``decoder``'s four functions and counts their calls.
"""
import collections
import json
import os
import shutil
import sys
import time
import types

import jax.numpy as jnp
import pytest

from bench import harness, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WORKLOAD = "pool2-route.mcq"
SEED = 2**33 + 2**31 + 91
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# Eight requests, every one due within the window's first 10 ms: one
# dispatch round serves them all whatever the host's speed, so two runs
# of one seed do the same work and compare the same rows.
BURST = dict(mix_overrides={"rate_per_s": 800.0}, seconds=0.01)


def _checkout(tmp_path, reference_name):
    """The benchmark's data files in ``tmp_path``, the members' files
    naming ``reference_name`` (None: no "reference" key)."""
    tmp_path.mkdir(parents=True, exist_ok=True)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for part in ("configs", "members", "traffic", "metrics"):
        shutil.copytree(os.path.join(ROOT, "bench", part),
                        tmp_path / "bench" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for path in (tmp_path / "bench" / "members").glob("*.json"):
        member = json.loads(path.read_text())
        member.pop("reference")
        if reference_name is not None:
            member["reference"] = reference_name
        path.write_text(json.dumps(member))
    return str(tmp_path)


def _counting_decoder():
    """A module with ``decoder``'s four functions, each counting its
    calls; ``logits_at`` also keeps the dtype of each call."""
    from bench.reference import decoder

    mod = types.ModuleType("bench.reference.counted")
    mod.calls = collections.Counter()
    mod.dtypes = []
    for name in reference.MEMBER_FUNCTIONS:
        def call(*args, _name=name, _real=getattr(decoder, name), **kw):
            mod.calls[_name] += 1
            if _name == "logits_at":
                mod.dtypes.append(jnp.dtype(kw.get("dtype", jnp.float32)))
            return _real(*args, **kw)
        setattr(mod, name, call)
    return mod


def _run(root, reader_runs):
    """One untraced run of the burst; (result, the run record its metric
    readers were given)."""
    res = harness.run_cell(root, WORKLOAD, SEED, BURST["seconds"], False,
                           t_start=time.perf_counter(), peaks=PEAKS,
                           full_width=False,
                           mix_overrides=BURST["mix_overrides"],
                           controls=["bfloat16"])
    return res, reader_runs[-1]


def test_a_member_reference_module_of_its_own_serves_every_part(
        tmp_path, monkeypatch, reader_runs):
    counted = _counting_decoder()
    monkeypatch.setitem(sys.modules, "bench.reference.counted", counted)
    own, run = _run(_checkout(tmp_path / "own", "counted"), reader_runs)
    plain, _ = _run(_checkout(tmp_path / "plain", "decoder"), reader_runs)
    assert run.members[0]["reference"] == "counted"

    # The step's share of the peak counts each member's active parameters
    # through the member's module; the window is too short to hold a
    # generate call, so read it over the whole drive.
    whole = types.SimpleNamespace(**vars(run))
    whole.window = types.SimpleNamespace(**vars(run.window))
    whole.window.t_close = run.window.t_end
    mfu = harness.load_reader(str(tmp_path / "own"), "mfu_pct.open")(whole)
    assert mfu is not None and mfu > 0

    # Each member's smoke sizes, its size check, its logits for the
    # program and for the bfloat16 control, and its active parameters.
    assert counted.calls["smoke_member"] == 2
    assert counted.calls["check_sizes"] == 2
    assert counted.calls["n_active"] == 2
    assert counted.calls["logits_at"] >= 4
    assert set(counted.dtypes) == {jnp.dtype(jnp.float32),
                                   jnp.dtype(jnp.bfloat16)}
    assert own["correct"] is True
    assert own["attempted"] == 8
    assert own["compared"] == plain["compared"]
    assert own["control"] == plain["control"]


@pytest.mark.parametrize("name,why", [
    (None, "names no module"), ("", "names no module"),
    ("decoder.py", "names no module"), ("no_such_module", "no module"),
    ("router", "not a member reference")])
def test_a_missing_or_unknown_reference_is_refused(name, why, tmp_path):
    root = _checkout(tmp_path, name)
    with pytest.raises(ValueError, match=why) as e:
        harness.run_cell(root, WORKLOAD, SEED, 1.0, False,
                         t_start=time.perf_counter(), peaks=PEAKS,
                         full_width=False)
    assert "qwen3-0.6b" in str(e.value) and '"reference"' in str(e.value)


def test_a_module_without_the_four_functions_is_refused(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.reference.partial",
                        types.ModuleType("bench.reference.partial"))
    with pytest.raises(ValueError, match="lacks logits_at, n_active"):
        reference.for_member({"name": "m", "reference": "partial"})


@pytest.mark.parametrize("dtype,want", [("float32", jnp.float32),
                                        ("bfloat16", jnp.bfloat16)])
def test_weights_are_made_in_the_configuration_dtype(dtype, want):
    assert harness.weights_dtype({"dtype": dtype}) == jnp.dtype(want)


@pytest.mark.parametrize("dtype", [None, "float", "int8", 32])
def test_a_missing_or_unknown_dtype_is_an_error(dtype):
    spec = harness.load_cell(ROOT, WORKLOAD)
    spec.config = {k: v for k, v in spec.config.items() if k != "dtype"}
    if dtype is not None:
        spec.config["dtype"] = dtype
    with pytest.raises(ValueError, match='"dtype"'):
        harness.build(spec, SEED, full_width=False)
