"""The benchmark measures the chip only: a platform that is not a TPU, a
device kind that the peaks table does not know, or a checkout without the
program makes it exit non-zero with no result line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import peaks, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "pool2-route.mcq", "--seed", str(2**33 + 5),
        "--seconds", "1", "--trace", "0"]


def test_peaks_table_has_v5e_with_its_source():
    row = peaks.lookup(ROOT, "TPU v5 lite", "tpu")
    assert row["flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in row["source"]


@pytest.mark.parametrize("kind,platform", [("TPU v9 imaginary", "tpu"),
                                           ("cpu", "cpu"),
                                           ("TPU v5 lite", "gpu")])
def test_unknown_kind_or_platform_is_an_error(kind, platform):
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup(ROOT, kind, platform)


def test_run_refuses_the_cpu_and_prints_no_result(capsys):
    assert run.main(ARGS) != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "not a TPU" in err


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", *ARGS],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


def test_unknown_workload_is_refused(capsys):
    args = list(ARGS)
    args[1] = "no-such-cell"
    assert run.main(args) != 0
    assert capsys.readouterr().out == ""


def test_result_line_holds_its_keys_with_compared_last(capsys):
    result = {"correct": True, "attempted": 3, "failed": 0,
              "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1, "memory_peak_bytes": 1},
              "compared": {"score_err": {"value": 0.0, "limit": 1e-4}},
              "_lines": ["score_err 0.0 limit 0.0001"]}
    run.emit(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert err.strip().splitlines()[-1] == "score_err 0.0 limit 0.0001"


def test_benchmark_json_names_a_file_for_every_piece():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    for c in bm["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in bm["workloads"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in bm["end_to_end"])
