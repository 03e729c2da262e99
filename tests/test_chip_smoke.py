"""chip_smoke.py: it refuses to pass without a TPU or without the repo,
and its phases pass on the CPU at smoke widths (the platform check is
steered here, through ``run``'s arguments, never by a script option)."""
import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_script(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_without_a_tpu():
    r = _run_script(ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "not 'tpu'" in r.stderr


def test_exits_nonzero_alone_in_a_directory(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    r = _run_script(tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_phases_pass_at_smoke_widths(chip_smoke, tmp_path, monkeypatch,
                                     capsys):
    # serve.main's compile-cache helper then leaves JAX's config alone.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    device = chip_smoke.run(platform="cpu", full_width=False)
    out = capsys.readouterr().out
    assert device == {"platform": "cpu", "kind": "cpu", "count": 1}
    lines = [ln for ln in out.splitlines() if ln[:3] in
             ("(a)", "(b)", "(c)", "(d)")]
    assert [ln[:3] for ln in lines] == ["(a)", "(b)", "(c)", "(d)"]
    assert f"completed {chip_smoke.REQUESTS}/{chip_smoke.REQUESTS}" in out


def test_tpu_check_is_enforced(chip_smoke):
    with pytest.raises(SystemExit, match="not 'tpu'"):
        chip_smoke.run()
