"""The benchmark's tests import ``bench`` from the checkout root and the
program from ``src``. No test here touches a TPU when it is imported."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def reader_runs(monkeypatch):
    """The run records that ``harness.run_cell`` hands its metric
    readers, in the order it reads them."""
    from bench import harness

    runs = []
    load_reader = harness.load_reader

    def recording_reader(root, metric):
        read = load_reader(root, metric)

        def read_and_keep(run):
            runs.append(run)
            return read(run)
        return read_and_keep

    monkeypatch.setattr(harness, "load_reader", recording_reader)
    return runs
