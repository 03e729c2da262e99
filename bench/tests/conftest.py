"""The benchmark's tests import ``bench`` from the checkout root and the
program from ``src``. No test here touches a TPU when it is imported."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
