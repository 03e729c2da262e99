"""Per-chip peaks, keyed by the ``device_kind`` JAX reports.

The table is ``bench/peaks.json`` with its source. A kind that is not in
it is an error: a share of a peak is never computed against a guess.
"""
from __future__ import annotations

import json
import os


class UnknownDevice(Exception):
    pass


def lookup(root: str, kind: str, platform: str) -> dict:
    if platform != "tpu":
        raise UnknownDevice(f"platform {platform!r} is not a TPU; the "
                            f"benchmark measures the chip only")
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["kinds"]:
        raise UnknownDevice(f"device kind {kind!r} is not in bench/peaks.json"
                            f" (have {sorted(table['kinds'])})")
    return dict(table["kinds"][kind], source=table["source"])
