"""Readings that the limits of ``correct`` are set from.

    python3 bench/limits.py --workload <name> --seeds <a,b,...> --seconds <s> [--controls bfloat16,float8_e4m3fn,token_altered]

Runs the cell once per seed in one process, as ``run.py`` does, and
prints one JSON line per seed with every number compared: the
program's (``compared``) and, with ``--controls``, each control's on
the same window (``control``): the plain references computed in that
dtype in the program's place, or the fault ``token_altered`` (see
``bench/correct.py``). A limit lies above the largest of the program's
readings and below the smallest of the control's (see PERF.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="",
                    help="comma-separated names from correct.CONTROLS")
    args = ap.parse_args(argv)
    peak, rc = run.prepare(args.workload)
    if rc is not None:
        return rc
    from bench import harness

    controls = [c for c in args.controls.split(",") if c]
    for seed in (int(x) for x in args.seeds.split(",")):
        res = harness.run_cell(run.ROOT, args.workload, seed, args.seconds,
                               False, t_start=time.perf_counter(),
                               peaks=peak, controls=controls)
        print(json.dumps({"seed": seed, "attempted": res["attempted"],
                          "metrics": res["metrics"],
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"],
                          "compared": res["compared"],
                          "control": res.get("control"),
                          "window": res["_lines"][0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
