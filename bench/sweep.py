"""Find the highest rate an open-loop cell sustains, by a sweep.

    python3 bench/sweep.py --workload pool2-route.mcq --seed <n> --seconds 102 --rates 0.2,0.3,0.4

Runs the cell once per rate in one process (each with its whole set-up,
the compile cache shared) and prints one JSON line per rate: requests
due, those done by the window's close, the backlog at the close, and the
latency percentiles of the requests due in the window's first and second
half. A window of several dispatch rounds shows whether the queue grows.

A rate is sustained (``"sustained": true``) when the queue does not
grow: the median latency of the second half is at most SLACK times the
first half's, and the backlog at the close is at most SLACK times what
a steady queue holds by Little's law (the rate times the first half's
median latency). The traffic file then states four fifths of the
highest rate sustained, as a number.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402

SLACK = 1.25


def summary(win, rate: float) -> dict:
    import numpy as np

    half = win.t_close / 2
    lat = {k: [] for k in ("first", "second")}
    for r in win.requests:
        end = r.finish_s if r.status == "done" else win.t_end
        lat["first" if r.arrival_s < half else "second"].append(
            end - r.arrival_s)
    out = {"rate_per_s": rate, "due": len(win.requests),
           "done_by_close": sum(r.status == "done"
                                and r.finish_s <= win.t_close
                                for r in win.requests),
           "failed": win.failed, "drain_s": win.t_end - win.t_close}
    out["backlog_at_close"] = out["due"] - out["done_by_close"]
    for k, v in lat.items():
        if v:
            out[f"p50_{k}_s"] = float(np.percentile(v, 50))
            out[f"p90_{k}_s"] = float(np.percentile(v, 90))
    if lat["first"] and lat["second"]:
        out["sustained"] = bool(
            out["p50_second_s"] <= SLACK * out["p50_first_s"]
            and out["backlog_at_close"]
            <= SLACK * rate * out["p50_first_s"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args(argv)
    peak, rc = run.prepare(args.workload)
    if rc is not None:
        return rc
    from bench import harness

    for rate in (float(x) for x in args.rates.split(",")):
        wins = []
        harness.run_cell(run.ROOT, args.workload, args.seed, args.seconds,
                         False, t_start=time.perf_counter(), peaks=peak,
                         mix_overrides={"rate_per_s": rate},
                         on_window=wins.append)
        print(json.dumps(summary(wins[0], rate)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
