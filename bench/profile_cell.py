"""Run one cell traced, with the program's layer profiler installed.

    python3 bench/profile_cell.py --workload <name> --seed <n> --seconds <s>

The same run as ``bench/run.py ... --trace 1``, and the same lines out,
with the program's ``LayerProfiler`` installed from the end of the
warm-up to the end of the drive, so the traced window also holds the
program's own ``repro.*`` spans. Its result line adds ``"program"``: the
per-layer numbers of ``bench.program_spans.readings`` (prefill seconds a
generate call, decode seconds a step, and the share of generate and of
scoring spent compiling) and the compiles charged to each span name in
the window; one more standard-error line names the device's idle time by
program span (``compile in repro.lm.decode_step``, ...) and its busy time
inside each span name.

Set beside the parent's ``bench/run.py --trace 1`` on the same seed, the
window counts, ``generate_s_per_call.*`` and ``score_ms_per_batch*`` say
what the profiler costs when it is on.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402


def profiled_run(root: str, workload: str, seed: int, seconds: float, *,
                 trace: bool = True, **run_cell_kw):
    """``harness.run_cell`` with the layer profiler on from the warm-up's
    end to the drive's: (the result, with ``"program"`` added; a record
    of the program's spans on the window's clock and of the window)."""
    from bench import harness, program_spans
    from repro.common import profile_slot
    from repro.obs.profiling import LayerProfiler

    prof = LayerProfiler()
    clocks, wins = [], []
    warm_up, wall_clock = harness.warm_up, harness.WallClock

    def warm_up_then_install(built):
        warm_up(built)
        profile_slot.install(prof)

    class Clock(wall_clock):
        def __init__(self):
            super().__init__()
            clocks.append(self)

    def on_window(win):
        profile_slot.install(None)
        wins.append(win)

    # run_cell builds, warms up and drives the cell through these module
    # names.
    harness.warm_up, harness.WallClock = warm_up_then_install, Clock
    try:
        result = harness.run_cell(root, workload, seed, seconds, trace,
                                  on_window=on_window, **run_cell_kw)
    finally:
        profile_slot.install(None)
        harness.warm_up, harness.WallClock = warm_up, wall_clock

    t0 = clocks[0].t0
    rec = types.SimpleNamespace(
        program_spans=[(n, a - t0, b - t0, args)
                       for n, a, b, args in prof.spans],
        window=wins[0])
    compiles: dict = {}
    for name, a, b, args in rec.program_spans:
        if a >= 0.0 and b <= rec.window.t_close and args.get("compiles"):
            c = compiles.setdefault(name, [0, 0.0])
            c[0] += args["compiles"]
            c[1] += args["compile_s"]
    result["program"] = {"readings": program_spans.readings(rec),
                         "compiles_by_span": compiles}
    return result, rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    peak, rc = run.prepare(args.workload)
    if rc is not None:
        return rc
    from bench import program_spans, trace_reduce

    result, _ = profiled_run(run.ROOT, args.workload, args.seed,
                             args.seconds, t_start=T_START, peaks=peak)
    trace_dir = os.path.join(run.ROOT, "bench_out", "trace",
                             f"{args.workload}-{args.seed}")
    pt = program_spans.collect(trace_reduce.find_xplane(trace_dir),
                               window_s=args.seconds)
    result["_lines"].insert(1, program_spans.line(pt))
    run.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
