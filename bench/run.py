"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for. The cell (``BENCHMARK.json`` workload) is built from its
files, warmed up, driven for ``--seconds`` on the wall clock, and what the
program produced is compared with the plain references. ``--trace 0``
reports the cell's end-to-end metrics; ``--trace 1`` profiles the window
and reports its per-layer metrics, the device's busy time and a
breakdown.

The last lines on standard error are the numbers compared, each with its
limit; the last line on standard output is one JSON object. Without a
TPU, with a device kind that ``bench/peaks.json`` does not know, or with
fewer chips than the cell asks for, it exits non-zero before any work and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def emit(result: dict) -> None:
    lines = result.pop("_lines")
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def prepare(workload: str):
    """Put the checkout on the path, load the cell and check the device:
    ``(peaks row, None)``, or ``(None, exit code)`` after saying why."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return None, fail("the program under test (src/repro) is not in "
                          "this checkout")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, peaks

    try:
        spec = harness.load_cell(ROOT, workload)
    except (KeyError, FileNotFoundError) as e:
        return None, fail(str(e))
    import jax

    devices = jax.devices()
    dev = devices[0]
    try:
        peak = peaks.lookup(ROOT, dev.device_kind, dev.platform)
    except peaks.UnknownDevice as e:
        return None, fail(str(e))
    if len(devices) < spec.chips:
        return None, fail(f"{spec.name} needs {spec.chips} chips, JAX found "
                          f"{len(devices)}")
    return peak, None


def main(argv=None) -> int:
    args = parse(argv)
    peak, rc = prepare(args.workload)
    if rc is not None:
        return rc
    from bench import harness

    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START, peaks=peak)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
