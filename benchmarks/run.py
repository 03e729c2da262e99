"""Benchmark harness entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run table2     # one table

Prints ``name,us_per_call,derived`` CSV (see benchmarks/common.py) and
writes a machine-readable ``BENCH_<suite>.json`` per suite (headline
metric, gate pass/fail, wall time, every emitted row) under
``$REPRO_BENCH_OUT`` (default reports/bench). A suite that raises still
gets its JSON (with the ``error`` field set) before the runner exits
non-zero. Env: REPRO_BENCH_QUERIES (default 4000), REPRO_BENCH_EPOCHS
(default 300; paper uses 1000), REPRO_BENCH_CACHE, REPRO_BENCH_OUT.
"""
from __future__ import annotations

import os
import sys
import time

from benchmarks.common import BenchReport, set_active_report
from repro.common.compile_cache import enable_compile_cache

from benchmarks import (
    cascade_bench,
    distributed_bench,
    fig4_5_domains,
    fig6_distribution,
    kernel_bench,
    online_bench,
    roofline,
    serving_bench,
    table1_rewards,
    table2_routers,
    table3_6_ablation,
)

SUITES = {
    "table1": table1_rewards.main,
    "table2": table2_routers.main,
    "table3_6": table3_6_ablation.main,
    "fig4_5": fig4_5_domains.main,
    "fig6": fig6_distribution.main,
    "kernels": kernel_bench.main,
    "roofline": roofline.main,
    "serving": serving_bench.main,
    "online": online_bench.main,
    "distributed": distributed_bench.main,
    "cascade": cascade_bench.main,
}


OUT_DIR = os.environ.get("REPRO_BENCH_OUT", "reports/bench")


def main() -> None:
    enable_compile_cache()
    selected = sys.argv[1:] or list(SUITES)
    print("name,us_per_call,derived")
    failures = []
    for name in selected:
        if name not in SUITES:
            raise SystemExit(f"unknown suite {name!r}; choose from {list(SUITES)}")
        report = BenchReport(name)
        set_active_report(report)
        t0 = time.time()
        try:
            SUITES[name]()
        except Exception as e:  # noqa: BLE001 — recorded, then re-raised
            report.error = f"{type(e).__name__}: {e}"
            failures.append(name)
        finally:
            report.wall_s = time.time() - t0
            set_active_report(None)
            report.save(os.path.join(OUT_DIR, f"BENCH_{name}.json"))
        if report.error is None and any(
                not g["passed"] for g in report.gates):
            failures.append(name)
        print(f"# suite {name} done in {report.wall_s:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmark failures in: {sorted(set(failures))}")


if __name__ == "__main__":
    main()
