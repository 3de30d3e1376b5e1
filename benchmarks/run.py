"""Benchmark driver: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--full] [--only name]``
prints ``name,us_per_call,derived`` CSV rows.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = (
    "table1_accuracy",
    "table2_efficiency",
    "fig7_precision_sweep",
    "fig8_variability",
    "fig9_mixed_mapping",
    "compiler_report",
    "kernel_bench",
    "serve_bench",
    "traffic_report",
    "calib_report",
    "silicon_report",
    "macro_report",
    "roofline_report",
    "obs_report",
)


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size sweeps (quick by default)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    print("name,us_per_call,derived")
    failures = 0
    for suite in SUITES:
        if args.only and args.only not in suite:
            continue
        mod = __import__(f"benchmarks.{suite}", fromlist=["run"])
        t0 = time.time()
        try:
            rows = mod.run(quick=not args.full)
        except Exception:                      # noqa: BLE001
            traceback.print_exc()
            print(f"{suite},0,SUITE_FAILED")
            failures += 1
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        print(f"{suite}__total,{(time.time() - t0) * 1e6:.0f},ok",
              flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
