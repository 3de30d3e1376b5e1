"""Readings behind a cell's correctness limits, on the chip, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1 2 3 --seconds 12 \
        [--control bf16] [--fault stale_state altered_token]

For each seed: serve the cell's traffic for ``--seconds`` through the
program, read the numbers ``correct`` compares (``harness.compare``,
with the candidates that it does not hold to a limit) and whether the
run's check (``harness.judge``) finds it correct; for each ``--control``
precision the same of the plain reference at that lower precision put in
the program's place, on the same served requests. Each ``--fault`` serves
the seed again with the timed path broken (``bench.system.fault``) and
reads the same. One JSON line per
reading on standard output; ``bench/run.py`` never runs these.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", nargs="*", default=[])
    ap.add_argument("--fault", nargs="*", default=[])
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from bench import harness

    enable_compile_cache()
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.load_cell(bench, args.workload, False)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 2
    for seed in args.seeds:
        for fault in [None] + args.fault:
            t0 = time.perf_counter()
            sv = harness.serve(cell, seed, args.seconds, False, t0, fault)
            t1 = time.perf_counter()
            reading = {"workload": args.workload, "seed": seed,
                       "fault": fault, "setup_s": sv.setup_s,
                       "checked_tokens": sum(len(r.tokens)
                                             for r in sv.sample),
                       "cached_positions": sum(len(q)
                                               for q in sv.kv["seqs"]),
                       "failed": sv.failed}
            for prec in [""] + (args.control if fault is None else []):
                numbers = harness.compare(cell, sv, prec)
                reading[prec or "program"] = {
                    "correct": harness.is_correct(
                        harness.judge(cell, numbers, sv.failed)),
                    "numbers": numbers}
                if not prec:
                    reading["reference_s"] = time.perf_counter() - t1
            reading["device"] = dev.device_kind
            print(json.dumps(reading), flush=True)
            del sv
    return 0


if __name__ == "__main__":
    sys.exit(main())
