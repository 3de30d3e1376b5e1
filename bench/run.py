"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the result as one JSON object on the last line of standard output:
the cell's end-to-end metrics (``--trace 0``) or its per-layer metrics
read from a profiler trace of part of the window (``--trace 1``), whether
what the timed path produced (served tokens, the step's logits, the KV
cache) agrees with the plain reference, and the device. The
numbers compared against their limits are the last lines of standard
error. Exits non-zero, printing no result, without a TPU or with fewer
chips than the cell asks for.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# JAX's persistent compilation cache, at a fixed path inside the checkout.
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from bench import harness
    from bench.peaks import peaks_for

    enable_compile_cache()
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.load_cell(bench, args.workload, bool(args.trace))
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform}); this "
              f"benchmark measures nothing elsewhere", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = peaks_for(devices[0].device_kind)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_PROCESS, peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
