"""Sweep the offered rate of an open-loop cell on the chip, to find the
highest rate its traffic sustains without a growing backlog.

    python3 bench/knee_sweep.py --workload qwen3-pinned.chat-open \
        --rates 2 4 6 8 --seconds 20 --seed 7

One process, one engine: for each rate the cell's mix is generated at that
rate (same seed) and served for ``--seconds``; the slots are emptied
between rates. Prints one JSON line per rate: offered and completed rate,
TTFT p50/p95, queue wait p50 over the first and the second half of the
window (a backlog that grows shows as a second half that waits longer),
and the requests due in the window still waiting at its close. The rate
found goes, as a number, into the mix's file.
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from bench import generator, harness, stats, system

    enable_compile_cache()
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.load_cell(bench, args.workload, False)
    if jax.devices()[0].platform != "tpu":
        print("knee_sweep: no TPU", file=sys.stderr)
        return 2
    conf, sizes = cell.conf, cell.conf["model"]
    w = args.seconds
    with jax.default_matmul_precision(conf["matmul_precision"]):
        params = cell.reference.init_params(args.seed, sizes)
        engine = system.build_engine(params, conf, args.seed)
        system.warm_up(engine, generator.prefill_buckets(
            cell.mix, conf["serving"]["max_len"]))
        for rate in args.rates:
            mix = dict(cell.mix, rate_rps=rate)
            specs = generator.generate(
                mix, args.seed,
                generator.request_count(mix, w, harness.TAIL_S),
                sizes["vocab_size"])
            recs = harness.serve_window(engine, specs, w, True)
            for slot in engine.occupied_slots:
                engine.evict(slot)
            due = stats.due_in_window(recs, w)
            first = [r for r in due if r.due_s < w / 2]
            second = [r for r in due if r.due_s >= w / 2]
            done = sum(1 for r in recs if r.done and r.token_s[-1] <= w)
            print(json.dumps({
                "rate_rps": rate, "due": len(due),
                "offered_rps": len(due) / w, "completed_rps": done / w,
                "ttft_p50_s": stats.percentile(stats.ttfts(due, w), 50),
                "ttft_p95_s": stats.percentile(stats.ttfts(due, w), 95),
                "itl_p95_ms": 1e3 * stats.percentile(
                    stats.itl_gaps(recs, w), 95),
                "wait_p50_first_half_s": stats.percentile(
                    stats.queue_waits(first, w), 50),
                "wait_p50_second_half_s": stats.percentile(
                    stats.queue_waits(second, w), 50),
                "waiting_at_close": sum(
                    1 for r in due if r.admit_s is None or r.admit_s > w),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
