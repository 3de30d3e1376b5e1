"""Traffic benchmark: throughput / latency / SLO curves under offered load.

Three sections on the qwen3 smoke config with every MF projection mapped
to ``cim_sim`` and served from a pinned fleet:

  * **offered-load sweep** — the per-tick cost of the jitted decode step
    (and of one batched-prefill wave) is measured on the live engine and
    used to calibrate a :class:`~repro.traffic.batching.VirtualClock`;
    the same keyed workload is then replayed at >= 4 offered-load
    fractions of the estimated capacity. Each point emits a full
    :class:`~repro.traffic.report.TrafficReport` (p50/p99/p999 latency,
    TTFT, tok/s, SLO attainment, queue depth, per-wave Eq. 4 energy).
    Gate: SLO attainment >= 0.99 at every point below the knee.
  * **mesh parity** — a single-device serve mesh
    (:func:`repro.traffic.shard.shard_engine`) must decode bitwise
    identically to the unsharded engine. Gate: hard assert.
  * **multi-device scaling** — steady-state aggregate decode tok/s on a
    data-parallel serve mesh vs the single-device engine, measured
    in-process over the devices this process holds; a CPU-only process
    (``JAX_PLATFORMS=cpu``) rehearses in a CPU child that forces
    ``--xla_force_host_platform_device_count`` host devices. Gate (CPU
    rehearsal only): >= 1.5x, asserted ONLY when the host
    actually has >= 2 cores (XLA's forced host devices share one thread
    pool per core; on a 1-core machine the gate is recorded as vacuous
    with ``host_parallel_capable: false``). CI exports
    ``BENCH_TRAFFIC_REQUIRE_MULTIDEV=1``, which turns the vacuous
    fallback into a hard failure — on the 4-vCPU runners the >= 1.5x
    gate must actually be measured and asserted.

Emits ``BENCH_traffic.json`` and the ``benchmarks/run.py`` CSV rows.

CLI: ``PYTHONPATH=src python -m benchmarks.traffic_report [--smoke]``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compiler.tiling import Fleet
from repro.configs.base import MFTechniqueConfig
from repro.configs.qwen3_0_6b import SMOKE
from repro.core.cim import CimConfig
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine
from repro.traffic import (ContinuousBatcher, VirtualClock, WallClock,
                           WorkloadConfig, generate, shard_engine)
from repro.traffic.report import from_run
from repro.launch.mesh import make_serve_mesh

OUT_PATH = os.environ.get("BENCH_TRAFFIC_OUT", "BENCH_traffic.json")

LOAD_FRACTIONS = (0.25, 0.5, 0.75, 1.0, 1.5, 2.0)
KNEE_SLO = 0.99


def _traffic_cfg(quick: bool):
    cim = CimConfig(w_bits=8, x_bits=8, adc_bits=5, m_columns=31)
    mf = MFTechniqueConfig(mode="cim_sim", cim=cim)
    base = SMOKE if quick else dataclasses.replace(
        SMOKE, d_model=256, d_ff=768, head_dim=64, vocab_size=2048)
    return dataclasses.replace(base, dtype=jnp.float32, mf=mf)


def _measure_tick_s(engine: ServeEngine, ticks: int = 8,
                    reps: int = 3) -> float:
    """Median wall cost of one full-batch jitted decode step."""
    for _ in range(engine.slots):
        engine.submit(Request(prompt=[1], max_new_tokens=1 << 30))
    for _ in range(3):
        engine.step()                               # warmup / compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(ticks):
            engine.step()
        jax.block_until_ready(engine.cache["pos"])
        times.append((time.perf_counter() - t0) / ticks)
    for slot in list(engine.occupied_slots):
        engine.evict(slot)
    return float(np.median(times))


def _measure_prefill_s(engine: ServeEngine, prompt_len: int,
                       reps: int = 3) -> float:
    """Median wall cost of one batched-prefill admission wave."""
    times = []
    for _ in range(reps + 1):                        # first rep compiles
        reqs = [Request(prompt=list(range(1, prompt_len + 1)),
                        max_new_tokens=1 << 30)
                for _ in range(engine.slots)]
        t0 = time.perf_counter()
        engine.submit_many(reqs)
        jax.block_until_ready(engine.cache["pos"])
        times.append(time.perf_counter() - t0)
        for slot in list(engine.occupied_slots):
            engine.evict(slot)
    return float(np.median(times[1:]))


def _sweep_point(engine, workload_cfg, tick_s, prefill_s, max_ticks):
    reqs = generate(workload_cfg)
    clock = VirtualClock(tick_s, prefill_s=prefill_s)
    bat = ContinuousBatcher(engine, clock=clock)
    log = bat.run(reqs, max_ticks=max_ticks)
    return from_run(log, engine)


def _run_sweep(engine, quick, tick_s, prefill_s):
    slots = engine.slots
    mean_new = 6.0
    # Each occupied slot emits one token per tick, so the fleet completes
    # ~slots/mean_new requests per tick at full occupancy.
    capacity_rps = slots / (mean_new * tick_s)
    ttft_slo = prefill_s + 50.0 * tick_s
    tpot_slo = 3.0 * tick_s
    n_requests = 24 if quick else 64
    points = []
    for frac in LOAD_FRACTIONS:
        wcfg = WorkloadConfig(
            rate_rps=frac * capacity_rps, n_requests=n_requests,
            process="poisson", prompt_len_min=2, prompt_len_max=6,
            decode_len_min=4, decode_len_max=8,
            vocab_size=engine.cfg.vocab_size,
            ttft_slo_s=ttft_slo, tpot_slo_s=tpot_slo, seed=11)
        rep = _sweep_point(engine, wcfg, tick_s, prefill_s,
                           max_ticks=50_000)
        assert not rep.out_of_ticks
        points.append((frac, rep))
    return capacity_rps, points


def _wallclock_smoke(engine, tick_s, prefill_s, quick) -> dict:
    """One LIVE run next to the virtual-clock sweeps: same workload
    machinery on a :class:`WallClock` (arrivals in real perf_counter
    time, idle gaps actually slept). Wall timing is machine-dependent,
    so the gate is completion-shaped — every offered request reaches a
    terminal state and tokens flowed — while latency/SLO numbers are
    recorded for the trajectory, not asserted."""
    capacity_rps = engine.slots / (6.0 * tick_s)
    n_requests = 8 if quick else 16
    wcfg = WorkloadConfig(
        rate_rps=0.5 * capacity_rps, n_requests=n_requests,
        process="poisson", prompt_len_min=2, prompt_len_max=6,
        decode_len_min=4, decode_len_max=8,
        vocab_size=engine.cfg.vocab_size,
        ttft_slo_s=prefill_s + 50.0 * tick_s, tpot_slo_s=3.0 * tick_s,
        seed=13)
    reqs = generate(wcfg)
    bat = ContinuousBatcher(engine, clock=WallClock())
    log = bat.run(reqs, max_ticks=50_000)
    rep = from_run(log, engine)
    assert not log.out_of_ticks
    assert rep.completed + rep.rejected + rep.evicted == n_requests
    assert rep.completed > 0 and rep.decode_tokens > 0
    return dict(offered_frac=0.5, clock="wall", **rep.to_json())


def _mesh_parity(params, cfg, fleet):
    """Single-device serve mesh vs unsharded engine: bitwise tokens."""
    outs, info = [], None
    for shard in (False, True):
        eng = ServeEngine(params, cfg, slots=2, max_len=32, fleet=fleet)
        if shard:
            info = shard_engine(eng, make_serve_mesh(
                data=1, fleet=1, devices=jax.devices()[:1]))
        done = eng.run([Request(prompt=[1 + i, 2 + i, 3 + i],
                                max_new_tokens=6) for i in range(4)])
        outs.append([r.out for r in done])
    return outs[0] == outs[1], info


def _measure_multidevice(devices, ticks: int) -> dict:
    """Steady-state aggregate decode tok/s of a data-parallel serve mesh
    over ``devices`` vs the same engine on the default device alone."""
    cfg = _traffic_cfg(True)
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    fleet = Fleet(n_macros=4096, cfg=cfg.mf.cim)
    slots = 8
    data = max(d for d in (8, 4, 2, 1) if d <= len(devices))

    def tok_s(eng):
        for _ in range(eng.slots):
            eng.submit(Request(prompt=[1], max_new_tokens=1 << 30))
        for _ in range(3):
            eng.step()
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(ticks):
                eng.step()
            jax.block_until_ready(eng.cache["pos"])
            times.append(time.perf_counter() - t0)
        return eng.slots * ticks / float(np.median(times))

    single = ServeEngine(params, cfg, slots=slots, max_len=128, fleet=fleet)
    t_single = tok_s(single)
    meshed = ServeEngine(params, cfg, slots=slots, max_len=128, fleet=fleet)
    info = shard_engine(meshed, make_serve_mesh(
        data=data, fleet=1, devices=devices[:data]))
    t_mesh = tok_s(meshed)
    return {"devices": data, "platform": devices[0].platform,
            "device_kind": devices[0].device_kind, "slots": slots,
            "ticks": ticks, "single_tok_s": t_single, "mesh_tok_s": t_mesh,
            "speedup": t_mesh / t_single, "shard_info": info}


# The CPU rehearsal child: four forced host devices, pinned to the CPU
# backend so it never contends for an accelerator.
REHEARSAL_SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, "src")
    import jax
    from benchmarks.traffic_report import _measure_multidevice
    print("MULTIDEV_RESULT " + json.dumps(
        _measure_multidevice(jax.devices(), int(sys.argv[1]))))
""")


def _multidevice_scaling(quick: bool) -> dict:
    """Multi-device serving throughput.

    A process that may hold an accelerator measures in-process over the
    devices it has (one process per chip: a child would contend for
    them); with a single device there is nothing to measure. A CPU-only
    process (``JAX_PLATFORMS=cpu``) rehearses in a CPU child on four
    forced host devices instead, gated >= 1.5x when the host has the
    cores to show parallel speedup."""
    ticks = 8 if quick else 24
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        devices = jax.devices()
        if len(devices) < 2:
            return {"devices": 1, "platform": devices[0].platform,
                    "device_kind": devices[0].device_kind,
                    "measured": False, "gate_1_5x": "single_device"}
        return dict(_measure_multidevice(devices, ticks), measured=True,
                    gate_1_5x="not_gated_in_process")
    cpu_count = len(os.sched_getaffinity(0)) if hasattr(
        os, "sched_getaffinity") else (os.cpu_count() or 1)
    capable = cpu_count >= 2
    # CI runs on multi-vCPU hosts and exports this to FORBID the vacuous
    # fallback: a single-core runner there means the gate silently
    # stopped measuring anything, which should fail loudly instead.
    if os.environ.get("BENCH_TRAFFIC_REQUIRE_MULTIDEV") == "1" \
            and not capable:
        raise RuntimeError(
            f"BENCH_TRAFFIC_REQUIRE_MULTIDEV=1 but this host exposes only "
            f"{cpu_count} core(s) — the >=1.5x multi-device gate would be "
            f"vacuous")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, "-c", REHEARSAL_SCRIPT, str(ticks)],
        capture_output=True, text=True, timeout=1200, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("MULTIDEV_RESULT ")), None)
    assert line is not None, r.stdout + r.stderr
    result = json.loads(line[len("MULTIDEV_RESULT "):])
    result["measured"] = True
    result["cpu_count"] = cpu_count
    result["host_parallel_capable"] = capable
    if capable:
        # The acceptance gate: a 4-device data-parallel serve mesh must
        # deliver >= 1.5x aggregate decode tok/s at saturating load.
        assert result["speedup"] >= 1.5, (
            f"multi-device mesh speedup {result['speedup']:.2f}x < 1.5x "
            f"on a {cpu_count}-core host")
        result["gate_1_5x"] = True
    else:
        # One core: XLA's forced host devices time-slice a single thread
        # pool, so parallel speedup is physically unobtainable — record
        # the measurement and mark the gate vacuous for this host.
        result["gate_1_5x"] = "vacuous_single_core_host"
    return result


def run(quick: bool = True):
    cfg = _traffic_cfg(quick)
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    cim = cfg.mf.cim
    fleet = Fleet(n_macros=4096, cfg=cim)
    slots = 4
    engine = ServeEngine(params, cfg, slots=slots, max_len=64,
                         fleet=fleet)
    assert engine.schedule is not None and engine.schedule.pinned

    tick_s = _measure_tick_s(engine)
    prefill_s = _measure_prefill_s(engine, prompt_len=6)
    capacity_rps, points = _run_sweep(engine, quick, tick_s, prefill_s)

    # Knee: the highest offered load still meeting the SLO bar. Gate:
    # every point below it (and at least the lowest point) attains it.
    attain = [(frac, rep.slo_attainment) for frac, rep in points]
    knee_frac = max((f for f, a in attain if a >= KNEE_SLO), default=0.0)
    assert len(points) >= 4, "sweep must cover >= 4 offered-load points"
    assert knee_frac > 0.0, f"no load point attained SLO: {attain}"
    below_knee = [(f, a) for f, a in attain if f <= knee_frac]
    assert all(a >= KNEE_SLO for _, a in below_knee), (
        f"SLO attainment dipped below {KNEE_SLO} below the knee: {attain}")

    wall = _wallclock_smoke(engine, tick_s, prefill_s, quick)

    parity, shard_info = _mesh_parity(params, cfg, fleet)
    assert parity, "single-device mesh decode diverged from unsharded"

    multidev = _multidevice_scaling(quick)

    payload = {
        "bench": "traffic_serving",
        "config": cfg.name,
        "quick": quick,
        "slots": slots,
        "tick_s": tick_s,
        "prefill_s": prefill_s,
        "capacity_rps_est": capacity_rps,
        "knee_offered_frac": knee_frac,
        "knee_rps": knee_frac * capacity_rps,
        "gate_slo_below_knee": KNEE_SLO,
        "sweep": [dict(offered_frac=frac, **rep.to_json())
                  for frac, rep in points],
        "wallclock_smoke": wall,
        "mesh_parity": {"single_device_bitwise": parity,
                        **(shard_info or {})},
        "multidevice": multidev,
    }
    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")

    rows = []
    for frac, rep in points:
        rows.append((
            f"traffic_load_{frac:g}x", 1e6 / rep.tok_s if rep.tok_s else 0,
            f"offered={rep.offered_rps:.2f}rps tok_s={rep.tok_s:.1f} "
            f"slo={rep.slo_attainment:.3f} p99={rep.latency_p99_s:.3f}s "
            f"q_max={rep.queue_depth_max}"))
    rows.append(("traffic_knee", 0.0,
                 f"knee={knee_frac:g}x_capacity "
                 f"({knee_frac * capacity_rps:.2f}rps) "
                 f"gate_slo>={KNEE_SLO} json={OUT_PATH}"))
    rows.append(("traffic_wallclock_smoke", 0.0,
                 f"tok_s={wall['tok_s']:.1f} "
                 f"completed={wall['completed']}/{wall['n_requests']} "
                 f"slo={wall['slo_attainment']:.3f} "
                 f"wall_s={wall['wall_s']:.2f}"))
    rows.append(("traffic_mesh_parity", 0.0,
                 f"single_device_bitwise={parity} "
                 f"cache_leaves={shard_info['cache_sharded_leaves']}"))
    rows.append(("traffic_multidevice", 0.0,
                 f"devices={multidev['devices']} "
                 f"speedup={multidev.get('speedup', float('nan')):.2f}x "
                 f"gate={multidev['gate_1_5x']}"))
    return rows


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small qwen3 smoke shapes (CI)")
    args = ap.parse_args()
    for name, us, derived in run(quick=args.smoke):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
