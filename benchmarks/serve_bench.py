"""Serving benchmarks: programmed decode, batched prefill, reprogram cost.

Three sections on the qwen3 config with every MF projection mapped to
``cim_sim``:

  * **decode** — programmed (weight-stationary) vs legacy on-the-fly CIM
    serving: steady-state decode tokens/sec (PR 2's >= 2x gate).
  * **prefill** — batched programmed prefill (one (B, T) forward per
    admission wave, the T > 1 prompt axis folded into the collapsed
    step-time matmuls) vs prefill-as-decode (one decode step per prompt
    token): prompt-ingestion tokens/sec, gated >= 2x.
  * **reprogram** — the same model served from a fleet too small to pin
    it: round-interleaved decode (``rounds > 1``) must produce bit-exact
    tokens vs the pinned path, and the run's ``ServeReport`` charges
    every reprogram event against the Eq. 4 roll-up (reload bits / nJ).

Emits ``BENCH_serve.json`` (the serving perf trajectory anchor) and the
``benchmarks/run.py`` CSV rows.

CLI: ``PYTHONPATH=src python -m benchmarks.serve_bench [--smoke]``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.compiler.tiling import Fleet
from repro.configs.base import MFTechniqueConfig
from repro.configs.qwen3_0_6b import SMOKE
from repro.core.cim import CimConfig
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine

OUT_PATH = os.environ.get("BENCH_SERVE_OUT", "BENCH_serve.json")


def _serve_cfg(quick: bool):
    """qwen3 proportions with cim_sim projections.

    The smoke point keeps qwen3's layer pattern at reduced width; the full
    point widens toward the real shapes (still laptop-runnable: the
    behavioural µArray simulator is ~Pw*K*N work per projection call).
    """
    cim = CimConfig(w_bits=8, x_bits=8, adc_bits=5, m_columns=31)
    mf = MFTechniqueConfig(mode="cim_sim", cim=cim)
    base = SMOKE if quick else dataclasses.replace(
        SMOKE, d_model=256, d_ff=768, head_dim=64, vocab_size=2048)
    return dataclasses.replace(base, dtype=jnp.float32, mf=mf)


def _decode_tok_per_s(engine: ServeEngine, ticks: int, warmup: int = 3,
                      reps: int = 3) -> float:
    """Median steady-state decode throughput over ``reps`` windows."""
    import numpy as np
    for _ in range(engine.slots):
        engine.submit(Request(prompt=[1], max_new_tokens=1 << 30))
    for _ in range(warmup):
        engine.step()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(ticks):
            engine.step()
        jax.block_until_ready(engine.cache["pos"])
        times.append(time.perf_counter() - t0)
    return engine.slots * ticks / float(np.median(times))


def _prompt_tok_per_s(engine: ServeEngine, prompt_len: int, reps: int = 3
                      ) -> float:
    """Median prompt-ingestion throughput (prompt tokens/sec) over full
    ``run()`` waves of ``slots`` requests with one generated token each."""
    import numpy as np

    def one_wave():
        reqs = [Request(prompt=list(range(1, prompt_len + 1)),
                        max_new_tokens=1) for _ in range(engine.slots)]
        t0 = time.perf_counter()
        done = engine.run(reqs)
        dt = time.perf_counter() - t0
        assert all(r.done for r in done)
        return dt

    one_wave()                                    # warmup (compile)
    times = [one_wave() for _ in range(reps)]
    return engine.slots * (prompt_len - 1) / float(np.median(times))


def _greedy_tokens(engine: ServeEngine, prompt: list[int], n: int,
                   n_reqs: int) -> list[list[int]]:
    done = engine.run([Request(prompt=list(prompt), max_new_tokens=n)
                       for _ in range(n_reqs)])
    return [r.out for r in done]


def run(quick: bool = True):
    cfg = _serve_cfg(quick)
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    ticks = 10 if quick else 30
    warmup, reps = 3, 3
    max_len = reps * ticks + warmup + 4
    slots = 2

    prog_eng = ServeEngine(params, cfg, slots=slots, max_len=max_len,
                           program=True)
    legacy_eng = ServeEngine(params, cfg, slots=slots, max_len=max_len,
                             program=False)
    assert prog_eng.programmed and not legacy_eng.programmed
    from repro.core.programmed import (programmed_bytes,
                                       programmed_bytes_unpacked)
    state_bytes = programmed_bytes(prog_eng._exec_params)
    state_bytes_unpacked = programmed_bytes_unpacked(prog_eng._exec_params,
                                                     cfg.mf.cim)
    # Bit-packing gate: packed plane/magnitude cells must strictly shrink
    # the programmed state versus the one-int8-per-cell layouts.
    assert state_bytes < state_bytes_unpacked, (state_bytes,
                                                state_bytes_unpacked)

    prog_tok_s = _decode_tok_per_s(prog_eng, ticks, warmup, reps)
    legacy_tok_s = _decode_tok_per_s(legacy_eng, ticks, warmup, reps)
    speedup = prog_tok_s / legacy_tok_s if legacy_tok_s else 0.0

    # ---- batched programmed prefill vs prefill-as-decode -----------------
    prompt_len = 33 if quick else 65              # 32 / 64 prefill tokens
    pre_len = prompt_len + 8
    pre_batched = ServeEngine(params, cfg, slots=slots, max_len=pre_len)
    pre_decode = ServeEngine(params, cfg, slots=slots, max_len=pre_len,
                             batched_prefill=False)
    assert pre_batched.batched_prefill and not pre_decode.batched_prefill
    batched_ptok_s = _prompt_tok_per_s(pre_batched, prompt_len, reps)
    decode_ptok_s = _prompt_tok_per_s(pre_decode, prompt_len, reps)
    prefill_speedup = batched_ptok_s / decode_ptok_s if decode_ptok_s \
        else 0.0
    # Acceptance gate: batched prefill must at least double prompt
    # ingestion over paying one decode step per token.
    assert prefill_speedup >= 2.0, (
        f"batched prefill speedup {prefill_speedup:.2f}x < 2x "
        f"({batched_ptok_s:.1f} vs {decode_ptok_s:.1f} prompt tok/s)")

    # ---- round-interleaved serving on a fleet too small to pin -----------
    cim = cfg.mf.cim
    swap_fleet = Fleet(n_macros=64 if quick else 1024, cfg=cim)
    swap_eng = ServeEngine(params, cfg, slots=slots, max_len=16,
                           fleet=swap_fleet, batched_prefill=False)
    sched = swap_eng.schedule
    pinned_fleet = Fleet(n_macros=-(-sched.total_tiles // 2), cfg=cim)
    pin_eng = ServeEngine(params, cfg, slots=slots, max_len=16,
                          fleet=pinned_fleet, batched_prefill=False)
    assert pin_eng.schedule.pinned and not sched.pinned
    assert sched.rounds_max > 1, (
        f"fleet {swap_fleet.n_macros} macros did not force rounds > 1")
    # The executed datapath really is round-interleaved: every projection
    # the swap engine serves carries SwappedMacro state (apply_projection
    # dispatches on it), while the pinned engine holds resident macros.
    from repro.core.programmed import SwappedMacro, iter_projections
    swap_progs = [n["prog"] for _, n, _ in
                  iter_projections(swap_eng._exec_params)]
    assert swap_progs and all(isinstance(p, SwappedMacro)
                              for p in swap_progs)
    assert not any(isinstance(n.get("prog"), SwappedMacro) for _, n, _ in
                   iter_projections(pin_eng._exec_params))
    n_new = 4
    pin_out = _greedy_tokens(pin_eng, [1, 2, 3], n_new, slots)
    t0 = time.perf_counter()
    swap_out = _greedy_tokens(swap_eng, [1, 2, 3], n_new, slots)
    swap_dt = time.perf_counter() - t0
    bit_exact = swap_out == pin_out
    assert bit_exact, "round-interleaved decode diverged from pinned path"
    rep = swap_eng.last_report
    assert rep.streams > 0 and rep.reprogram_events > 0
    pin_rep = pin_eng.last_report
    assert pin_rep.reprogram_events == 0 and pin_rep.reload_bits == 0

    payload = {
        "bench": "serve_decode",
        "config": cfg.name,
        "quick": quick,
        "slots": slots,
        "ticks": ticks,
        "w_bits": cfg.mf.cim.w_bits,
        "x_bits": cfg.mf.cim.x_bits,
        "adc_bits": cfg.mf.cim.adc_bits,
        "m_columns": cfg.mf.cim.m_columns,
        "programmed_state_bytes": state_bytes,
        "programmed_state_bytes_unpacked": state_bytes_unpacked,
        "bit_packing_ratio": state_bytes_unpacked / max(state_bytes, 1),
        "programmed_tok_s": prog_tok_s,
        "legacy_tok_s": legacy_tok_s,
        "speedup": speedup,
        "prefill": {
            "prompt_len": prompt_len,
            "batched_prompt_tok_s": batched_ptok_s,
            "as_decode_prompt_tok_s": decode_ptok_s,
            "speedup": prefill_speedup,
            "gate_2x": prefill_speedup >= 2.0,
        },
        "reprogram": {
            "n_macros": swap_fleet.n_macros,
            "tile_slots": swap_fleet.tile_slots,
            "total_tiles": sched.total_tiles,
            "pinned": sched.pinned,
            "rounds_max": sched.rounds_max,
            "reprogram_events_per_stream": sched.total_reprogram_events,
            "reload_bits_per_stream": sched.total_reload_bits,
            "streams": rep.streams,
            "reprogram_events": rep.reprogram_events,
            "reload_bits": rep.reload_bits,
            "reload_energy_nj": rep.reload_energy_nj,
            "reload_s": rep.reload_s,
            "utilization": rep.utilization,
            "swapped_tok_s": slots * n_new / swap_dt,
            "bit_exact_vs_pinned": bit_exact,
        },
    }
    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")

    return [
        ("serve_decode_programmed", 1e6 / prog_tok_s,
         f"tok_s={prog_tok_s:.1f}"),
        ("serve_decode_legacy", 1e6 / legacy_tok_s,
         f"tok_s={legacy_tok_s:.1f}"),
        ("serve_decode_speedup", 0.0,
         f"programmed/legacy={speedup:.2f}x json={OUT_PATH}"),
        ("serve_prefill_batched", 1e6 / batched_ptok_s,
         f"prompt_tok_s={batched_ptok_s:.1f}"),
        ("serve_prefill_as_decode", 1e6 / decode_ptok_s,
         f"prompt_tok_s={decode_ptok_s:.1f}"),
        ("serve_prefill_speedup", 0.0,
         f"batched/as_decode={prefill_speedup:.2f}x gate>=2x"),
        ("serve_reprogram_rounds", 0.0,
         f"rounds_max={sched.rounds_max} "
         f"reprog/stream={sched.total_reprogram_events} "
         f"reload_bits/stream={sched.total_reload_bits} "
         f"bit_exact={bit_exact}"),
        ("serve_reprogram_rollup", 0.0,
         f"streams={rep.streams} events={rep.reprogram_events} "
         f"reload={rep.reload_energy_nj:.2f}nJ util={rep.utilization:.2f}"),
    ]


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
