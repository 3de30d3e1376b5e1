"""Silicon variation report: yield curves, offset-correction recovery,
σ=0 parity, and the drift → alarm → auto-recalibration serving loop.

Four sections, all on the qwen3 smoke LM with every projection on
``cim_sim`` (plus a representative projection for the vmapped sweeps):

  * **sigma0** — a fleet whose every slot samples EXACTLY nominal silicon
    (σ=0) must decode bitwise identically to the silicon-free programmed
    engine, on both a pinned fleet and a round-interleaved (swapped) one.
    This gates the per-tile silicon route against the nominal fast path.
  * **yield** — vmapped multi-seed Monte-Carlo: projection SQNR vs
    cap-DAC mismatch σ at the exactly-lossless design point (31×5) and
    the real-rounding points (31×6, 31×4), plus model-level logits rel-L2
    over sampled fleets via the calibration lab's evaluators.
  * **offset_correction** — mean-SQNR delta of the 2-bit tail-current
    comparator calibration over the same sampling keys (gated: the
    correction must recover >= ``OFFSET_RECOVERY_GATE_DB``).
  * **drift** — a served engine with an aging fleet: comparator offsets
    drift past the ADC decision boundaries, the probe alarm fires,
    auto-recalibration (comparator re-trim + scale re-programming)
    brings the probe error back under the alarm line, and the rewrite is
    charged in the ``ServeReport`` (all gated).

Emits ``BENCH_silicon.json`` and the ``benchmarks/run.py`` CSV rows.

CLI: ``PYTHONPATH=src python -m benchmarks.silicon_report [--smoke]``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.calib.corpus import attach_observer_ids
from repro.calib.report import accuracy_report, calibrate_lm, lm_ref_config
from repro.compiler.tiling import Fleet
from repro.configs.base import MFTechniqueConfig
from repro.configs.qwen3_0_6b import SMOKE
from repro.core.cim import CimConfig
from repro.core.programmed import program_weights
from repro.data.synthetic import DataConfig, lm_batch
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine
from repro.silicon.drift import DriftPolicy
from repro.silicon.instance import SiliconConfig, attach_silicon, sample_fleet
from repro.silicon.montecarlo import (offset_correction_delta_db,
                                      projection_yield_curve)

OUT_PATH = os.environ.get("BENCH_SILICON_OUT", "BENCH_silicon.json")

# Design points for the yield sweeps: the paper's exactly-lossless 31x5
# pairing plus the two real-rounding ADCs of BENCH_calib.json.
DESIGNS = ((31, 5), (31, 6), (31, 4))
# Mean-SQNR the 2-bit tail-current calibration must win back at the
# bench's comparator sigma (measured ~100 dB at the lossless point — the
# uncorrected offset crosses ADC decision boundaries everywhere, the
# corrected residue almost never does).
OFFSET_RECOVERY_GATE_DB = 6.0
# Comparator sigma for the offset/drift scenarios: the post-calibration
# residue (<= half a cal-DAC LSB = 6 mV) sits just under the 31-level
# half-LSB decision boundary (~6.5 mV at 0.4 V full scale), so fresh
# silicon is healthy and any drift crosses into visible error.
CMP_SIGMA_V = 0.008
# Pre-drift recovery gate: after auto-recalibration the probe rel-L2 must
# come back to within this factor of the pre-drift baseline (the alarm
# fired at ~5.6x baseline; the re-trimmed residue lands ~1.3x — the gap
# to 1.0 is the re-measured activation scales, which now reflect the
# served CIM datapath rather than the float reference).
RECOVERY_GATE_RATIO = 1.5
# Fused-kernel serving gate: a σ>0 fleet decoding through the fused
# Pallas route (in-kernel SA-ADC; silicon folded into the kernel
# operands) may cost at most this factor over the nominal fused fast
# path. The extra work is real but small — the cap-folded stationary
# operand rides the same dot, silicon adds the denominator/offset tiles
# and (for thermal fleets) the per-conversion dither draw.
KERNEL_SLOWDOWN_GATE = 1.5
# The kernel-only payload (tier-1 TIER1_KERNEL_BENCH / --only-kernel).
KERNEL_OUT_PATH = os.environ.get("BENCH_SILICON_KERNEL_OUT",
                                 "BENCH_silicon_kernel.json")


def _lm_cfg(cim: CimConfig):
    return dataclasses.replace(
        SMOKE, dtype=jnp.float32,
        mf=MFTechniqueConfig(mode="cim_sim", cim=cim))


def _batches(cfg, n, seed0=0, b=4, t=16):
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=t, global_batch=b,
                    task="uniform")
    return [{"tokens": jnp.asarray(lm_batch(dc, seed0 + i)["tokens"])}
            for i in range(n)]


def _greedy_tokens(engine: ServeEngine, n_new: int, n_reqs: int):
    done = engine.run([Request(prompt=[1, 2, 3], max_new_tokens=n_new)
                       for _ in range(n_reqs)])
    return [r.out for r in done]


def _sigma0_section(params, cfg, cim, rows):
    """σ=0 silicon decode must be bitwise identical to the nominal
    programmed path — pinned AND round-interleaved."""
    nominal0 = SiliconConfig(cap_sigma=0.0, comparator_sigma_v=0.0)
    assert nominal0.is_nominal
    pin_fleet = Fleet(n_macros=4096, cfg=cim)
    swap_fleet = Fleet(n_macros=64, cfg=cim)
    t0 = time.time()
    eng_ref = ServeEngine(params, cfg, slots=2, max_len=16,
                          fleet=pin_fleet, batched_prefill=False)
    assert eng_ref.schedule.pinned
    ref_toks = _greedy_tokens(eng_ref, 4, 2)
    eng_pin = ServeEngine(params, cfg, slots=2, max_len=16,
                          fleet=pin_fleet, batched_prefill=False,
                          silicon=nominal0)
    pin_toks = _greedy_tokens(eng_pin, 4, 2)
    eng_swap = ServeEngine(params, cfg, slots=2, max_len=16,
                           fleet=swap_fleet, batched_prefill=False,
                           silicon=nominal0)
    assert not eng_swap.schedule.pinned
    swap_toks = _greedy_tokens(eng_swap, 4, 2)
    us = (time.time() - t0) * 1e6
    pin_ok = pin_toks == ref_toks
    swap_ok = swap_toks == ref_toks
    assert pin_ok, "sigma=0 silicon decode diverged from nominal (pinned)"
    assert swap_ok, "sigma=0 silicon decode diverged from nominal (swapped)"
    rows.append(("silicon_sigma0_parity", us,
                 f"pinned={pin_ok} swapped={swap_ok}"))
    return {"pinned_bit_exact": pin_ok, "swapped_bit_exact": swap_ok,
            "swap_rounds_max": eng_swap.schedule.rounds_max}


def _yield_section(cfg, rows, quick):
    """Projection-level vmapped sweeps + model-level seeded fleets."""
    sigmas = (0.01, 0.03, 0.05, 0.08, 0.12)
    n_seeds = 16 if quick else 64
    key = jax.random.PRNGKey(42)
    k, n = cfg.d_model, cfg.d_ff
    x = jax.random.normal(jax.random.PRNGKey(0), (8, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    base = SiliconConfig(comparator_sigma_v=0.0)
    out = {}
    for m, a in DESIGNS:
        cim = CimConfig(w_bits=8, x_bits=8, adc_bits=a, m_columns=m)
        t0 = time.time()
        pts = projection_yield_curve(key, x, w, cim, base, sigmas, n_seeds)
        out[f"{m}x{a}"] = [p.to_dict() for p in pts]
        worst = pts[-1]
        rows.append((f"silicon_yield_{m}x{a}", (time.time() - t0) * 1e6,
                     f"sqnr@sigma{sigmas[0]}={pts[0].mean_sqnr_db:.1f}dB "
                     f"@sigma{worst.cap_sigma}={worst.mean_sqnr_db:.1f}dB "
                     f"yield={worst.yield_frac:.2f} seeds={n_seeds}"))
    return {"sigmas": list(sigmas), "n_seeds": n_seeds,
            "projection": out}


def _model_yield_section(params, cfg, rows, quick):
    """Model-level accuracy over sampled fleets (calib-lab evaluators)."""
    cim = cfg.mf.cim
    tagged, registry = attach_observer_ids(params)
    progd = program_weights(tagged, cim, prefer_lossless=False)
    ev = _batches(cfg, 2, seed0=1000)
    ref_cfg = lm_ref_config(cfg)

    def ref_fwd(b):
        return T.lm_forward(params, b, ref_cfg)[0]

    n_seeds = 3 if quick else 8
    cells = {}
    for cap_sigma in (0.02, 0.05):
        scfg = SiliconConfig(cap_sigma=cap_sigma,
                             comparator_sigma_v=CMP_SIGMA_V)
        rels, sqnrs = [], []
        t0 = time.time()
        for seed in range(n_seeds):
            sil = sample_fleet(jax.random.PRNGKey(100 + seed), 2048,
                               cim.m_columns, scfg)
            exec_params = attach_silicon(progd, sil, scfg, cim)
            rep = accuracy_report(
                ref_fwd,
                lambda b, p=exec_params: T.lm_forward(p, b, cfg)[0],
                ev, registry)
            rels.append(rep.rel_l2)
            sqnrs.append(rep.mean_sqnr_db)
        cells[f"cap{cap_sigma}"] = {
            "cap_sigma": cap_sigma,
            "comparator_sigma_v": CMP_SIGMA_V,
            "rel_l2_mean": float(np.mean(rels)),
            "rel_l2_max": float(np.max(rels)),
            "mean_sqnr_db": float(np.mean(sqnrs)),
            "n_seeds": n_seeds,
        }
        rows.append((f"silicon_model_yield_cap{cap_sigma}",
                     (time.time() - t0) * 1e6,
                     f"rel_l2={np.mean(rels):.4f} "
                     f"sqnr={np.mean(sqnrs):.1f}dB seeds={n_seeds}"))
    return cells


def _offset_section(rows, quick):
    cim = CimConfig(w_bits=8, x_bits=8, adc_bits=5, m_columns=31)
    k, n = 64, 128
    x = jax.random.normal(jax.random.PRNGKey(0), (8, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    scfg = SiliconConfig(comparator_sigma_v=CMP_SIGMA_V)
    n_seeds = 16 if quick else 64
    t0 = time.time()
    delta, on_db, off_db = offset_correction_delta_db(
        jax.random.PRNGKey(7), x, w, cim, scfg, n_seeds)
    rows.append(("silicon_offset_correction", (time.time() - t0) * 1e6,
                 f"delta={delta:.1f}dB corrected={on_db:.1f}dB "
                 f"uncorrected={off_db:.1f}dB gate>={OFFSET_RECOVERY_GATE_DB}"))
    assert delta >= OFFSET_RECOVERY_GATE_DB, (
        f"2-bit offset correction recovered only {delta:.1f} dB "
        f"(gate {OFFSET_RECOVERY_GATE_DB} dB)")
    return {"comparator_sigma_v": CMP_SIGMA_V, "n_seeds": n_seeds,
            "delta_db": delta, "corrected_db": on_db,
            "uncorrected_db": off_db,
            "gate_db": OFFSET_RECOVERY_GATE_DB, "gate_pass": True}


def _kernel_parity_matrix(cim: CimConfig, scfg, rows) -> dict:
    """σ>0 fused-kernel vs reference-einsum exactness, all three serving
    layouts (pinned / compiler-tiled / round-interleaved). The fixed-point
    cap fold makes both routes produce identical integer ADC codes."""
    from repro.compiler.execute import (compiled_matmul_programmed,
                                        program_layer_tiles)
    from repro.compiler.tiling import plan_tiling
    from repro.core import quant
    from repro.core.programmed import (cim_mf_matmul_programmed,
                                       cim_mf_matmul_swapped,
                                       program_macro, swap_macro)
    from repro.silicon.instance import projection_silicon
    cim_k = dataclasses.replace(cim, use_kernel=True)
    m = cim.m_columns

    def sil(slots, k, n, seed):
        fleet = sample_fleet(jax.random.PRNGKey(seed), slots, m, scfg)
        return projection_silicon(fleet, scfg, k, n)

    t0 = time.time()
    out = {}
    # pinned
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 2 * m + 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (2 * m + 8, 9))
    sx = quant.calibrate_scale(x, cim.x_bits)
    s = sil(24, w.shape[0], w.shape[1], 50)
    y_k = cim_mf_matmul_programmed(x, program_macro(w, cim_k, sx=sx),
                                   cim_k, silicon=s)
    y_p = cim_mf_matmul_programmed(
        x, program_macro(w, cim, sx=sx, prefer_lossless=False), cim,
        silicon=s)
    out["pinned_exact"] = bool(np.array_equal(np.asarray(y_k),
                                              np.asarray(y_p)))
    # compiler-tiled
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 3 * m + 7))
    w = jax.random.normal(jax.random.PRNGKey(3), (3 * m + 7, 21))
    sx = quant.calibrate_scale(x, cim.x_bits)
    plan = plan_tiling(w.shape[0], w.shape[1], cim, tile_k_chunks=2,
                       tile_n=8)
    prog = program_layer_tiles(w, plan, cim, sx=sx)
    s = sil(96, w.shape[0], w.shape[1], 51)
    y_k = compiled_matmul_programmed(x, prog, plan, cim_k, silicon=s)
    y_p = compiled_matmul_programmed(x, prog, plan, cim, silicon=s)
    out["tiled_exact"] = bool(np.array_equal(np.asarray(y_k),
                                             np.asarray(y_p)))
    # round-interleaved (swap-scheduled)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 3 * m))
    w = jax.random.normal(jax.random.PRNGKey(5), (3 * m, 7))
    sx = quant.calibrate_scale(x, cim.x_bits)
    swap = swap_macro(w, cim, tile_slots=5, sx=sx)
    s = sil(5, w.shape[0], w.shape[1], 52)
    y_k = cim_mf_matmul_swapped(x, w, swap, cim_k, silicon=s)
    y_p = cim_mf_matmul_swapped(x, w, swap, cim, silicon=s)
    out["swapped_exact"] = bool(np.array_equal(np.asarray(y_k),
                                               np.asarray(y_p)))
    rows.append(("silicon_kernel_parity", (time.time() - t0) * 1e6,
                 " ".join(f"{k}={v}" for k, v in out.items())))
    return out


def _kernel_section(params, cfg, cim, rows, quick):
    """Fused Pallas step-time kernels: σ>0 fleets decode at nominal speed.

    Gates:
      * silicon fused decode tok/s >= (1/KERNEL_SLOWDOWN_GATE) x the
        nominal fused decode tok/s;
      * σ=0 silicon through the fused kernel decodes bitwise the nominal
        fused engine;
      * σ>0 fused output == the reference einsum route EXACTLY (integer
        ADC codes) on the pinned, tiled and swapped layouts, and the
        served σ>0 token streams of the fused and einsum engines match.
    """
    from benchmarks.serve_bench import _decode_tok_per_s
    from repro.kernels.ops import interpret_mode
    cim_k = dataclasses.replace(cim, use_kernel=True)
    cfg_k = _lm_cfg(cim_k)
    fleet = Fleet(n_macros=4096, cfg=cim_k)
    scfg = SiliconConfig(cap_sigma=0.02, comparator_sigma_v=CMP_SIGMA_V)
    ticks, warmup, reps = (4, 2, 3) if quick else (10, 3, 3)
    max_len = reps * ticks + warmup + 4

    def mk(cfg_, silicon=None, ml=max_len):
        return ServeEngine(params, cfg_, slots=2, max_len=ml, fleet=fleet,
                           batched_prefill=False, silicon=silicon)

    t0 = time.time()
    nom_tok_s = _decode_tok_per_s(mk(cfg_k), ticks, warmup, reps)
    sil_tok_s = _decode_tok_per_s(mk(cfg_k, scfg), ticks, warmup, reps)
    us = (time.time() - t0) * 1e6
    ratio = sil_tok_s / nom_tok_s if nom_tok_s else 0.0
    ratio_ok = ratio >= 1.0 / KERNEL_SLOWDOWN_GATE
    rows.append(("silicon_kernel_toks", us,
                 f"nominal_fused={nom_tok_s:.1f}tok/s "
                 f"silicon_fused={sil_tok_s:.1f}tok/s ratio={ratio:.2f} "
                 f"gate>={1.0 / KERNEL_SLOWDOWN_GATE:.2f} "
                 f"interpret={interpret_mode()}"))

    sigma0 = SiliconConfig(cap_sigma=0.0, comparator_sigma_v=0.0)
    sigma0_ok = (_greedy_tokens(mk(cfg_k, sigma0, ml=16), 4, 2)
                 == _greedy_tokens(mk(cfg_k, None, ml=16), 4, 2))
    # σ>0 served-token parity: both engines sample the SAME fleet
    # (PRNGKey(scfg.seed)), one decodes fused, one through the einsums.
    token_parity = (_greedy_tokens(mk(cfg_k, scfg, ml=16), 4, 2)
                    == _greedy_tokens(mk(cfg, scfg, ml=16), 4, 2))
    parity = _kernel_parity_matrix(cim, scfg, rows)

    assert ratio_ok, (
        f"silicon fused decode {sil_tok_s:.1f} tok/s fell below "
        f"1/{KERNEL_SLOWDOWN_GATE} of the nominal fused "
        f"{nom_tok_s:.1f} tok/s")
    assert sigma0_ok, "sigma=0 fused decode diverged from nominal fused"
    assert token_parity, "sigma>0 fused tokens diverged from einsum route"
    assert all(parity.values()), f"fused/einsum code parity broke: {parity}"
    return {
        "slowdown_gate": KERNEL_SLOWDOWN_GATE,
        "cap_sigma": scfg.cap_sigma,
        "comparator_sigma_v": scfg.comparator_sigma_v,
        "decode_ticks": ticks * reps,
        "pallas_interpret": bool(interpret_mode()),
        "nominal_fused_tok_s": nom_tok_s,
        "silicon_fused_tok_s": sil_tok_s,
        "silicon_over_nominal_ratio": ratio,
        "ratio_gate_pass": ratio_ok,
        "sigma0_fused_bit_exact": sigma0_ok,
        "sigma_pos_token_parity": token_parity,
        "sigma_pos_code_parity": parity,
    }


def _drift_section(params, cfg, cim, rows):
    """Aging fleet under serving: alarm fires, recalibration recovers."""
    cal = _batches(cfg, 3)
    artifact = calibrate_lm(params, cfg, cal, method="amax")
    policy = DriftPolicy(probe_batches=cal[:2], check_interval=16,
                         silicon_update_interval=8,
                         rel_l2_alarm_ratio=1.3, rel_l2_alarm_floor=0.02)
    # Accelerated aging: ~0.3 mV of comparator drift per stream pushes a
    # typical slot across the 31-level half-LSB boundary (~6.5 mV) within
    # one check interval; the cal-DAC range (+-3 sigma = 24 mV) still
    # covers the first alarms, so the re-trim can recover.
    scfg = SiliconConfig(cap_sigma=0.02, comparator_sigma_v=CMP_SIGMA_V,
                         drift_sigma_v_per_kstream=0.3)
    fleet = Fleet(n_macros=4096, cfg=cim)
    t0 = time.time()
    eng = ServeEngine(params, cfg, slots=2, max_len=48, fleet=fleet,
                      batched_prefill=False, calibration=artifact,
                      silicon=scfg, drift=policy)
    baseline = eng._monitor.baseline_rel_l2
    eng.run([Request(prompt=[1, 2, 3], max_new_tokens=32)
             for _ in range(2)])
    us = (time.time() - t0) * 1e6
    rep = eng.last_report
    log = [s.to_dict() for s in eng.drift_log]
    first_recal = next((s for s in eng.drift_log if s.recalibrated), None)
    alarm_fired = rep.drift_alarms >= 1
    recovered = (first_recal is not None
                 and not math.isnan(first_recal.post_rel_l2)
                 and first_recal.post_rel_l2
                 <= RECOVERY_GATE_RATIO * baseline)
    charged = rep.recalibrations >= 1 and rep.recal_reload_bits > 0 \
        and rep.recal_energy_j > 0.0
    assert alarm_fired, "drift scenario never raised the drift alarm"
    assert recovered, (
        f"auto-recalibration did not bring the probe back under the "
        f"pre-drift gate: post={getattr(first_recal, 'post_rel_l2', None)}"
        f" baseline={baseline}")
    assert charged, "recalibration events were not charged in ServeReport"
    rows.append(("silicon_drift_recovery", us,
                 f"baseline={baseline:.4f} "
                 f"alarm_rel={first_recal.rel_l2:.4f} "
                 f"post={first_recal.post_rel_l2:.4f} "
                 f"alarms={rep.drift_alarms} recals={rep.recalibrations} "
                 f"recal_nj={rep.recal_energy_nj:.1f}"))
    return {
        "baseline_rel_l2": baseline,
        "recovery_gate_ratio": RECOVERY_GATE_RATIO,
        "drift_sigma_v_per_kstream": scfg.drift_sigma_v_per_kstream,
        "check_interval": policy.check_interval,
        "drift_checks": rep.drift_checks,
        "drift_alarms": rep.drift_alarms,
        "recalibrations": rep.recalibrations,
        "recal_reload_bits": rep.recal_reload_bits,
        "recal_energy_nj": rep.recal_energy_nj,
        "first_alarm_rel_l2": first_recal.rel_l2,
        "first_recal_post_rel_l2": first_recal.post_rel_l2,
        "alarm_fired": alarm_fired,
        "recovered_within_gate": recovered,
        "charged_in_report": charged,
        "log": log,
    }


def run(quick: bool = True):
    rows = []
    cim = CimConfig(w_bits=8, x_bits=8, adc_bits=5, m_columns=31)
    cfg = _lm_cfg(cim)
    params = T.lm_init(jax.random.PRNGKey(0), cfg)

    payload = {
        "bench": "silicon_report",
        "quick": quick,
        "config": cfg.name,
        "designs": [f"{m}x{a}" for m, a in DESIGNS],
        "sigma0": _sigma0_section(params, cfg, cim, rows),
        "kernel": _kernel_section(params, cfg, cim, rows, quick),
        "yield": _yield_section(cfg, rows, quick),
        "model_yield": _model_yield_section(params, cfg, rows, quick),
        "offset_correction": _offset_section(rows, quick),
        "drift": _drift_section(params, cfg, cim, rows),
    }
    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    d = payload["drift"]
    k = payload["kernel"]
    rows.append(("silicon_gate", 0.0,
                 f"sigma0_bit_exact=True offset_recovery_pass=True "
                 f"drift_recovered={d['recovered_within_gate']} "
                 f"kernel_ratio={k['silicon_over_nominal_ratio']:.2f} "
                 f"json={OUT_PATH}"))
    return rows


def run_kernel(quick: bool = True):
    """Just the fused-kernel section (tier-1 TIER1_KERNEL_BENCH flag) —
    the same gates, written to ``BENCH_silicon_kernel.json`` so it never
    clobbers a full report."""
    rows = []
    cim = CimConfig(w_bits=8, x_bits=8, adc_bits=5, m_columns=31)
    cfg = _lm_cfg(cim)
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    payload = {
        "bench": "silicon_report_kernel",
        "quick": quick,
        "config": cfg.name,
        "kernel": _kernel_section(params, cfg, cim, rows, quick),
    }
    with open(KERNEL_OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    k = payload["kernel"]
    rows.append(("silicon_kernel_gate", 0.0,
                 f"ratio={k['silicon_over_nominal_ratio']:.2f} "
                 f"ratio_pass={k['ratio_gate_pass']} "
                 f"sigma0={k['sigma0_fused_bit_exact']} "
                 f"tokens={k['sigma_pos_token_parity']} "
                 f"json={KERNEL_OUT_PATH}"))
    return rows


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small seed counts (CI)")
    ap.add_argument("--only-kernel", action="store_true",
                    help="run only the fused-kernel section "
                         "(BENCH_silicon_kernel.json)")
    args = ap.parse_args()
    runner = run_kernel if args.only_kernel else run
    for name, us, derived in runner(quick=args.smoke):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
