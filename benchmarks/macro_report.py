"""Macro zoo report: pluggable CIM macro models behind one registry.

Five sections, gating the subsystem of ``repro.macros``:

  * **parity** — the SA-ADC *plug-in* must be indistinguishable from the
    pre-registry silicon path: bitwise-identical served tokens at σ=0
    for EVERY registered flavour's ``nominal()``, and exact-code
    identity (same sampled fleet, same projection views, same served
    tokens) between ``SAADC(silicon=cfg)`` and the raw ``SiliconConfig``
    at σ>0.
  * **design_points** — the area re-budget table: per flavour, the
    widest µArray half that fits the source paper's fixed 31×5 area
    envelope once the flavour's (amortised) ADC cost is paid. Gated:
    collaborative digitization must open ≥ 2 NEW design points strictly
    wider than M=31, all within the envelope.
  * **compiler** — the same smoke LM lowered onto the reference SA-ADC
    fleet and onto a collaborative re-budgeted fleet of the same macro
    count and area: strictly fewer µArray tiles, with the Eq. 4
    latency/energy deltas of the trade (wider MAV, arbitration tail,
    bridge charge) rolled up honestly.
  * **yield** — Monte-Carlo mismatch sweeps (``projection_yield_curve``)
    parameterised over macro models, at the new collaborative design
    points next to the SA-ADC 31×5 baseline, plus the P-8T matching
    advantage at the mismatch corner.
  * **aging** — error creep of an aging fleet: per service age, the
    offset residue and projection SQNR without maintenance, with the
    fine-only re-trim, and with the tiered coarse re-trim; tier counts
    (fine / coarse / retired) per age. A serving engine under
    accelerated drift then surfaces screened-out slots in
    ``ServeReport.retired_slots``.

Emits ``BENCH_macros.json`` and the ``benchmarks/run.py`` CSV rows.

CLI: ``PYTHONPATH=src python -m benchmarks.macro_report [--smoke]``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.calib.report import calibrate_lm
from repro.compiler.cost import model_cost
from repro.compiler.frontend import projection_layer_stats
from repro.compiler.schedule import compile_model
from repro.compiler.tiling import Fleet
from repro.configs.base import MFTechniqueConfig
from repro.configs.qwen3_0_6b import SMOKE
from repro.core.cim import CimConfig, cim_mf_matmul
from repro.data.synthetic import DataConfig, lm_batch
from repro.macros import (CollaborativeDigitization, P8T, SAADC, available,
                          fleet_for_macro, get_macro, reference_budget_units)
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine
from repro.silicon.drift import DriftPolicy
from repro.silicon.instance import (SiliconConfig, age, fleet_silicon,
                                    projection_silicon,
                                    recalibrate_comparators,
                                    retrim_comparators, sample_fleet)
from repro.silicon.montecarlo import projection_yield_curve

OUT_PATH = os.environ.get("BENCH_MACROS_OUT", "BENCH_macros.json")

# The fixed area envelope every flavour re-budgets against: the source
# paper's 8x62 half (M=31, A_P=5) at 8·31 cells + un-shared SA-ADC.
BASE_CIM = CimConfig(w_bits=8, x_bits=8, adc_bits=5, m_columns=31)
# Collaborative design points the re-budget must open (group_size,
# adc_bits); ≥ MIN_NEW_DESIGNS of them must land strictly wider than
# M=31 inside the envelope.
COLLAB_POINTS = ((4, 5), (4, 6), (2, 6))
MIN_NEW_DESIGNS = 2
# σ>0 parity / aging silicon (the silicon_report conventions: 8 mV
# comparator sigma puts the calibrated residue just under the 31-level
# half-LSB decision boundary).
CMP_SIGMA_V = 0.008
SIGMA_POS = SiliconConfig(cap_sigma=0.02, comparator_sigma_v=CMP_SIGMA_V)


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


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------

def _parity_section(params, cfg, cim, rows):
    fleet = Fleet(n_macros=4096, cfg=cim)

    def mk(silicon):
        return ServeEngine(params, cfg, slots=2, max_len=16, fleet=fleet,
                           batched_prefill=False, silicon=silicon)

    t0 = time.time()
    ref_toks = _greedy_tokens(mk(None), 4, 2)
    nominal_exact = {}
    for name in available():
        model = get_macro(name).nominal()
        assert model.is_nominal
        nominal_exact[name] = _greedy_tokens(mk(model), 4, 2) == ref_toks

    # σ>0: the SAADC wrapper IS the raw-config path — same sampled
    # fleet, same projection views, same served tokens.
    raw = fleet_silicon(fleet, SIGMA_POS)
    wrapped = fleet_silicon(fleet, SAADC(silicon=SIGMA_POS))
    fleet_exact = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(raw), jax.tree.leaves(wrapped)))
    k, n = 3 * cim.m_columns + 5, 9
    view_cfg = projection_silicon(raw, SIGMA_POS, k, n)
    view_mac = projection_silicon(raw, SAADC(silicon=SIGMA_POS), k, n)
    view_exact = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(view_cfg),
                        jax.tree.leaves(view_mac)))
    token_exact = (_greedy_tokens(mk(SIGMA_POS), 4, 2)
                   == _greedy_tokens(mk(SAADC(silicon=SIGMA_POS)), 4, 2))
    us = (time.time() - t0) * 1e6

    assert all(nominal_exact.values()), (
        f"nominal macro decode diverged from silicon-free: {nominal_exact}")
    assert fleet_exact, "SAADC plug-in sampled a different fleet at sigma>0"
    assert view_exact, "SAADC plug-in projection views diverged at sigma>0"
    assert token_exact, "SAADC plug-in served tokens diverged at sigma>0"
    rows.append(("macro_parity", us,
                 f"nominal_bit_exact={sorted(nominal_exact)} "
                 f"saadc_sigma_pos_exact={token_exact}"))
    return {
        "flavours": sorted(available()),
        "nominal_bit_exact": nominal_exact,
        "saadc_sigma_pos_fleet_exact": fleet_exact,
        "saadc_sigma_pos_view_exact": view_exact,
        "saadc_sigma_pos_token_exact": token_exact,
    }


# ---------------------------------------------------------------------------
# design points
# ---------------------------------------------------------------------------

def _design_section(rows):
    budget = reference_budget_units(BASE_CIM)
    base_fleet = Fleet(n_macros=64, cfg=BASE_CIM)
    t0 = time.time()
    table = []
    models = [("saadc", SAADC(), BASE_CIM.adc_bits),
              ("p8t", P8T(), BASE_CIM.adc_bits)]
    models += [(f"collaborative_g{g}", CollaborativeDigitization(group_size=g),
                a) for g, a in COLLAB_POINTS]
    new_points = []
    for label, model, adc_bits in models:
        f = fleet_for_macro(model, base_fleet, adc_bits=adc_bits)
        entry = {
            "label": label,
            "design": f"{f.cfg.m_columns}x{f.cfg.adc_bits}",
            "m_columns": f.cfg.m_columns,
            "adc_bits": f.cfg.adc_bits,
            "within_envelope": model.half_area_units(f.cfg) <= budget,
        } | model.describe(f.cfg)
        table.append(entry)
        assert entry["within_envelope"], (
            f"{label} re-budget exceeded the {budget:.0f}-unit envelope")
        if label.startswith("collaborative") \
                and f.cfg.m_columns > BASE_CIM.m_columns:
            new_points.append(entry["design"])
    us = (time.time() - t0) * 1e6
    assert len(set(new_points)) >= MIN_NEW_DESIGNS, (
        f"collaborative re-budget opened only {sorted(set(new_points))}, "
        f"need >= {MIN_NEW_DESIGNS} points wider than "
        f"M={BASE_CIM.m_columns}")
    rows.append(("macro_design_points", us,
                 f"budget={budget:.0f}u new={sorted(set(new_points))} "
                 f"saadc={BASE_CIM.m_columns}x{BASE_CIM.adc_bits}"))
    return {"budget_units": budget,
            "reference_design":
                f"{BASE_CIM.m_columns}x{BASE_CIM.adc_bits}",
            "min_new_designs": MIN_NEW_DESIGNS,
            "new_collaborative_designs": sorted(set(new_points)),
            "table": table}


# ---------------------------------------------------------------------------
# compiler
# ---------------------------------------------------------------------------

def _compiler_section(params, rows):
    """Same LM, same macro count, same per-slot area — the collaborative
    fleet's wider halves must strictly shrink the tile count, and the
    Eq. 4 roll-up must price the flavour's latency/energy overheads."""
    stats, _ = projection_layer_stats(params)
    base = Fleet(n_macros=4096, cfg=BASE_CIM)
    collab = CollaborativeDigitization(group_size=4)
    rebud = fleet_for_macro(collab, base, adc_bits=BASE_CIM.adc_bits)
    t0 = time.time()
    sched_b = compile_model(stats, base)
    sched_c = compile_model(stats, rebud)
    _, cost_b = model_cost(sched_b)
    _, cost_c = model_cost(sched_c)
    us = (time.time() - t0) * 1e6
    tiles_ok = sched_c.total_tiles < sched_b.total_tiles
    cols_ok = rebud.cfg.m_columns > base.cfg.m_columns
    assert cols_ok, "collaborative re-budget did not widen the µArray half"
    assert tiles_ok, (
        f"wider halves did not shrink the schedule: "
        f"{sched_c.total_tiles} vs {sched_b.total_tiles} tiles")
    rows.append(("macro_compiler_rebudget", us,
                 f"m={base.cfg.m_columns}->{rebud.cfg.m_columns} "
                 f"tiles={sched_b.total_tiles}->{sched_c.total_tiles} "
                 f"unit_ops={cost_b.unit_ops}->{cost_c.unit_ops} "
                 f"energy={cost_b.energy_j:.3e}->{cost_c.energy_j:.3e}J"))
    return {
        "design": {"base": f"{base.cfg.m_columns}x{base.cfg.adc_bits}",
                   "collaborative":
                       f"{rebud.cfg.m_columns}x{rebud.cfg.adc_bits}"},
        "total_tiles": {"base": sched_b.total_tiles,
                        "collaborative": sched_c.total_tiles},
        "tiles_strictly_fewer": tiles_ok,
        "unit_ops": {"base": cost_b.unit_ops,
                     "collaborative": cost_c.unit_ops},
        "cycles": {"base": cost_b.cycles, "collaborative": cost_c.cycles},
        "latency_s": {"base": cost_b.latency_s,
                      "collaborative": cost_c.latency_s},
        "compute_energy_j": {"base": cost_b.compute_energy_j,
                             "collaborative": cost_c.compute_energy_j},
        "tops_per_w": {"base": cost_b.tops_per_w,
                       "collaborative": cost_c.tops_per_w},
        "eq4_delta": {
            "unit_ops_ratio": cost_c.unit_ops / cost_b.unit_ops,
            "cycles_ratio": cost_c.cycles / cost_b.cycles,
            "energy_ratio": cost_c.energy_j / cost_b.energy_j,
        },
    }


# ---------------------------------------------------------------------------
# yield
# ---------------------------------------------------------------------------

def _yield_section(rows, quick):
    """MC mismatch sweeps over the zoo, at each flavour's re-budgeted
    design point (same fixed area envelope for all)."""
    sigmas = (0.05, 0.12, 0.2)
    n_seeds = 16 if quick else 64
    base_fleet = Fleet(n_macros=64, cfg=BASE_CIM)
    sweeps = [("saadc_31x5", SAADC(silicon=SiliconConfig(
        comparator_sigma_v=0.0)), BASE_CIM)]
    for g, a in COLLAB_POINTS:
        m = CollaborativeDigitization(
            group_size=g, silicon=SiliconConfig(comparator_sigma_v=0.0))
        f = fleet_for_macro(m, base_fleet, adc_bits=a)
        sweeps.append((f"collaborative_g{g}_{f.cfg.m_columns}x{a}", m,
                       f.cfg))
    p8t = P8T(silicon=SiliconConfig(comparator_sigma_v=0.0))
    f = fleet_for_macro(p8t, base_fleet)
    sweeps.append((f"p8t_{f.cfg.m_columns}x{f.cfg.adc_bits}", p8t, f.cfg))

    out = {}
    for label, model, cim in sweeps:
        k, n = 2 * cim.m_columns, 6
        x = jax.random.normal(jax.random.PRNGKey(0), (8, k))
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
        t0 = time.time()
        pts = projection_yield_curve(jax.random.PRNGKey(42), x, w, cim,
                                     model, sigmas, n_seeds)
        out[label] = [p.to_dict() for p in pts]
        worst = pts[-1]
        rows.append((f"macro_yield_{label}", (time.time() - t0) * 1e6,
                     f"sqnr@sigma{sigmas[0]}={pts[0].mean_sqnr_db:.1f}dB "
                     f"@sigma{worst.cap_sigma}={worst.mean_sqnr_db:.1f}dB "
                     f"yield={worst.yield_frac:.2f} seeds={n_seeds}"))
    collab_curves = [k for k in out if k.startswith("collaborative")]
    assert len(collab_curves) >= MIN_NEW_DESIGNS, (
        f"yield sweeps cover only {collab_curves}")
    # the P-8T matching advantage must show at the mismatch corner
    p8t_label = next(k for k in out if k.startswith("p8t"))
    p8t_ok = (out[p8t_label][-1]["mean_sqnr_db"]
              > out["saadc_31x5"][-1]["mean_sqnr_db"])
    return {"sigmas": list(sigmas), "n_seeds": n_seeds,
            "p8t_matching_wins_at_corner": p8t_ok, "curves": out}


# ---------------------------------------------------------------------------
# aging
# ---------------------------------------------------------------------------

def _aging_fleet_section(rows):
    """Error creep vs service age at the projection level: no
    maintenance, fine-only re-trim, tiered re-trim — with tier counts."""
    cim = BASE_CIM
    scfg = dataclasses.replace(SIGMA_POS, cap_sigma=0.0,
                               drift_sigma_v_per_kstream=0.3)
    k, n = 2 * cim.m_columns, 6
    n_slots = 256
    x = jax.random.normal(jax.random.PRNGKey(0), (8, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    y0 = cim_mf_matmul(x, w, cim)

    def sqnr(sil):
        view = projection_silicon(sil, scfg, k, n)
        y = cim_mf_matmul(x, w, cim, silicon=view)
        num = float(np.sum(np.asarray(y0, np.float64) ** 2))
        err = float(np.sum((np.asarray(y, np.float64)
                            - np.asarray(y0, np.float64)) ** 2))
        return 10.0 * np.log10(num / max(err, num * 1e-12))

    fresh = sample_fleet(jax.random.PRNGKey(11), n_slots, cim.m_columns,
                         scfg)
    t0 = time.time()
    creep = []
    for streams in (0, 50, 100, 150, 300, 1000):
        aged = age(fresh, streams)
        fine = recalibrate_comparators(aged, scfg)
        tiered, tiers = retrim_comparators(aged, scfg)
        tiers = np.asarray(tiers)
        creep.append({
            "age_streams": streams,
            "sqnr_db_no_recal": sqnr(aged),
            "sqnr_db_fine_recal": sqnr(fine),
            "sqnr_db_tiered_retrim": sqnr(tiered),
            "tier_fine": int((tiers == 0).sum()),
            "tier_coarse": int((tiers == 1).sum()),
            "tier_retired": int((tiers == 2).sum()),
        })
    us = (time.time() - t0) * 1e6
    last = creep[-1]
    # once drift saturates the fine DAC, the coarse tier must be the
    # better maintenance action
    saturated = [c for c in creep if c["tier_coarse"] > 0]
    tiered_wins = all(c["sqnr_db_tiered_retrim"]
                      >= c["sqnr_db_fine_recal"] for c in saturated)
    assert saturated, "aging sweep never engaged the coarse tier"
    losses = [(c["age_streams"], c["sqnr_db_fine_recal"],
               c["sqnr_db_tiered_retrim"]) for c in saturated]
    assert tiered_wins, (
        f"tiered re-trim lost to the saturated fine DAC: {losses}")
    assert last["tier_retired"] > 0, (
        "deep-age fleet retired no slots — screening is vacuous")
    rows.append(("macro_aging_creep", us,
                 f"ages={[c['age_streams'] for c in creep]} "
                 f"retired@{last['age_streams']}={last['tier_retired']} "
                 f"tiered>=fine={tiered_wins}"))
    return {"drift_sigma_v_per_kstream": scfg.drift_sigma_v_per_kstream,
            "n_slots": n_slots, "tiered_beats_fine_when_saturated":
                tiered_wins, "creep": creep}


def _aging_engine_section(params, cfg, cim, rows):
    """Accelerated drift under serving: the drift alarm triggers the
    tiered re-trim and the screened-out slots surface in ServeReport."""
    cal = _batches(cfg, 3)
    artifact = calibrate_lm(params, cfg, cal, method="amax")
    policy = DriftPolicy(probe_batches=cal[:2], check_interval=16,
                         silicon_update_interval=8,
                         rel_l2_alarm_ratio=1.3, rel_l2_alarm_floor=0.02)
    # ~12 V/kstream: by the first check (stream 16) the drift scale is
    # ~190 mV — far past the ±90 mV coarse window for most slots, so the
    # re-trim retires a visible fraction of the fleet.
    scfg = dataclasses.replace(SIGMA_POS, drift_sigma_v_per_kstream=12.0)
    fleet = Fleet(n_macros=4096, cfg=cim)
    t0 = time.time()
    eng = ServeEngine(params, cfg, slots=2, max_len=48, fleet=fleet,
                      batched_prefill=False, calibration=artifact,
                      silicon=scfg, drift=policy)
    eng.run([Request(prompt=[1, 2, 3], max_new_tokens=32)
             for _ in range(2)])
    us = (time.time() - t0) * 1e6
    rep = eng.last_report
    # ServeReport.retired_slots is the LEVEL after the latest re-trim, so
    # it must agree with the last recalibrated drift-log entry (earlier
    # entries saw less drift and retired fewer slots).
    recal = next((s for s in reversed(eng.drift_log) if s.recalibrated),
                 None)
    assert rep.recalibrations >= 1, "accelerated drift never re-trimmed"
    assert rep.retired_slots > 0, (
        "saturating drift retired no slots in ServeReport")
    assert recal is not None and recal.retired_slots == rep.retired_slots
    rows.append(("macro_aging_serve", us,
                 f"recals={rep.recalibrations} "
                 f"retired={rep.retired_slots}/{fleet.tile_slots} "
                 f"coarse={recal.retrim_coarse_slots}"))
    return {"drift_sigma_v_per_kstream": scfg.drift_sigma_v_per_kstream,
            "tile_slots": fleet.tile_slots,
            "recalibrations": rep.recalibrations,
            "retired_slots": rep.retired_slots,
            "retrim_coarse_slots": recal.retrim_coarse_slots,
            "drift_log": [s.to_dict() for s in eng.drift_log]}


def run(quick: bool = True):
    rows = []
    cfg = _lm_cfg(BASE_CIM)
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    payload = {
        "bench": "macro_report",
        "quick": quick,
        "config": cfg.name,
        "registry": sorted(available()),
        "parity": _parity_section(params, cfg, BASE_CIM, rows),
        "design_points": _design_section(rows),
        "compiler": _compiler_section(params, rows),
        "yield": _yield_section(rows, quick),
        "aging_fleet": _aging_fleet_section(rows),
        "aging_serve": _aging_engine_section(params, cfg, BASE_CIM, rows),
    }
    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    d = payload["design_points"]
    c = payload["compiler"]
    rows.append(("macro_gate", 0.0,
                 f"parity=True new_designs={d['new_collaborative_designs']} "
                 f"tiles_fewer={c['tiles_strictly_fewer']} "
                 f"retired={payload['aging_serve']['retired_slots']} "
                 f"json={OUT_PATH}"))
    return rows


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small seed counts (CI)")
    args = ap.parse_args()
    for name, us, derived in run(quick=args.smoke):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
