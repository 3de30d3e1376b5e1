"""Calibration accuracy report: does data-driven `sx` beat full-scale?

For each evaluation model (the qwen3 smoke LM with every projection on
``cim_sim``, and the paper's LeNet-5 conv net) this suite:

  1. collects per-projection activation statistics over a synthetic
     calibration corpus (one observe pass, float MF reference forward),
  2. programs the model four ways — static full-scale ``act_amax=4.0``
     (the PR 2 default) and the three corpus-driven policies (amax /
     percentile / MSE-optimal) — at both paper ADC design points
     (8x62 -> 5-bit, 8x30 -> 4-bit, exactly lossless) AND two non-lossless
     points where real ADC quantisation error is in play: A_P=6 at M=31
     (moderate rounding noise, gated) and A_P=4 at M=31 (noise-dominated,
     reported as a diagnostic only — see ``UNGATED_DESIGNS``), plus the
     macro zoo's collaborative re-budgeted geometries (``MACRO_DESIGNS``,
     ungated — the ADC-starved regime per-channel calibration targets),
  3. measures each against the fp32 MF reference on held-out batches:
     end-to-end logits error (relative L2), top-1 agreement, and
     per-projection SQNR through the error tap,
  4. checks the acceptance gate — the best calibrated policy must beat
     the static baseline on logits error AND mean SQNR for every
     (model, design) cell — and that programming the static default
     *through the scales hook* reproduces the baseline bit for bit.

Emits ``BENCH_calib.json`` (the calibration-quality trajectory anchor)
and the ``benchmarks/run.py`` CSV rows.

CLI: ``PYTHONPATH=src python -m benchmarks.calib_report [--smoke]``.
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

from repro.calib.corpus import (attach_observer_ids, collect_stats,
                                scales_from_stats)
from repro.calib.observers import ObserverConfig
from repro.calib.report import accuracy_report, lm_ref_config
from repro.configs.base import MFTechniqueConfig
from repro.configs.qwen3_0_6b import SMOKE
from repro.core.cim import CimConfig
from repro.core.programmed import (DEFAULT_ACT_AMAX, adc_exactly_lossless,
                                   default_static_sx, program_weights)
from repro.data.synthetic import DataConfig, image_batch, lm_batch
from repro.models import convnets as C
from repro.models import transformer as T

OUT_PATH = os.environ.get("BENCH_CALIB_OUT", "BENCH_calib.json")

# (m_columns, adc_bits) design points: the two paper pairings are exactly
# lossless (2^A_P - 1 == M: ADC code == discharge count), so their cells
# coincide by the lossless identity and never exercise real ADC
# quantisation. The third point (A_P=6 at M=31) is deliberately NOT
# lossless — 63 ADC levels digitising 31-column charge averages round
# every non-trivial count — so calibration there interacts with genuine
# ADC quantisation error (SQNR drops ~14 dB vs the lossless points) and
# the calibrated-beats-static gate covers it.
DESIGNS = ((31, 5), (15, 4), (31, 6))
# Diagnostic-only design points, reported but NOT gated: A_P=4 at M=31
# (the severely under-provisioned ADC) is so lossy that outputs are
# rounding-noise dominated (rel_l2 > 1, SQNR ~3-5 dB) — no activation
# scale policy reliably beats another inside pure ADC noise, which is
# itself a finding worth keeping on the record.
UNGATED_DESIGNS = ((31, 4),)
# Macro-zoo design points (also ungated): the collaborative-digitization
# re-budget trades shared ADC area for µArray columns at fixed macro
# area (repro.macros.fleet_for_macro), opening WIDER halves than any
# 2^A_P - 1 pairing — 38x5 is the ADC-starved regime (31 levels
# digitising 38-column averages) where the per-channel input-DAC trims
# are expected to earn their keep, 38x6 the moderately-rounded one.
# Computed through the same feasible_columns the compiler uses, so these
# cells track the zoo's geometry by construction.


def _macro_design_points() -> tuple[tuple[int, int], ...]:
    from repro.macros import (CollaborativeDigitization, feasible_columns,
                              reference_budget_units)
    budget = reference_budget_units(CimConfig())
    return tuple(
        (feasible_columns(CollaborativeDigitization(group_size=g), a,
                          budget_units=budget), a)
        for g, a in ((4, 5), (4, 6)))


MACRO_DESIGNS = _macro_design_points()
METHODS = ("static", "amax", "percentile", "mse")
# Per-channel variants: the scalar policy's scale shaped over each
# projection's per-feature amax profile (input-DAC gain trims; see
# repro.calib.corpus.scales_from_stats(per_channel=True)). Reported as
# an SQNR delta against the matching scalar cell, not gated, because the
# sign flips with the design point: at the exactly-lossless pairings
# (31x5, 15x4) the gain-weighted charge averages break the code==count
# identity — every S2/R_x conversion picks up real ADC rounding and the
# delta is tens of dB NEGATIVE — while at rounding-limited ADCs (31x6,
# 31x4) the finer per-channel input grids win a few dB. Per-channel
# calibration is an under-provisioned-ADC tool, not a free win.
PC_METHODS = ("amax", "mse")


@dataclasses.dataclass
class _Setup:
    """One evaluation model: forwards + corpus, design-point agnostic."""

    name: str
    params: dict
    ref_forward: callable            # (params, batch) -> logits, float MF
    cim_forward_builder: callable    # CimConfig -> (params, batch) -> logits
    cal_batches: list
    eval_batches: list


def _lm_setup(quick: bool) -> _Setup:
    base = SMOKE if quick else dataclasses.replace(
        SMOKE, d_model=128, d_ff=384, vocab_size=512)
    cfg = dataclasses.replace(
        base, dtype=jnp.float32,
        mf=MFTechniqueConfig(mode="cim_sim", cim=CimConfig()))
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    b, t = (4, 16) if quick else (8, 32)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=t, global_batch=b,
                    task="uniform")
    n_cal, n_eval = (4, 2) if quick else (6, 3)
    cal = [{"tokens": jnp.asarray(lm_batch(dc, i)["tokens"])}
           for i in range(n_cal)]
    ev = [{"tokens": jnp.asarray(lm_batch(dc, 1000 + i)["tokens"])}
          for i in range(n_eval)]

    def ref_forward(p, batch):
        return T.lm_forward(p, batch, lm_ref_config(cfg))[0]

    def cim_builder(cim: CimConfig):
        ccfg = dataclasses.replace(
            cfg, mf=dataclasses.replace(cfg.mf, cim=cim))

        def fwd(p, batch):
            return T.lm_forward(p, batch, ccfg)[0]

        return fwd

    return _Setup(cfg.name, params, ref_forward, cim_builder, cal, ev)


_LENET_REF = {"conv1": "mf", "conv2": "mf", "fc1": "mf", "fc2": "regular"}
_LENET_CIM = {"conv1": "cim_sim", "conv2": "cim_sim", "fc1": "cim_sim",
              "fc2": "regular"}


def _lenet_setup(quick: bool) -> _Setup:
    params = C.lenet_init(jax.random.PRNGKey(0))
    batch = 16 if quick else 32
    n_cal, n_eval = (4, 2) if quick else (6, 3)
    cal = [jnp.asarray(image_batch(batch, 10, 28, 1, i)[0])
           for i in range(n_cal)]
    ev = [jnp.asarray(image_batch(batch, 10, 28, 1, 1000 + i)[0])
          for i in range(n_eval)]

    def ref_forward(p, x):
        return C.lenet_apply(p, x, _LENET_REF)

    def cim_builder(cim: CimConfig):
        def fwd(p, x):
            return C.lenet_apply(p, x, _LENET_CIM, cim_cfg=cim)

        return fwd

    return _Setup("paper-mnist-lenet5", params, ref_forward, cim_builder,
                  cal, ev)


def _static_scales_map(registry, cim: CimConfig) -> dict:
    """Every projection pinned to the full-scale default — must reproduce
    the no-scales baseline bit for bit (the parity gate)."""
    sx = np.float32(default_static_sx(cim))
    return {name: np.full(shape or (), sx, np.float32)
            for name, (_, shape) in registry.entries.items()}


def run(quick: bool = True):
    rows = []
    payload = {
        "bench": "calib_accuracy",
        "quick": quick,
        "act_amax_static": DEFAULT_ACT_AMAX,
        "methods": list(METHODS),
        "per_channel_methods": [f"{m}_pc" for m in PC_METHODS],
        "designs": [f"{m}x{a}" for m, a in DESIGNS],
        "ungated_designs": [f"{m}x{a}" for m, a in UNGATED_DESIGNS],
        "macro_designs": [f"{m}x{a}" for m, a in MACRO_DESIGNS],
        "configs": {},
    }
    obs_cfg = ObserverConfig()
    all_improved = True
    for setup in (_lm_setup(quick), _lenet_setup(quick)):
        tagged, registry = attach_observer_ids(setup.params)
        t0 = time.time()
        collector = collect_stats(setup.ref_forward, tagged,
                                  setup.cal_batches, registry, obs_cfg)
        collect_us = (time.time() - t0) * 1e6
        rows.append((f"calib_collect_{setup.name}", collect_us,
                     f"projections={registry.n_ids}"))
        per_design = {}
        for m, a in DESIGNS + UNGATED_DESIGNS + MACRO_DESIGNS:
            gated = (m, a) in DESIGNS
            cim = CimConfig(w_bits=8, x_bits=8, adc_bits=a, m_columns=m)
            cim_fwd = setup.cim_forward_builder(cim)
            cells = {}
            for method in (METHODS
                           + tuple(f"{m}_pc" for m in PC_METHODS)):
                if method == "static":
                    scales = None
                else:
                    base = method.removesuffix("_pc")
                    scales = scales_from_stats(
                        collector, registry, cim.x_bits, base,
                        per_channel=method.endswith("_pc"))
                progd = program_weights(tagged, cim, scales=scales)
                t0 = time.time()
                rep = accuracy_report(
                    lambda b: setup.ref_forward(setup.params, b),
                    lambda b: cim_fwd(progd, b),
                    setup.eval_batches, registry)
                cells[method] = rep.to_dict()
                rows.append((
                    f"calib_{setup.name}_{m}x{a}_{method}",
                    (time.time() - t0) * 1e6,
                    f"rel_l2={rep.rel_l2:.5f} "
                    f"sqnr={rep.mean_sqnr_db:.2f}dB "
                    f"top1={rep.top1_agree:.3f}"))
            static = cells["static"]
            best = min((cells[meth] for meth in METHODS[1:]),
                       key=lambda c: c["rel_l2"])
            improved = (best["rel_l2"] < static["rel_l2"]
                        and best["mean_sqnr_db"] > static["mean_sqnr_db"])
            if gated:
                all_improved = all_improved and improved
            # Parity gate: the static default programmed THROUGH the
            # scales hook is the identical computation.
            prog_a = program_weights(tagged, cim)
            prog_b = program_weights(tagged, cim,
                                     scales=_static_scales_map(registry,
                                                               cim))
            batch0 = setup.eval_batches[0]
            parity = bool(np.array_equal(
                np.asarray(setup.cim_forward_builder(cim)(prog_a, batch0)),
                np.asarray(setup.cim_forward_builder(cim)(prog_b, batch0))))
            pc_delta = {
                meth: (cells[f"{meth}_pc"]["mean_sqnr_db"]
                       - cells[meth]["mean_sqnr_db"])
                for meth in PC_METHODS}
            rows.append((
                f"calib_{setup.name}_{m}x{a}_pc_delta", 0.0,
                " ".join(f"{meth}={d:+.2f}dB"
                         for meth, d in pc_delta.items())))
            per_design[f"{m}x{a}"] = {
                "cells": cells,
                "adc_exactly_lossless": adc_exactly_lossless(cim),
                "gated": gated,
                "macro_zoo": (m, a) in MACRO_DESIGNS,
                "calibrated_beats_static": improved,
                "per_channel_sqnr_delta_db": pc_delta,
                "static_scales_parity": parity,
            }
            if not parity:
                raise RuntimeError(
                    f"{setup.name} {m}x{a}: static scales through the "
                    f"scales hook broke bit-exact parity")
        payload["configs"][setup.name] = per_design
    payload["calibrated_beats_static_everywhere"] = all_improved

    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")

    rows.append(("calib_gate", 0.0,
                 f"calibrated_beats_static={all_improved} json={OUT_PATH}"))
    if not all_improved:
        raise RuntimeError(
            "calibrated scales did not beat the static full-scale baseline "
            f"on every (model, design) cell — see {OUT_PATH}")
    return rows


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes (CI)")
    args = ap.parse_args()
    for name, us, derived in run(quick=args.smoke):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
