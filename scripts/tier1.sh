#!/usr/bin/env bash
# Tier-1 gate: fast test suite + compiler-report benchmark smoke.
# TIER1_SERVE_BENCH=1 additionally runs the serve-decode bench smoke
# (programmed vs legacy CIM decode) and leaves BENCH_serve.json behind.
# TIER1_CALIB_BENCH=1 additionally runs the calibration accuracy smoke
# (calibrated vs static activation scales) and leaves BENCH_calib.json.
# TIER1_SILICON_BENCH=1 additionally runs the silicon variation smoke
# (sigma=0 parity, yield sweeps, offset-correction recovery, drift
# auto-recalibration) and leaves BENCH_silicon.json.
# TIER1_TRAFFIC_BENCH=1 additionally runs the traffic serving smoke
# (offered-load sweep, SLO knee, mesh parity, multi-device scaling) and
# leaves BENCH_traffic.json.
# TIER1_KERNEL_BENCH=1 additionally runs ONLY the fused Pallas kernel
# section of the silicon report (nominal vs silicon fused decode tok/s
# gate, sigma=0 bitwise collapse, sigma>0 exact-code parity) and leaves
# BENCH_silicon_kernel.json — a fast alternative to the full
# TIER1_SILICON_BENCH report, which includes the same section.
# TIER1_MACRO_BENCH=1 additionally runs the macro-zoo smoke (registry
# parity, collaborative area re-budget + compiler tile shrink, MC yield
# over macro models, tiered re-trim aging) and leaves BENCH_macros.json.
# TIER1_OBS_BENCH=1 additionally runs the observability smoke (drift
# alarm -> retrim/retire -> recal story reconstructed from the exported
# trace) and leaves BENCH_obs.json + BENCH_obs_trace.jsonl.
# TIER1_LINT=1 additionally gates on the static passes: repro-lint
# (python -m repro.analysis, zero unsuppressed findings vs the shrink-only
# analysis_baseline.json) and ruff when it is installed.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${TIER1_LINT:-0}" == "1" ]]; then
  python -m repro.analysis src benchmarks tests --stats
  if command -v ruff >/dev/null 2>&1; then
    ruff check src tests benchmarks
  else
    echo "tier1: ruff not installed; skipping (CI runs it)" >&2
  fi
fi
python -m pytest -x -q -m "not slow"
python -m benchmarks.run --only compiler
if [[ "${TIER1_SERVE_BENCH:-0}" == "1" ]]; then
  python -m benchmarks.serve_bench --smoke
fi
if [[ "${TIER1_CALIB_BENCH:-0}" == "1" ]]; then
  python -m benchmarks.calib_report --smoke
fi
if [[ "${TIER1_SILICON_BENCH:-0}" == "1" ]]; then
  python -m benchmarks.silicon_report --smoke
fi
if [[ "${TIER1_TRAFFIC_BENCH:-0}" == "1" ]]; then
  python -m benchmarks.traffic_report --smoke
fi
if [[ "${TIER1_KERNEL_BENCH:-0}" == "1" ]]; then
  python -m benchmarks.silicon_report --smoke --only-kernel
fi
if [[ "${TIER1_MACRO_BENCH:-0}" == "1" ]]; then
  python -m benchmarks.macro_report --smoke
fi
if [[ "${TIER1_OBS_BENCH:-0}" == "1" ]]; then
  python -m benchmarks.obs_report --smoke
fi
