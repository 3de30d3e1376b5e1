"""Observability report: the drift story, from the exported trace.

A drifting silicon fleet served under a detail bus; the exported JSONL
trace alone (re-read from disk, not the live buffer) must reconstruct the
full drift-alarm -> retrim/retire -> recalibration maintenance narrative,
render the fleet tier heatmap, and the engine's metrics must round-trip
through the Prometheus text exposition. (Time on the chip is not measured
here: a ``jax.profiler`` trace holds the program's spans and scopes.)

Emits ``BENCH_obs.json`` plus the sample trace ``BENCH_obs_trace.jsonl``
(both CI artifacts) and the ``benchmarks/run.py`` CSV rows.

CLI: ``PYTHONPATH=src python -m benchmarks.obs_report [--smoke]``.
"""
# repro-lint: module=observability

from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax
import jax.numpy as jnp

from repro import obs
from repro.compiler.tiling import Fleet
from repro.configs.base import MFTechniqueConfig, ModelConfig
from repro.core.cim import CimConfig
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine

OUT_PATH = os.environ.get("BENCH_OBS_OUT", "BENCH_obs.json")
TRACE_PATH = os.environ.get("BENCH_OBS_TRACE_OUT", "BENCH_obs_trace.jsonl")


# ---------------------------------------------------------------------------
# The drift story + heatmap + export round-trips.
# ---------------------------------------------------------------------------

def _drift_cfg():
    return ModelConfig(
        name="serve-tiny", family="lm", n_layers=2, d_model=32,
        n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
        dtype=jnp.float32,
        mf=MFTechniqueConfig(mode="cim_sim",
                             cim=CimConfig(4, 4, 5, 31)))


def _drift_section() -> dict:
    """Aggressively drifting tiny fleet under a detail bus; every gate is
    evaluated on the RE-READ JSONL export, proving the on-disk artifact
    alone explains the maintenance incident."""
    from repro.calib.report import calibrate_lm
    from repro.data.synthetic import DataConfig, lm_batch
    from repro.silicon.drift import DriftPolicy
    from repro.silicon.instance import SiliconConfig

    cfg = _drift_cfg()
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    fleet = Fleet(n_macros=256, cfg=cfg.mf.cim)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=8,
                    global_batch=2, task="uniform")
    cal = [{"tokens": jnp.asarray(lm_batch(dc, i)["tokens"])}
           for i in range(2)]
    art = calibrate_lm(params, cfg, cal, method="amax")
    scfg = SiliconConfig(cap_sigma=0.02, comparator_sigma_v=0.008,
                         drift_sigma_v_per_kstream=8.0)
    pol = DriftPolicy(probe_batches=cal, check_interval=8,
                      silicon_update_interval=4,
                      rel_l2_alarm_ratio=1.2, rel_l2_alarm_floor=0.01)
    with obs.tracing(detail=True) as buf:
        eng = ServeEngine(params, cfg, slots=2, max_len=48, fleet=fleet,
                          batched_prefill=False, calibration=art,
                          silicon=scfg, drift=pol)
        eng.run([Request(prompt=[1, 2, 3], max_new_tokens=12)
                 for _ in range(2)])
        prom_text = obs.to_prometheus(eng.metrics)
        live = buf.events()
        assert buf.dropped == 0, "ring evicted events at smoke scale"

    # JSONL round-trip: the export IS the trace.
    n_written = obs.write_trace_jsonl(live, TRACE_PATH)
    events = obs.read_trace_jsonl(TRACE_PATH)
    assert [e.to_json() for e in events] == [e.to_json() for e in live]

    story = obs.drift_story(events)
    assert story.complete, (
        f"drift story incomplete from exported trace: alarm="
        f"{story.alarm_stream} recal={story.recal_stream} "
        f"retire={story.retire_stream}")
    timeline = obs.from_events(events)
    heat = obs.fleet_heatmap(timeline)
    assert heat["retired_now"] > 0 and heat["coarse_now"] > 0, heat
    assert timeline.residue_fs.size > 0, "detail bus shipped no residues"
    assert sum(timeline.recal_reload_bits) > 0
    assert sum(timeline.recal_energy_nj) > 0.0

    # Prometheus round-trip: parse back and compare against the live
    # registry, repr-exact for scalars, count-exact for histograms.
    parsed = obs.parse_prometheus(prom_text)
    for m in eng.metrics.metrics():
        if isinstance(m, (obs.Counter, obs.Gauge)):
            assert parsed[m.name]["value"] == float(m.value), m.name
        else:
            assert parsed[m.name]["count"] == float(sum(m.counts)), m.name
    drift_counters = eng.counters()
    assert drift_counters["drift_alarms"] >= 1
    assert drift_counters["recals"] >= 1

    return {
        "trace_path": TRACE_PATH,
        "events_exported": n_written,
        "event_kinds": sorted({e.kind for e in events}),
        "jsonl_roundtrip": True,
        "prometheus_roundtrip": True,
        "story": {
            "complete": story.complete,
            "alarm_stream": story.alarm_stream,
            "recal_stream": story.recal_stream,
            "retire_stream": story.retire_stream,
            "steps": story.steps,
        },
        "probes": [dataclasses.asdict(p) for p in timeline.probes],
        "recal_reload_bits": timeline.recal_reload_bits,
        "recal_energy_nj": timeline.recal_energy_nj,
        "heatmap": heat,
    }


def run(quick: bool = True):
    """The drift story has one size; ``quick`` is the suite protocol's."""
    drift = _drift_section()
    payload = {"bench": "obs_report", "drift": drift}
    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")

    story = drift["story"]
    return [
        ("obs_drift_story", 0.0,
         f"complete={story['complete']} alarm@{story['alarm_stream']} "
         f"recal@{story['recal_stream']} retire@{story['retire_stream']} "
         f"retired={drift['heatmap']['retired_now']} "
         f"coarse={drift['heatmap']['coarse_now']}"),
        ("obs_export_roundtrip", 0.0,
         f"events={drift['events_exported']} jsonl+prometheus exact "
         f"json={OUT_PATH} trace={TRACE_PATH}"),
    ]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="accepted for CI; the drift story has one size")
    args = ap.parse_args()
    for name, us, derived in run(quick=args.smoke):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
