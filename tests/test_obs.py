"""Fleet telemetry (``repro.obs``): bus, metrics, exports, engine taps.

What is pinned here:

  * the program's host spans (``batcher.*``, ``serve.*``) land in a
    running ``jax.profiler`` trace, nested as the code nests them, and
    cost no bus event when no bus is installed;
  * the compiled step programs carry the named scopes (``attention``,
    ``kv_write``, ``mf_projection`` with the route taken) in their op
    metadata, and the engine compiles one decode program;
  * a running profiler or an installed bus leaves decoded tokens BITWISE
    identical on the pinned, swapped (rounds > 1) and silicon paths;
  * the prefill slab counter counts slots x bucket per wave;
  * histogram merge is order-invariant; windowed counter deltas sum
    exactly even when a recalibration lands inside a window;
  * Prometheus text exposition and trace JSONL both round-trip;
  * ``src/repro/obs`` is tagged ``observability`` and stays OUT of
    repro-lint's ``exactness-critical`` float-accumulation scope.
"""

from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.compiler.tiling import Fleet
from repro.configs.base import MFTechniqueConfig, ModelConfig
from repro.core.cim import CimConfig
from repro.models import transformer as T
from repro.obs import trace as obs_trace
from repro.serve.engine import Request, ServeEngine

CIM = CimConfig(4, 4, 5, 31)


def _cfg(**kw):
    base = dict(
        name="obs-tiny", family="lm", n_layers=2, d_model=32,
        n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
        dtype=jnp.float32,
        mf=MFTechniqueConfig(mode="cim_sim", cim=CIM))
    base.update(kw)
    return ModelConfig(**base)


def _params(cfg):
    return T.lm_init(jax.random.PRNGKey(0), cfg)


def _serve(eng, n=4):
    done = eng.run([Request(prompt=[1, 2, 3], max_new_tokens=n)
                    for _ in range(2)])
    return [r.out for r in done]


class TestTraceBus:
    def test_emit_without_bus_is_noop(self):
        obs_trace.emit("program", stream=0)   # must not raise or record
        assert obs.bus() is None

    def test_ring_keeps_newest_and_counts_drops(self):
        buf = obs.TraceBuffer(capacity=4)
        with obs.tracing(capacity=4) as scoped:
            del buf
            for i in range(7):
                obs_trace.emit("decode_tick", stream=i)
        assert scoped.total == 7 and scoped.dropped == 3
        assert [e.stream for e in scoped.events()] == [3, 4, 5, 6]
        seqs = [e.seq for e in scoped.events()]
        assert seqs == sorted(seqs)

    def test_tracing_scope_restores_previous_bus(self):
        outer = obs.install()
        try:
            with obs.tracing() as inner:
                obs_trace.emit("admit")
                assert obs.bus() is inner
            assert obs.bus() is outer
            assert len(inner.events()) == 1 and outer.total == 0
        finally:
            obs.uninstall()

    def test_span_records_duration(self):
        with obs.tracing() as buf:
            with obs_trace.span("recal", stream=3):
                pass
        (ev,) = buf.events()
        assert ev.kind == "recal" and ev.data["dur_ns"] >= 0


def _profile(tmp_path, fn):
    """Run ``fn`` under a ``jax.profiler`` trace; returns its result and
    the program's host spans as (name, start_ns, end_ns, stats)."""
    import glob
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(("serve.", "batcher."))]
    return out, spans


def _engine(kind, cfg, params, **kw):
    from repro.silicon.instance import SiliconConfig
    kw = dict(dict(slots=2, max_len=16, batched_prefill=False), **kw)
    if kind == "swapped":
        return ServeEngine(params, cfg, fleet=Fleet(n_macros=8, cfg=CIM),
                           **kw)
    fleet = Fleet(n_macros=1024, cfg=cfg.mf.cim)
    if kind in ("silicon", "kernel_silicon"):
        return ServeEngine(params, cfg, fleet=fleet,
                           silicon=SiliconConfig(cap_sigma=0.0,
                                                 comparator_sigma_v=0.0),
                           **kw)
    return ServeEngine(params, cfg, fleet=fleet, **kw)


class TestProgramSpans:
    def test_profile_holds_batcher_and_engine_spans(self, tmp_path):
        from repro.traffic.batching import ContinuousBatcher, VirtualClock
        from repro.traffic.workload import TrafficRequest
        cfg = _cfg()
        eng = _engine("pinned", cfg, _params(cfg), max_len=32,
                      batched_prefill=True)
        inf = float("inf")
        reqs = [TrafficRequest(rid=i, t_arrival_s=0.0, prompt=[1, 2, 3],
                               max_new_tokens=3, ttft_deadline_s=inf,
                               deadline_s=inf) for i in range(3)]
        log, spans = _profile(tmp_path, lambda: ContinuousBatcher(
            eng, VirtualClock(0.01)).run(reqs))
        assert log.ticks > 0
        names = {s[0] for s in spans}
        assert {"batcher.tick", "batcher.queue", "batcher.admit",
                "batcher.clock", "batcher.observe", "serve.admit",
                "serve.prefill", "serve.step", "serve.dispatch",
                "serve.sync", "serve.bookkeep"} <= names
        steps = [s for s in spans if s[0] == "serve.step"]
        ticks = [s for s in spans if s[0] == "batcher.tick"]
        for _, a, b, _ in (s for s in spans if s[0] == "serve.sync"):
            assert any(x <= a and b <= y for _, x, y, _ in steps)
        for _, a, b, _ in steps:
            assert any(x <= a and b <= y for _, x, y, _ in ticks)
        # The admitted request ids ride the admission span, all of them.
        rids = [int(r) for s in spans if s[0] == "batcher.admit"
                for r in str(s[3]["rids"]).split()]
        assert sorted(rids) == [0, 1, 2]

    def test_span_without_bus_appends_nothing(self):
        assert obs.bus() is None
        seq = obs_trace._SEQ              # bus-wide emission index
        with obs_trace.span("serve.step", stream=1):
            pass
        assert obs_trace._SEQ == seq and obs.bus() is None


class TestStepScopes:
    """The compiled decode and prefill programs name their layers in the
    op metadata a profile reads (``op_name``)."""

    @pytest.mark.parametrize("route", ["lossless", "planes", "swapped",
                                       "kernel", "kernel_silicon"])
    def test_scopes_in_op_metadata(self, route):
        import dataclasses
        import re
        cim = dataclasses.replace(CIM, use_kernel=route.startswith("kernel"))
        cfg = _cfg(mf=MFTechniqueConfig(mode="cim_sim", cim=cim))
        kind = {"planes": "silicon"}.get(route, route)
        eng = _engine(kind, cfg, _params(cfg), batched_prefill=True)
        assert not hasattr(eng, "_traced_step_fn")
        step = eng.step_fn.lower(
            eng._exec_params, eng.cache, jnp.zeros((2,), jnp.int32),
            jax.random.PRNGKey(0), jnp.int32(0)).compile().as_text()
        prefill = eng._prefill_fn.lower(
            eng._exec_params, eng.cache, jnp.zeros((2, 4), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.int32(0)).compile().as_text()
        for text, extra in ((step, ("lm_head", "sampling")), (prefill, ())):
            paths = set(re.findall(r'op_name="([^"]*)"', text))
            parts = {p for path in paths for p in path.split("/")}
            assert {"attention", "kv_write", "mf_projection",
                    *extra} <= parts
            assert any(f"/mf_projection/{route}/" in p for p in paths)
            assert "callback" not in text


class TestDisabledPathParity:
    """Tokens must be bitwise identical with no bus and no profiler, with
    a bus installed, and with a ``jax.profiler`` trace running: spans
    and scopes are host annotations and op metadata, never values."""

    @pytest.mark.parametrize("kind", ["pinned", "swapped", "silicon"])
    def test_bitwise_parity(self, kind, tmp_path):
        cfg = _cfg()
        params = _params(cfg)
        probe = _engine(kind, cfg, params)
        if kind == "swapped":
            assert not probe.schedule.pinned
            assert probe.schedule.rounds_max > 1
        assert obs.bus() is None
        ref = _serve(probe)
        with obs.tracing() as buf:
            assert _serve(_engine(kind, cfg, params)) == ref
        assert len(buf.by_kind("admit")) > 0
        traced, spans = _profile(
            tmp_path, lambda: _serve(_engine(kind, cfg, params)))
        assert traced == ref
        assert any(s[0] == "serve.step" for s in spans)


class TestPrefillSlabCounter:
    def test_slab_tokens_are_slots_times_bucket(self):
        cfg = _cfg()
        eng = _engine("pinned", cfg, _params(cfg), slots=4, max_len=32,
                      batched_prefill=True)
        slab = eng.metrics["serve_prefill_slab_tokens_total"]
        tokens = eng.metrics["serve_prefill_tokens_total"]
        # Waves of 2 prompts (longest 6 tokens: 5 ingested, bucket 8) and
        # of 1 prompt (3 tokens: 2 ingested, bucket 2).
        eng.submit_many([Request(prompt=[1] * 6, max_new_tokens=2),
                         Request(prompt=[2] * 3, max_new_tokens=2)])
        assert (slab.value, tokens.value) == (4 * 8, 5 + 2)
        eng.submit_many([Request(prompt=[3] * 3, max_new_tokens=2)])
        assert (slab.value, tokens.value) == (4 * 8 + 4 * 2, 5 + 2 + 2)
        assert eng.counters()["prefill_calls"] == 2


class TestMetrics:
    def test_counter_monotonic(self):
        c = obs.Counter("c")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError, match="negative"):
            c.inc(-1)

    def test_histogram_merge_is_order_invariant(self):
        rng = np.random.default_rng(0)
        xs = rng.exponential(0.1, size=300)
        shards = []
        for part in np.array_split(xs, 3):
            h = obs.Histogram("h", obs.LATENCY_EDGES_S)
            h.observe_many(part)
            shards.append(h)
        orders = [(0, 1, 2), (2, 0, 1), (1, 2, 0)]
        merged = []
        for order in orders:
            acc = obs.Histogram("h", obs.LATENCY_EDGES_S)
            for i in order:
                acc.merge(shards[i])
            merged.append(acc)
        one = obs.Histogram("h", obs.LATENCY_EDGES_S)
        one.observe_many(xs)
        for acc in merged:
            np.testing.assert_array_equal(acc.counts, merged[0].counts)
            np.testing.assert_array_equal(acc.counts, one.counts)
            assert acc.count == one.count == 300

    def test_histogram_merge_rejects_edge_mismatch(self):
        a = obs.Histogram("h", (1.0, 2.0))
        b = obs.Histogram("h", (1.0, 3.0))
        with pytest.raises(ValueError, match="incompatible"):
            a.merge(b)

    def test_histogram_edges_validated(self):
        with pytest.raises(ValueError, match="ascending"):
            obs.Histogram("h", (1.0, 1.0))

    def test_quantile_interpolates_and_clamps(self):
        h = obs.Histogram("h", (1.0, 2.0, 4.0))
        assert np.isnan(h.quantile(0.5))
        h.observe_many([0.5, 1.5, 3.0, 100.0])
        assert 0.0 <= h.quantile(0.25) <= 1.0
        assert h.quantile(1.0) == 4.0          # overflow rank clamps
        with pytest.raises(ValueError, match="outside"):
            h.quantile(1.5)

    def test_registry_get_or_create_and_conflicts(self):
        m = obs.MetricsRegistry()
        c = m.counter("x_total", "help")
        assert m.counter("x_total") is c
        with pytest.raises(ValueError, match="already"):
            m.gauge("x_total")
        h = m.histogram("lat_s", (1.0, 2.0))
        assert m.histogram("lat_s", (1.0, 2.0)) is h
        with pytest.raises(ValueError, match="edges"):
            m.histogram("lat_s", (1.0, 3.0))
        with pytest.raises(ValueError, match="Prometheus"):
            m.counter("bad name")

    def test_window_deltas_sum_exactly_gauges_stay_levels(self):
        m = obs.MetricsRegistry()
        c = m.counter("events_total")
        g = m.gauge("level_now")
        s0 = m.snapshot()
        c.inc(3)
        g.set(7)
        s1 = m.snapshot()
        c.inc(5)
        g.set(2)
        w1 = {k: s1[k] - s0.get(k, 0.0) for k in ("events_total",)}
        w2 = m.delta(s1)
        assert w1["events_total"] + w2["events_total"] == 8.0
        assert w2["level_now"] == 2.0          # level, not a difference


class TestEngineWindowedCounters:
    def test_recal_inside_window_counted_once(self):
        """A recalibration straddled by a snapshot boundary must appear
        in exactly one window, and the two windows must sum to the run
        totals (the TrafficReport windowing contract)."""
        from repro.calib.report import calibrate_lm
        from repro.data.synthetic import DataConfig, lm_batch
        from repro.silicon.drift import DriftPolicy
        from repro.silicon.instance import SiliconConfig
        cfg = _cfg()
        params = _params(cfg)
        dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=8,
                        global_batch=2, task="uniform")
        cal = [{"tokens": jnp.asarray(lm_batch(dc, i)["tokens"])}
               for i in range(2)]
        art = calibrate_lm(params, cfg, cal, method="amax")
        scfg = SiliconConfig(cap_sigma=0.02, comparator_sigma_v=0.008,
                             drift_sigma_v_per_kstream=8.0)
        pol = DriftPolicy(probe_batches=cal, check_interval=8,
                          silicon_update_interval=4,
                          rel_l2_alarm_ratio=1.2,
                          rel_l2_alarm_floor=0.01)
        eng = ServeEngine(params, cfg, slots=2, max_len=48,
                          fleet=Fleet(n_macros=256, cfg=CIM),
                          batched_prefill=False, calibration=art,
                          silicon=scfg, drift=pol)
        c0 = eng.counters()
        for r in [Request(prompt=[1, 2, 3], max_new_tokens=12)
                  for _ in range(2)]:
            eng.submit(r)
        with obs.tracing() as buf:
            # Window 1 ends at stream 6; the first drift probe (and on
            # alarm, its recalibration) fires at stream 8 — inside
            # window 2, straddling nothing.
            for _ in range(6):
                eng.step()
            c1 = eng.counters()
            while eng.occupied_slots:
                eng.step()
            c2 = eng.counters()
        for key in ("decode_steps", "decode_tokens", "recals",
                    "recal_bits", "drift_checks", "drift_alarms"):
            w1 = c1[key] - c0[key]
            w2 = c2[key] - c1[key]
            assert w1 >= 0 and w2 >= 0, key
            assert w1 + w2 == c2[key] - c0[key], key
        assert c2["recals"] >= 1
        assert (c1["recals"] - c0["recals"]) == 0   # recal in window 2
        # Trace agreement: recal events on the bus == counter delta.
        assert len(buf.by_kind("recal")) == c2["recals"] - c0["recals"]
        # The retrim-tier numbers are gauges (levels): window 2's level
        # stands alone, it is never summed with window 1's.
        assert c2["retired_slots"] >= 0
        rep = eng.report_since(c1, elapsed_s=1.0)
        assert rep.recalibrations == c2["recals"] - c1["recals"]


class TestHealthTimelines:
    def _trace(self):
        with obs.tracing(capacity=256, detail=True) as buf:
            obs_trace.emit("drift_probe", stream=8, rel_l2=0.05,
                           baseline_rel_l2=0.01, max_clip_ratio=0.0,
                           alarm=True, recalibrated=True,
                           reasons=["rel_l2"],
                           residue_fs=[0.1, 0.9, 0.2, 0.05])
            obs_trace.emit("retrim", stream=8, coarse=1, retired=1,
                           tiers=[1, 2, 0, 0])
            obs_trace.emit("retire", stream=8, retired=1, newly=1)
            obs_trace.emit("program", stream=8, calibrated=True)
            obs_trace.emit("recal", stream=8, reload_bits=1024,
                           energy_nj=3.2, post_rel_l2=0.012)
        return buf.events()

    def test_drift_story_complete_and_ordered(self):
        story = obs.drift_story(self._trace())
        assert story.complete
        assert story.alarm_stream == story.recal_stream \
            == story.retire_stream == 8
        kinds = [s["kind"] for s in story.steps]
        assert kinds == ["drift_alarm", "retrim", "retire", "recal"]

    def test_timeline_and_heatmap(self):
        tl = obs.from_events(self._trace())
        assert len(tl.probes) == 1 and tl.alarms == [8]
        assert tl.probes[0].sqnr_db == pytest.approx(26.0206, abs=1e-3)
        assert tl.recal_reload_bits == [1024]
        assert tl.retired_now == 1 and tl.coarse_now == 1
        heat = obs.fleet_heatmap(tl)
        assert heat["render"] == ["o#.."]
        per_slot = obs.slot_timelines(tl)
        assert per_slot[1][0]["residue_fs"] == pytest.approx(0.9)
        assert per_slot[1][1]["tier"] == 2

    def test_story_incomplete_without_alarm(self):
        with obs.tracing() as buf:
            obs_trace.emit("recal", stream=4, reload_bits=8)
        story = obs.drift_story(buf.events())
        assert not story.complete and story.recal_stream is None


class TestExports:
    def test_prometheus_round_trip(self):
        m = obs.MetricsRegistry()
        m.counter("serve_ticks_total", "ticks").inc(12345)
        m.gauge("queue_depth").set(0.30000000000000004)
        h = m.histogram("lat_s", (0.001, 0.1, 1.0), "latency")
        h.observe_many([0.0005, 0.05, 0.5, 5.0])
        text = obs.to_prometheus(m)
        parsed = obs.parse_prometheus(text)
        assert parsed["serve_ticks_total"] == {
            "type": "counter", "value": 12345.0}
        assert parsed["queue_depth"]["value"] == 0.30000000000000004
        assert parsed["lat_s"]["type"] == "histogram"
        assert parsed["lat_s"]["buckets"] == [
            (0.001, 1.0), (0.1, 2.0), (1.0, 3.0), (float("inf"), 4.0)]
        assert parsed["lat_s"]["count"] == 4.0
        assert parsed["lat_s"]["sum"] == pytest.approx(h.sum)

    def test_trace_jsonl_round_trip(self, tmp_path: Path):
        with obs.tracing() as buf:
            obs_trace.emit("admit", stream=1, slot=0, rid="r-1",
                           prompt_tokens=3)
            obs_trace.emit("evict", stream=9, slot=0, rid="r-1",
                           tokens=7)
        path = tmp_path / "trace.jsonl"
        n = obs.write_trace_jsonl(buf, path)
        assert n == 2
        back = obs.read_trace_jsonl(path)
        assert [e.to_json() for e in back] == \
            [e.to_json() for e in buf.events()]

    def test_sanitize_findings_land_on_the_bus(self):
        from repro.analysis.sanitize import SanitizeError, _finding
        with obs.tracing() as buf:
            err = _finding("boom", check="nan_logits", stream=4)
        assert isinstance(err, SanitizeError)
        (ev,) = buf.by_kind("sanitize")
        assert ev.stream == 4 and ev.data["check"] == "nan_logits"


class TestReproLintScope:
    def test_obs_modules_tagged_out_of_exactness_scope(self):
        from repro.analysis.engine import _scan_comments, _scan_directives
        obs_dir = Path(obs.__file__).parent
        files = sorted(obs_dir.glob("*.py"))
        assert files, obs_dir
        for f in files:
            src = f.read_text()
            tags, _, _ = _scan_directives(src, _scan_comments(src))
            assert "observability" in tags, f.name
            assert "exactness-critical" not in tags, f.name
