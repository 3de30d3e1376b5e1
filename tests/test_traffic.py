"""Traffic lab: arrival processes, continuous batching, mesh serving
(ISSUE 6).

The contracts under test:

  * arrival generation is keyed-deterministic — same ``WorkloadConfig``
    ⇒ bit-identical trace; the MMPP process is measurably burstier than
    Poisson at the same mean rate;
  * admission control never overflows the engine's cache slots, admits
    FIFO within a priority level, *rejects* (never raises on) oversized
    requests, and deadline eviction frees slots mid-run;
  * per-conversion thermal dither is keyed by the conversion-clock step:
    same step ⇒ bitwise-identical conversions, different steps differ,
    ``thermal_sigma_v = 0`` stays bitwise nominal;
  * a SINGLE-device serve mesh decodes bitwise identically to the
    unsharded engine (the sharding acceptance gate);
  * sharded ``collect_stats`` merges per-device observer states exactly.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler.tiling import Fleet
from repro.configs.base import MFTechniqueConfig, ModelConfig
from repro.core import cim
from repro.core.cim import CimConfig, cim_mf_matmul
from repro.launch.mesh import make_serve_mesh
from repro.serve.engine import ServeEngine
from repro.silicon import SiliconConfig, projection_silicon, sample_fleet
from repro.traffic import (AdmissionConfig, ContinuousBatcher, VirtualClock,
                           WorkloadConfig, generate, percentile, replay_trace,
                           shard_engine)
from repro.traffic.report import from_run
from repro.traffic.workload import TrafficRequest

CIM = CimConfig(4, 4, 5, 31)


def _cfg(**kw):
    base = dict(
        name="traffic-tiny", family="lm", n_layers=2, d_model=32,
        n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
        dtype=jnp.float32,
        mf=MFTechniqueConfig(mode="cim_sim", cim=CIM))
    base.update(kw)
    return ModelConfig(**base)


def _engine(slots=2, max_len=32, fleet=None, **kw):
    from repro.models import transformer as T
    cfg = _cfg()
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    return ServeEngine(params, cfg, slots=slots, max_len=max_len,
                       fleet=fleet, **kw)


def _req(rid, t, prompt, n_new, ttft_dl, dl, priority=0):
    return TrafficRequest(rid=rid, t_arrival_s=t, prompt=prompt,
                          max_new_tokens=n_new, ttft_deadline_s=ttft_dl,
                          deadline_s=dl, priority=priority)


class TestArrivalDeterminism:
    @pytest.mark.parametrize("process", ["poisson", "mmpp"])
    def test_same_seed_same_trace(self, process):
        cfg = WorkloadConfig(process=process, n_requests=32, seed=7)
        a, b = generate(cfg), generate(cfg)
        assert len(a) == len(b) == 32
        for ra, rb in zip(a, b):
            assert ra.t_arrival_s == rb.t_arrival_s
            assert ra.prompt == rb.prompt
            assert ra.max_new_tokens == rb.max_new_tokens
            assert ra.ttft_deadline_s == rb.ttft_deadline_s
            assert ra.deadline_s == rb.deadline_s
            assert ra.priority == rb.priority

    def test_different_seed_differs(self):
        a = generate(WorkloadConfig(n_requests=16, seed=0))
        b = generate(WorkloadConfig(n_requests=16, seed=1))
        assert [r.t_arrival_s for r in a] != [r.t_arrival_s for r in b]

    def test_processes_differ(self):
        a = generate(WorkloadConfig(n_requests=16, seed=0))
        b = generate(WorkloadConfig(n_requests=16, seed=0,
                                    process="mmpp"))
        assert [r.t_arrival_s for r in a] != [r.t_arrival_s for r in b]

    def test_mmpp_burstier_than_poisson(self):
        # Same mean rate; the MMPP inter-arrival coefficient of variation
        # must exceed the (≈1) Poisson one. Deterministic given the seed.
        def cv(reqs):
            dt = np.diff([r.t_arrival_s for r in reqs])
            return dt.std() / dt.mean()
        n = 512
        po = generate(WorkloadConfig(n_requests=n, seed=3))
        mm = generate(WorkloadConfig(n_requests=n, seed=3, process="mmpp",
                                     burst_rate_mult=8.0,
                                     burst_fraction=0.2))
        assert cv(mm) > 1.2 * cv(po)

    def test_mmpp_mean_rate_normalised(self):
        cfg = WorkloadConfig(n_requests=2048, seed=5, process="mmpp",
                             rate_rps=4.0, burst_rate_mult=6.0,
                             burst_fraction=0.3)
        reqs = generate(cfg)
        rate = (len(reqs) - 1) / (reqs[-1].t_arrival_s
                                  - reqs[0].t_arrival_s)
        assert abs(rate - cfg.rate_rps) / cfg.rate_rps < 0.25

    def test_deadlines_are_absolute(self):
        cfg = WorkloadConfig(n_requests=8, seed=2, ttft_slo_s=0.3,
                             tpot_slo_s=0.05)
        for r in generate(cfg):
            assert r.ttft_deadline_s == pytest.approx(
                r.t_arrival_s + 0.3)
            assert r.deadline_s == pytest.approx(
                r.ttft_deadline_s + 0.05 * r.max_new_tokens)
            assert 1 <= len(r.prompt)
            assert all(1 <= t < cfg.vocab_size for t in r.prompt)

    def test_config_validation(self):
        with pytest.raises(ValueError, match="degenerate"):
            WorkloadConfig(rate_rps=0.0)
        with pytest.raises(ValueError, match="unknown arrival process"):
            WorkloadConfig(process="pareto")
        with pytest.raises(ValueError, match="burst_fraction"):
            WorkloadConfig(process="mmpp", burst_fraction=1.5)

    def test_replay_trace(self):
        reqs = replay_trace([0.0, 0.5, 1.25], [[1, 2], [3], [4, 5, 6]],
                            [4, 2, 8], ttft_slo_s=0.2, tpot_slo_s=0.1,
                            priorities=[1, 0, 1])
        assert [r.t_arrival_s for r in reqs] == [0.0, 0.5, 1.25]
        assert reqs[2].deadline_s == pytest.approx(1.25 + 0.2 + 0.8)
        assert [r.priority for r in reqs] == [1, 0, 1]
        with pytest.raises(ValueError, match="sorted"):
            replay_trace([1.0, 0.5], [[1], [2]], [1, 1])
        with pytest.raises(ValueError, match="columns disagree"):
            replay_trace([0.0], [[1], [2]], [1])


class TestClockAndPercentile:
    def test_percentile_known_values(self):
        xs = [5.0, 1.0, 3.0, 2.0, 4.0]
        assert percentile(xs, 0) == 1.0
        assert percentile(xs, 50) == 3.0
        assert percentile(xs, 100) == 5.0
        assert percentile(xs, 25) == 2.0
        assert percentile([7.0], 99) == 7.0
        assert np.isnan(percentile([], 50))
        with pytest.raises(ValueError, match="outside"):
            percentile(xs, 101)

    def test_small_sample_tail_percentiles(self):
        # Hyndman-Fan type 7 (the documented method): with n samples the
        # tail sits at fractional rank (n-1)*q/100 BETWEEN the two
        # largest order statistics — never extrapolated past the max.
        xs = [1.0, 2.0, 3.0, 4.0]
        assert percentile(xs, 99) == pytest.approx(3.97)
        assert percentile(xs, 99.9) == pytest.approx(3.997)
        assert percentile(xs, 99) <= max(xs)
        # Adding one large sample moves the tail deterministically: the
        # p99 rank (4*0.99 = 3.96) now interpolates into the new max.
        assert percentile(xs + [40.0], 99) == pytest.approx(
            4.0 + 0.96 * 36.0)
        # Degenerate sizes: n=1 clamps to the sample, n=2 interpolates.
        assert percentile([7.0], 99.9) == 7.0
        assert percentile([1.0, 2.0], 99) == pytest.approx(1.99)

    def test_virtual_clock(self):
        c = VirtualClock(0.25, prefill_s=1.0)
        c.on_decode()
        c.on_prefill()
        assert c.now == pytest.approx(1.25)
        c.fast_forward(0.5)         # never backwards
        assert c.now == pytest.approx(1.25)
        c.fast_forward(3.0)
        assert c.now == pytest.approx(3.0)
        assert VirtualClock(0.1).prefill_s == 0.1
        with pytest.raises(ValueError, match="tick_s"):
            VirtualClock(0.0)


class TestAdmissionInvariants:
    def test_no_slot_overflow_and_fifo(self):
        eng = _engine(slots=2, max_len=32)
        # 8 simultaneous arrivals against 2 slots: queue must drain
        # strictly FIFO and in-flight never exceeds the slot count.
        reqs = [_req(i, 0.0, [1 + i, 2 + i], 4, 1e9, 1e9)
                for i in range(8)]
        bat = ContinuousBatcher(eng, clock=VirtualClock(0.01))
        log = bat.run(reqs)
        assert all(r.state == "completed" for r in reqs)
        assert max(log.occupied) <= eng.slots
        assert not eng.occupied_slots
        admits = [r.t_admit_s for r in reqs]
        assert admits == sorted(admits)      # FIFO by rid at equal t

    def test_priority_admitted_first(self):
        eng = _engine(slots=1, max_len=32)
        reqs = [_req(0, 0.0, [3], 2, 1e9, 1e9, priority=1),
                _req(1, 0.0, [4], 2, 1e9, 1e9, priority=1),
                _req(2, 0.0, [5], 2, 1e9, 1e9, priority=0),
                _req(3, 0.0, [6], 2, 1e9, 1e9, priority=0)]
        bat = ContinuousBatcher(eng, clock=VirtualClock(0.01))
        bat.run(reqs)
        assert all(r.state == "completed" for r in reqs)
        lo = max(reqs[2].t_admit_s, reqs[3].t_admit_s)
        hi = min(reqs[0].t_admit_s, reqs[1].t_admit_s)
        assert lo < hi        # both priority-0 served before priority-1

    def test_oversized_request_rejected_not_raised(self):
        eng = _engine(slots=2, max_len=16)
        reqs = [_req(0, 0.0, [1] * 30, 4, 1e9, 1e9),   # prompt > cache
                _req(1, 0.0, [2, 3], 30, 1e9, 1e9),    # decode > cache
                _req(2, 0.0, [4, 5], 4, 1e9, 1e9)]
        bat = ContinuousBatcher(eng, clock=VirtualClock(0.01))
        bat.run(reqs)
        assert reqs[0].state == "rejected"
        assert reqs[1].state == "rejected"
        assert reqs[2].state == "completed"

    def test_queue_overflow_sheds(self):
        eng = _engine(slots=1, max_len=32)
        reqs = [_req(i, 0.0, [1 + i], 2, 1e9, 1e9) for i in range(6)]
        bat = ContinuousBatcher(
            eng, clock=VirtualClock(0.01),
            admission=AdmissionConfig(max_queue=2))
        bat.run(reqs)
        states = [r.state for r in reqs]
        # All 6 arrive in one pull: exactly max_queue of them fit the
        # queue, the rest shed at admission.
        assert states.count("completed") == 2
        assert states.count("rejected") == 4
        assert [r.rid for r in reqs if r.state == "completed"] == [0, 1]

    def test_deadline_eviction_frees_slot(self):
        eng = _engine(slots=1, max_len=64)
        # A can never finish by its deadline (20 ticks x 0.1 s against a
        # 1.2 s completion budget): it must be EVICTED, and B — arriving
        # after the eviction point — must then complete in the freed slot.
        a = _req(0, 0.0, [7], 20, ttft_dl=1.0, dl=1.2)
        b = _req(1, 2.0, [8], 2, ttft_dl=1e9, dl=1e9)
        bat = ContinuousBatcher(eng, clock=VirtualClock(0.1))
        bat.run([a, b])
        assert a.state == "evicted" and a.t_done_s < 2.0
        assert b.state == "completed" and not a.slo_met and b.slo_met
        assert not eng.occupied_slots

    def test_eviction_surfaces_freed_token_count(self):
        from repro import obs
        eng = _engine(slots=1, max_len=64)
        a = _req(0, 0.0, [7], 20, ttft_dl=1.0, dl=1.2)
        b = _req(1, 2.0, [8], 2, ttft_dl=1e9, dl=1e9)
        bat = ContinuousBatcher(eng, clock=VirtualClock(0.1))
        with obs.tracing() as buf:
            rep = from_run(bat.run([a, b]), eng)
        # The deadline eviction threw away A's in-flight decode output;
        # that count must flow engine counter -> report -> trace event.
        freed = len(a.serve.out)
        assert a.state == "evicted" and freed > 0
        assert rep.evicted == 1
        assert rep.evicted_tokens == freed
        assert eng.counters()["evicted_tokens"] == freed
        (ev,) = buf.by_kind("evict")
        assert ev.data["tokens"] == freed
        assert rep.to_json()["evicted_tokens"] == freed

    def test_drop_late_sheds_queued_past_ttft(self):
        def run(drop):
            eng = _engine(slots=1, max_len=64)
            blocker = _req(0, 0.0, [3], 30, 1e9, 1e9)
            late = _req(1, 0.0, [4], 2, ttft_dl=0.05, dl=1e9)
            bat = ContinuousBatcher(
                eng, clock=VirtualClock(0.1),
                admission=AdmissionConfig(drop_late=drop))
            bat.run([blocker, late])
            return late
        assert run(True).state == "rejected"
        kept = run(False)
        assert kept.state == "completed" and not kept.slo_met

    def test_out_of_ticks_drains_terminal(self):
        eng = _engine(slots=1, max_len=32)
        reqs = [_req(i, 0.0, [1 + i], 8, 1e9, 1e9) for i in range(4)]
        bat = ContinuousBatcher(eng, clock=VirtualClock(0.01))
        log = bat.run(reqs, max_ticks=3)
        assert log.out_of_ticks
        assert all(r.state in ("completed", "rejected", "evicted")
                   for r in reqs)
        assert not eng.occupied_slots    # drain really freed the slots

    def test_report_roll_up(self):
        import json
        fleet = Fleet(n_macros=4096, cfg=CIM)
        eng = _engine(slots=2, max_len=32, fleet=fleet)
        reqs = generate(WorkloadConfig(
            rate_rps=50.0, n_requests=10, seed=1, prompt_len_max=6,
            decode_len_max=6, vocab_size=64, ttft_slo_s=1e6,
            tpot_slo_s=1e6))
        bat = ContinuousBatcher(eng, clock=VirtualClock(0.02))
        rep = from_run(bat.run(reqs), eng)
        assert rep.completed == 10 and rep.slo_attainment == 1.0
        assert rep.completed + rep.rejected + rep.evicted == 10
        assert rep.tok_s > 0 and rep.decode_tokens > 0
        assert rep.latency_p50_s <= rep.latency_p99_s
        assert 0.0 < rep.slot_utilization <= 1.0
        assert rep.wave is not None and rep.energy_per_token_j > 0
        json.dumps(rep.to_json())        # artifact-safe payload


THERMAL = SiliconConfig(cap_sigma=0.0, comparator_sigma_v=0.0,
                        thermal_sigma_v=0.004)


class TestThermalDither:
    def _sil(self, scfg, k=70, n=9):
        fleet = sample_fleet(jax.random.PRNGKey(5), 24, 31, scfg)
        return projection_silicon(fleet, scfg, k, n)

    def _y(self, sil, step):
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 70))
        w = jax.random.normal(jax.random.PRNGKey(1), (70, 9))
        cfg = CimConfig(8, 8, 5, 31)
        with cim.conversion_clock(step):
            return np.asarray(cim_mf_matmul(x, w, cfg, silicon=sil))

    def test_same_step_bitwise_identical(self):
        sil = self._sil(THERMAL)
        np.testing.assert_array_equal(self._y(sil, 5), self._y(sil, 5))

    def test_steps_decorrelate(self):
        sil = self._sil(THERMAL)
        assert not np.array_equal(self._y(sil, 0), self._y(sil, 1))

    def test_sigma0_thermal_is_bitwise_nominal(self):
        quiet = SiliconConfig(cap_sigma=0.0, comparator_sigma_v=0.0)
        assert quiet.is_nominal and not THERMAL.is_nominal
        sil = self._sil(quiet)
        assert sil.thermal_fs is None
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 70))
        w = jax.random.normal(jax.random.PRNGKey(1), (70, 9))
        cfg = CimConfig(8, 8, 5, 31)
        np.testing.assert_array_equal(
            np.asarray(cim_mf_matmul(x, w, cfg)), self._y(sil, 3))

    def test_thermal_serving_is_reproducible(self):
        # The engine threads its stream counter into the jitted step, so
        # two identical engines replay the same dither sequence.
        fleet = Fleet(n_macros=4096, cfg=CIM)
        outs = []
        for _ in range(2):
            eng = _engine(slots=2, max_len=32, fleet=fleet,
                          silicon=THERMAL)
            reqs = [_req(i, 0.0, [5 + i, 6 + i], 6, 1e9, 1e9)
                    for i in range(4)]
            ContinuousBatcher(eng, clock=VirtualClock(0.01)).run(reqs)
            outs.append([r.serve.out for r in reqs])
        assert outs[0] == outs[1]


class TestMeshServing:
    def test_make_serve_mesh_rejects_wrong_device_count(self):
        with pytest.raises(ValueError):
            make_serve_mesh(data=2, fleet=2,
                            devices=list(jax.devices())[:1])

    def test_single_device_mesh_bitwise_parity(self):
        # THE sharding acceptance gate: a (1, 1) serve mesh must decode
        # bitwise identically to the unsharded engine.
        fleet = Fleet(n_macros=4096, cfg=CIM)
        outs = []
        for shard in (False, True):
            eng = _engine(slots=2, max_len=32, fleet=fleet)
            if shard:
                info = shard_engine(eng, make_serve_mesh(
                    data=1, fleet=1, devices=jax.devices()[:1]))
                assert info["data"] == 1 and info["fleet"] == 1
            reqs = [_req(i, 0.0, [1 + i, 2 + i, 3 + i], 6, 1e9, 1e9)
                    for i in range(5)]
            ContinuousBatcher(eng, clock=VirtualClock(0.01)).run(reqs)
            outs.append([r.serve.out for r in reqs])
        assert outs[0] == outs[1]


MULTIDEV_TRAFFIC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.compiler.tiling import Fleet
    from repro.configs.base import MFTechniqueConfig, ModelConfig
    from repro.core.cim import CimConfig
    from repro.launch.mesh import make_serve_mesh
    from repro.models import transformer as T
    from repro.serve.engine import ServeEngine
    from repro.traffic import shard_engine
    from repro.traffic.workload import TrafficRequest

    CIM = CimConfig(4, 4, 5, 31)
    cfg = ModelConfig(name="t", family="lm", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                      dtype=jnp.float32,
                      mf=MFTechniqueConfig(mode="cim_sim", cim=CIM))
    params = T.lm_init(jax.random.PRNGKey(0), cfg)
    fleet = Fleet(n_macros=4096, cfg=CIM)

    def mkreqs():
        from repro.serve.engine import Request
        return [Request(prompt=[1 + i, 2 + i, 3 + i], max_new_tokens=6)
                for i in range(8)]

    outs = []
    infos = []
    for mesh_kw in (None, dict(data=4, fleet=1), dict(data=2, fleet=2)):
        eng = ServeEngine(params, cfg, slots=4, max_len=32, fleet=fleet)
        if mesh_kw is not None:
            infos.append(shard_engine(eng, make_serve_mesh(**mesh_kw)))
        outs.append([r.out for r in eng.run(mkreqs())])
    assert outs[0] == outs[1], "data=4 mesh decode diverged"
    assert outs[0] == outs[2], "data=2 x fleet=2 mesh decode diverged"
    assert infos[0]["cache_sharded_leaves"] > 0
    assert infos[1]["param_sharded_leaves"] > 0
    # ragged slot split must refuse, not silently replicate
    eng = ServeEngine(params, cfg, slots=3, max_len=32, fleet=fleet)
    try:
        shard_engine(eng, make_serve_mesh(data=4, fleet=1))
    except ValueError:
        pass
    else:
        raise AssertionError("ragged slot split did not raise")
    print("MULTIDEV_TRAFFIC_OK")
""")


@pytest.mark.slow
def test_traffic_multidevice_subprocess():
    """Sharded serving on a real 4-device host mesh is bitwise equal to
    the unsharded engine (subprocess so the fake device count doesn't
    leak into this test session)."""
    # Pinned to the CPU backend: the forced host devices are the point,
    # and the child must never contend for an accelerator this session
    # may hold.
    r = subprocess.run([sys.executable, "-c", MULTIDEV_TRAFFIC_SCRIPT],
                       capture_output=True, text=True, timeout=540,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "MULTIDEV_TRAFFIC_OK" in r.stdout, r.stdout + r.stderr


class TestShardedCollectStats:
    def test_duplicate_device_shards_merge_exactly(self):
        from repro.calib import observers as obs
        from repro.calib.corpus import attach_observer_ids, collect_stats
        from repro.models import transformer as T
        cfg = _cfg(mf=MFTechniqueConfig(mode="mf"))
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        tagged, registry = attach_observer_ids(params)
        fwd = lambda p, b: T.lm_forward(p, b, cfg)[0]
        batches = [{"tokens": jax.random.randint(jax.random.PRNGKey(i),
                                                 (4, 8), 0, 64)}
                   for i in (1, 2)]
        ocfg = obs.ObserverConfig()
        c0 = collect_stats(fwd, tagged, batches, registry, ocfg)
        dev = jax.devices()[0]
        # Duplicate device list: exercises the shard/dispatch/merge path
        # on a single-device host; a 3-way split of a 4-row batch also
        # covers uneven block sizes.
        c3 = collect_stats(fwd, tagged, batches, registry, ocfg,
                           devices=[dev, dev, dev])
        np.testing.assert_array_equal(c0.count, c3.count)
        np.testing.assert_array_equal(c0.amax, c3.amax)
        np.testing.assert_array_equal(c0.hist, c3.hist)

    def test_more_devices_than_rows_skips_empty_shards(self):
        from repro.calib import observers as obs
        from repro.calib.corpus import attach_observer_ids, collect_stats
        from repro.models import transformer as T
        cfg = _cfg(mf=MFTechniqueConfig(mode="mf"))
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        tagged, registry = attach_observer_ids(params)
        fwd = lambda p, b: T.lm_forward(p, b, cfg)[0]
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(9),
                                              (1, 8), 0, 64)}
        ocfg = obs.ObserverConfig()
        c0 = collect_stats(fwd, tagged, [batch], registry, ocfg)
        dev = jax.devices()[0]
        c4 = collect_stats(fwd, tagged, [batch], registry, ocfg,
                           devices=[dev] * 4)
        np.testing.assert_array_equal(c0.count, c4.count)
        np.testing.assert_array_equal(c0.hist, c4.hist)
        with pytest.raises(ValueError, match="non-empty"):
            collect_stats(fwd, tagged, [batch], registry, ocfg,
                          devices=[])
