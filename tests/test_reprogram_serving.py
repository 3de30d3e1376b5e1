"""Reprogram-aware serving: round-interleaved decode, batched prefill,
and the serving/calibration bug batch (ISSUE 4).

The contracts under test:

  * round partitions cover every µArray tile exactly once and the round
    count equals the compiler schedule's ``ceil(tiles / slots)``;
  * swapped (round-interleaved) execution is bit-identical to the pinned
    programmed path, standalone and through a served model;
  * ``ServeReport.reprogram_events`` equals
    ``ModelSchedule.total_reprogram_events x streams``;
  * batched programmed prefill matches prefill-as-decode greedy tokens
    and skips the per-prompt-token decode steps;
  * ``submit``/``run`` reject empty prompts, and ``run`` returns requests
    in submission order (multi-wave and timeout cases included);
  * ``collect_stats`` traces the observe forward once per batch shape.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler.schedule import schedule_layer
from repro.compiler.tiling import Fleet
from repro.configs.base import MFTechniqueConfig, ModelConfig
from repro.core.cim import CimConfig
from repro.core.programmed import (SwappedMacro, build_swap_schedule,
                                   cim_mf_matmul_programmed,
                                   cim_mf_matmul_swapped, cim_swapped_partials,
                                   default_static_sx,
                                   program_macro, program_weights,
                                   swap_macro)
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine

DESIGNS = [(31, 5), (15, 4)]


def _cfg(w_bits=4, x_bits=4, m=31, a=5, **kw):
    base = dict(
        name="serve-tiny", family="lm", n_layers=2, d_model=32,
        n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
        dtype=jnp.float32,
        mf=MFTechniqueConfig(mode="cim_sim",
                             cim=CimConfig(w_bits, x_bits, a, m)))
    base.update(kw)
    return ModelConfig(**base)


class TestSwapSchedule:
    @pytest.mark.parametrize("k,n,m,slots", [
        (64, 48, 31, 16), (31, 5, 31, 3), (70, 7, 15, 10),
        (8, 3, 31, 100), (97, 33, 15, 64), (31, 1, 31, 1)])
    def test_rounds_cover_tiles_exactly_once(self, k, n, m, slots):
        sch = build_swap_schedule(k, n, m, slots)
        cover = np.zeros((sch.n_chunks, n), int)
        for segments in sch.rounds:
            tiles = 0
            for (n0, n1, k0, k1) in segments:
                assert 0 <= n0 < n1 <= n and 0 <= k0 < k1 <= k
                assert k0 % m == 0          # chunk-aligned slice starts
                c0, c1 = k0 // m, -(-k1 // m)
                cover[c0:c1, n0:n1] += 1
                tiles += (c1 - c0) * (n1 - n0)
            assert tiles <= slots           # round fits the fleet
        np.testing.assert_array_equal(cover, 1)

    @pytest.mark.parametrize("k,n,m,slots", [
        (64, 48, 31, 16), (70, 7, 15, 10), (97, 33, 15, 64)])
    def test_round_count_matches_compiler_schedule(self, k, n, m, slots):
        cfg = CimConfig(m_columns=m)
        fleet = Fleet(n_macros=slots, cfg=cfg, halves_per_macro=1)
        sched = schedule_layer(fleet.plan(k, n), fleet)
        assert build_swap_schedule(k, n, m, slots).n_rounds == sched.rounds

    def test_degenerate_inputs_raise(self):
        with pytest.raises(ValueError, match="degenerate"):
            build_swap_schedule(0, 4, 31, 8)
        with pytest.raises(ValueError, match="tile_slots"):
            build_swap_schedule(4, 4, 31, 0)


class TestSwappedMatmul:
    @pytest.mark.parametrize("m,a", DESIGNS)
    @pytest.mark.parametrize("w_bits", [4, 8])
    @pytest.mark.parametrize("slots", [3, 16, 1000])
    def test_bit_exact_vs_pinned_macro(self, m, a, w_bits, slots):
        cfg = CimConfig(w_bits=w_bits, x_bits=8, adc_bits=a, m_columns=m)
        x = jax.random.normal(jax.random.PRNGKey(0), (5, 70))
        w = jax.random.normal(jax.random.PRNGKey(1), (70, 9))
        sx = default_static_sx(cfg)
        y0 = np.asarray(cim_mf_matmul_programmed(
            x, program_macro(w, cfg, sx=sx), cfg))
        sm = swap_macro(w, cfg, slots, sx=sx)
        y1 = np.asarray(cim_mf_matmul_swapped(x, w, sm, cfg))
        np.testing.assert_array_equal(y0, y1)

    def test_stacked_swap_macro_slices_like_params(self):
        # Stacked (scan-period) weights: per-instance sw, one shared
        # static schedule; scanning over instances must reproduce each
        # instance's standalone swapped result.
        cfg = CimConfig(w_bits=8, x_bits=8, adc_bits=5, m_columns=31)
        w = jax.random.normal(jax.random.PRNGKey(2), (3, 40, 6))
        x = jax.random.normal(jax.random.PRNGKey(3), (4, 40))
        sx = default_static_sx(cfg)
        sm = swap_macro(w, cfg, 4, sx=sx)
        assert sm.sw.shape == (3,) and sm.sx.shape == (3,)

        def body(carry, inp):
            wi, smi = inp
            return carry, cim_swapped_partials(x, wi, smi, cfg)

        _, ps = jax.lax.scan(body, 0, (w, sm))
        for i in range(3):
            smi = swap_macro(w[i], cfg, 4, sx=sx)
            np.testing.assert_array_equal(np.asarray(sm.sw[i]),
                                          np.asarray(smi.sw))
            # The integer code sums are exact in any program; the float
            # recombine is not compared across programs, where scan-
            # compiled and standalone code may fuse its FMA differently
            # (the cross-program effect documented in EXPERIMENTS.md).
            # The bitwise contract on recombined outputs — swapped vs
            # pinned under the SAME program — is TestFleetServing's.
            want = cim_swapped_partials(x, w[i], smi, cfg)
            for got_f, want_f in zip(ps, want):
                np.testing.assert_array_equal(np.asarray(got_f[i]),
                                              np.asarray(want_f))

    def test_program_weights_swap_hook_embeds_swapped_macros(self):
        cfg = _cfg()
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        from repro.compiler.frontend import projection_layer_stats
        _, groups = projection_layer_stats(params)
        progd = program_weights(params, cfg.mf.cim,
                                swap={g.name: 8 for g in groups})
        from repro.core.programmed import iter_projections
        for _, node, _ in iter_projections(progd):
            assert isinstance(node["prog"], SwappedMacro)

    def test_swap_hook_rejects_non_linear(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (3, 3, 2, 4))
        params = {"conv1": {"w": w, "alpha": jnp.ones((4,))}}
        with pytest.raises(NotImplementedError, match="linear"):
            program_weights(params, CimConfig(), swap={"conv1": 8})


class TestFleetServing:
    def _engines(self, fleet_macros, **eng_kw):
        cfg = _cfg()
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        fleet = Fleet(n_macros=fleet_macros, cfg=cfg.mf.cim)
        eng = ServeEngine(params, cfg, slots=2, max_len=16, fleet=fleet,
                          batched_prefill=False, **eng_kw)
        ref = ServeEngine(params, cfg, slots=2, max_len=16,
                          batched_prefill=False)
        return eng, ref

    def _serve(self, eng, n=4):
        done = eng.run([Request(prompt=[1, 2, 3], max_new_tokens=n)
                        for _ in range(2)])
        return [r.out for r in done]

    def test_pinned_fleet_matches_no_fleet_engine(self):
        eng, ref = self._engines(fleet_macros=1024)
        assert eng.schedule is not None and eng.schedule.pinned
        assert self._serve(eng) == self._serve(ref)
        rep = eng.last_report
        assert rep.pinned and rep.reprogram_events == 0
        assert rep.reload_bits == 0

    def test_round_interleaved_decode_is_bit_exact(self):
        # Fleet sized to force rounds > 1: every layer swaps, the deepest
        # one through multiple rounds, and tokens match the pinned path
        # bit for bit.
        eng, ref = self._engines(fleet_macros=8)
        sched = eng.schedule
        assert not sched.pinned and sched.rounds_max > 1
        assert self._serve(eng) == self._serve(ref)

    def test_report_reprogram_identity(self):
        eng, _ = self._engines(fleet_macros=8)
        self._serve(eng)
        rep = eng.last_report
        sched = eng.schedule
        assert rep.decode_steps == rep.streams > 0
        assert rep.reprogram_events == \
            sched.total_reprogram_events * rep.decode_steps
        assert rep.reload_bits == sched.total_reload_bits * rep.decode_steps
        assert rep.reload_energy_j == pytest.approx(
            rep.reload_bits * eng.fleet.reload_j_per_bit)
        assert rep.rounds_max == sched.rounds_max > 1
        assert 0.0 < rep.utilization <= 1.0

    def test_fleet_requires_programming(self):
        cfg = _cfg()
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError, match="fleet"):
            ServeEngine(params, cfg, slots=1, max_len=8, program=False,
                        fleet=Fleet(n_macros=8, cfg=cfg.mf.cim))

    def test_fleet_geometry_must_match_model(self):
        cfg = _cfg(m=31)
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        bad = Fleet(n_macros=8, cfg=CimConfig(m_columns=15))
        with pytest.raises(ValueError, match="geometry"):
            ServeEngine(params, cfg, slots=1, max_len=8, fleet=bad)

    def test_no_fleet_report_has_no_schedule_fields(self):
        cfg = _cfg()
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(params, cfg, slots=1, max_len=8,
                          batched_prefill=False)
        eng.run([Request(prompt=[1], max_new_tokens=2)])
        rep = eng.last_report
        assert rep.pinned is None and rep.reprogram_events == 0
        assert rep.decode_tokens == 2 and rep.tok_s > 0


class TestBatchedPrefill:
    def test_prefill_matches_as_decode_greedy_tokens(self):
        cfg = _cfg()
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        prompt = [1, 2, 3, 4, 5]
        eng_b = ServeEngine(params, cfg, slots=2, max_len=16)
        eng_d = ServeEngine(params, cfg, slots=2, max_len=16,
                            batched_prefill=False)
        assert eng_b.batched_prefill and not eng_d.batched_prefill
        out_b = [r.out for r in eng_b.run(
            [Request(prompt=prompt, max_new_tokens=5) for _ in range(2)])]
        out_d = [r.out for r in eng_d.run(
            [Request(prompt=prompt, max_new_tokens=5) for _ in range(2)])]
        assert out_b == out_d
        rb, rd = eng_b.last_report, eng_d.last_report
        # Prompt ingestion stops paying one decode step per token.
        assert rb.prefill_calls == 1
        assert rb.prefill_tokens == 2 * (len(prompt) - 1)
        assert rb.decode_steps == rd.decode_steps - (len(prompt) - 1)

    def test_prefill_wave_leaves_mid_decode_slots_untouched(self):
        # Serve request A alone past its prompt, then admit B (long
        # prompt, batched prefill wave): A's continuation must be
        # unchanged vs serving A with no neighbour.
        cfg = _cfg()
        params = T.lm_init(jax.random.PRNGKey(0), cfg)

        def serve_a(with_b):
            eng = ServeEngine(params, cfg, slots=2, max_len=16)
            a = Request(prompt=[1, 2], max_new_tokens=8)
            assert eng.submit(a)
            for _ in range(3):
                eng.step()
            if with_b:
                assert eng.submit(Request(prompt=[3, 4, 5, 6],
                                          max_new_tokens=2))
            while not a.done:
                eng.step()
            return a.out

        assert serve_a(with_b=False) == serve_a(with_b=True)

    def test_swapped_serving_composes_with_prefill(self):
        cfg = _cfg()
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        fleet = Fleet(n_macros=8, cfg=cfg.mf.cim)
        eng = ServeEngine(params, cfg, slots=2, max_len=16, fleet=fleet)
        ref = ServeEngine(params, cfg, slots=2, max_len=16)
        reqs = lambda: [Request(prompt=[1, 2, 3, 4], max_new_tokens=3)
                        for _ in range(2)]
        assert [r.out for r in eng.run(reqs())] == \
            [r.out for r in ref.run(reqs())]
        rep = eng.last_report
        assert rep.prefill_calls == 1
        # Prefill waves are input streams too: they reprogram the fleet.
        assert rep.streams == rep.decode_steps + 1
        assert rep.reprogram_events == \
            eng.schedule.total_reprogram_events * rep.streams

    def test_forcing_prefill_on_unsupported_arch_raises(self):
        cfg = _cfg(window=8)          # sliding-window ring cache
        assert not T.prefill_supported(cfg)
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError, match="prefill"):
            ServeEngine(params, cfg, slots=1, max_len=8,
                        batched_prefill=True)
        # auto mode silently falls back to prefill-as-decode
        eng = ServeEngine(params, cfg, slots=1, max_len=8)
        assert not eng.batched_prefill
        done = eng.run([Request(prompt=[1, 2, 3], max_new_tokens=2)])
        assert len(done[0].out) == 2


class TestSubmitRunBugfixes:
    def _engine(self, slots=2):
        cfg = _cfg(mf=MFTechniqueConfig(enabled=False))
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        return ServeEngine(params, cfg, slots=slots, max_len=32)

    def test_empty_prompt_rejected_on_submit(self):
        eng = self._engine()
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(Request(prompt=[], max_new_tokens=2))
        # no partial admission happened
        assert eng.free_slots == [0, 1]

    def test_empty_prompt_rejected_on_run(self):
        eng = self._engine()
        good = Request(prompt=[1], max_new_tokens=2)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.run([good, Request(prompt=[], max_new_tokens=2)])
        # rejected before any request was mutated
        assert good.out == [] and not good.done
        assert eng.free_slots == [0, 1]

    def test_overlong_prompt_rejected(self):
        # Symmetric to the empty-prompt guard: a prompt longer than the
        # KV cache would silently wrap and corrupt it (batched prefill
        # and prefill-as-decode alike).
        eng = self._engine()            # max_len=32
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(Request(prompt=[1] * 33, max_new_tokens=1))
        with pytest.raises(ValueError, match="max_len"):
            eng.run([Request(prompt=[1] * 33, max_new_tokens=1)])
        assert eng.free_slots == [0, 1]

    def test_run_returns_submission_order_multi_wave(self):
        # 5 requests through 2 slots with staggered lengths: completion
        # order differs from submission order, the result must not.
        eng = self._engine()
        reqs = [Request(prompt=[i + 1], max_new_tokens=n)
                for i, n in enumerate([6, 1, 3, 1, 2])]
        done = eng.run(reqs)
        assert [id(r) for r in done] == [id(r) for r in reqs]
        assert all(len(r.out) == r.max_new_tokens and not r.timed_out
                   for r in done)

    def test_run_submission_order_with_timeout(self):
        eng = self._engine()
        reqs = [Request(prompt=[i + 1], max_new_tokens=50)
                for i in range(4)]
        done = eng.run(reqs, max_ticks=3)
        assert [id(r) for r in done] == [id(r) for r in reqs]
        assert all(r.timed_out for r in done)
        assert len(done[0].out) == 3          # partial output preserved
        assert len(done[2].out) == 0          # never scheduled
        assert eng.free_slots == [0, 1]

    def test_presubmitted_extras_append_after(self):
        eng = self._engine()
        direct = Request(prompt=[9], max_new_tokens=1)
        assert eng.submit(direct)
        reqs = [Request(prompt=[1], max_new_tokens=2)]
        done = eng.run(reqs)
        assert done[0] is reqs[0] and done[1] is direct


class TestCollectStatsJitsOnce:
    def test_observe_forward_traces_once_per_shape(self):
        from repro.calib.corpus import attach_observer_ids, collect_stats
        cfg = _cfg(w_bits=8, x_bits=8,
                   mf=MFTechniqueConfig(mode="mf"))
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        tagged, registry = attach_observer_ids(params)
        traces = 0

        def fwd(p, batch):
            nonlocal traces
            traces += 1
            return T.lm_forward(p, batch, cfg)[0]

        batches = [{"tokens": jax.random.randint(
            jax.random.PRNGKey(i), (2, 8), 0, cfg.vocab_size)}
            for i in range(4)]
        collector = collect_stats(fwd, tagged, batches, registry)
        assert traces == 1                   # jitted once, replayed 3x
        assert np.all(collector.count > 0)   # every projection observed

    def test_jitted_stats_match_eager_pass(self):
        from repro.calib import tap
        from repro.calib.corpus import (StatsCollector, attach_observer_ids,
                                        collect_stats)
        cfg = _cfg(w_bits=8, x_bits=8, mf=MFTechniqueConfig(mode="mf"))
        params = T.lm_init(jax.random.PRNGKey(0), cfg)
        tagged, registry = attach_observer_ids(params)
        batches = [{"tokens": jax.random.randint(
            jax.random.PRNGKey(i), (2, 8), 0, cfg.vocab_size)}
            for i in range(3)]

        def fwd(p, batch):
            return T.lm_forward(p, batch, cfg)[0]

        jit_col = collect_stats(fwd, tagged, batches, registry)
        eager_col = StatsCollector(registry.n_ids)
        with tap.observing(eager_col):
            for b in batches:
                jax.block_until_ready(fwd(tagged, b))
        jax.effects_barrier()
        np.testing.assert_allclose(jit_col.count, eager_col.count)
        np.testing.assert_allclose(jit_col.amax, eager_col.amax)
        np.testing.assert_allclose(jit_col.hist, eager_col.hist)
