"""Batched serving engine: slot-based continuous batching over the decode
step, greedy/temperature sampling, and prompt ingestion.

The engine owns a fixed-capacity KV cache (`slots` x `max_len`); requests
occupy slots, prompts are ingested through batched programmed prefill
(one (B, T) forward per admission wave) when the architecture supports it
— token-by-token through the jitted decode step otherwise — and finished
slots are recycled. `serve_step` — the function the decode dry-run cells
lower — is a single fused (decode + sample) step over the whole batch.

Weight-stationary CIM serving: when the model config maps projections to
``cim_sim``, the engine programs the whole model ONCE at construction
(`core.programmed.program_weights`) and every jitted decode step serves
from the frozen macro state — the per-step weight recalibrate/requantise/
bitplane/pack work of the on-the-fly path disappears from the hot loop,
mirroring how the hardware writes the µArray once and streams inputs.

Fleet-faithful serving: constructed with a ``Fleet``, the engine compiles
the model's projections onto it (`repro.compiler.schedule.compile_model`).
A model whose µArray tiles all fit the fleet's ``tile_slots`` is *pinned*
— weights stay resident, reloads amortise to zero. A model that does NOT
fit decodes through round-interleaved execution: every projection becomes
a :class:`~repro.core.programmed.SwappedMacro` whose step re-programs
tile rounds per input stream (program round r, stream the step-time
inputs through the resident tiles, swap in round r+1) — bit-exact against
the pinned path, with every reprogram event charged against the Eq. 4
roll-up (`repro.compiler.cost.serve_reload_cost`) in the
:class:`ServeReport` each ``run()`` produces.

Silicon-aware serving (``repro.silicon`` + ``repro.macros``):
constructed with a ``SiliconConfig``, a macro model, or a registered
macro name (``silicon="collaborative"``), the engine samples the
flavour's silicon instances over the fleet's tile slots (cap-DAC
mismatch — per slot or shared per group, comparator offset +
tail-current correction, conversion noise, drift directions) and every
stream decodes through the per-tile silicon datapath. A ``DriftPolicy``
adds the aging loop: the fleet ages one unit per input stream, drifted
views are refreshed on cadence, and a probe corpus is replayed against
the float MF reference on cadence — past the alarm thresholds the
engine re-runs the macro's tiered comparator re-trim (fine DAC, coarse
tier once drift saturates the ±3σ range, retirement screening beyond
that), re-measures per-projection activation scales on the healed
datapath, re-programs every macro, and charges the rewrite in the
``ServeReport`` next to the per-stream reload costs.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry

# Monotonic engine tags for trace events (``TraceEvent.engine``): small,
# stable, and human-readable where ``id()`` is neither.
_ENGINE_TAGS = itertools.count(1)


def make_serve_step(cfg: ModelConfig, pctx=None,
                    temperature: float = 0.0) -> Callable:
    """(params, cache, tokens, rng, step) -> (next_tokens, logits, cache).

    ``step`` is the engine's input-stream counter (decode steps + prefill
    calls), threaded through :func:`repro.core.cim.conversion_clock` so
    per-conversion thermal dither decorrelates across stream steps. It is
    unused (and free) when the exec tree carries no thermal silicon.
    """
    pctx = pctx or T.ParallelContext()
    from repro.core import cim

    def serve_step(params, cache, tokens, rng, step=0):
        with cim.conversion_clock(step):
            logits, new_cache = T.lm_decode_step(params, cache, tokens,
                                                 cfg, pctx)
        with jax.named_scope("sampling"):
            if temperature > 0.0:
                nxt = jax.random.categorical(rng, logits / temperature,
                                             axis=-1)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            nxt = nxt.astype(jnp.int32)
        return nxt, logits, new_cache

    return serve_step


@dataclasses.dataclass
class Request:
    prompt: list[int]
    max_new_tokens: int
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # Set by ServeEngine.run when the tick budget ran out before the
    # request finished (or before it was ever scheduled): the request is
    # returned with whatever it produced instead of being dropped.
    timed_out: bool = False
    # Set by ServeEngine.evict (deadline-aware schedulers reclaiming the
    # slot): the request keeps its partial output but never finished.
    evicted: bool = False


@dataclasses.dataclass(frozen=True)
class ServeReport:
    """Per-``run()`` serving accounting (also at ``engine.last_report``).

    ``streams`` counts the input streams the fleet served: decode steps
    plus batched-prefill calls — each one replays the full weight reload
    of a non-pinned schedule, which is what the Eq. 4 reload fields
    charge (``repro.compiler.cost.serve_reload_cost``). Pinned models
    (and engines built without a fleet) report zero reload cost.

    The ``drift_*`` / ``recal_*`` fields account the silicon lab's
    auto-recalibration (``repro.silicon.drift``): every recalibration
    rewrites the whole model's µArray weights (the scales changed), so
    its reload bits are charged next to the per-stream reload cost at the
    same fleet write energy / load-port bandwidth.
    """

    decode_tokens: int          # tokens generated this run
    decode_steps: int           # engine ticks this run
    prefill_tokens: int         # prompt tokens ingested via batched prefill
    prefill_calls: int          # batched-prefill invocations (waves)
    elapsed_s: float
    tok_s: float                # generated tokens / elapsed
    pinned: Optional[bool]      # None = no fleet attached
    rounds_max: int             # deepest weight-swap round of any layer
    reprogram_events: int       # schedule events x streams
    reload_bits: int
    reload_energy_j: float
    reload_s: float
    utilization: float          # fleet compute-slot occupancy (schedule)
    drift_checks: int = 0       # drift probes run this run
    drift_alarms: int = 0       # probes that raised the drift alarm
    recalibrations: int = 0     # auto-recalibration events this run
    recal_reload_bits: int = 0  # µArray weight bits rewritten by recals
    recal_energy_j: float = 0.0
    recal_s: float = 0.0
    # Slots whose drifted offset exceeded even the coarse re-trim DAC
    # range at the LAST recalibration (a fleet-health level, not a
    # per-window delta): screened for retirement — their residue can no
    # longer be trimmed and only grows with further drift.
    retired_slots: int = 0
    # Generated tokens discarded by slot evictions this window (deadline
    # shedding): work the fleet paid for that no caller received.
    evicted_tokens: int = 0

    @property
    def streams(self) -> int:
        return self.decode_steps + self.prefill_calls

    @property
    def reload_energy_nj(self) -> float:
        return self.reload_energy_j * 1e9

    @property
    def recal_energy_nj(self) -> float:
        return self.recal_energy_j * 1e9


class ServeEngine:
    def __init__(self, params, cfg: ModelConfig, slots: int, max_len: int,
                 temperature: float = 0.0, eos_id: Optional[int] = None,
                 seed: int = 0, program: bool = True, calibration=None,
                 fleet=None, batched_prefill: Optional[bool] = None,
                 silicon=None, silicon_key=None, drift=None):
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        # Weight-stationary programming: freeze every CIM projection's
        # macro state now so the jitted step does input-side work only.
        # ``program=False`` keeps the legacy on-the-fly path (benchmarks).
        # ``calibration`` (a repro.calib CalibrationArtifact, or a path to
        # a saved one) programs its measured per-projection activation
        # scales instead of the static full-scale default.
        # ``fleet`` (a repro.compiler.tiling.Fleet) makes serving
        # fleet-faithful: models that exceed its resident tile slots are
        # served round-interleaved (see module docstring).
        # ``silicon`` (a repro.silicon SiliconConfig, a repro.macros
        # MacroModel, or a registered macro name like "collaborative")
        # samples the flavour's silicon instances over the fleet's tile
        # slots (keyed by ``silicon_key``, default PRNGKey(seed)) and
        # serves every decode/prefill stream through the per-tile
        # silicon datapath.
        # ``drift`` (a repro.silicon.drift DriftPolicy) probes the live
        # datapath against the calibration baseline every
        # ``check_interval`` streams and auto-recalibrates on alarm.
        # Host work is marked with ``obs_trace.span`` (``serve.*``), which
        # a running ``jax.profiler`` trace records and a bus hears; the
        # compiled programs carry named scopes only (op metadata).
        self._exec_params = params
        self.trace_tag = next(_ENGINE_TAGS)
        # The metrics registry every stream/health counter lives in:
        # ``ServeReport`` (and the traffic lab's ``TrafficReport``) are
        # views over this registry — counters are monotonic so windowed
        # reports difference snapshots and disjoint windows sum exactly;
        # the retrim-tier numbers are gauges (fleet-health LEVELS).
        self.metrics = MetricsRegistry()
        m = self.metrics
        self._c_decode_steps = m.counter(
            "serve_decode_steps_total", "engine decode ticks")
        self._c_decode_tokens = m.counter(
            "serve_decode_tokens_total", "tokens generated")
        self._c_prefill_calls = m.counter(
            "serve_prefill_calls_total", "batched-prefill waves")
        self._c_prefill_tokens = m.counter(
            "serve_prefill_tokens_total", "prompt tokens ingested")
        self._c_prefill_slab_tokens = m.counter(
            "serve_prefill_slab_tokens_total",
            "prompt-slab positions computed (slots x bucket per wave)")
        self._c_drift_checks = m.counter(
            "serve_drift_checks_total", "drift probes run")
        self._c_drift_alarms = m.counter(
            "serve_drift_alarms_total", "drift probes that alarmed")
        self._c_recals = m.counter(
            "serve_recalibrations_total", "auto-recalibration events")
        self._c_recal_bits = m.counter(
            "serve_recal_reload_bits_total",
            "uArray weight bits rewritten by recalibrations")
        self._c_evictions = m.counter(
            "serve_evictions_total", "slots reclaimed before completion")
        self._c_evicted_tokens = m.counter(
            "serve_evicted_tokens_total",
            "generated tokens discarded by evictions")
        self._g_coarse = m.gauge(
            "fleet_coarse_slots",
            "slots on the coarse trim tier NOW (level)")
        self._g_retired = m.gauge(
            "fleet_retired_slots",
            "slots screened for retirement NOW (level)")
        # Per-stream Eq. 4 reload payload for "reload" trace events
        # (None = pinned or fleet-less: nothing is reloaded per stream).
        self._stream_reload_payload = None
        self.programmed = False
        self.calibration = None
        self.fleet = fleet
        self.schedule = None
        self.silicon = None                 # sampled FleetSilicon
        self.silicon_cfg = None             # the macro model serving it
        self.macro = None                   # alias of silicon_cfg
        self.drift = drift
        self.drift_log = []                 # DriftStatus per probe
        self.last_drift_status = None
        self._monitor = None
        self._registry = None
        self._swap_map = None
        self._drifting = False
        programmable = (program and cfg.mf.enabled
                        and cfg.mf.mode == "cim_sim")
        if calibration is not None and not programmable:
            raise ValueError(
                "a calibration artifact was supplied but the engine is not "
                "programming CIM macros (program=False or the config does "
                "not map projections to cim_sim) — the scales would be "
                "silently dropped")
        if fleet is not None and not programmable:
            raise ValueError(
                "a fleet was supplied but the engine is not programming "
                "CIM macros (program=False or the config does not map "
                "projections to cim_sim) — the schedule would not "
                "describe the executed datapath")
        if silicon is not None:
            if not programmable or fleet is None:
                raise ValueError(
                    "silicon variation is per fleet tile slot: it needs a "
                    "programmed CIM engine built with a fleet (the slots "
                    "the sampled ADC instances live in)")
        if drift is not None and calibration is None:
            raise ValueError(
                "drift monitoring compares live probes against the "
                "programmed calibration artifact — construct the engine "
                "with calibration=")
        if programmable:
            scales = None
            if calibration is not None:
                from repro.calib.artifact import CalibrationArtifact
                if not isinstance(calibration, CalibrationArtifact):
                    calibration = CalibrationArtifact.load(calibration)
                if calibration.x_bits != cfg.mf.cim.x_bits:
                    raise ValueError(
                        f"calibration artifact is for x_bits="
                        f"{calibration.x_bits}, model runs x_bits="
                        f"{cfg.mf.cim.x_bits}")
                _check_calibration_names(params, calibration)
                scales = calibration.scales
                self.calibration = calibration
            self._swap_map = self._compile_fleet_schedule() \
                if fleet is not None else None
            self._base_params = params
            if drift is not None:
                # Observer ids ride the programmed tree so the live amax
                # tap (and recalibration observe passes) can address every
                # projection instance.
                from repro.calib.corpus import attach_observer_ids
                self._base_params, self._registry = \
                    attach_observer_ids(params)
            if silicon is not None:
                from repro.macros.registry import as_macro
                from repro.silicon.instance import fleet_silicon
                # Any macro-shaped spec: SiliconConfig (→ the SA-ADC
                # flavour, the pre-registry physics), a MacroModel, or
                # a registered name. Unknown names/types fail with the
                # registry's precise error.
                model = as_macro(silicon)
                self.silicon_cfg = model
                self.macro = model
                self.silicon = fleet_silicon(fleet, model, silicon_key)
                self._drifting = model.is_drifting
            self._program(scales)
            self.programmed = True
        self.cache = T.lm_init_cache(cfg, slots, max_len)
        self.step_fn = jax.jit(make_serve_step(cfg,
                                               temperature=temperature))
        self.requests: list[Optional[Request]] = [None] * slots
        self._feed = np.zeros((slots,), np.int32)       # next token to feed
        self._prompt_left = np.zeros((slots,), np.int64)
        self._rng = jax.random.PRNGKey(seed)
        # Batched programmed prefill: one (slots, T) forward per admission
        # wave instead of one decode step per prompt token. Auto-enabled
        # when the architecture supports it (GQA attention caches);
        # ``batched_prefill=False`` forces prefill-as-decode.
        supported = T.prefill_supported(cfg)
        if batched_prefill and not supported:
            raise ValueError(
                f"{cfg.name}: batched prefill needs an all-GQA-attention "
                f"pattern with a full-length KV cache")
        self.batched_prefill = supported if batched_prefill is None \
            else bool(batched_prefill)
        from repro.core import cim as _cim

        def _prefill(p, c, tok, val, step=0):
            with _cim.conversion_clock(step):
                return T.lm_prefill_cache(p, c, tok, val, cfg)

        self._prefill_fn = jax.jit(_prefill)
        # Wave-admission observers: each hook receives the admitted
        # [(slot, request), ...] wave — schedulers (repro.traffic) track
        # slot occupancy through this instead of polling.
        self.admission_hooks: list[Callable] = []
        # Exec-tree refresh observers: called after _refresh_silicon
        # rebuilds self._exec_params (drift refresh, recalibration) —
        # mesh sharding (repro.traffic.shard) re-places the new tree.
        self.exec_refresh_hooks: list[Callable] = []
        self.last_report: Optional[ServeReport] = None
        # Runtime sanitizer (REPRO_SANITIZE=1): shadow-execute every
        # decode tick through the reference einsum datapath and assert
        # bitwise agreement (see repro.analysis.sanitize).
        self._sanitizer = None
        from repro.analysis.sanitize import sanitize_enabled
        if sanitize_enabled() and self.programmed:
            from repro.analysis.sanitize import ServeSanitizer
            self._sanitizer = ServeSanitizer(self, temperature=temperature)
        if drift is not None:
            from repro.silicon.drift import DriftMonitor
            self._monitor = DriftMonitor(cfg, params, drift, self._registry,
                                         scales or {}, cfg.mf.cim.x_bits)
            # Pin the pre-drift probe error: the recovery gate every
            # post-recalibration measurement is judged against.
            self._monitor.record_baseline(self._exec_params)

    def _emit(self, kind: str, **kw) -> None:
        """Host-side trace emission tagged with this engine (a no-op
        global read when no bus is installed)."""
        obs_trace.emit(kind, engine=self.trace_tag, **kw)

    def _program(self, scales) -> None:
        """(Re-)program every macro from the base tree, then overlay the
        current silicon state. Plane-level (bit-packed) state is forced
        whenever silicon is attached — the lossless collapse has no ADC
        evaluations to perturb. With ``use_kernel`` configs the macros
        keep their Pallas kernel layout instead: silicon folds into the
        fused kernel operands (``attach_silicon``'s ``silk`` entries), so
        sigma>0 fleets decode on the fused fast path."""
        from repro.core.programmed import program_weights
        self._last_scales = scales   # the shadow sanitizer re-programs with these
        self._programmed_params = program_weights(
            self._base_params, self.cfg.mf.cim, scales=scales,
            swap=self._swap_map, prefer_lossless=self.silicon is None)
        if obs_trace.enabled():
            data = {"calibrated": scales is not None,
                    "reprogram": self.programmed}
            if self.schedule is not None:
                from repro.compiler.cost import serve_reload_cost
                data.update(
                    pinned=self.schedule.pinned,
                    tiles=self.schedule.total_tiles,
                    weight_bits=(self.schedule.total_tiles
                                 * self.fleet.tile_weight_bits),
                    per_stream=serve_reload_cost(self.schedule,
                                                 1).to_payload())
            self._emit("program", stream=self.stream_index, **data)
        self._refresh_silicon()

    def _refresh_silicon(self) -> None:
        """Re-gather the per-projection silicon views from the fleet's
        CURRENT state (age/corrections) into the exec tree."""
        if self.silicon is None:
            self._exec_params = self._programmed_params
        else:
            from repro.silicon.instance import attach_silicon
            pinned = self.schedule.pinned if self.schedule is not None \
                else True
            self._exec_params = attach_silicon(
                self._programmed_params, self.silicon, self.silicon_cfg,
                self.cfg.mf.cim, pinned=pinned)
        # getattr: _refresh_silicon first runs from __init__ before the
        # hook list (and the sanitizer) exist.
        for hook in getattr(self, "exec_refresh_hooks", ()):
            hook(self)
        san = getattr(self, "_sanitizer", None)
        if san is not None:
            san.refresh(self)

    def _compile_fleet_schedule(self):
        """Compile the model's projections onto the fleet; returns the
        ``program_weights`` swap map (None when the model pins)."""
        fleet, cim = self.fleet, self.cfg.mf.cim
        if (fleet.cfg.m_columns, fleet.cfg.w_bits) != (cim.m_columns,
                                                       cim.w_bits):
            raise ValueError(
                f"fleet µArray geometry (M={fleet.cfg.m_columns}, "
                f"W_P={fleet.cfg.w_bits}) does not match the model's "
                f"CimConfig (M={cim.m_columns}, W_P={cim.w_bits})")
        self.schedule, groups = fleet_schedule(self.params, fleet,
                                               self.slots)
        # The schedule is frozen for the engine's lifetime: roll up its
        # Eq. 4 utilization once instead of per run().
        from repro.compiler.cost import model_cost
        self._fleet_utilization = model_cost(self.schedule)[1].utilization
        if self.schedule.pinned:
            return None
        # Round-interleaved serving replays this reload charge on every
        # input stream — cache the Eq. 4 payload once for the per-stream
        # "reload" trace events.
        from repro.compiler.cost import serve_reload_cost
        self._stream_reload_payload = \
            serve_reload_cost(self.schedule, 1).to_payload()
        not_linear = [g.name for g in groups if g.kind != "linear"]
        if not_linear:
            raise NotImplementedError(
                f"model does not fit the fleet ({self.schedule.total_tiles}"
                f" tiles > {fleet.tile_slots} slots) and round-interleaved "
                f"serving covers linear projections only; non-linear "
                f"projections: {not_linear[:4]}")
        return {g.name: fleet.tile_slots for g in groups}

    @property
    def free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.requests) if r is None]

    @property
    def occupied_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.requests) if r is not None]

    @property
    def stream_index(self) -> int:
        """The engine's input-stream counter (decode steps + prefill
        calls) — the conversion clock threaded into the jitted forwards
        and the age clock of the silicon lab."""
        return int(self._c_decode_steps.value + self._c_prefill_calls.value)

    def evict(self, slot: int) -> Request:
        """Reclaim an occupied slot before its request finishes (deadline-
        aware schedulers shedding a stream that can no longer meet its
        SLO). The request is marked ``evicted`` and returned with its
        partial output; the slot is free for the next admission wave —
        whose `_reset_slots` scatter zeroes the cache positions, so no
        state leaks to the next occupant.

        The freed slot's in-flight work is made visible: the generated
        tokens the eviction discards feed ``serve_evicted_tokens_total``
        (surfacing as ``ServeReport.evicted_tokens`` and the traffic
        lab's ``TrafficReport.evicted_tokens``) and ride the ``evict``
        trace event next to the un-ingested prompt remainder."""
        req = self.requests[slot]
        if req is None:
            raise ValueError(f"slot {slot} is not occupied")
        req.evicted = True
        self.requests[slot] = None
        freed = len(req.out)
        self._c_evictions.inc()
        self._c_evicted_tokens.inc(freed)
        self._emit("evict", stream=self.stream_index, slot=slot,
                   tokens=freed, prompt_left=int(self._prompt_left[slot]))
        return req

    def submit_many(self, reqs: list[Request]) -> int:
        """Admit up to ``len(free_slots)`` requests in ONE jitted scatter.

        Multi-slot admission waves (engine start, post-completion refills)
        previously paid one ``_reset_slot`` dispatch per request; all
        admitted slots now reset through a single ``_reset_slots`` call
        whose slot vector is padded to a fixed length (repeating the first
        slot — idempotent zeroing), so every wave reuses one compiled
        program. With batched prefill enabled, the admitted requests'
        prompts (all but the final token, which feeds the first sampling
        decode step) are then ingested in one ``lm_prefill_cache`` call
        instead of one decode tick per token. Returns the number of
        requests admitted.
        """
        with obs_trace.span("serve.admit"):
            self._validate(reqs)
            free = self.free_slots
            take = reqs[:len(free)]
            if not take:
                return 0
            sel = free[:len(take)]
            for s, req in zip(sel, take):
                self.requests[s] = req
                self._feed[s] = req.prompt[0]
                self._prompt_left[s] = len(req.prompt) - 1
            pad = np.full((self.slots,), sel[0], np.int32)
            pad[:len(sel)] = sel
            self.cache = _reset_slots(self.cache, jnp.asarray(pad))
            if obs_trace.enabled():
                self._emit("admit", stream=self.stream_index,
                           slots=len(sel),
                           prompt_tokens=sum(len(r.prompt) for r in take))
            for hook in self.admission_hooks:
                hook(list(zip(sel, take)))
            if self.batched_prefill:
                self._prefill_wave([(s, r) for s, r in zip(sel, take)
                                    if len(r.prompt) > 1])
            return len(take)

    def _prefill_wave(self, wave: list[tuple[int, Request]]) -> None:
        """Ingest the admitted prompts' first ``len - 1`` tokens in one
        batched forward; the final prompt token stays in ``_feed`` so the
        next ordinary decode tick samples the first output token exactly
        like the prefill-as-decode flow. Slab length buckets to the next
        power of two to bound recompiles; non-participating slots carry
        ``valid = 0`` and are untouched."""
        if not wave:
            return
        with obs_trace.span("serve.prefill"):
            t_max = max(len(r.prompt) - 1 for _, r in wave)
            t_b = min(1 << (t_max - 1).bit_length(), self.max_len)
            tokens = np.zeros((self.slots, t_b), np.int32)
            valid = np.zeros((self.slots,), np.int32)
            for s, req in wave:
                n = len(req.prompt) - 1
                tokens[s, :n] = req.prompt[:n]
                valid[s] = n
                self._feed[s] = req.prompt[n]
                self._prompt_left[s] = 0
            stream = self.stream_index
            self.cache = self._prefill_fn(self._exec_params, self.cache,
                                          jnp.asarray(tokens),
                                          jnp.asarray(valid),
                                          jnp.int32(stream))
            self._c_prefill_calls.inc()
            self._c_prefill_tokens.inc(int(valid.sum()))
            self._c_prefill_slab_tokens.inc(tokens.size)
            self._emit("prefill_wave", stream=stream, slots=len(wave),
                       tokens=int(valid.sum()), bucket=t_b)
            self._after_stream()

    def _validate(self, reqs: list[Request]) -> None:
        """Reject malformed requests BEFORE any engine state mutates."""
        for req in reqs:
            if not req.prompt:
                raise ValueError(
                    "request has an empty prompt — the decode step needs "
                    "at least one token to feed (submit a BOS token "
                    "explicitly if that is what you mean)")
            if len(req.prompt) > self.max_len:
                raise ValueError(
                    f"prompt of {len(req.prompt)} tokens exceeds the "
                    f"engine's KV cache (max_len={self.max_len}) — "
                    f"ingesting it would silently wrap and corrupt the "
                    f"cache")

    def submit(self, req: Request) -> bool:
        return self.submit_many([req]) == 1

    def step(self) -> None:
        """One engine tick: decode every occupied slot by one token."""
        with obs_trace.span("serve.step"):
            with obs_trace.span("serve.dispatch"):
                self._rng, sub = jax.random.split(self._rng)
                tokens = jnp.asarray(self._feed)
                step_idx = jnp.int32(self.stream_index)
                cache_before = (self.cache if self._sanitizer is not None
                                else None)
                nxt, logits, self.cache = self.step_fn(self._exec_params,
                                                       self.cache, tokens,
                                                       sub, step_idx)
            if self._sanitizer is not None:
                self._sanitizer.check_step(self, cache_before, tokens, sub,
                                           step_idx, logits)
            self._c_decode_steps.inc()
            with obs_trace.span("serve.sync"):
                nxt = np.asarray(nxt)
            with obs_trace.span("serve.bookkeep"):
                for s, req in enumerate(self.requests):
                    if req is None:
                        continue
                    if self._prompt_left[s] > 0:
                        # still ingesting the prompt: feed the next one
                        k = len(req.prompt) - int(self._prompt_left[s])
                        self._feed[s] = req.prompt[k]
                        self._prompt_left[s] -= 1
                        continue
                    tok = int(nxt[s])
                    req.out.append(tok)
                    self._c_decode_tokens.inc()
                    self._feed[s] = tok
                    if (self.eos_id is not None and tok == self.eos_id) \
                            or len(req.out) >= req.max_new_tokens:
                        req.done = True
                        self.requests[s] = None
            self._after_stream()

    # -- silicon aging + drift monitoring -----------------------------------

    # Re-gather cadence for a drifting fleet served WITHOUT a DriftPolicy
    # (the silicon still ages; nobody is watching the probe).
    _SILICON_UPDATE_DEFAULT = 8

    def _after_stream(self) -> None:
        """Per-input-stream hook: charge the stream's reload trace event
        (non-pinned schedules), age the silicon, refresh the drifted
        views on cadence, run the drift probe on cadence."""
        if (self._stream_reload_payload is not None
                and obs_trace.enabled()):
            self._emit("reload", stream=self.stream_index,
                       **self._stream_reload_payload)
        if not self._drifting and self._monitor is None:
            return
        with obs_trace.span("serve.after_stream"):
            streams = self.stream_index
            if self._drifting:
                # A fleet with zero drift sigmas never changes with age,
                # so static-silicon engines skip the per-token aging.
                from repro.silicon.instance import age
                self.silicon = age(self.silicon, 1)
                interval = (self.drift.silicon_update_interval
                            if self.drift is not None
                            else self._SILICON_UPDATE_DEFAULT)
                if streams % max(interval, 1) == 0:
                    self._refresh_silicon()
            if (self._monitor is not None
                    and streams % max(self.drift.check_interval, 1) == 0):
                self._drift_check(streams)

    def _drift_check(self, streams: int) -> None:
        self._c_drift_checks.inc()
        status = self._monitor.check(self._exec_params, streams)
        # Emit the probe BEFORE any recalibration it triggers, so the
        # trace's seq order reads causally: drift_probe(alarm) →
        # retrim → retire → program → recal.
        if obs_trace.enabled():
            data = dict(rel_l2=float(status.rel_l2),
                        baseline_rel_l2=float(status.baseline_rel_l2),
                        max_clip_ratio=float(status.max_clip_ratio),
                        alarm=bool(status.alarm),
                        reasons=list(status.reasons))
            if obs_trace.detail_enabled() and self.silicon is not None:
                off = np.asarray(
                    self.macro.effective_offsets(self.silicon))
                data["residue_fs"] = [round(float(x), 6) for x in off]
            self._emit("drift_probe", stream=streams, **data)
        if status.alarm:
            self._c_drift_alarms.inc()
            if self.drift.auto_recalibrate:
                post = self._recalibrate(streams)
                status = dataclasses.replace(
                    status, recalibrated=True, post_rel_l2=post,
                    retrim_coarse_slots=int(self._g_coarse.value),
                    retired_slots=int(self._g_retired.value))
        self.drift_log.append(status)
        self.last_drift_status = status

    def _recalibrate(self, streams: int) -> float:
        """Auto-recalibration: re-run the macro's tiered comparator
        re-trim against the DRIFTED silicon (fine DAC where it still
        captures, the coarse tier where drift saturated the ±3σ range,
        retirement screening beyond even that), re-measure
        per-projection activation scales on the healed datapath,
        re-program every macro, and charge the full weight rewrite.
        Returns the post-recovery probe rel-L2.
        """
        from repro.calib.artifact import CalibrationArtifact
        from repro.calib.corpus import scales_from_stats
        if self.silicon is not None:
            prev_retired = int(self._g_retired.value)
            self.silicon, tiers = self.macro.retrim(self.silicon)
            tiers = np.asarray(tiers)
            coarse = int((tiers == 1).sum())
            retired = int((tiers == 2).sum())
            self._g_coarse.set(coarse)
            self._g_retired.set(retired)
            self._refresh_silicon()
            if obs_trace.enabled():
                data = dict(coarse=coarse, retired=retired)
                if obs_trace.detail_enabled():
                    data["tiers"] = [int(t) for t in tiers]
                self._emit("retrim", stream=streams, **data)
                if retired > prev_retired:
                    self._emit("retire", stream=streams, retired=retired,
                               newly=retired - prev_retired)
        # One probe replay on the healed datapath measures the live
        # activation statistics (the monitor's observe forward is
        # compiled once; re-attachment changes leaf values only).
        _, collector = self._monitor.observe(self._exec_params)
        scales = scales_from_stats(collector, self._registry,
                                   self.cfg.mf.cim.x_bits,
                                   self.calibration.method)
        self._program(scales)
        self._monitor.set_scales(scales)
        self.calibration = CalibrationArtifact(
            method=self.calibration.method, x_bits=self.calibration.x_bits,
            scales=scales,
            meta=dict(self.calibration.meta,
                      recalibrated_at_stream=streams))
        self._c_recals.inc()
        bits = 0
        if self.schedule is not None:
            bits = self.schedule.total_tiles * self.fleet.tile_weight_bits
            self._c_recal_bits.inc(bits)
        post = self._monitor.rel_l2(self._exec_params)
        # Future drift is judged against the healed datapath, not day
        # zero — the re-programmed scales shifted the noise floor.
        self._monitor.rebaseline(post)
        if obs_trace.enabled():
            nj = (bits * self.fleet.reload_j_per_bit * 1e9
                  if self.schedule is not None else 0.0)
            self._emit("recal", stream=streams, reload_bits=bits,
                       energy_nj=nj, post_rel_l2=float(post))
        return post

    def counters(self) -> dict:
        """Snapshot of the engine's cumulative stream counters (a view
        over ``self.metrics``). Take one before a serving window and hand
        it to :meth:`report_since` after — how external schedulers
        (``repro.traffic``) get per-window :class:`ServeReport`s without
        going through :meth:`run`. Every entry except the two fleet-
        health LEVELS (``retired_slots`` / ``retrim_coarse_slots``) is a
        monotonic counter, so deltas over disjoint windows sum exactly to
        the full-run totals — no event (a recalibration straddling a
        window boundary included) is ever counted twice."""
        return dict(decode_steps=int(self._c_decode_steps.value),
                    decode_tokens=int(self._c_decode_tokens.value),
                    prefill_calls=int(self._c_prefill_calls.value),
                    prefill_tokens=int(self._c_prefill_tokens.value),
                    drift_checks=int(self._c_drift_checks.value),
                    drift_alarms=int(self._c_drift_alarms.value),
                    recals=int(self._c_recals.value),
                    recal_bits=int(self._c_recal_bits.value),
                    evictions=int(self._c_evictions.value),
                    evicted_tokens=int(self._c_evicted_tokens.value),
                    retired_slots=int(self._g_retired.value),
                    retrim_coarse_slots=int(self._g_coarse.value))

    def report_since(self, before: dict, elapsed_s: float) -> ServeReport:
        """Eq. 4-charged :class:`ServeReport` of the window between a
        :meth:`counters` snapshot and now (also stored at
        ``last_report``)."""
        now = self.counters()
        self.last_report = self._build_report(
            decode_steps=now["decode_steps"] - before["decode_steps"],
            decode_tokens=now["decode_tokens"] - before["decode_tokens"],
            prefill_calls=now["prefill_calls"] - before["prefill_calls"],
            prefill_tokens=now["prefill_tokens"] - before["prefill_tokens"],
            elapsed_s=elapsed_s,
            drift_checks=now["drift_checks"] - before["drift_checks"],
            drift_alarms=now["drift_alarms"] - before["drift_alarms"],
            recalibrations=now["recals"] - before["recals"],
            recal_reload_bits=now["recal_bits"] - before["recal_bits"],
            # A fleet-health level as of the last recalibration, not a
            # windowed delta — retirement is a standing condition.
            retired_slots=now["retired_slots"],
            # .get: snapshots predating the telemetry counters lack the
            # key (saved-to-JSON benchmark baselines).
            evicted_tokens=(now["evicted_tokens"]
                            - before.get("evicted_tokens", 0)))
        return self.last_report

    def run(self, reqs: list[Request], max_ticks: int = 10_000
            ) -> list[Request]:
        """Serve ``reqs`` to completion (or until ``max_ticks``).

        Every submitted request comes back, in SUBMISSION order — callers
        zipping results to inputs stay aligned no matter which wave or
        slot a request landed on (requests already in flight from direct
        ``submit`` calls are appended after, in completion order).
        Requests still in flight — or never scheduled — when the tick
        budget runs out are marked ``timed_out`` and returned with their
        partial output, and their slots are released.

        Each run also produces a :class:`ServeReport` (``last_report``)
        charging the fleet schedule's reprogram events against the run's
        input streams.
        """
        self._validate(reqs)
        t0 = time.perf_counter()
        counters0 = self.counters()
        pending = list(reqs)
        done: list[Request] = []
        ticks = 0
        while (pending or any(r is not None for r in self.requests)) \
                and ticks < max_ticks:
            if pending and self.free_slots:
                admitted = self.submit_many(pending)
                del pending[:admitted]
            before = [r for r in self.requests]
            self.step()
            for r in before:
                if r is not None and r.done:
                    done.append(r)
            ticks += 1
        for s, r in enumerate(self.requests):
            if r is not None:
                r.timed_out = True
                done.append(r)
                self.requests[s] = None
        for r in pending:
            r.timed_out = True
            done.append(r)
        self.report_since(counters0, time.perf_counter() - t0)
        # Submission order first; extras (in-flight from direct submit
        # calls before this run) keep completion order after.
        submitted = {id(r) for r in reqs}
        extras = [r for r in done if id(r) not in submitted]
        return list(reqs) + extras

    def _build_report(self, *, decode_steps: int, decode_tokens: int,
                      prefill_calls: int, prefill_tokens: int,
                      elapsed_s: float, drift_checks: int = 0,
                      drift_alarms: int = 0, recalibrations: int = 0,
                      recal_reload_bits: int = 0, retired_slots: int = 0,
                      evicted_tokens: int = 0) -> ServeReport:
        pinned = None
        rounds_max = 0
        utilization = 0.0
        reprogram = reload_bits = 0
        reload_j = reload_s = 0.0
        recal_j = recal_s = 0.0
        if self.schedule is not None:
            from repro.compiler.cost import serve_reload_cost
            pinned = self.schedule.pinned
            rounds_max = self.schedule.rounds_max
            utilization = self._fleet_utilization
            reload = serve_reload_cost(self.schedule,
                                       decode_steps + prefill_calls)
            reprogram = reload.reprogram_events
            reload_bits = reload.reload_bits
            reload_j = reload.reload_energy_j
            reload_s = reload.reload_s
            # Recalibration rewrites are priced at the same fleet weight-
            # load port the per-stream reloads go through.
            recal_j = recal_reload_bits * self.fleet.reload_j_per_bit
            recal_s = recal_reload_bits / self.fleet.reload_bits_per_s
        return ServeReport(
            decode_tokens=decode_tokens, decode_steps=decode_steps,
            prefill_tokens=prefill_tokens, prefill_calls=prefill_calls,
            elapsed_s=elapsed_s,
            tok_s=decode_tokens / elapsed_s if elapsed_s > 0 else 0.0,
            pinned=pinned, rounds_max=rounds_max,
            reprogram_events=reprogram, reload_bits=reload_bits,
            reload_energy_j=reload_j, reload_s=reload_s,
            utilization=utilization, drift_checks=drift_checks,
            drift_alarms=drift_alarms, recalibrations=recalibrations,
            recal_reload_bits=recal_reload_bits, recal_energy_j=recal_j,
            recal_s=recal_s, retired_slots=retired_slots,
            evicted_tokens=evicted_tokens)


def fleet_schedule(params, fleet, calls: int):
    """Compile a model's projections onto ``fleet``: the schedule a
    :class:`ServeEngine` with ``slots=calls`` serves from. Returns
    ``(ModelSchedule, projection groups)``."""
    from repro.compiler.frontend import projection_layer_stats
    from repro.compiler.schedule import compile_model
    from repro.core.mapping import MappingPolicy
    stats, groups = projection_layer_stats(params, calls=calls)
    # Every walked projection executes in cim_sim here, so the policy
    # gate is wide open — the fleet decides residency, not ops/param.
    return compile_model(stats, fleet, policy=MappingPolicy(
        threshold=0.0, always_digital=())), groups


def _check_calibration_names(params, calibration) -> None:
    """Fail loudly when an artifact's projection names don't belong to
    this model — otherwise every scale lookup would miss and the engine
    would serve the static full-scale default while claiming to be
    calibrated."""
    from repro.core.programmed import iter_projections
    expected: set[str] = set()
    for name, _, kind in iter_projections(params):
        if kind == "experts":
            expected.update(f"{name}.{k}" for k in ("up", "gate", "down"))
        else:
            expected.add(name)
    unknown = set(calibration.scales) - expected
    if unknown or not (set(calibration.scales) & expected):
        raise ValueError(
            f"calibration artifact does not match this model's "
            f"projections (unknown names: {sorted(unknown)[:5]}; model "
            f"has {len(expected)} projections) — was it calibrated for a "
            f"different config?")


@partial(jax.jit, donate_argnums=0)
def _reset_slots(cache, slots):
    """Zero a VECTOR of slots' positions in one on-device scatter.

    ``slots`` is an int32 vector (duplicates allowed — zeroing is
    idempotent, which is what lets ``submit_many`` pad admission waves to
    a fixed length and reuse one compiled program). The cache argument is
    donated — callers always rebind (``cache = _reset_slots(cache, s)``),
    so the untouched KV leaves alias in place instead of being copied per
    admission."""
    def fix(path, v):
        last = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if last in ("len", "pos"):
            if v.ndim == 1:
                return v.at[slots].set(0)
            return v.at[:, slots].set(0)
        return v
    return jax.tree_util.tree_map_with_path(fix, cache)


@partial(jax.jit, donate_argnums=0)
def _reset_slot(cache, slot):
    """Single-slot variant of :func:`_reset_slots` (kept for callers that
    admit one request outside a wave)."""
    def fix(path, v):
        last = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if last in ("len", "pos"):
            if v.ndim == 1:
                return v.at[slot].set(0)
            return v.at[:, slot].set(0)
        return v
    return jax.tree_util.tree_map_with_path(fix, cache)
