"""Runtime sanitizer for the CIM datapath (``REPRO_SANITIZE=1``).

The static rules (R001-R006) catch the bug *shapes*; this module checks
the bitwise contracts themselves while an engine serves:

* **Shadow execution** — every decode tick re-runs from the SAME inputs
  with each programmed projection paired with its reference-route twin
  (``use_kernel=False``, plane-level programmed state): on every call the
  integer ADC code sums of the serving route (fused kernel, lossless
  collapse, silicon kernel) must equal the einsum reference's bitwise.
  Any difference means a broken exactness proof — exactly the class the
  PR 7 sigma>0 parity gate guards, but live, against the engine's real
  silicon state and cache. The check sits at the partials, not the
  logits: equal integer partials are the contract, while the float model
  around them may round differently in two separately compiled programs
  (it does on a TPU, where fusion and matmul passes differ per program).
* **NaN / saturation tripwires** — :func:`repro.core.cim.adc_codes`
  stages a debug callback per conversion while armed; a conversion
  tensor containing NaN, or sitting entirely at full scale (the ADC
  pegged: scales are wrong), raises at the step that produced it.
* **cap_fixed integer-quanta invariant** — on every silicon refresh,
  each attached cap/operand tensor must sit on the 2^-14 fixed-point
  grid with per-conversion denominators far below 2^24 quanta; this is
  the premise of every ``# exact-ok`` pragma on the einsum path.

The sanitizer costs roughly a second full forward per tick plus host
transfers — a debug mode, enabled by environment so production call
sites carry no flag plumbing.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Any, Optional

import jax
import numpy as np

_ENV = "REPRO_SANITIZE"
_TRUTHY = ("1", "true", "yes", "on")

# Conversion tripwire records staged by adc_codes while armed:
# (nan_fraction, saturated_fraction) per digitised tensor, drained by the
# sanitizer (or a test) after the step that produced them completes.
_TRIPWIRE_LOG: list[tuple[float, float]] = []
_FORCE_ARMED = False


def sanitize_enabled() -> bool:
    return os.environ.get(_ENV, "").strip().lower() in _TRUTHY


def tripwires_armed() -> bool:
    """Read live at trace time: each engine owns a fresh jit cache, so
    arming before the first step stages the callbacks for that engine."""
    return _FORCE_ARMED or sanitize_enabled()


def arm_tripwires(on: bool = True) -> None:
    """Explicit arm/disarm for tests that bypass the environment."""
    global _FORCE_ARMED
    _FORCE_ARMED = on


def stage_conversion_tripwire(codes: jax.Array, levels: float) -> None:
    """Called from ``adc_codes`` under trace while armed."""
    import jax.numpy as jnp

    nan_frac = jnp.mean(jnp.isnan(codes).astype(jnp.float32))
    sat_frac = jnp.mean((codes >= levels).astype(jnp.float32))

    def record(nf, sf):
        _TRIPWIRE_LOG.append((float(nf), float(sf)))

    jax.debug.callback(record, nan_frac, sat_frac)


def drain_tripwires() -> list[tuple[float, float]]:
    out = list(_TRIPWIRE_LOG)
    _TRIPWIRE_LOG.clear()
    return out


class SanitizeError(AssertionError):
    """A bitwise datapath contract was violated at runtime."""


def _finding(msg: str, *, check: str, stream: Optional[int] = None,
             **data: Any) -> SanitizeError:
    """Record a sanitizer finding on the telemetry bus (kind
    ``sanitize``), then hand back the error to raise — a crash-stopped
    serve leaves its last finding in the exported trace next to the
    events that led up to it."""
    from repro.obs import trace as obs_trace
    obs_trace.emit("sanitize", stream=stream, check=check, error=msg,
                   **data)
    return SanitizeError(msg)


def _tree_nodes(tree: Any, cls: type) -> list[Any]:
    """All ``cls`` NamedTuple nodes in a params tree (dict/list/tuple
    recursion; NamedTuples are leaves unless they ARE the target)."""
    found: list[Any] = []

    def walk(node: Any) -> None:
        if isinstance(node, cls):
            found.append(node)
            return
        if isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)) \
                and not hasattr(node, "_fields"):
            for v in node:
                walk(v)

    walk(tree)
    return found


@partial(jax.jit, static_argnames="last_axis_sum")
def _quanta_stats(arr: jax.Array, last_axis_sum: bool):
    """(finite, on-grid, largest per-conversion quanta) of one operand,
    reduced on the device so a full-width fold never round-trips to the
    host. Scaling by 2^CAP_FIXED_BITS is exact in float32 (a power-of-
    two exponent shift), so ``round(q) == q`` tests the grid exactly."""
    import jax.numpy as jnp
    from repro.core.cim import CAP_FIXED_BITS
    q = arr.astype(jnp.float32) * jnp.float32(2.0 ** CAP_FIXED_BITS)
    per_conv = jnp.sum(q, axis=-1) if last_axis_sum else q
    return (jnp.all(jnp.isfinite(q)), jnp.all(jnp.round(q) == q),
            jnp.max(per_conv, initial=0.0))


def check_cap_quanta(exec_params: Any) -> None:
    """Assert the cap_fixed integer-quanta invariant over an exec tree.

    Every silicon operand the datapath will contract against must be an
    integer multiple of 2^-CAP_FIXED_BITS, and every per-conversion
    denominator (the largest possible pre-ADC numerator) must stay below
    2^24 quanta — the premise under which float32 contraction order
    cannot matter.
    """
    from repro.core.cim import (CAP_FIXED_BITS, CimKernelSilicon,
                                ProjectionSilicon, cap_fixed)
    budget = 2.0 ** 24

    def must_be_quanta(arr: jax.Array, what: str,
                       last_axis_sum: bool = False) -> float:
        finite, on_grid, peak = _quanta_stats(arr, last_axis_sum)
        if not bool(finite):
            raise SanitizeError(f"{what}: non-finite silicon operand")
        if not bool(on_grid):
            raise SanitizeError(
                f"{what}: values are off the 2^-{CAP_FIXED_BITS} "
                f"fixed-point grid — float32 contraction order is no "
                f"longer provably irrelevant")
        return float(peak)

    for sil in _tree_nodes(exec_params, ProjectionSilicon):
        for name in ("cap", "rx_cap"):
            # quanta per chunk conversion
            peak = must_be_quanta(cap_fixed(getattr(sil, name)),
                                  f"ProjectionSilicon.{name}",
                                  last_axis_sum=True)
            if peak >= budget:
                raise SanitizeError(
                    f"ProjectionSilicon.{name}: a conversion denominator "
                    f"reaches {peak:.3g} quanta >= 2^24 — "
                    f"float32 partial sums can round")
    for silk in _tree_nodes(exec_params, CimKernelSilicon):
        for name in ("wpc", "gwc", "rxp"):
            must_be_quanta(getattr(silk, name), f"CimKernelSilicon.{name}")
        for name in ("den", "rx_den"):
            peak = must_be_quanta(getattr(silk, name),
                                  f"CimKernelSilicon.{name}")
            if peak >= budget:
                raise SanitizeError(
                    f"CimKernelSilicon.{name}: a conversion denominator "
                    f"reaches {peak:.3g} quanta >= 2^24 — float32 "
                    f"partial sums can round")


# Key under which the sanitizer pairs each programmed projection node with
# its reference-route twin (read by ``repro.core.mf.apply_projection``).
SANITIZE_REF = "sanitize_ref"

# Per-projection partial mismatch counts staged by check_partials, drained
# by ServeSanitizer.check_step after the step that produced them.
_PARTIALS_LOG: list[int] = []


def check_partials(x: jax.Array, node: dict, cfg) -> None:
    """Stage, under trace, the exactness check of one paired projection:
    its integer code sums down the serving route against the plane-level
    einsum reference (``node[SANITIZE_REF]``) on the SAME input, counted
    on the device and logged per call.

    The partials are integer-valued floats under every route, so the
    comparison is exact within one program whatever the backend's matmul
    precision or fusion choices; recombined float outputs of two
    separately compiled programs carry no such guarantee."""
    import jax.numpy as jnp
    from repro.core.programmed import projection_partials
    x2 = x.reshape(-1, x.shape[-1])
    got = projection_partials(x2, node, cfg)
    want = projection_partials(x2, node[SANITIZE_REF],
                               dataclasses.replace(cfg, use_kernel=False))
    n_diff = sum(jnp.sum(g != w) for g, w in zip(got, want))
    jax.debug.callback(lambda n: _PARTIALS_LOG.append(int(n)), n_diff,
                       ordered=True)


def paired_exec(primary: Any, reference: Any) -> Any:
    """``primary`` with every programmed linear projection node carrying
    its ``reference`` twin under :data:`SANITIZE_REF` (same names: both
    trees come from the same parameter tree)."""
    from repro.core.programmed import iter_projections, map_projections
    refs = {name: node for name, node, _ in iter_projections(reference)}

    def pair(name, node, kind):
        if "prog" not in node:
            return node
        ref = refs[name]
        return dict(node, **{SANITIZE_REF: {
            k: ref[k] for k in ("w", "prog", "sil") if k in ref}})

    return map_projections(primary, pair)


class ServeSanitizer:
    """Shadow-execution harness attached to a :class:`ServeEngine`.

    Owns a reference-route programmed/exec tree (fused kernel off,
    lossless collapse off → the plane-level einsum pipeline) kept in sync
    through the engine's refresh path, and a jitted step of the engine's
    own config over the engine's exec tree paired with it.
    ``check_step`` replays the tick through that step: every projection
    computes its integer code sums down both routes on the same input
    and any difference raises.
    """

    def __init__(self, engine, temperature: float = 0.0):
        from repro.serve.engine import make_serve_step
        self._cim = dataclasses.replace(engine.cfg.mf.cim, use_kernel=False)
        self.step_fn = jax.jit(make_serve_step(engine.cfg,
                                               temperature=temperature))
        self._programmed_src: Optional[int] = None
        self._shadow_programmed = None
        self.shadow_exec = None
        self.checked_steps = 0
        self.refresh(engine)

    def refresh(self, engine) -> None:
        """Rebuild the shadow exec tree against the engine's CURRENT
        programmed/silicon state; runs the quanta invariant on both."""
        from repro.core.programmed import program_weights
        if self._programmed_src != id(engine._programmed_params):
            # Re-program only when the engine re-programmed (scales /
            # swap changed); silicon-only refreshes reuse the state.
            self._shadow_programmed = program_weights(
                engine._base_params, self._cim,
                scales=engine._last_scales, swap=engine._swap_map,
                prefer_lossless=False)
            self._programmed_src = id(engine._programmed_params)
        if engine.silicon is None:
            self.shadow_exec = self._shadow_programmed
        else:
            from repro.silicon.instance import attach_silicon
            pinned = engine.schedule.pinned \
                if engine.schedule is not None else True
            self.shadow_exec = attach_silicon(
                self._shadow_programmed, engine.silicon,
                engine.silicon_cfg, self._cim, pinned=pinned)
        check_cap_quanta(engine._exec_params)
        check_cap_quanta(self.shadow_exec)

    def check_step(self, engine, cache_before, tokens, rng, step,
                   logits) -> None:
        """Replay one decode tick with every projection's partials checked
        against the reference route; then inspect the tripwire log."""
        _PARTIALS_LOG.clear()
        self.step_fn(paired_exec(engine._exec_params, self.shadow_exec),
                     cache_before, tokens, rng, step)
        jax.effects_barrier()
        if np.any(np.isnan(np.asarray(logits))):
            raise _finding(
                f"NaN logits at stream step {int(step)} on the primary "
                f"datapath", check="nan_logits", stream=int(step))
        counts = list(_PARTIALS_LOG)
        if not counts:
            raise _finding(
                f"no projection partials were checked at stream step "
                f"{int(step)} — the exec tree holds no programmed "
                f"projection", check="no_partials", stream=int(step))
        bad = [n for n in counts if n]
        if bad:
            raise _finding(
                f"fused/einsum divergence at stream step {int(step)}: "
                f"{sum(bad)} integer partial(s) differ in {len(bad)} of "
                f"{len(counts)} projection calls — the exactness contract "
                f"between the serving datapath and the reference einsums "
                f"is broken",
                check="partial_divergence", stream=int(step),
                n_diff=sum(bad))
        for nan_frac, sat_frac in drain_tripwires():
            if nan_frac > 0.0:
                raise _finding(
                    f"conversion tripwire: {nan_frac:.1%} NaN ADC codes "
                    f"at stream step {int(step)}",
                    check="nan_codes", stream=int(step),
                    nan_frac=nan_frac)
            if sat_frac >= 1.0:
                raise _finding(
                    f"conversion tripwire: a conversion tensor is fully "
                    f"saturated at stream step {int(step)} — activation "
                    f"scales are pegging the ADC",
                    check="saturation", stream=int(step),
                    sat_frac=sat_frac)
        self.checked_steps += 1
