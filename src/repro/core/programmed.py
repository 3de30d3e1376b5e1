"""Weight-stationary programmed-macro runtime (program-time/step-time split).

In the paper's macro the weights are programmed ONCE into the µArray (sign
row + magnitude bitplane rows) and only inputs stream per cycle — the
SA-ADC digitises charge-averaged MAVs against *stationary* weight
bitplanes. This module mirrors that discipline for the behavioural
simulator:

  * :class:`ProgrammedMacro` — the frozen per-projection weight state: the
    calibrated weight scale ``sw``, a *static* activation scale ``sx``
    fixed at program time, the exact digital ``r_w`` residue, and either
    the bit-packed plane-level state (:class:`CimPackedPlanes` — 8 µArray
    cells per byte), the Pallas kernel's pre-packed chunk layout
    (:class:`~repro.core.cim.CimKernelState`), or the collapsed
    exactly-lossless state (:class:`CimLosslessState`).
  * :func:`program_macro` — program one (K, N) projection.
  * :func:`program_weights` — walk a model parameter tree and attach a
    ``"prog"`` entry to every MF projection dict (those carrying the MF
    neuron's ``alpha``), stacked-layer, conv, and MoE-expert layouts
    included, so the programmed state flows through ``jax.lax.scan``
    exactly like the parameters it shadows. ``core.mf.apply_projection``
    picks it up in CIM_SIM mode; ``convnets.conv_apply`` and
    ``moe._expert_ffn`` consume the conv / expert variants.
  * :func:`map_projections` / :func:`iter_projections` — the shared tree
    walk (also used by the calibration lab in ``repro.calib`` to attach
    observers with the SAME names scale programming looks up).
  * :class:`ProgrammedLayer` — per-tile programmed slices of one
    compiler-tiled projection (see ``repro.compiler.execute``).

Bit-exactness contract: for the same ``CimConfig`` and the same ``sx``,
the programmed path is bit-identical to the on-the-fly path (monolithic
and tiled) — both phases run the very same ops on the very same arrays,
just split across time; bit-packing is a pure storage transform (unpack
reproduces the exact {0,1} cells). The *static* ``sx`` is the one
modelling choice (hardware cannot re-calibrate the input DAC per batch);
``repro.calib`` records corpus statistics and programs measured
per-projection scales through the ``scales=`` hook below — see
EXPERIMENTS.md "Corpus-driven activation calibration".
"""
# repro-lint: module=exactness-critical

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.cim import (CimConfig, CimKernelSilicon, CimKernelState,
                            CimPartials, CimWeightState, ProjectionSilicon,
                            _input_operands, _weight_sign_magnitude,
                            cap_fixed,
                            cim_input_partials, cim_kernel_partials,
                            cim_kernel_silicon_partials, cim_mf_recombine,
                            cim_program_kernel_state, cim_program_silicon,
                            cim_program_weight_state, cim_rx_partials)

# Full-scale assumption for the default static activation calibration:
# post-norm activations are ~unit-RMS, so |x| <= ~4 covers >4 sigma. Used
# only when no measured scale is supplied (see EXPERIMENTS.md).
DEFAULT_ACT_AMAX = 4.0

# The sign gate occupies the top bit of every packed byte; magnitude
# planes fill bits [0, W_P-1). W_P <= 8 always holds (magnitudes are
# stored as int8 |w_q| <= 127 everywhere in the simulator).
_SIGN_BIT = 7


def adc_exactly_lossless(cfg: CimConfig) -> bool:
    """True at the paper's exactly-lossless pairings (2^A_P - 1 == M).

    There the SA-ADC code of every chunk MAV *is* the integer discharge
    count (code = round(count/M * (2^A_P - 1)) = count), so plane/chunk
    decomposition, digitisation, and plane recombination collapse
    algebraically: sum_p 2^p sum_c code[c,p] == sum_k gate_k * |v_k|.
    Both hardware design points (8x62 -> 5-bit, 8x30 -> 4-bit) qualify.
    """
    return 2 ** cfg.adc_bits - 1 == cfg.m_columns


def _check_packable(cfg: CimConfig) -> None:
    if cfg.w_planes > _SIGN_BIT:
        raise ValueError(
            f"w_bits={cfg.w_bits}: {cfg.w_planes} magnitude planes + sign "
            f"do not fit one packed byte (the simulator stores |w_q| as "
            f"int8, so w_bits <= 8)")


class CimPackedPlanes(NamedTuple):
    """Bit-packed plane-level programmed weight state (8 cells/byte).

    One uint8 per µArray cell in the program-time (C, m, N) layout: bits
    [0, W_P-1) hold the |w_q| magnitude bitplanes (LSB first — exactly the
    rows the hardware stores), bit 7 holds the step(w) sign gate. This
    cuts programmed-state memory ~(W_P)x versus one int8 per plane-cell
    (8x at W_P=8); :func:`unpack_weight_state` reproduces the exact {0,1}
    cells, so the step-time datapath is bit-identical to unpacked state.
    """

    packed: jax.Array   # (C, m, N) uint8 plane bits + sign gate
    r_w: jax.Array      # (1, N) float32 exact digital sum_k |w_q|_kn


def pack_weight_state(ws: CimWeightState, cfg: CimConfig) -> CimPackedPlanes:
    """Pack chunked {0,1} plane/gate cells into one byte per cell."""
    _check_packable(cfg)
    bits = jnp.arange(cfg.w_planes, dtype=jnp.int32)
    # exact-ok: int32 shift-sum of {0,1} plane bits — integer arithmetic
    mag = jnp.sum(ws.wt.astype(jnp.int32) << bits, axis=-1)      # (C, m, N)
    packed = mag | (ws.gwt.astype(jnp.int32) << _SIGN_BIT)
    return CimPackedPlanes(packed.astype(jnp.uint8), ws.r_w)


def unpack_weight_state(ps: CimPackedPlanes, cfg: CimConfig) -> CimWeightState:
    """Exact inverse of :func:`pack_weight_state` (step-time expand)."""
    p32 = ps.packed.astype(jnp.int32)
    bits = jnp.arange(cfg.w_planes, dtype=jnp.int32)
    wt = ((p32[..., None] >> bits) & 1).astype(jnp.int8)     # (C, m, N, Pw)
    gwt = ((p32 >> _SIGN_BIT) & 1).astype(jnp.int8)          # (C, m, N)
    return CimWeightState(wt, gwt, ps.r_w)


class CimLosslessState(NamedTuple):
    """Collapsed weight state for exactly-lossless ADC design points.

    One uint8 per (K, N) cell: bits [0, 7) hold the integer |w_q|
    magnitude (<= 127), bit 7 the step(w) sign gate — the sign bit rides
    in the byte the hardware would spend on the sign row. The step
    becomes two (B, K) @ (K, N) matmuls — bit-identical to the plane-level
    pipeline (every partial sum is integer-valued, exact in float32) while
    streaming W_P-1 times fewer weight bytes per decode step.
    """

    packed: jax.Array   # (K, N) uint8: |w_q| magnitude | sign gate << 7

    def magnitudes(self) -> jax.Array:
        """(K, N) float32 integer |w_q| magnitudes."""
        return (self.packed.astype(jnp.int32)
                & (2 ** _SIGN_BIT - 1)).astype(jnp.float32)

    def gates(self) -> jax.Array:
        """(K, N) float32 {0,1} step(w) sign gates."""
        return ((self.packed.astype(jnp.int32) >> _SIGN_BIT)
                & 1).astype(jnp.float32)


class ProgrammedMacro(NamedTuple):
    """Frozen weight state of one macro-mapped (K, N) projection.

    ``dac_gains`` (present iff the macro was programmed with a per-feature
    activation scale VECTOR) holds the attenuation-only input-DAC column
    gains g_k = clip(sx_k / max(sx), 2^-8, 1) on the :func:`cap_fixed`
    grid; ``sx`` is then the shared scalar max(sx). Inputs quantise
    against sx * g_k and the |x|-side bit streams are attenuated by g_k
    before the charge average — the hardware realisation of per-channel
    calibration on a DAC that has one reference per macro.
    """

    sw: jax.Array                          # calibrated weight scale
    sx: jax.Array                          # STATIC activation scale
    r_w: jax.Array                         # (1, N) digital |w| residue
    state: Optional[CimPackedPlanes]       # einsum-path bit-packed state
    kernel: Optional[CimKernelState]       # Pallas-path pre-packed state
    lossless: Optional[CimLosslessState]   # collapsed exact-ADC state
    dac_gains: Optional[jax.Array] = None  # (K,) per-feature DAC gains

    @property
    def n_out(self) -> int:
        return self.r_w.shape[-1]


# Attenuation floor of the per-feature input-DAC gain trim: a feature
# whose calibrated scale is >256x below the macro max saturates at
# 2^-8 of full scale rather than driving the shared reference down.
DAC_GAIN_FLOOR = 2.0 ** -8


def _split_channel_sx(sx: jax.Array):
    """Split a per-feature (K,) static scale into (scalar max, DAC gains).

    The macro's input DAC has ONE full-scale reference; per-feature scales
    are realised as attenuation-only column gain trims on the
    :func:`cap_fixed` fixed-point grid (so gain-weighted bit streams keep
    the float32-exact summation property that makes tiled/swapped
    execution bitwise reproducible). Scalar scales pass through unchanged.
    """
    if sx.ndim == 0:
        return sx, None
    sbar = jnp.max(sx)
    gains = cap_fixed(jnp.clip(sx / sbar, DAC_GAIN_FLOOR, 1.0))
    return sbar, gains


def program_macro(w: jax.Array, cfg: CimConfig, *, sx, sw=None,
                  prefer_lossless: bool = True) -> ProgrammedMacro:
    """Program one (K, N) projection's weights into macro state.

    ``sx`` is the static activation scale the macro will quantise inputs
    against for its whole service life — a scalar, or a per-feature (K,)
    vector (per-channel calibration), which splits into a scalar
    full-scale reference plus fixed-point DAC gain trims (see
    :func:`_split_channel_sx`). ``sw`` defaults to the max-abs calibration
    the on-the-fly path uses. The expensive weight-side work (quantise,
    sign/magnitude split, bitplanes, chunk/kernel packing) happens exactly
    once, here. Plane-level and lossless states store one byte per cell
    (magnitude bits + sign gate, :class:`CimPackedPlanes` /
    :class:`CimLosslessState`); the kernel layout stays int8 — Mosaic
    wants the cells pre-expanded.

    At exactly-lossless ADC design points the collapsed
    :class:`CimLosslessState` is programmed instead of the plane-level
    state (``prefer_lossless=False`` forces planes — needed for per-step
    variability injection and the compiler's tiled partial accumulation).
    DAC gain trims also force plane/kernel state: a gain-weighted MAV
    count is no longer integer, so the lossless collapse (code == count)
    does not hold.
    """
    if sw is None:
        sw = quant.calibrate_scale(w, cfg.w_bits)
    sw = jnp.asarray(sw, jnp.float32)
    sx = jnp.asarray(sx, jnp.float32)
    sx, dac_gains = _split_channel_sx(sx)
    if dac_gains is not None and dac_gains.shape[-1] != w.shape[0]:
        raise ValueError(
            f"per-feature sx vector has {dac_gains.shape[-1]} entries, "
            f"projection contracts over K={w.shape[0]}")
    if cfg.use_kernel:
        ks = cim_program_kernel_state(w, cfg, sw)
        return ProgrammedMacro(sw, sx, ks.r_w, None, ks, None, dac_gains)
    _check_packable(cfg)
    if prefer_lossless and adc_exactly_lossless(cfg) and dac_gains is None:
        step_w, abs_w = _weight_sign_magnitude(w, cfg, sw)
        # exact-ok: integer |w_q| magnitudes, column sums below 2^24 — exact in f32
        r_w = jnp.sum(abs_w, axis=0).astype(jnp.float32)[None, :]
        packed = (abs_w.astype(jnp.int32)
                  | (step_w.astype(jnp.int32) << _SIGN_BIT))
        ls = CimLosslessState(packed.astype(jnp.uint8))
        return ProgrammedMacro(sw, sx, r_w, None, None, ls)
    ws = cim_program_weight_state(w, cfg, sw)
    return ProgrammedMacro(sw, sx, ws.r_w, pack_weight_state(ws, cfg),
                           None, None, dac_gains)


def _lossless_partials(x2: jax.Array, ls: CimLosslessState, cfg: CimConfig,
                       sx: jax.Array, r_w: jax.Array) -> CimPartials:
    """Collapsed step at an exactly-lossless design point.

    With code == count, the plane-weighted code sums reduce to the dense
    correlations sum_k step(x)*|w| and sum_k |x|*step(w); all entries are
    integers below 2^24, so the float32 matmuls are exact and the result
    is bit-identical to the plane-level path fed through the same
    ``cim_mf_recombine``.
    """
    step_x, abs_x, _ = _input_operands(x2, cfg, sx)
    # exact-ok: integer-valued f32 operands below 2^24 — exact matmul
    s1c = step_x @ ls.magnitudes()                             # (B, N)
    # exact-ok: integer-valued f32 operands below 2^24 — exact matmul
    s2c = abs_x.astype(jnp.float32) @ ls.gates()
    # exact-ok: integer |x_q| magnitudes, row sums below 2^24 — exact in f32
    rxc = jnp.sum(abs_x, axis=-1, keepdims=True).astype(jnp.float32)
    return CimPartials(s1c, s2c, rxc, r_w)


def programmed_partials(x2: jax.Array, prog: ProgrammedMacro,
                        cfg: CimConfig,
                        cap_weights: Optional[jax.Array] = None,
                        comparator_offset: Optional[jax.Array] = None,
                        silicon: Optional[ProjectionSilicon] = None,
                        silicon_kernel: Optional[CimKernelSilicon] = None
                        ) -> CimPartials:
    """Integer code sums of x2:(B, K) against a programmed macro, down
    whichever route its state holds (plane einsum, fused kernel, lossless
    collapse); :func:`cim_mf_matmul_programmed` recombines them.

    Variability injection: the legacy shared draw (``cap_weights`` /
    ``comparator_offset``) runs on the bit-packed plane-level state
    (:class:`CimPackedPlanes`) — the packed bytes expand to the exact
    {0,1} cells, so injection composes with bit packing. Per-tile
    ``silicon`` instances run on plane-level state OR on the Pallas
    kernel layout — there the SA-ADC instances evaluate inside the fused
    kernel (:func:`~repro.core.cim.cim_kernel_silicon_partials`), with
    ``silicon_kernel`` optionally supplying the program-time cap fold
    (:func:`~repro.core.cim.cim_program_silicon`) so the hot loop skips
    the per-step fold. The collapsed lossless state has no per-chunk ADC
    evaluations to perturb and raises for every injection flavour.
    """
    K = x2.shape[-1]
    inject = (cap_weights is not None or comparator_offset is not None
              or silicon is not None)
    if prog.state is not None:
        sx_q = (prog.sx if prog.dac_gains is None
                else prog.sx * prog.dac_gains)
        with jax.named_scope("planes"):
            ws = unpack_weight_state(prog.state, cfg)
            return cim_input_partials(x2, ws, cfg, sx_q, cap_weights,
                                      comparator_offset, silicon,
                                      dac_gains=prog.dac_gains)
    if prog.kernel is not None:
        if cap_weights is not None or comparator_offset is not None:
            raise ValueError(
                "the legacy shared cap_weights/comparator_offset injection "
                "is not available on the Pallas kernel layout — only "
                "per-tile `silicon` instances fold into the fused kernel. "
                "Re-program with use_kernel=False and "
                "prefer_lossless=False for the legacy knobs.")
        if silicon is None:
            with jax.named_scope("kernel"):
                return cim_kernel_partials(x2, prog.kernel, cfg, prog.sx,
                                           prog.dac_gains)
        if prog.dac_gains is not None:
            raise ValueError(
                "per-feature DAC gain trims (per-channel sx "
                "calibration) do not compose with silicon injection; "
                "program per-tensor scales for silicon fleets")
        with jax.named_scope("kernel_silicon"):
            silk = silicon_kernel
            if silk is None:
                silk = cim_program_silicon(prog.kernel, silicon, cfg,
                                           n_chunks=-(-K // cfg.m_columns))
            return cim_kernel_silicon_partials(x2, prog.kernel, silk, cfg,
                                               prog.sx, silicon)
    if inject:
        raise ValueError(
            "variability injection needs per-chunk ADC evaluations, but "
            "this macro holds the collapsed exactly-lossless state — its "
            "step collapses the conversions that mismatch and comparator "
            "offset perturb. Re-program the projection with "
            "prefer_lossless=False (program_weights(..., "
            "prefer_lossless=False)).")
    with jax.named_scope("lossless"):
        return _lossless_partials(x2, prog.lossless, cfg, prog.sx, prog.r_w)


def cim_mf_matmul_programmed(x: jax.Array, prog: ProgrammedMacro,
                             cfg: CimConfig,
                             cap_weights: Optional[jax.Array] = None,
                             comparator_offset: Optional[jax.Array] = None,
                             silicon: Optional[ProjectionSilicon] = None,
                             silicon_kernel: Optional[CimKernelSilicon]
                             = None) -> jax.Array:
    """Step-time MF correlation x:(...,K) against a programmed macro
    (:func:`programmed_partials`, then one recombine).

    Bit-identical to ``cim_mf_matmul(x, w, cfg)`` whenever ``prog`` was
    programmed with the same ``cfg`` and the dynamic activation scale of
    ``x`` (the parity tested by tests/test_programmed.py).
    """
    batch_shape = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    parts = programmed_partials(x2, prog, cfg, cap_weights,
                                comparator_offset, silicon, silicon_kernel)
    y = cim_mf_recombine(parts, prog.sw, prog.sx, cfg)
    return y.reshape(batch_shape + (prog.n_out,)).astype(x.dtype)


class ProgrammedLayer(NamedTuple):
    """Per-tile programmed slices of one compiler-tiled (K, N) projection.

    ``tiles[j][i]`` is the :class:`ProgrammedMacro` of n-slice j / k-slice
    i of the owning :class:`~repro.compiler.tiling.TilingPlan`; every tile
    shares the layer-global ``sw``/``sx`` so tiled step-time execution
    stays bit-exact against the monolithic programmed path.
    """

    sw: jax.Array
    sx: jax.Array
    tiles: tuple[tuple[ProgrammedMacro, ...], ...]

    @property
    def n_tiles(self) -> int:
        # exact-ok: host-side integer byte/count arithmetic
        return sum(len(row) for row in self.tiles)


# ---------------------------------------------------------------------------
# Round-interleaved (weight-swapped) serving of oversized projections.
# ---------------------------------------------------------------------------

class CimSwapSchedule(NamedTuple):
    """STATIC round partition of one (K, N) projection over a fleet.

    When a model's µArray tiles exceed the fleet's resident ``tile_slots``,
    the layer executes in *rounds* (paper Sec. V dataflow): program up to
    ``tile_slots`` tiles, stream every input through them, swap in the
    next batch. Tiles are enumerated column-major (output channel outer,
    K-chunk inner), so each round covers at most three contiguous operand
    segments: a partial leading channel, a block of whole channels, and a
    partial trailing channel. ``rounds[r]`` lists that round's segments as
    ``(n0, n1, k0, k1)`` half-open index ranges over the original operand;
    every k-range is M-chunk aligned (except the ragged final chunk), which
    is exactly the tiled-bit-exactness condition of
    :mod:`repro.compiler.execute`.

    All fields are plain ints / int tuples — the schedule is hashable and
    rides pytrees as static aux data (see :class:`SwappedMacro`).
    """

    k: int
    n: int
    m_columns: int
    tile_slots: int
    rounds: tuple[tuple[tuple[int, int, int, int], ...], ...]

    @property
    def n_rounds(self) -> int:
        return len(self.rounds)

    @property
    def n_chunks(self) -> int:
        return -(-self.k // self.m_columns)

    @property
    def n_tiles(self) -> int:
        return self.n_chunks * self.n


def build_swap_schedule(k: int, n: int, m_columns: int,
                        tile_slots: int) -> CimSwapSchedule:
    """Partition a (k, n) projection's µArray tiles into weight-swap
    rounds of at most ``tile_slots`` tiles. The round count equals the
    compiler's ``ceil(tiles / tile_slots)``
    (:func:`repro.compiler.schedule.schedule_layer`) by construction."""
    if k <= 0 or n <= 0:
        raise ValueError(f"degenerate projection ({k}, {n})")
    if tile_slots < 1:
        raise ValueError(f"tile_slots must be >= 1, got {tile_slots}")
    m = m_columns
    chunks = -(-k // m)
    total = chunks * n

    def k_range(c0: int, c1: int) -> tuple[int, int]:
        return c0 * m, min(c1 * m, k)

    rounds = []
    for t0 in range(0, total, tile_slots):
        t1 = min(t0 + tile_slots, total)
        n_lo, c_lo = divmod(t0, chunks)
        n_hi, c_hi = divmod(t1 - 1, chunks)
        c_hi += 1                       # exclusive chunk end in channel n_hi
        segs: list[tuple[int, int, int, int]] = []
        if n_lo == n_hi:
            segs.append((n_lo, n_lo + 1) + k_range(c_lo, c_hi))
        else:
            mid_lo = n_lo
            if c_lo > 0:
                segs.append((n_lo, n_lo + 1) + k_range(c_lo, chunks))
                mid_lo = n_lo + 1
            mid_hi = n_hi + 1 if c_hi == chunks else n_hi
            if mid_hi > mid_lo:
                segs.append((mid_lo, mid_hi) + k_range(0, chunks))
            if c_hi < chunks:
                segs.append((n_hi, n_hi + 1) + k_range(0, c_hi))
        rounds.append(tuple(segs))
    return CimSwapSchedule(k=k, n=n, m_columns=m, tile_slots=tile_slots,
                           rounds=tuple(rounds))


@jax.tree_util.register_pytree_node_class
class SwappedMacro:
    """Swap-scheduled (NOT fleet-resident) state of one (K, N) projection.

    The fleet is too small to pin this model, so the projection owns no
    frozen weight-plane state: every input stream re-programs its tiles
    round by round (the schedule's reprogram events) and only the scales
    persist — ``sw``/``sx`` are fixed at construction exactly like a
    resident :class:`ProgrammedMacro`'s, which is what keeps swapped
    execution bit-identical to the pinned path. Children are the scale
    arrays (stacked leading axes ride ``jax.lax.scan`` like parameters);
    the :class:`CimSwapSchedule` is static aux data.
    """

    def __init__(self, sw: jax.Array, sx: jax.Array,
                 sched: CimSwapSchedule):
        self.sw = sw
        self.sx = sx
        self.sched = sched

    def tree_flatten(self):
        return (self.sw, self.sx), self.sched

    @classmethod
    def tree_unflatten(cls, sched, children):
        return cls(children[0], children[1], sched)


def swap_macro(w: jax.Array, cfg: CimConfig, tile_slots: int, *,
               sx, sw=None) -> SwappedMacro:
    """Build swap-scheduled state for a (..., K, N) weight (stacked leading
    axes get per-instance ``sw``/``sx``, sharing one static schedule)."""
    K, N = w.shape[-2:]
    sched = build_swap_schedule(K, N, cfg.m_columns, tile_slots)
    if sw is None:
        w2 = w.reshape((-1, K, N))
        sw = jax.vmap(lambda wi: quant.calibrate_scale(wi, cfg.w_bits))(w2)
        sw = sw.reshape(w.shape[:-2])
    sw = jnp.asarray(sw, jnp.float32)
    sx = jnp.asarray(sx, jnp.float32)
    if sx.ndim > w.ndim - 2:
        raise NotImplementedError(
            "per-feature (per-channel) static activation scales are not "
            "supported on swap-scheduled projections: the DAC gain trims "
            "belong to resident macro state, and a swapped projection "
            "re-programs its tiles every stream. Use a scalar sx here.")
    sx = jnp.broadcast_to(sx, w.shape[:-2])
    return SwappedMacro(sw, sx, sched)


def cim_swapped_partials(x2: jax.Array, w: jax.Array, swap: SwappedMacro,
                         cfg: CimConfig,
                         silicon: Optional[ProjectionSilicon] = None
                         ) -> CimPartials:
    """Round-interleaved µArray pass of x2:(B, K) against a swap-scheduled
    projection: program round r's tiles (weight-side work, per STREAM — the
    reprogram events billed by the compiler's Eq. 4 roll-up), stream the
    step-time inputs through them, swap in round r+1. Returns the
    integer-valued code sums; :func:`cim_mf_matmul_swapped` recombines.

    Partial code sums are integer-valued floats, so per-segment
    ``.at[].add`` accumulation is exact regardless of the round partition.

    ``silicon`` carries the per-TILE ADC instances of the projection: the
    swap rounds fill fleet slots 0..S-1 in tile order, and the silicon
    gather (``repro.silicon.instance.projection_silicon``) uses exactly
    that assignment, so tile (c, n) digitises through the same physical
    slot's instance whether the projection is pinned or swapped.
    """
    sched = swap.sched
    K, N = sched.k, sched.n
    if w.shape != (K, N):
        raise ValueError(f"swap schedule is for ({K}, {N}), weight is "
                         f"{w.shape}")
    b = x2.shape[0]
    s1 = jnp.zeros((b, N), jnp.float32)
    s2 = jnp.zeros((b, N), jnp.float32)
    r_w = jnp.zeros((1, N), jnp.float32)
    for segments in sched.rounds:
        for (n0, n1, k0, k1) in segments:
            ws = cim_program_weight_state(w[k0:k1, n0:n1], cfg, swap.sw)
            sil = None if silicon is None else \
                silicon.slice(n0, n1, k0, k1, sched.m_columns)
            p = cim_input_partials(x2[:, k0:k1], ws, cfg, swap.sx,
                                   silicon=sil)
            s1 = s1.at[:, n0:n1].add(p.s1c)
            s2 = s2.at[:, n0:n1].add(p.s2c)
            r_w = r_w.at[:, n0:n1].add(p.r_w)
    rxc = cim_rx_partials(x2, cfg, swap.sx, silicon)
    return CimPartials(s1, s2, rxc, r_w)


def cim_mf_matmul_swapped(x: jax.Array, w: jax.Array, swap: SwappedMacro,
                          cfg: CimConfig,
                          silicon: Optional[ProjectionSilicon] = None
                          ) -> jax.Array:
    """Round-interleaved MF correlation x:(...,K) against a swap-scheduled
    projection (:func:`cim_swapped_partials`, then one recombine).

    Bit-identical to ``cim_mf_matmul_programmed`` against a resident macro
    programmed with the same ``sw``/``sx``: the integer code sums are
    exact, and the single final recombine applies the same rounding
    sequence.
    """
    batch_shape = x.shape[:-1]
    x2 = x.reshape(-1, swap.sched.k)
    with jax.named_scope("swapped"):
        parts = cim_swapped_partials(x2, w, swap, cfg, silicon)
    y = cim_mf_recombine(parts, swap.sw, swap.sx, cfg)
    return y.reshape(batch_shape + (swap.sched.n,)).astype(x.dtype)


def projection_partials(x2: jax.Array, node: dict,
                        cfg: CimConfig) -> CimPartials:
    """Integer code sums of one programmed projection node (``"prog"``,
    plus ``"w"``/``"sil"``/``"silk"`` where present) on x2:(B, K): the
    pre-recombine statistics every route must agree on bit for bit."""
    prog = node["prog"]
    if isinstance(prog, SwappedMacro):
        return cim_swapped_partials(x2, node["w"], prog, cfg,
                                    node.get("sil"))
    return programmed_partials(x2, prog, cfg, silicon=node.get("sil"),
                               silicon_kernel=node.get("silk"))


# ---------------------------------------------------------------------------
# Projection-tree walk shared by programming and the calibration lab.
# ---------------------------------------------------------------------------

def default_static_sx(cfg: CimConfig,
                      act_amax: float = DEFAULT_ACT_AMAX) -> float:
    """Static activation scale from a full-scale amax assumption."""
    return float(act_amax) / quant.qmax(cfg.x_bits)


def _is_projection(node: Any) -> bool:
    """MF projection dicts are exactly those carrying the neuron's alpha."""
    return (isinstance(node, dict) and "w" in node and "alpha" in node
            and hasattr(node["w"], "ndim") and node["w"].ndim >= 2
            and hasattr(node["alpha"], "ndim"))


def _is_conv_projection(node: Any) -> bool:
    """Conv projections carry a (kh, kw, Cin, Cout) weight against a
    per-channel alpha: two extra leading weight axes relative to a
    (possibly stack-vmapped) linear projection, whose w/alpha ranks always
    differ by exactly one."""
    return node["w"].ndim - node["alpha"].ndim == 3


_EXPERT_KEYS = ("up", "gate", "down")


def _is_expert_bank(node: Any) -> bool:
    """The MoE expert layout: stacked (E, K, N) arrays per projection role
    plus the stacked MF alphas (``moe.moe_init``)."""
    return (isinstance(node, dict) and "alpha_up" in node
            and all(k in node and hasattr(node[k], "ndim")
                    and node[k].ndim >= 3 for k in _EXPERT_KEYS))


def map_projections(params: Any, fn: Callable[[str, dict, str], dict]) -> Any:
    """Rebuild a parameter tree, transforming every MF projection.

    ``fn(name, node, kind)`` is called with a stable dotted path name
    (dict keys / sequence indices joined by '.'), the projection dict, and
    ``kind`` in {'linear', 'conv', 'experts'}; its return value replaces
    the node. Non-projection structure is preserved. The same walk (and
    therefore the same names) drives both scale programming here and the
    calibration observers in ``repro.calib`` — names line up by
    construction.
    """
    def walk(node, path):
        if _is_expert_bank(node):
            return fn(".".join(path), node, "experts")
        if _is_projection(node):
            kind = "conv" if _is_conv_projection(node) else "linear"
            return fn(".".join(path), node, kind)
        if isinstance(node, dict):
            return {k: walk(v, path + (str(k),)) for k, v in node.items()}
        if isinstance(node, tuple):
            if hasattr(node, "_fields"):
                # NamedTuple pytree nodes are leaves here: they hold
                # arrays, never projection dicts, and a plain-tuple
                # rebuild would corrupt the treedef.
                return node
            return tuple(walk(v, path + (str(i),))
                         for i, v in enumerate(node))
        if isinstance(node, list):
            return [walk(v, path + (str(i),)) for i, v in enumerate(node)]
        return node

    return walk(params, ())


def iter_projections(params: Any) -> list[tuple[str, dict, str]]:
    """List (name, node, kind) for every MF projection in ``params``."""
    found: list[tuple[str, dict, str]] = []

    def collect(name, node, kind):
        found.append((name, node, kind))
        return node

    map_projections(params, collect)
    return found


def conv_weight_matrix(w: jax.Array) -> jax.Array:
    """(kh, kw, Cin, Cout) conv weight -> the (Cin*kh*kw, Cout) im2col
    matmul operand, matching ``convnets.conv_apply``'s patch layout."""
    kh, kw, cin, cout = w.shape
    return jnp.transpose(w, (2, 0, 1, 3)).reshape(cin * kh * kw, cout)


# ---------------------------------------------------------------------------
# Whole-model programming (the serve-time entry point).
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "prefer_lossless"))
def _program_nd(w: jax.Array, cfg: CimConfig, sx: jax.Array,
                prefer_lossless: bool = True) -> ProgrammedMacro:
    """Program a (..., K, N) weight, vmapping over stacked leading axes
    (scan periods, experts) so programmed leaves slice exactly like the
    parameter leaves they shadow; ``sx`` carries one scale per stacked
    instance (shape = the leading axes).

    Compiled as one program: run op by op, the int32 bit-plane and
    packing intermediates of a stacked full-width projection take
    several GB of device memory at once."""
    if w.ndim == 2:
        return program_macro(w, cfg, sx=sx, prefer_lossless=prefer_lossless)
    return jax.vmap(lambda wi, si: _program_nd(wi, cfg, si,
                                               prefer_lossless))(w, sx)


def program_weights(params: Any, cfg: CimConfig, *,
                    act_amax: float = DEFAULT_ACT_AMAX,
                    scales: Optional[dict] = None,
                    swap: Optional[dict[str, int]] = None,
                    prefer_lossless: bool = True) -> Any:
    """Program every MF projection in a model parameter tree.

    Returns a copy of ``params`` where each projection dict gains a
    ``"prog"`` entry (a :class:`ProgrammedMacro`, possibly with stacked
    leading axes); MoE expert banks gain ``"prog_up"/"prog_gate"/
    "prog_down"`` and conv projections a ``"prog"`` over the im2col
    operand. ``apply_projection`` / ``conv_apply`` / ``_expert_ffn`` then
    serve CIM_SIM projections from the programmed state with no per-step
    weight-side work.

    ``scales`` maps projection names (the :func:`map_projections` dotted
    paths; expert banks use ``<name>.up/gate/down``) to static activation
    scales — a scalar, an array over the stacked leading axes (scan
    periods, experts) for per-instance calibration, or a per-feature
    (..., K) vector (conv projections: per-Cin, expanded over the im2col
    patch) for per-CHANNEL calibration, realised as input-DAC gain trims
    (:func:`_split_channel_sx`). Unnamed projections fall back to the
    full-scale ``act_amax`` assumption. Calibration artifacts from
    ``repro.calib`` produce exactly this mapping.

    ``swap`` maps projection names to a fleet's resident ``tile_slots``:
    those projections are NOT pinned — they get a :class:`SwappedMacro`
    whose round-interleaved execution re-programs tiles every input
    stream (the fleet cannot hold the model; see ``repro.serve.engine``).
    Only linear projections can swap; scales compose with ``swap``.

    ``prefer_lossless=False`` forces plane-level (bit-packed) state even
    at exactly-lossless ADC design points — required when per-tile
    silicon variation will be injected at step time (the lossless
    collapse has no per-chunk ADC evaluations to perturb).
    """
    default_sx = jnp.float32(default_static_sx(cfg, act_amax))
    scales = scales or {}
    swap = swap or {}
    if cfg.use_kernel and cfg.m_columns > 0:
        # Fail early with the pack_chunks precondition rather than deep in
        # a traced program.
        from repro.kernels.cim_mav import CHUNK_PAD
        if cfg.m_columns > CHUNK_PAD:
            raise ValueError(
                f"m_columns={cfg.m_columns} > CHUNK_PAD={CHUNK_PAD}: the "
                f"kernel layout cannot hold this µArray geometry")

    def sx_for(name: str, w: jax.Array) -> jax.Array:
        sx = jnp.asarray(scales.get(name, default_sx), jnp.float32)
        lead = w.shape[:-2]
        if sx.shape == lead:
            return sx
        if sx.ndim >= 1 and sx.shape[-1] == w.shape[-2]:
            # Per-feature (K,) scale vector -> per-channel calibration.
            return jnp.broadcast_to(sx, lead + (w.shape[-2],))
        return jnp.broadcast_to(sx, lead)

    def prog(name, node, kind):
        out = dict(node)
        if name in swap:
            if kind != "linear":
                raise NotImplementedError(
                    f"{name}: round-interleaved weight swapping covers "
                    f"linear projections only ({kind} projections must "
                    f"stay fleet-resident)")
            out["prog"] = swap_macro(node["w"], cfg, swap[name],
                                     sx=sx_for(name, node["w"]))
            return out
        if kind == "experts":
            for key in _EXPERT_KEYS:
                w = node[key]
                out[f"prog_{key}"] = _program_nd(
                    w, cfg, sx_for(f"{name}.{key}", w), prefer_lossless)
        elif kind == "conv":
            kh, kw, cin, _ = node["w"].shape
            w2 = conv_weight_matrix(node["w"])
            sxc = jnp.asarray(scales.get(name, default_sx), jnp.float32)
            if sxc.ndim >= 1 and sxc.shape[-1] == cin:
                # Per-Cin calibration: the im2col operand is Cin-major
                # (conv_weight_matrix), so each channel's gain covers its
                # kh*kw patch columns.
                sxc = jnp.repeat(sxc, kh * kw, axis=-1)
            out["prog"] = program_macro(w2, cfg, sx=sxc,
                                        prefer_lossless=prefer_lossless)
        else:
            out["prog"] = _program_nd(node["w"], cfg,
                                      sx_for(name, node["w"]),
                                      prefer_lossless)
        return out

    return map_projections(params, prog)


def _is_prog_key(k: Any) -> bool:
    return isinstance(k, str) and (k == "prog" or k.startswith("prog_"))


def strip_keys(params: Any, drop: Callable[[Any], bool]) -> Any:
    """Rebuild a parameter tree without the dict entries whose KEY
    matches ``drop`` — the shared walk behind :func:`strip_programmed`
    and the silicon lab's ``strip_silicon``. NamedTuple pytree nodes
    (ProgrammedMacro, ProjectionSilicon, ...) are leaves: rebuilding
    them as plain tuples would corrupt the tree, and they cannot contain
    dict entries to strip."""
    def walk(node):
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items() if not drop(k)}
        if isinstance(node, tuple):
            if hasattr(node, "_fields"):
                return node
            return tuple(walk(v) for v in node)
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node
    return walk(params)


def strip_programmed(params: Any) -> Any:
    """Inverse of :func:`program_weights` (drop every programmed entry)."""
    return strip_keys(params, _is_prog_key)


def _walk_programmed(params: Any, fn: Callable[[Any], None]) -> None:
    """Call ``fn`` on every programmed entry in a parameter tree."""
    def walk(node):
        if isinstance(node, dict):
            for k, v in node.items():
                if _is_prog_key(k):
                    fn(v)
                else:
                    walk(v)
        elif isinstance(node, (tuple, list)):
            for v in node:
                walk(v)
    walk(params)


def programmed_bytes(params: Any) -> int:
    """Total bytes held by programmed state in a parameter tree."""
    total = 0

    def count(v):
        nonlocal total
        # exact-ok: host-side integer byte/count arithmetic
        total += sum(leaf.size * leaf.dtype.itemsize
                     for leaf in jax.tree.leaves(v))

    _walk_programmed(params, count)
    return total


def programmed_bytes_unpacked(params: Any, cfg: CimConfig) -> int:
    """Bytes the same programmed state would occupy WITHOUT bit-packing.

    The pre-packing layouts held one int8 per µArray plane-cell plus one
    int8 sign-gate cell (plane-level state: ``w_planes + 1`` bytes per
    cell) and separate int8 magnitude/gate arrays for the lossless
    collapse (2 bytes per cell). Kernel-layout state is not packed, so it
    counts as-is. The ratio against :func:`programmed_bytes` is the
    packing win tracked in ``BENCH_serve.json``.
    """
    total = 0

    def count(v):
        nonlocal total

        def one(pm):
            nonlocal total
            for leaf in jax.tree.leaves((pm.sw, pm.sx, pm.r_w,
                                         pm.dac_gains)):
                total += leaf.size * leaf.dtype.itemsize
            if pm.state is not None:
                total += pm.state.packed.size * (cfg.w_planes + 1)
                total += pm.state.r_w.size * pm.state.r_w.dtype.itemsize
            if pm.lossless is not None:
                total += pm.lossless.packed.size * 2
            if pm.kernel is not None:
                # exact-ok: host-side integer byte/count arithmetic
                total += sum(leaf.size * leaf.dtype.itemsize
                             for leaf in jax.tree.leaves(pm.kernel))

        if isinstance(v, ProgrammedMacro):
            one(v)
        elif isinstance(v, ProgrammedLayer):
            for row in v.tiles:
                for pm in row:
                    one(pm)

    _walk_programmed(params, count)
    return total
