"""Behavioural compute-in-SRAM simulator (paper Sec. IV).

Models the µArray execution of the MF operator bit-for-bit:

  * operands quantised to W_P-bit weights / X_P-bit inputs (sign-magnitude),
  * |w| bitplanes stored as rows, one output channel per µArray,
  * the K contraction dim split into column chunks of M (µArray half width);
    padded columns store 0 so they never discharge (denominator stays M),
  * per (chunk, plane): multiply-average MAV = (1/M) sum_j bit_pj * step_j,
  * SA-ADC digitisation of each MAV to A_P bits (uniform mid-tread code on
    [0, 1]; code = round(MAV * (2^A_P - 1)) — exactly lossless when
    2^A_P >= M + 1, reproducing the paper's 8x62 -> 5-bit / 8x30 -> 4-bit
    pairings),
  * Eq. 2 recombination with the two residues: sum|x| via an ADC'd dummy
    all-ones row, sum|w| as an exact digital weight statistic.

Optional process variability (the silicon lab, ``repro.silicon``) perturbs
the charge averaging with per-column capacitor mismatch and adds comparator
offset before digitisation — either one shared draw (legacy
``cap_weights``/``comparator_offset``) or one sampled ADC instance per
µArray tile slot (:class:`ProjectionSilicon`).

The datapath is split along the hardware's program-time / step-time
boundary: ``cim_program_weight_state`` / ``cim_program_kernel_state`` do
everything that depends only on the weights (quantise, sign/magnitude
decompose, chunk or kernel-pack, digital residue) and the step-time passes
(``cim_input_partials`` / ``cim_kernel_partials``) consume that frozen
state, computing only the input-side work per call. ``cim_mf_partials`` /
``cim_mf_matmul`` compose both phases on the fly; ``core/programmed.py``
builds persistent programmed state for weight-stationary serving.

This path is forward-only hardware emulation; ``cim_mf_matmul_ste`` wraps it
with a straight-through estimator whose backward is the float MF surrogate
gradient, enabling hardware-in-the-loop QAT.
"""
# repro-lint: module=exactness-critical,step-time

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.mf import mf_matmul


# ---------------------------------------------------------------------------
# Conversion clock (per-stream thermal dither)
# ---------------------------------------------------------------------------
#
# Thermal noise is a PER-CONVERSION phenomenon: every SA-ADC evaluation sees
# a fresh input-referred sample, unlike the static per-slot mismatch/offset
# lottery. The dither draw is keyed by (projection noise key, stream step,
# role salt), so the serving engine threads its input-stream counter into
# the jitted decode through this clock — a tap-style trace-time holder, the
# same idiom as ``repro.calib.tap``. Outside any clock the step is 0
# (single-shot forwards stay deterministic and reproducible).

_CONV_STEP: list = [None]


@contextlib.contextmanager
def conversion_clock(step):
    """Install ``step`` (int or traced scalar) as the current stream index
    for per-conversion thermal dither. Wrap the TRACE of a jitted forward
    (the engine wraps ``lm_decode_step`` / ``lm_prefill_cache``); the
    traced value is baked into the noise-key fold of every silicon ADC
    evaluation inside."""
    prev = _CONV_STEP[0]
    _CONV_STEP[0] = step
    try:
        yield
    finally:
        _CONV_STEP[0] = prev


def conversion_step():
    """Current conversion-clock value (0 when no clock is installed)."""
    s = _CONV_STEP[0]
    return 0 if s is None else s


@dataclasses.dataclass(frozen=True)
class CimConfig:
    """Geometry + precision of a compute-in-SRAM macro.

    The paper's two design points:
      8x62 µArray: m_columns=31 (per half), adc_bits=5  (~105 TOPS/W)
      8x30 µArray: m_columns=15 (per half), adc_bits=4  (~84 TOPS/W)
    """

    w_bits: int = 8          # weight precision W_P (sign + W_P-1 planes)
    x_bits: int = 8          # input precision
    adc_bits: int = 5        # SA-ADC precision A_P
    m_columns: int = 31      # columns per µArray half (vector-parallelism l)
    use_kernel: bool = False  # route the MAV loop through the Pallas kernel

    @property
    def w_planes(self) -> int:
        return self.w_bits - 1

    @property
    def x_planes(self) -> int:
        return self.x_bits - 1


def adc_codes(mav: jax.Array, adc_bits: int,
              comparator_offset: Optional[jax.Array] = None) -> jax.Array:
    """SA-ADC transfer returning the raw integer code (as float32).

    code = clip(round(mav * (2^A_P - 1))): the capacitive-DAC binary search
    settles on the nearest of 2^A_P evenly spaced reference levels. A
    comparator offset (fraction of full scale) shifts every comparison.

    Codes are small integers (<= 2^A_P - 1), exactly representable in
    float32 — every downstream accumulation of codes is therefore exact,
    which is what lets the tiled compiler path (repro.compiler.execute)
    reproduce the monolithic result bit for bit.
    """
    levels = 2 ** adc_bits - 1
    v = mav if comparator_offset is None else mav + comparator_offset
    codes = jnp.clip(jnp.round(v * levels), 0, levels)
    from repro.analysis import sanitize
    if sanitize.tripwires_armed():
        # REPRO_SANITIZE=1 only: stage a NaN/saturation tripwire callback
        # per conversion (armed at trace time; each engine owns a fresh
        # jit cache, so production traces carry no callback).
        sanitize.stage_conversion_tripwire(codes, float(levels))
    return codes


def adc_quantize(mav: jax.Array, adc_bits: int,
                 comparator_offset: Optional[jax.Array] = None) -> jax.Array:
    """SA-ADC transfer: uniform A_P-bit code on [0,1], returned dequantised."""
    return adc_codes(mav, adc_bits, comparator_offset) / (2 ** adc_bits - 1)


# Fractional bits of the cap-DAC fixed-point grid. The tail-current trim
# DACs that set each unit cap's effective weight have finite resolution;
# modelling them on a 2^-14 grid (~6e-5 of a unit cap, far below the
# sigma~0.02 mismatch being modelled) buys an exactness property the
# float-valued model cannot have: every pre-ADC numerator is a sum of
# {0,1}-gated cap products, i.e. an integer multiple of 2^-14 bounded far
# below 2^24 quanta — EXACT in float32 under any summation order. The
# fused Pallas kernel's per-chunk dot and XLA's einsum contraction then
# produce bit-identical numerators, hence identical integer ADC codes
# (the sigma>0 kernel-vs-reference parity gate of BENCH_silicon.json).
CAP_FIXED_BITS = 14

# Matmul precision of every contraction whose operands leave the bf16
# grid: 2^-14-grid caps, gain-trimmed bit streams, and code sums past
# 256 (the chunk axis is summed before the plane-weight dot). A TPU runs
# an f32 dot at DEFAULT precision as one bf16 pass, which rounds those
# operands; HIGHEST keeps the f32 products the exactness proofs assume.
# Pure {0,1} x {0,1} (or <= 127 integer) contractions are exact in bf16
# and keep the default.
EXACT = jax.lax.Precision.HIGHEST


def cap_fixed(cap: jax.Array) -> jax.Array:
    """Quantise cap-DAC weights to the 2^-CAP_FIXED_BITS fixed-point grid.

    Applied identically by the reference einsum routes and the program-
    time kernel fold (:func:`cim_program_silicon`). At sigma=0 every cap
    is exactly 1.0 — a grid point — so the quantisation is the identity
    and all nominal-collapse invariants are untouched.
    """
    s = jnp.float32(2.0 ** CAP_FIXED_BITS)
    return jnp.round(cap.astype(jnp.float32) * s) / s


def _weight_sign_magnitude(w: jax.Array, cfg: CimConfig, sw: jax.Array):
    """Quantise the weight operand into sign gates + integer magnitudes.

    Sign bits are stored SEPARATELY from the magnitude planes in the
    µArray (sign row + W_P-1 magnitude rows), so they come from the
    ORIGINAL operand sign — a weight whose magnitude truncates to zero
    keeps its true sign bit (quantising first would flip small negative
    weights to +, a large systematic error at low W_P).

    Returns (step_w, abs_w): {0,1} sign gates (K, N) and integer
    magnitudes (K, N).
    """
    wq = quant.quantize(w, sw, cfg.w_bits)          # (K, N) int
    step_w = (w >= 0).astype(jnp.float32)           # (K, N)
    return step_w, jnp.abs(wq)


def _weight_operands(w: jax.Array, cfg: CimConfig, sw: jax.Array,
                     planes_dtype=jnp.float32):
    """:func:`_weight_sign_magnitude` plus the (Pw, K, N) magnitude
    bitplane stack (LSB first) of ``planes_dtype``."""
    step_w, abs_w = _weight_sign_magnitude(w, cfg, sw)
    w_planes = quant.bitplanes(abs_w, cfg.w_bits, planes_dtype)  # (Pw, K, N)
    return step_w, abs_w, w_planes


def _input_operands(x2: jax.Array, cfg: CimConfig, sx: jax.Array):
    """Input-side mirror of :func:`_weight_operands` (same conventions)."""
    xq = quant.quantize(x2, sx, cfg.x_bits)         # (B, K) int
    step_x = (x2 >= 0).astype(jnp.float32)          # (B, K)
    abs_x = jnp.abs(xq)
    x_planes = quant.bitplanes(abs_x, cfg.x_bits)   # (Px, B, K)
    return step_x, abs_x, x_planes


def _chunk(v: jax.Array, m: int, axis_len: int) -> jax.Array:
    """Pad the contraction axis (last) to a multiple of m and fold it."""
    pad = (-axis_len) % m
    if pad:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
    return v.reshape(v.shape[:-1] + ((axis_len + pad) // m, m))


class CimPartials(NamedTuple):
    """Pre-recombination macro statistics of one (x, w) tile.

    All four fields are *integer-valued* float32 arrays (plane-weighted sums
    of SA-ADC codes / digital |w| counts), so summing the partials of K-tiles
    is exact in float32 — the foundation of the compiler's bit-exact tiled
    execution. Recombine with :func:`cim_mf_recombine`.
    """

    s1c: jax.Array   # (B, N) plane-weighted code sum, Eq. 2b numerator side
    s2c: jax.Array   # (B, N) plane-weighted code sum, Eq. 2a numerator side
    rxc: jax.Array   # (B, 1) plane-weighted code sum of the |x| dummy row
    r_w: jax.Array   # (1, N) exact digital sum_k |w_q|_kn

    def __add__(self, other: "CimPartials") -> "CimPartials":
        return CimPartials(self.s1c + other.s1c, self.s2c + other.s2c,
                           self.rxc + other.rxc, self.r_w + other.r_w)


class ProjectionSilicon(NamedTuple):
    """Per-tile sampled ADC instances of one macro-mapped (K, N) projection.

    The SA-ADC of the paper is *memory-immersed*: its capacitive DAC is the
    bit-line parasitic capacitance of the µArray half it digitises, so cap
    mismatch and comparator offset are properties of the physical SLOT a
    tile occupies, not of the weights programmed into it. This struct is
    the projection-shaped gather of a fleet's per-slot silicon state
    (:mod:`repro.silicon.instance` builds it): tile (c, n) — K-chunk ``c``
    of output channel ``n`` — reads the cap-DAC weights and comparator
    offset of the slot it is placed in. The |x| dummy-row conversion of
    chunk ``c`` (shared across every output channel) uses a designated
    per-chunk instance (``rx_*``, the slot of channel 0's tile).

    With all caps exactly 1.0 and all offsets exactly 0.0 the silicon
    route below is *bitwise identical* to the nominal fast path: every
    pre-ADC numerator is an integer-valued count, the denominator sums to
    exactly ``m``, and plane/code recombinations sum the same integers in
    a different order — exact in float32 (the σ=0 collapse gate of
    ``benchmarks/silicon_report.py``).

    ``thermal_fs``/``noise_key`` (both optional, absent by default) add the
    comparator's input-referred noise floor as PER-CONVERSION dither: every
    ADC evaluation draws a fresh N(0, thermal_fs²) sample keyed by
    (``noise_key``, the :func:`conversion_clock` stream step, a role salt),
    instead of the old static per-slot draw. Same key + same step ⇒ the
    same dither (replayable); consecutive stream steps decorrelate. Dither
    is drawn per *executed* conversion batch: layouts that batch
    conversions differently (round-interleaved swapped segments vs one
    pinned pass) draw independent — statistically equivalent — samples, so
    the bit-exactness invariants are guaranteed in the thermal_fs=None
    regime only (where every exactness gate runs).
    """

    cap: jax.Array        # (N, C, m) per-tile cap-DAC weights, 1.0 nominal
    offset: jax.Array     # (N, C) per-tile comparator offset (FS fraction)
    rx_cap: jax.Array     # (C, m) dummy-row conversion instance
    rx_offset: jax.Array  # (C,) dummy-row comparator offset
    thermal_fs: Optional[jax.Array] = None   # () noise RMS (FS fraction)
    noise_key: Optional[jax.Array] = None    # PRNG key of the dither stream

    def slice(self, n0: int, n1: int, k0: int, k1: int,
              m_columns: int) -> "ProjectionSilicon":
        """The silicon view of operand segment [k0:k1, n0:n1].

        ``k0`` must be M-chunk aligned (the tiled/swapped bit-exactness
        condition), so segment chunk boundaries coincide with the
        projection's global chunking. The dither stream is re-keyed by the
        segment origin so distinct segments draw independent samples.
        """
        if k0 % m_columns:
            raise ValueError(
                f"segment k0={k0} is not aligned to m_columns={m_columns}: "
                f"the sliced silicon chunks would not match the tiles")
        c0, c1 = k0 // m_columns, -(-k1 // m_columns)
        nkey = self.noise_key
        if nkey is not None and (n0 or c0):
            nkey = jax.random.fold_in(jax.random.fold_in(nkey, n0), c0)
        return ProjectionSilicon(self.cap[n0:n1, c0:c1],
                                 self.offset[n0:n1, c0:c1],
                                 self.rx_cap[c0:c1], self.rx_offset[c0:c1],
                                 self.thermal_fs, nkey)

    def dither(self, shape, salt: int) -> Optional[jax.Array]:
        """Per-conversion thermal dither for one ADC tensor (``None`` when
        the noise floor is off). ``salt`` separates the S1/S2/Rx roles of
        one stream step."""
        if self.thermal_fs is None:
            return None
        step = conversion_step()
        key = jax.random.fold_in(jax.random.fold_in(self.noise_key, step),
                                 salt)
        return self.thermal_fs * jax.random.normal(key, shape)


class CimWeightState(NamedTuple):
    """Program-time weight-side state of one macro-mapped projection.

    This is exactly what the hardware holds after the µArray is written:
    chunked sign gates and magnitude bitplanes plus the digital |w| residue.
    Built once by :func:`cim_program_weight_state`; every subsequent input
    streams through :func:`cim_input_partials` without touching ``w``.

    The arrays are stored contraction-ready for the step-time batched dot
    (chunk batch leading, the m columns as the contraction axis) so the
    hot loop never transposes the big weight-side operand — and the {0,1}
    cells are held as int8 (exactly the µArray's storage density class),
    quartering the bytes the bandwidth-bound decode step streams per
    token; the widening cast to f32 is exact on {0,1} so bit-exactness is
    untouched.
    """

    wt: jax.Array    # (C, m, N, Pw) int8 chunked |w| magnitude bitplanes
    gwt: jax.Array   # (C, m, N) int8 chunked step(w) sign gates
    r_w: jax.Array   # (1, N) exact digital sum_k |w_q|_kn


def cim_program_weight_state(w: jax.Array, cfg: CimConfig,
                             sw: jax.Array) -> CimWeightState:
    """Program-time pass: quantise/decompose/chunk the weights once."""
    K, N = w.shape
    step_w, abs_w, w_planes = _weight_operands(w, cfg, sw, jnp.int8)
    m = cfg.m_columns
    wp = _chunk(jnp.moveaxis(w_planes, -1, 0), m, K)             # (N, Pw, C, m)
    wt = jnp.transpose(wp, (2, 3, 0, 1))                         # (C, m, N, Pw)
    gw = _chunk(step_w.T, m, K)                                  # (N, C, m)
    gwt = jnp.transpose(gw, (1, 2, 0)).astype(jnp.int8)          # (C, m, N)
    # exact-ok: integer |w_q| magnitudes, column sums below 2^24 — exact in f32
    r_w = jnp.sum(abs_w, axis=0).astype(jnp.float32)[None, :]    # (1, N)
    return CimWeightState(wt, gwt, r_w)


def cim_input_partials(x2: jax.Array, ws: CimWeightState, cfg: CimConfig,
                       sx: jax.Array,
                       cap_weights: Optional[jax.Array] = None,
                       comparator_offset: Optional[jax.Array] = None,
                       silicon: Optional[ProjectionSilicon] = None,
                       dac_gains: Optional[jax.Array] = None
                       ) -> CimPartials:
    """Step-time pass: stream x2:(B, Kt) through a programmed µArray.

    Only input-side work happens here (x quantisation against the static
    activation scale ``sx``, gates, MAVs, ADC) — the weight-side state was
    frozen at program time, mirroring the weight-stationary hardware.

    Bit-exactness across layouts: every pre-ADC MAV numerator is an
    integer-valued count (products of {0,1} gates and bits), exact in
    float32 for any summation order — so the nominal fast path below may
    contract in the program-time layout and still produce codes identical
    to the cap-weighted reference einsums.

    Variability injection, two flavours (mutually exclusive):
      * ``cap_weights`` (K,) + scalar ``comparator_offset`` — one shared
        mismatch draw across the projection (the legacy Fig. 8 model);
      * ``silicon`` — a :class:`ProjectionSilicon` giving every µArray
        TILE its own cap-DAC weights and comparator offset (the fleet-
        faithful per-slot model of ``repro.silicon``). With
        ``cfg.use_kernel`` the silicon state is folded into kernel
        operands and the fused Pallas route runs instead of the
        reference einsums (bit-identical codes by the fixed-point cap
        argument of :func:`cap_fixed`).

    ``dac_gains`` (K,) carries per-feature input-DAC gain trims <= 1 (the
    per-channel ``sx`` calibration of ``core.programmed``): the |x| bit
    stream is attenuated per column BEFORE the charge average, touching
    only the S2/R_x conversions (the sign-gate S1 stream is unscaled).
    """
    if silicon is not None and (cap_weights is not None
                                or comparator_offset is not None):
        raise ValueError(
            "pass either per-tile `silicon` or the legacy shared "
            "cap_weights/comparator_offset injection, not both")
    if dac_gains is not None and (silicon is not None
                                  or cap_weights is not None
                                  or comparator_offset is not None):
        raise ValueError(
            "per-channel DAC gain trims (per-channel sx calibration) do "
            "not compose with variability injection: the gain-cap "
            "products leave the fixed-point grid that guarantees "
            "cross-layout exactness. Program per-tensor scales for "
            "silicon-injected serving.")
    K = x2.shape[-1]
    m = cfg.m_columns

    if silicon is not None and cfg.use_kernel:
        # Fused Pallas fast path: fold the per-slot silicon state into
        # kernel operands and evaluate the SA-ADC instances in-kernel.
        ks = cim_kernel_state_from_weight_state(ws, cfg)
        silk = cim_program_silicon(ks, silicon, cfg, n_chunks=-(-K // m))
        return cim_kernel_silicon_partials(x2, ks, silk, cfg, sx, silicon)

    step_x, _, x_planes = _input_operands(x2, cfg, sx)

    def adc(mav: jax.Array) -> jax.Array:
        return adc_codes(mav, cfg.adc_bits, comparator_offset)

    pw = 2.0 ** jnp.arange(cfg.w_planes)
    px = 2.0 ** jnp.arange(cfg.x_planes)
    gx = _chunk(step_x, m, K)                                    # (B, C, m)
    xp = _chunk(x_planes, m, K)                                  # (Px, B, C, m)
    if dac_gains is not None:
        # Per-column attenuation of the streamed |x| bits (exact: gains
        # live on the cap_fixed grid, bits are {0,1}).
        xp = xp * _chunk(dac_gains.astype(jnp.float32)[None, :], m, K)[0]

    if silicon is not None:
        return _silicon_partials(gx, xp, ws, cfg, silicon, pw, px)

    if cap_weights is None and comparator_offset is None:
        # Nominal macro: the charge-average denominator is exactly m and
        # the counts are integers, so the contraction runs as a layout-
        # friendly batched dot straight against the program-time operand
        # layout — no per-step transpose of the weight state. (An offset
        # routes to the reference branch below: its broadcast contract is
        # defined against the (B, N, Pw, C) ADC tensor layout.)
        inv = jnp.float32(m)
        # S1 = sum_k step(x_k) * |w|_kn  (Eq. 2b numerator)
        # exact-ok: {0,1} bits x 2^-14-grid caps; integer quanta < 2^24 — exact in f32
        counts1 = jnp.einsum("bcm,cmnp->cbnp", gx,
                             ws.wt.astype(jnp.float32))
        codes1 = adc(counts1 / inv)                              # (C, B, N, Pw)
        # exact-ok: integer ADC codes x power-of-two plane weights — exact in f32
        s1c = jnp.einsum("cbnp,p->bn", codes1, pw, precision=EXACT)
        # S2 = sum_k step(w_kn) * |x|_k  (Eq. 2a numerator)
        # exact-ok: {0,1} bits x 2^-14-grid caps; integer quanta < 2^24 — exact in f32
        counts2 = jnp.einsum("qbcm,cmn->cqbn", xp,
                             ws.gwt.astype(jnp.float32),
                             precision=None if dac_gains is None else EXACT)
        codes2 = adc(counts2 / inv)                              # (C, Px, B, N)
        # exact-ok: integer ADC codes x power-of-two plane weights — exact in f32
        s2c = jnp.einsum("cqbn,q->bn", codes2, px, precision=EXACT)
        # R_x via the dummy all-ones row (shared across weight vectors).
        rxc = _nominal_rx(xp, cfg)                               # (B, 1)
        return CimPartials(s1c, s2c, rxc, ws.r_w)

    # Variability injection: capacitor mismatch and/or comparator offset
    # change the charge averaging / digitisation, so run the general
    # cap-weighted einsums against the (N, Pw, C, m) reference layout.
    nchunks = -(-K // m)
    if cap_weights is None:
        cap = jnp.ones((nchunks, m), jnp.float32)
    else:
        cap = cap_fixed(_chunk(cap_weights.astype(jnp.float32)[None, :],
                               m, K)[0])
    # exact-ok: 2^-14-grid caps; small fixed-point chunk sums — exact in f32
    cap_sum = jnp.sum(cap, axis=-1)                              # (C,)
    wp = jnp.transpose(ws.wt.astype(jnp.float32),
                       (2, 3, 0, 1))                             # (N, Pw, C, m)
    gw = jnp.transpose(ws.gwt.astype(jnp.float32), (2, 0, 1))    # (N, C, m)
    # exact-ok: {0,1} bits x 2^-14-grid caps; integer quanta < 2^24 — exact in f32
    num1 = jnp.einsum("bcm,npcm,cm->bnpc", gx, wp, cap, precision=EXACT)
    codes1 = adc(num1 / cap_sum[None, None, None, :])            # (B, N, Pw, C)
    # exact-ok: integer ADC codes x power-of-two plane weights — exact in f32
    s1c = jnp.einsum("bnpc,p->bn", codes1, pw, precision=EXACT)
    # exact-ok: {0,1} bits x 2^-14-grid caps; integer quanta < 2^24 — exact in f32
    num2 = jnp.einsum("pbcm,ncm,cm->pbnc", xp, gw, cap, precision=EXACT)
    codes2 = adc(num2 / cap_sum[None, None, None, :])            # (Px, B, N, C)
    # exact-ok: integer ADC codes x power-of-two plane weights — exact in f32
    s2c = jnp.einsum("pbnc,p->bn", codes2, px, precision=EXACT)
    # exact-ok: {0,1} bits x 2^-14-grid caps; integer quanta < 2^24 — exact in f32
    num_rx = jnp.einsum("pbcm,cm->pbc", xp, cap, precision=EXACT)
    codes_rx = adc(num_rx / cap_sum[None, None, :])              # (Px, B, C)
    # exact-ok: integer ADC codes x power-of-two plane weights — exact in f32
    rxc = jnp.einsum("pbc,p->b", codes_rx, px,
                     precision=EXACT)[:, None]                   # (B, 1)
    return CimPartials(s1c, s2c, rxc, ws.r_w)


def _silicon_partials(gx: jax.Array, xp: jax.Array, ws: CimWeightState,
                      cfg: CimConfig, sil: ProjectionSilicon,
                      pw: jax.Array, px: jax.Array) -> CimPartials:
    """Per-tile silicon route: every (chunk, channel) tile digitises with
    its own sampled cap-DAC weights and comparator offset.

    The zero-padded tail columns of the final chunk keep their sampled
    capacitance in the denominator (a padded cell stores 0 and never
    discharges, but its bit-line parasitic still loads the DAC) — at σ=0
    the denominator is therefore exactly ``m`` and this route collapses
    bitwise to the nominal fast path.
    """
    nchunks, n_out = gx.shape[-2], ws.wt.shape[2]
    if sil.cap.shape != (n_out, nchunks, cfg.m_columns):
        raise ValueError(
            f"silicon cap shape {sil.cap.shape} does not match this "
            f"projection's ({n_out}, {nchunks}, {cfg.m_columns}) tiles")
    cap = cap_fixed(sil.cap)                                     # (N, C, m)
    # exact-ok: 2^-14-grid caps; small fixed-point chunk sums — exact in f32
    cap_sum = jnp.sum(cap, axis=-1)                              # (N, C)
    off = sil.offset.astype(jnp.float32)                         # (N, C)
    wp = jnp.transpose(ws.wt.astype(jnp.float32),
                       (2, 3, 0, 1))                             # (N, Pw, C, m)
    gw = jnp.transpose(ws.gwt.astype(jnp.float32), (2, 0, 1))    # (N, C, m)
    # exact-ok: {0,1} bits x 2^-14-grid caps; integer quanta < 2^24 — exact in f32
    num1 = jnp.einsum("bcm,npcm,ncm->bnpc", gx, wp, cap, precision=EXACT)
    off1 = off[:, None, :]
    d1 = sil.dither(num1.shape, 1)
    if d1 is not None:
        off1 = off1 + d1
    codes1 = adc_codes(num1 / cap_sum[:, None, :], cfg.adc_bits,
                       off1)                                     # (B, N, Pw, C)
    # exact-ok: integer ADC codes x power-of-two plane weights — exact in f32
    s1c = jnp.einsum("bnpc,p->bn", codes1, pw, precision=EXACT)
    # exact-ok: {0,1} bits x 2^-14-grid caps; integer quanta < 2^24 — exact in f32
    num2 = jnp.einsum("qbcm,ncm,ncm->qbnc", xp, gw, cap, precision=EXACT)
    off2 = off
    d2 = sil.dither(num2.shape, 2)
    if d2 is not None:
        off2 = off2 + d2
    codes2 = adc_codes(num2 / cap_sum, cfg.adc_bits, off2)       # (Px, B, N, C)
    # exact-ok: integer ADC codes x power-of-two plane weights — exact in f32
    s2c = jnp.einsum("qbnc,q->bn", codes2, px, precision=EXACT)
    rxc = _silicon_rx(xp, cfg, sil)                              # (B, 1)
    return CimPartials(s1c, s2c, rxc, ws.r_w)


def _silicon_rx(xp: jax.Array, cfg: CimConfig, sil: ProjectionSilicon
                ) -> jax.Array:
    """|x| dummy-row code sum digitised by the per-chunk rx instances."""
    px = 2.0 ** jnp.arange(cfg.x_planes)
    rx_cap = cap_fixed(sil.rx_cap)                               # (C, m)
    # exact-ok: 2^-14-grid caps; small fixed-point chunk sums — exact in f32
    rx_sum = jnp.sum(rx_cap, axis=-1)                            # (C,)
    # exact-ok: {0,1} bits x 2^-14-grid caps; integer quanta < 2^24 — exact in f32
    num_rx = jnp.einsum("qbcm,cm->qbc", xp, rx_cap, precision=EXACT)
    off_rx = sil.rx_offset.astype(jnp.float32)
    d_rx = sil.dither(num_rx.shape, 3)
    if d_rx is not None:
        off_rx = off_rx + d_rx
    codes_rx = adc_codes(num_rx / rx_sum, cfg.adc_bits,
                         off_rx)                                 # (Px, B, C)
    # exact-ok: integer ADC codes x power-of-two plane weights — exact in f32
    return jnp.einsum("qbc,q->b", codes_rx, px,
                      precision=EXACT)[:, None]                  # (B, 1)


def _nominal_rx(xp: jax.Array, cfg: CimConfig) -> jax.Array:
    """Nominal |x| dummy-row code sum from chunked x-planes (Px, B, C, m).

    The single implementation behind both :func:`cim_input_partials`'s
    ``rxc`` field and :func:`cim_rx_partials` — sharing it makes their
    bit-identity structural rather than hand-synchronised.
    """
    px = 2.0 ** jnp.arange(cfg.x_planes)
    # exact-ok: {0,1} x-plane bits -> integer counts — exact in f32
    counts_rx = jnp.sum(xp, axis=-1)                             # (Px, B, C)
    codes_rx = adc_codes(counts_rx / jnp.float32(cfg.m_columns),
                         cfg.adc_bits)
    # exact-ok: integer ADC codes x power-of-two plane weights — exact in f32
    return jnp.einsum("pbc,p->b", codes_rx, px,
                      precision=EXACT)[:, None]                  # (B, 1)


def cim_rx_partials(x2: jax.Array, cfg: CimConfig, sx: jax.Array,
                    silicon: Optional[ProjectionSilicon] = None
                    ) -> jax.Array:
    """|x| dummy-row code sum R_x over the FULL contraction dim.

    x2: (B, K) -> (B, 1). Bit-identical to the ``rxc`` field
    :func:`cim_input_partials` produces for the same (full-K) input slice:
    the dummy all-ones row is shared across every weight vector and has no
    N dependence, so round-interleaved execution (``core.programmed
    .cim_mf_matmul_swapped``) computes it once per input stream instead of
    accumulating it tile by tile. With ``silicon``, the per-chunk rx
    instances digitise the dummy row instead of the nominal ADC.
    """
    K = x2.shape[-1]
    _, _, x_planes = _input_operands(x2, cfg, sx)
    xp = _chunk(x_planes, cfg.m_columns, K)
    if silicon is not None:
        return _silicon_rx(xp, cfg, silicon)
    return _nominal_rx(xp, cfg)


def cim_mf_partials(x2: jax.Array, w: jax.Array, cfg: CimConfig,
                    sw: jax.Array, sx: jax.Array,
                    cap_weights: Optional[jax.Array] = None,
                    comparator_offset: Optional[jax.Array] = None,
                    silicon: Optional[ProjectionSilicon] = None
                    ) -> CimPartials:
    """µArray pass over one tile: x2:(B, Kt) against w:(Kt, N_t).

    On-the-fly composition of the two phases (program + stream in one
    call). ``sw``/``sx`` are the *global* calibration scales of the full
    operands — a tile never re-calibrates, so slicing commutes with
    quantisation and a tiled execution reproduces the monolithic bitstream
    exactly. Kt must be a multiple of ``cfg.m_columns`` except for the
    final K-tile (the zero padding then matches the monolithic chunking).
    """
    ws = cim_program_weight_state(w, cfg, sw)
    return cim_input_partials(x2, ws, cfg, sx, cap_weights,
                              comparator_offset, silicon)


def cim_mf_recombine(parts: CimPartials, sw: jax.Array, sx: jax.Array,
                     cfg: CimConfig) -> jax.Array:
    """Eq. 2 recombination of (possibly tile-accumulated) partials -> (B, N).

    The code sums are rescaled by m / (2^A_P - 1) once, here — never per
    tile — so the floating-point rounding sequence is identical no matter
    how the contraction dimension was split.
    """
    levels = 2 ** cfg.adc_bits - 1
    s1 = cfg.m_columns * (parts.s1c / levels)
    s2 = cfg.m_columns * (parts.s2c / levels)
    r_x = cfg.m_columns * (parts.rxc / levels)
    sum_sign_x_abs_w = 2.0 * s1 - parts.r_w    # sum sign(x)|w|
    sum_sign_w_abs_x = 2.0 * s2 - r_x          # sum sign(w)|x|
    return sw * sum_sign_x_abs_w + sx * sum_sign_w_abs_x


class CimKernelState(NamedTuple):
    """Program-time weight-side state in the Pallas kernel's chunk layout.

    The packed arrays come straight from :func:`repro.kernels.ops
    .pack_chunks` at program time, so the fused kernel never re-packs the
    stationary weight operand per step. ``rx_gates`` is the chunk-packed
    all-ones dummy-row gate operand — static for a given (K, m_columns),
    so it is hoisted here too and step time packs only the input planes.
    """

    gw_packed: jax.Array   # (Kp, N) int8 chunk-packed step(w) gates
    wp_packed: jax.Array   # (Pw, Kp, N) int8 chunk-packed |w| planes
    r_w: jax.Array         # (1, N) exact digital sum_k |w_q|_kn
    rx_gates: Optional[jax.Array] = None   # (Kp, 1) packed dummy-row gates


def cim_program_kernel_state(w: jax.Array, cfg: CimConfig,
                             sw: jax.Array) -> CimKernelState:
    """Program-time pass for the fused Pallas path (pre-packed layout).

    The {0,1} cells are stored as int8 (the kernel widens them to f32 in
    VMEM, exact on {0,1}): (Pw + 1) * Kp/K bytes per weight, ~9 B at
    W_P=8 and K=1024 (Kp=1152), against ~36 B as f32 — the difference
    between a 28-layer qwen3-0.6b fitting one 16 GB chip or not.
    """
    from repro.kernels import ops as kops
    K = w.shape[0]
    step_w, abs_w, w_planes = _weight_operands(w, cfg, sw, jnp.int8)
    gw_packed = kops.pack_planes(step_w[None].astype(jnp.int8),
                                 cfg.m_columns)[0]
    wp_packed = kops.pack_planes(w_planes, cfg.m_columns)
    # exact-ok: integer |w_q| magnitudes, column sums below 2^24 — exact in f32
    r_w = jnp.sum(abs_w, axis=0).astype(jnp.float32)[None, :]
    return CimKernelState(gw_packed, wp_packed, r_w, _rx_gates(K, cfg))


def _rx_gates(k: int, cfg: CimConfig) -> jax.Array:
    """The all-ones dummy row, chunk-packed as a (Kp, 1) static operand."""
    from repro.kernels import ops as kops
    return kops.pack_planes(jnp.ones((1, k, 1), jnp.float32),
                            cfg.m_columns)[0]


def cim_kernel_state_from_weight_state(ws: CimWeightState,
                                       cfg: CimConfig) -> CimKernelState:
    """Re-layout programmed plane state into the kernel's packed layout.

    Lets paths that hold :class:`CimWeightState` (tiled compiler segments,
    swapped streams, on-the-fly matmuls) enter the fused silicon route
    without reprogramming from ``w``. Pure {0,1} relayout — bit-identical
    to packing the raw operands with :func:`cim_program_kernel_state`.
    """
    from repro.kernels import ops as kops
    m = cfg.m_columns
    wp = jnp.transpose(ws.wt.astype(jnp.int8), (3, 2, 0, 1))     # (Pw,N,C,m)
    wp_packed = jnp.moveaxis(kops.pack_chunked(wp, m), 1, -1)    # (Pw,Kp,N)
    gw = jnp.transpose(ws.gwt.astype(jnp.int8), (2, 0, 1))       # (N, C, m)
    gw_packed = kops.pack_chunked(gw, m).T                       # (Kp, N)
    return CimKernelState(gw_packed, wp_packed, ws.r_w)


def cim_kernel_partials(x2: jax.Array, ks: CimKernelState, cfg: CimConfig,
                        sx: jax.Array,
                        dac_gains: Optional[jax.Array] = None) -> CimPartials:
    """Step-time fused Pallas pass against programmed kernel state.

    Per-chunk MAV + ADC + plane-weighted code sums without materialising
    the MAV tensor; only the streaming input side is packed per call (the
    weight gates/planes AND the all-ones dummy-row gates were packed at
    program time). The integer code sums equal the einsum path's bit for
    bit; recombine with :func:`cim_mf_recombine`. Silicon-injected
    projections do not come through here — they take the fused
    :func:`cim_kernel_silicon_partials` route.

    The input planes stream as the kernel's gates, batch rows second-minor
    like ``x2`` itself: no operand asks for a transposed input, so the
    float ops that produce ``x2`` compile to the same layouts (and round
    the same) as on every other route.
    """
    from repro.kernels import ops as kops
    K = x2.shape[-1]
    m = cfg.m_columns
    sx_q = sx if dac_gains is None else sx * dac_gains
    step_x, _, x_planes = _input_operands(x2, cfg, sx_q)
    if dac_gains is not None:
        x_planes = x_planes * dac_gains.astype(jnp.float32)
    gx = kops.pack_chunks(step_x, m)                             # (B, Kp)
    xp = kops.pack_chunks(x_planes, m)                           # (Px, B, Kp)
    rx_gates = ks.rx_gates
    if rx_gates is None:
        rx_gates = _rx_gates(K, cfg)
    s1c = kops.cim_mav_packed(gx, ks.wp_packed, m_columns=m,
                              adc_bits=cfg.adc_bits)             # (B, N)
    s2c = kops.cim_mav_packed(xp, ks.gw_packed[None], m_columns=m,
                              adc_bits=cfg.adc_bits)             # (B, N)
    rxc = kops.cim_mav_packed(xp, rx_gates[None], m_columns=m,
                              adc_bits=cfg.adc_bits)             # (B, 1)
    return CimPartials(s1c, s2c, rxc, ks.r_w)


class CimKernelSilicon(NamedTuple):
    """Program-time fold of per-slot silicon state into kernel operands.

    Built once by :func:`cim_program_silicon`: the stationary {0,1} packs
    are weighted by their tile's fixed-point cap-DAC caps (see
    :func:`cap_fixed`), and the per-(chunk, channel) SA-ADC instances —
    cap-sum denominator, comparator offset — are laid out as
    (Kp/CHUNK_PAD, N) tiles the kernel indexes by grid position. Padded
    chunks carry den=1/off=0 so their all-zero planes digitise to code 0.
    Leading stacked axes (fleet instance stacking) are preserved.
    """

    wpc: jax.Array      # (..., Pw, Kp, N) cap-folded |w| magnitude planes
    gwc: jax.Array      # (..., Kp, N) cap-folded step(w) gates
    den: jax.Array      # (..., Ct, N) per-tile cap-sum denominator
    off: jax.Array      # (..., Ct, N) per-tile comparator offset
    rxp: jax.Array      # (..., Kp) packed dummy-row rx caps
    rx_den: jax.Array   # (..., Ct) dummy-row cap-sum denominator
    rx_off: jax.Array   # (..., Ct) dummy-row comparator offset


def _pad_axis(v: jax.Array, axis: int, pad: int, fill: float) -> jax.Array:
    if pad == 0:
        return v
    widths = [(0, 0)] * v.ndim
    widths[axis] = (0, pad)
    return jnp.pad(v, widths, constant_values=fill)


def cim_program_silicon(ks: CimKernelState, sil: ProjectionSilicon,
                        cfg: CimConfig,
                        n_chunks: Optional[int] = None) -> CimKernelSilicon:
    """Fold a :class:`ProjectionSilicon` into fused-kernel operands.

    The cap weighting moves entirely to the weight-stationary side
    (plane_bit * cap and gate * cap products are exact: caps live on the
    2^-CAP_FIXED_BITS grid, bits are {0,1}), so the streamed operand stays
    a plain {0,1} pack and the in-kernel dot reproduces the reference
    einsum numerators bit for bit.
    """
    from repro.kernels import ops as kops
    from repro.kernels.cim_mav import CHUNK_PAD
    m = cfg.m_columns
    n_out, c = sil.cap.shape[-3], sil.cap.shape[-2]
    if sil.cap.shape[-1] != m or n_out != ks.wp_packed.shape[-1]:
        raise ValueError(
            f"silicon cap shape {sil.cap.shape} does not match the "
            f"programmed kernel state (N={ks.wp_packed.shape[-1]}, "
            f"m={m}) tiles")
    if n_chunks is not None and c != n_chunks:
        raise ValueError(
            f"silicon cap shape {sil.cap.shape} holds {c} chunks, "
            f"projection needs {n_chunks}")
    kp = ks.wp_packed.shape[-2]
    c_tiles = kp // CHUNK_PAD
    if _round_up_chunks(c) != c_tiles:
        raise ValueError(
            f"silicon chunk count {c} does not pack to the kernel state's "
            f"K_pad={kp} ({c_tiles} chunk tiles)")
    cpad = c_tiles - c
    capq = cap_fixed(sil.cap)                                    # (...,N,C,m)
    capk = jnp.swapaxes(kops.pack_chunked(capq, m), -1, -2)      # (...,Kp,N)
    wpc = ks.wp_packed.astype(jnp.float32) * capk[..., None, :, :]
    gwc = ks.gw_packed.astype(jnp.float32) * capk
    # exact-ok: 2^-14-grid caps; small fixed-point chunk sums — exact in f32
    den = _pad_axis(jnp.swapaxes(jnp.sum(capq, -1), -1, -2), -2, cpad, 1.0)
    off = _pad_axis(jnp.swapaxes(sil.offset.astype(jnp.float32), -1, -2),
                    -2, cpad, 0.0)
    rxq = cap_fixed(sil.rx_cap)                                  # (..., C, m)
    rxp = kops.pack_chunked(rxq, m)                              # (..., Kp)
    # exact-ok: 2^-14-grid caps; small fixed-point chunk sums — exact in f32
    rx_den = _pad_axis(jnp.sum(rxq, -1), -1, cpad, 1.0)
    rx_off = _pad_axis(sil.rx_offset.astype(jnp.float32), -1, cpad, 0.0)
    return CimKernelSilicon(wpc, gwc, den, off, rxp, rx_den, rx_off)


def _round_up_chunks(c: int) -> int:
    from repro.kernels.cim_mav import CHUNKS_PER_TILE
    return -(-c // CHUNKS_PER_TILE) * CHUNKS_PER_TILE


def cim_kernel_silicon_partials(x2: jax.Array, ks: CimKernelState,
                                silk: CimKernelSilicon, cfg: CimConfig,
                                sx: jax.Array, sil: ProjectionSilicon
                                ) -> CimPartials:
    """Fused silicon step-time pass: the SA-ADC instances run IN-KERNEL.

    Thermal dither is drawn OUTSIDE the kernel with the exact tensor
    shapes/salts of the reference route (``_silicon_partials`` /
    ``_silicon_rx``) and rides in as a kernel operand, so the fused codes
    match the einsum codes bit for bit at thermal_fs>0 too — same
    ``noise_key``/:func:`conversion_step`/salt fold, same samples, same
    ``mav + (off + dither)`` associativity.
    """
    from repro.kernels import ops as kops
    K = x2.shape[-1]
    m = cfg.m_columns
    step_x, _, x_planes = _input_operands(x2, cfg, sx)
    B = x2.shape[0]
    N = ks.r_w.shape[-1]
    C = -(-K // m)
    gx = kops.pack_chunks(step_x, m)[None]                       # (1, B, Kp)
    xp = kops.pack_chunks(x_planes, m)                           # (Px, B, Kp)
    d1k = d2k = drk = None
    if sil.thermal_fs is not None:
        c_tiles = silk.den.shape[-2]
        cpad = c_tiles - C
        d1 = sil.dither((B, N, cfg.w_planes, C), 1)
        d2 = sil.dither((cfg.x_planes, B, N, C), 2)
        dr = sil.dither((cfg.x_planes, B, C), 3)
        d1k = _pad_axis(jnp.transpose(d1, (2, 3, 0, 1)), 1, cpad, 0.0)
        d2k = _pad_axis(jnp.transpose(d2, (0, 3, 1, 2)), 1, cpad, 0.0)
        drk = _pad_axis(jnp.transpose(dr, (0, 2, 1)), 1, cpad, 0.0)[..., None]
    s1c = kops.cim_mav_silicon(gx, silk.wpc, silk.den, silk.off, d1k,
                               adc_bits=cfg.adc_bits)            # (B, N)
    s2c = kops.cim_mav_silicon(xp, silk.gwc[None], silk.den, silk.off, d2k,
                               adc_bits=cfg.adc_bits)            # (B, N)
    rxc = kops.cim_mav_silicon(xp, silk.rxp[None, :, None],
                               silk.rx_den[:, None], silk.rx_off[:, None],
                               drk, adc_bits=cfg.adc_bits)       # (B, 1)
    return CimPartials(s1c, s2c, rxc, ks.r_w)


def cim_mf_matmul(x: jax.Array, w: jax.Array, cfg: CimConfig,
                  cap_weights: Optional[jax.Array] = None,
                  comparator_offset: Optional[jax.Array] = None,
                  silicon: Optional[ProjectionSilicon] = None) -> jax.Array:
    """Hardware-faithful MF correlation x:(...,K) (+) w:(K,N) -> (...,N).

    cap_weights: optional (K,) positive per-column capacitor weights
    (1.0 = nominal) applied to the charge averaging (variability
    injection); the zero-padded tail columns of the final chunk then drop
    out of the charge average (cap weight 0).
    comparator_offset: optional scalar/broadcastable offset in full-scale
    fractions added inside the ADC.
    silicon: optional :class:`ProjectionSilicon` giving every µArray tile
    its own sampled ADC instance (exclusive with the two legacy knobs).
    """
    K, N = w.shape
    batch_shape = x.shape[:-1]
    x2 = x.reshape(-1, K)

    sw = quant.calibrate_scale(w, cfg.w_bits)
    sx = quant.calibrate_scale(x2, cfg.x_bits)

    if cfg.use_kernel and cap_weights is None and comparator_offset is None \
            and silicon is None:
        # Fused Pallas path (no variability injection).
        ks = cim_program_kernel_state(w, cfg, sw)
        y = cim_mf_recombine(cim_kernel_partials(x2, ks, cfg, sx), sw, sx,
                             cfg)
        return y.reshape(batch_shape + (N,)).astype(x.dtype)

    parts = cim_mf_partials(x2, w, cfg, sw, sx, cap_weights,
                            comparator_offset, silicon)
    y = cim_mf_recombine(parts, sw, sx, cfg)
    return y.reshape(batch_shape + (N,)).astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def cim_mf_matmul_ste(x: jax.Array, w: jax.Array, cfg: CimConfig) -> jax.Array:
    """CIM forward with straight-through MF surrogate backward (QAT)."""
    return cim_mf_matmul(x, w, cfg)


def _cim_ste_fwd(x, w, cfg):
    return cim_mf_matmul(x, w, cfg), (x, w)


def _cim_ste_bwd(cfg, res, g):
    x, w = res
    _, vjp = jax.vjp(lambda a, b: mf_matmul(a, b, 0.5, 1.0), x, w)
    return vjp(g)


cim_mf_matmul_ste.defvjp(_cim_ste_fwd, _cim_ste_bwd)
