"""Fused bitplane-MAV + SA-ADC Pallas TPU kernel.

Emulates the µArray inner loop of the CIM macro for one side of the MF
operator: given 1-bit column gates G (B x K, e.g. step(x)) and weight
magnitude bitplanes P (Pw x K x N, bit p of |w|), compute the plane-
weighted SA-ADC *code sum*

    S[b, n] = sum_p 2^p * sum_chunks ADC_code( (1/M) * sum_{j in chunk}
                                               G[b, j] * P[p, j, n] )

The other side of the operator streams the planes instead: bit planes of
|x| (Px x B x K) as the gates against the static step(w) gates (1 x K x N).

i.e. the integer-valued ``CimPartials`` field of Eq. 2 (the m/levels
rescale is applied ONCE by ``core.cim.cim_mf_recombine``, never inside the
kernel — the same contract as the einsum paths, which is what makes the
fused output bitwise identical to the reference route at every design
point), fused so the (B, N, Pw, C) MAV tensor is never materialised in
HBM.

Hardware mapping: a µArray chunk holds M (e.g. 31) columns. M is not
lane-aligned, so the K axis is laid out as C chunks padded to CHUNK_PAD=32
lanes (pad columns store 0 bits: they never discharge, and the ADC divides
by the true M). A 128-lane K tile therefore carries 4 chunks; the kernel
does 4 (bb x 32) @ (32 x bn) MXU calls per tile and ADC-quantises each
chunk's MAV before accumulating, scaled by 2^p.

Grid: (B/bb, N/bn, Pw, C/4), plane+chunk innermost so the accumulator
stays resident in VMEM.

``cim_mav_sil_pallas`` is the silicon twin: the stationary operand arrives
cap-weighted (plane bit x its tile's fixed-point cap-DAC weight, see
``core.cim.cim_program_silicon``), and the per-(chunk, channel) cap-DAC
denominator, comparator offset, and optional per-conversion thermal dither
ride as extra operands — the full SA-ADC instance evaluates *inside* the
kernel, so sigma>0 fleets never fall back to the reference einsums.
"""
# repro-lint: module=exactness-critical

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK_PAD = 32          # lanes per µArray chunk after padding
CHUNKS_PER_TILE = 4     # 128-lane K tile carries 4 chunks


def _cim_mav_kernel(g_ref, p_ref, o_ref, acc_ref, *, m_columns: int,
                    adc_levels: int, n_planes: int, c_steps: int):
    plane = pl.program_id(2)
    chunk = pl.program_id(3)

    @pl.when(jnp.logical_and(plane == 0, chunk == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[0]              # (bb, 128) gates for 4 chunks
    p = p_ref[0]              # (128, bn) bitplane for 4 chunks
    scale = jnp.exp2(plane.astype(jnp.float32))
    inv_m = 1.0 / m_columns
    for s in range(CHUNKS_PER_TILE):
        gs = g[:, s * CHUNK_PAD:(s + 1) * CHUNK_PAD]
        ps = p[s * CHUNK_PAD:(s + 1) * CHUNK_PAD, :]
        # exact-ok: {0,1} gate x plane-bit/grid-cap operands — exact in f32
        counts = jnp.dot(gs, ps, preferred_element_type=jnp.float32)
        mav = counts * inv_m
        code = jnp.clip(jnp.round(mav * adc_levels), 0.0, adc_levels)
        acc_ref[...] += scale * code

    @pl.when(jnp.logical_and(plane == n_planes - 1, chunk == c_steps - 1))
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("m_columns", "adc_bits", "bb", "bn",
                                    "interpret"))
def cim_mav_pallas(gates: jax.Array, planes: jax.Array, *, m_columns: int,
                   adc_bits: int, bb: int = 8, bn: int = 128,
                   interpret: bool = False) -> jax.Array:
    """gates: (Pg, B, K_pad) in {0,1}; planes: (Pp, K_pad, N) in {0,1}.

    Exactly one of Pg/Pp may exceed 1: the bit-serial side carries the
    planes, the other operand is plane-static and broadcasts. Either way
    the batch rows stay the second-minor axis of the gates and the output,
    so the streamed input never needs a transposed layout. K_pad must be a
    multiple of 128 with the chunk layout described above (`ops.cim_mav`
    builds it). Returns (B, N) f32 plane-weighted integer ADC code sums (a
    ``CimPartials`` field — recombine with ``core.cim.cim_mf_recombine``).
    """
    gp, b, k_pad = gates.shape
    pp, k2, n = planes.shape
    assert k_pad == k2 and k_pad % (CHUNK_PAD * CHUNKS_PER_TILE) == 0
    assert gp == 1 or pp == 1, (gates.shape, planes.shape)
    assert b % bb == 0 and n % bn == 0, (gates.shape, planes.shape, (bb, bn))
    n_planes = max(gp, pp)
    c_steps = k_pad // (CHUNK_PAD * CHUNKS_PER_TILE)
    grid = (b // bb, n // bn, n_planes, c_steps)
    kernel = functools.partial(
        _cim_mav_kernel, m_columns=m_columns,
        adc_levels=2 ** adc_bits - 1, n_planes=n_planes, c_steps=c_steps)
    gsel = (lambda p: p) if gp > 1 else (lambda p: 0)
    psel = (lambda p: p) if pp > 1 else (lambda p: 0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bb, CHUNK_PAD * CHUNKS_PER_TILE),
                         lambda i, j, p, c: (gsel(p), i, c)),
            pl.BlockSpec((1, CHUNK_PAD * CHUNKS_PER_TILE, bn),
                         lambda i, j, p, c: (psel(p), c, j)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, p, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
        interpret=interpret,
    )(gates, planes)


def _cim_mav_sil_kernel(*refs, adc_levels: int, n_planes: int, c_steps: int,
                        has_dither: bool):
    """Silicon MAV + in-kernel SA-ADC instance evaluation.

    Per chunk s of the 128-lane tile: numerator = gates @ cap-folded
    planes, MAV = numerator / den[s], v = MAV + (offset[s] [+ dither]),
    code = clip(round(v * levels)) — the exact op sequence (and float
    associativity) of ``core.cim._silicon_partials``, which is what keeps
    the fused route's integer codes identical to the reference einsums.
    """
    if has_dither:
        g_ref, p_ref, den_ref, off_ref, d_ref, o_ref, acc_ref = refs
    else:
        g_ref, p_ref, den_ref, off_ref, o_ref, acc_ref = refs
        d_ref = None
    plane = pl.program_id(2)
    chunk = pl.program_id(3)

    @pl.when(jnp.logical_and(plane == 0, chunk == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[0]              # (bb, 128) gates for 4 chunks
    p = p_ref[0]              # (128, bn) cap-folded planes for 4 chunks
    scale = jnp.exp2(plane.astype(jnp.float32))
    for s in range(CHUNKS_PER_TILE):
        gs = g[:, s * CHUNK_PAD:(s + 1) * CHUNK_PAD]
        ps = p[s * CHUNK_PAD:(s + 1) * CHUNK_PAD, :]
        # Cap-folded planes sit on the 2^-14 grid, off bf16's: contract
        # at full f32 precision so the numerator stays exact on the MXU.
        # exact-ok: {0,1} gate x plane-bit/grid-cap operands — exact in f32
        num = jnp.dot(gs, ps, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)
        mav = num / den_ref[0, s:s + 1, :]
        off = off_ref[0, s:s + 1, :]
        if d_ref is not None:
            off = off + d_ref[0, s]
        v = mav + off
        code = jnp.clip(jnp.round(v * adc_levels), 0.0, adc_levels)
        acc_ref[...] += scale * code

    @pl.when(jnp.logical_and(plane == n_planes - 1, chunk == c_steps - 1))
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("adc_bits", "bb", "bn", "interpret"))
def cim_mav_sil_pallas(gates: jax.Array, planes: jax.Array, den: jax.Array,
                       off: jax.Array, dither: jax.Array | None = None, *,
                       adc_bits: int, bb: int = 8, bn: int = 128,
                       interpret: bool = False) -> jax.Array:
    """Fused silicon MAV: gates (Pg, B, Kp) x planes (Pp, Kp, N) -> (B, N).

    Exactly one of Pg/Pp may exceed 1 (the streaming bit-serial side); the
    other operand is plane-static and broadcasts. ``den``/``off`` give the
    per-(chunk, channel) cap-DAC denominator and comparator offset as
    (Kp/CHUNK_PAD, N) tiles (padded chunks carry den=1, off=0 so they
    digitise to code 0); ``dither`` optionally adds per-conversion thermal
    noise shaped (P, Kp/CHUNK_PAD, B, N). Returns plane-weighted integer
    ADC code sums, the same ``CimPartials`` contract as ``cim_mav_pallas``.
    """
    gp, b, k_pad = gates.shape
    pp, k2, n = planes.shape
    assert k_pad == k2 and k_pad % (CHUNK_PAD * CHUNKS_PER_TILE) == 0
    assert gp == 1 or pp == 1, (gates.shape, planes.shape)
    n_planes = max(gp, pp)
    c_tiles = k_pad // CHUNK_PAD
    assert den.shape == (c_tiles, n) and off.shape == (c_tiles, n)
    assert b % bb == 0 and n % bn == 0, (gates.shape, planes.shape, (bb, bn))
    c_steps = k_pad // (CHUNK_PAD * CHUNKS_PER_TILE)
    grid = (b // bb, n // bn, n_planes, c_steps)
    kernel = functools.partial(
        _cim_mav_sil_kernel, adc_levels=2 ** adc_bits - 1,
        n_planes=n_planes, c_steps=c_steps, has_dither=dither is not None)
    gsel = (lambda p: p) if gp > 1 else (lambda p: 0)
    psel = (lambda p: p) if pp > 1 else (lambda p: 0)
    in_specs = [
        pl.BlockSpec((1, bb, CHUNK_PAD * CHUNKS_PER_TILE),
                     lambda i, j, p, c: (gsel(p), i, c)),
        pl.BlockSpec((1, CHUNK_PAD * CHUNKS_PER_TILE, bn),
                     lambda i, j, p, c: (psel(p), c, j)),
        # den/off ride as (c_steps, 4, N): a (1, 4, bn) block spans the
        # whole of the second-minor axis, which Mosaic's (8, 128) tiling
        # rule requires of a 4-row block.
        pl.BlockSpec((1, CHUNKS_PER_TILE, bn), lambda i, j, p, c: (c, 0, j)),
        pl.BlockSpec((1, CHUNKS_PER_TILE, bn), lambda i, j, p, c: (c, 0, j)),
    ]
    operands = [gates, planes, den.reshape(c_steps, CHUNKS_PER_TILE, n),
                off.reshape(c_steps, CHUNKS_PER_TILE, n)]
    if dither is not None:
        assert dither.shape == (n_planes, c_tiles, b, n), dither.shape
        in_specs.append(pl.BlockSpec((1, CHUNKS_PER_TILE, bb, bn),
                                     lambda i, j, p, c: (p, c, i, j)))
        operands.append(dither)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, p, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
        interpret=interpret,
    )(*operands)
