"""Public jit'd wrappers for the Pallas kernels.

Handles shape padding/unpadding, batch-dim flattening and block-size
selection so models can call these unconditionally. On the CPU backend
(tests) the kernels run in Pallas interpret mode; on TPU they compile to
Mosaic; any other backend raises.
"""
# repro-lint: module=exactness-critical

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.kernels.cim_mav import (CHUNK_PAD, CHUNKS_PER_TILE,
                                   cim_mav_pallas, cim_mav_sil_pallas)
from repro.kernels.mf_matmul import mf_matmul_pallas


def interpret_mode() -> bool:
    """True on the CPU backend (Pallas interpreter), False on TPU (Mosaic).

    Any other backend has no lowering for these kernels: it raises rather
    than silently interpreting on an accelerator."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas CIM kernels run on 'tpu' (Mosaic) or 'cpu' "
            f"(interpret mode); the default backend is {backend!r}")
    return backend == "cpu"


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _pick_block(dim: int, preferred: int, align: int) -> int:
    """Largest aligned block <= preferred that keeps padding overhead sane."""
    if dim >= preferred:
        return preferred
    return max(align, _round_up(dim, align))


def mf_matmul(x: jax.Array, w: jax.Array, *, bm: int = 128, bn: int = 128,
              bk: int = 128) -> jax.Array:
    """Fused MF correlation x:(...,K) (+) w:(K,N) -> (...,N).

    Pads every dim to its block multiple (sign/abs of zero-padding
    contribute nothing: sign(0)*|w| + |0|*sign(w) = 0).
    """
    batch_shape = x.shape[:-1]
    k, n = w.shape
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bm = _pick_block(m, bm, 8)
    bn = _pick_block(n, bn, 128)
    bk = _pick_block(k, bk, 128)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    xpad = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
    wpad = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    y = mf_matmul_pallas(xpad, wpad, bm=bm, bn=bn, bk=bk,
                         interpret=interpret_mode())
    return y[:m, :n].reshape(batch_shape + (n,))


def pack_chunks(v: jax.Array, m_columns: int) -> jax.Array:
    """Lay out the last (K) axis as chunks of CHUNK_PAD lanes.

    Splits K into µArray chunks of ``m_columns`` real lanes, zero-pads each
    chunk to CHUNK_PAD, and pads the chunk count to a multiple of
    CHUNKS_PER_TILE so the kernel's 128-lane tiles line up.

    The packed layout is position-stable: it depends only on (K,
    m_columns), so weight-side operands can be packed once at program time
    (see ``core/programmed.py``) and reused for every streamed input.
    """
    if m_columns > CHUNK_PAD:
        raise ValueError(
            f"m_columns={m_columns} exceeds the kernel chunk width "
            f"CHUNK_PAD={CHUNK_PAD}: a µArray half must fit one padded "
            f"lane group (use m_columns <= {CHUNK_PAD} or widen CHUNK_PAD "
            f"in kernels/cim_mav.py)")
    if m_columns < 1:
        raise ValueError(f"m_columns must be >= 1, got {m_columns}")
    k = v.shape[-1]
    c = -(-k // m_columns)
    kp = c * m_columns
    v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, kp - k)])
    return pack_chunked(v.reshape(v.shape[:-1] + (c, m_columns)), m_columns)


def pack_chunked(v: jax.Array, m_columns: int) -> jax.Array:
    """Lane/tile-pad an ALREADY-chunked (..., C, m) layout -> (..., Kp).

    The tail of :func:`pack_chunks` factored out so operands that are
    natively chunk-shaped — per-tile cap-DAC weights (N, C, m), the
    program-time (C, m, N) weight state — pack into the kernel's K layout
    with bit-identical padding."""
    if not 1 <= m_columns <= CHUNK_PAD or v.shape[-1] != m_columns:
        raise ValueError(
            f"chunked operand {v.shape} does not match m_columns="
            f"{m_columns} (lane axis must hold exactly the µArray half, "
            f"1 <= m <= CHUNK_PAD={CHUNK_PAD})")
    v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, CHUNK_PAD - m_columns)],
                )  # pad lanes within chunk
    cpad = _round_up(v.shape[-2], CHUNKS_PER_TILE) - v.shape[-2]
    v = jnp.pad(v, [(0, 0)] * (v.ndim - 2) + [(0, cpad), (0, 0)])
    return v.reshape(v.shape[:-2] + (v.shape[-2] * CHUNK_PAD,))


def pack_planes(planes: jax.Array, m_columns: int) -> jax.Array:
    """Chunk-pack a (P, K, N) bitplane stack along K -> (P, Kp, N)."""
    p = pack_chunks(jnp.moveaxis(planes, -1, 1), m_columns)    # (P, N, Kp)
    return jnp.moveaxis(p, 1, -1)                               # (P, Kp, N)


def cim_mav_packed(gates: jax.Array, planes: jax.Array, *, m_columns: int,
                   adc_bits: int, bb: int = 8, bn: int = 128) -> jax.Array:
    """Digitised step-side partial sum over PRE-PACKED operands -> (B, N).

    gates: (B, Kp) or (Pg, B, Kp) from :func:`pack_chunks`; planes: (Pp,
    Kp, N) from :func:`pack_planes`, with Pg or Pp equal to 1. Only B/N
    padding happens per call — the chunk layout is assumed final, which is
    what lets programmed (weight-stationary) state skip the per-step
    re-pack entirely.
    """
    if gates.ndim == 2:
        gates = gates[None]
    b = gates.shape[1]
    n = planes.shape[-1]
    bb = _pick_block(b, bb, 8)
    bn = _pick_block(n, bn, 128)
    bp, npad = _round_up(b, bb), _round_up(n, bn)
    g = jnp.pad(gates, ((0, 0), (0, bp - b), (0, 0)))
    p = jnp.pad(planes, ((0, 0), (0, 0), (0, npad - n)))
    y = cim_mav_pallas(g, p, m_columns=m_columns, adc_bits=adc_bits,
                       bb=bb, bn=bn, interpret=interpret_mode())
    return y[:b, :n]


def cim_mav(gates: jax.Array, planes: jax.Array, *, m_columns: int,
            adc_bits: int, bb: int = 8, bn: int = 128) -> jax.Array:
    """Digitised step-side partial sum (see kernels/cim_mav.py).

    gates: (B, K) {0,1}; planes: (Pw, K, N) {0,1} — un-packed layout;
    this wrapper packs chunks then delegates to :func:`cim_mav_packed`.
    """
    g = pack_chunks(gates, m_columns)
    p = pack_planes(planes, m_columns)
    return cim_mav_packed(g, p, m_columns=m_columns, adc_bits=adc_bits,
                          bb=bb, bn=bn)


def cim_mav_silicon(gates: jax.Array, planes: jax.Array, den: jax.Array,
                    off: jax.Array, dither: jax.Array = None, *,
                    adc_bits: int, bb: int = 8, bn: int = 128) -> jax.Array:
    """Fused silicon code sum over PRE-FOLDED operands -> (B, N).

    gates: (Pg, B, Kp) streamed {0,1} packs; planes: (Pp, Kp, N) cap-
    folded stationary operand with den/off: (Kp/CHUNK_PAD, N) per-(chunk,
    channel) SA-ADC instances and optional dither (P, Kp/CHUNK_PAD, B, N)
    — all built at program time by ``core.cim.cim_program_silicon``. Only
    B/N padding happens per call (padded channels get den=1/off=0 so they
    stay inert; padded batch rows are sliced away).
    """
    b = gates.shape[1]
    n = planes.shape[-1]
    bb = _pick_block(b, bb, 8)
    bn = _pick_block(n, bn, 128)
    bp, npad = _round_up(b, bb), _round_up(n, bn)
    g = jnp.pad(gates, ((0, 0), (0, bp - b), (0, 0)))
    p = jnp.pad(planes, ((0, 0), (0, 0), (0, npad - n)))
    d = jnp.pad(den, ((0, 0), (0, npad - n)), constant_values=1.0)
    o = jnp.pad(off, ((0, 0), (0, npad - n)))
    dt = None if dither is None else jnp.pad(
        dither, ((0, 0), (0, 0), (0, bp - b), (0, npad - n)))
    y = cim_mav_sil_pallas(g, p, d, o, dt, adc_bits=adc_bits, bb=bb, bn=bn,
                           interpret=interpret_mode())
    return y[:b, :n]
