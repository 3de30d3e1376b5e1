"""Fixed-point quantisation (paper Sec. III / Fig. 2c).

The paper operates the MF network at 8-bit fixed-precision inputs/weights
with accuracy equivalent to float. We provide symmetric signed quantisers
(per-tensor and per-channel max-abs calibration), fake-quant with a
straight-through estimator for QAT, and integer encode/decode used by the
CIM bitplane path.

A b-bit symmetric signed code uses the integer grid [-(2^(b-1)-1),
2^(b-1)-1] (no -2^(b-1): the hardware stores sign + (b-1) magnitude
bitplanes, so codes are sign-magnitude symmetric).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def qmax(bits: int) -> int:
    """Largest magnitude code for a b-bit symmetric signed format."""
    return 2 ** (bits - 1) - 1


def calibrate_scale(v: jax.Array, bits: int, axis: Optional[int] = None,
                    eps: float = 1e-8) -> jax.Array:
    """Max-abs scale such that v/scale fits the b-bit grid.

    axis=None -> per-tensor scalar scale; axis=k -> per-channel along k
    (scale shape broadcastable against v with that axis reduced).
    """
    if axis is None:
        amax = jnp.max(jnp.abs(v))
    else:
        amax = jnp.max(jnp.abs(v), axis=axis, keepdims=True)
    return jnp.maximum(amax, eps) / qmax(bits)


def quantize(v: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """Encode to the integer grid (returned as int32)."""
    q = jnp.round(v / scale)
    return jnp.clip(q, -qmax(bits), qmax(bits)).astype(jnp.int32)


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(scale.dtype if hasattr(scale, "dtype") else jnp.float32) * scale


def fake_quant(v: jax.Array, bits: int, axis: Optional[int] = None) -> jax.Array:
    """Quantise-dequantise with a straight-through gradient (QAT)."""
    scale = calibrate_scale(v, bits, axis)
    q = dequantize(quantize(v, scale, bits), scale)
    # STE: forward q, backward identity.
    return v + jax.lax.stop_gradient(q - v)


def bitplanes(mag: jax.Array, bits: int, dtype=jnp.float32) -> jax.Array:
    """Decompose non-negative integer magnitudes into bitplanes.

    mag: (...,) int32 in [0, 2^(bits-1)-1] -> (bits-1, ...) {0,1} planes
    of ``dtype``, plane p holding bit p (LSB first). The hardware stores
    |w| as (bits-1) magnitude rows in a µArray (the sign occupies its own
    row).
    """
    nplanes = bits - 1
    shifts = jnp.arange(nplanes, dtype=jnp.int32)
    planes = (mag[None, ...] >> shifts.reshape((nplanes,) + (1,) * mag.ndim)) & 1
    return planes.astype(dtype)


def from_bitplanes(planes: jax.Array) -> jax.Array:
    """Inverse of ``bitplanes`` (plane axis leading)."""
    nplanes = planes.shape[0]
    weights = (2.0 ** jnp.arange(nplanes)).reshape(
        (nplanes,) + (1,) * (planes.ndim - 1))
    return jnp.sum(planes * weights, axis=0)
