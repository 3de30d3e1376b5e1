"""Multiplication-free (MF) operator — the paper's core contribution (Eq. 1-3).

The MF correlation of an input vector ``x`` and weight vector ``w`` is

    x (+) w = sum_i sign(x_i) * |w_i| + sign(w_i) * |x_i|

which is an l1-flavoured correlation (``x (+) x = 2 * ||x||_1``). A neuron is
``phi(alpha * (x (+) w) + b)``; the operator is itself nonlinear, so ``phi``
may be identity.

On TPU we realise the operator as TWO MXU matmuls over transformed operands:

    X (+) W = sign(X) @ |W| + |X| @ sign(W)

(`kernels/mf_matmul.py` fuses both into one Pallas kernel that reads X and W
from HBM once). Training uses the paper's surrogate gradients (Eq. 3):
``d sign(x)/dx = 2*delta(x)`` approximated by a steep zero-centred Gaussian,
``d|x|/dx = sign(x)`` exact a.e.

Sign convention: ``jnp.sign`` (sign(0) = 0) for the float/training path; the
hardware path (`core/cim.py`) uses the storage convention sign(0) = +1 which
is what an SRAM sign bit encodes — see ``hw_sign``.
"""

from __future__ import annotations

import contextlib
import enum
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

# Calibration-lab observation taps (no-ops unless a calibration collector
# is installed; `tap` deliberately imports nothing from repro so this
# module-load import cannot cycle).
from repro.calib import tap as _calib_tap


class ExecMode(str, enum.Enum):
    """Execution backend for a weight-activation projection."""

    REGULAR = "regular"        # typical operator: x @ w
    MF = "mf"                  # MF operator, jnp dual-matmul, surrogate grads
    MF_KERNEL = "mf_kernel"    # MF operator, fused Pallas kernel forward
    CIM_SIM = "cim_sim"        # bitplane + SA-ADC hardware-faithful forward
    BNN = "bnn"                # binarized-weight baseline (Table I / BNN)


def hw_sign(v: jax.Array) -> jax.Array:
    """Hardware sign convention: +1 for v >= 0, -1 otherwise.

    An SRAM sign bit has no third state; 0 is stored as +. Satisfies
    ``hw_sign(v) == 2 * step(v) - 1`` with ``step(v) = (v >= 0)``.
    """
    return jnp.where(v >= 0, jnp.ones_like(v), -jnp.ones_like(v))


def mf_correlate_ref(x: jax.Array, w: jax.Array, *, hw: bool = False) -> jax.Array:
    """Reference (x (+) w) along the last axis of ``x`` / first of ``w``.

    x: (..., K), w: (K, N) -> (..., N). ``hw=True`` uses the sign(0)=+1
    storage convention (matches the CIM path bit-for-bit).
    """
    sgn = hw_sign if hw else jnp.sign
    return sgn(x) @ jnp.abs(w) + jnp.abs(x) @ sgn(w)


def _gauss_delta(v: jax.Array, sigma: float) -> jax.Array:
    """Steep zero-centred Gaussian approximating the Dirac delta (Eq. 3)."""
    inv = 1.0 / (sigma * math.sqrt(2.0 * math.pi))
    return inv * jnp.exp(-0.5 * (v / sigma) ** 2)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def mf_matmul(x: jax.Array, w: jax.Array, delta_sigma: float = 0.5,
              delta_coeff: float = 1.0) -> jax.Array:
    """MF correlation with the paper's surrogate gradients (Eq. 3).

    Forward: ``sign(x) @ |w| + |x| @ sign(w)`` with x: (..., K), w: (K, N).

    Backward (per Eq. 3, vectorised):
      dX = sign(X) * (g @ sign(W)^T) + 2*delta(X) * (g @ |W|^T)
      dW = sign(W) * (sign(X)^T @ g) + 2*delta(W) * (|X|^T @ g)
    with delta(.) a steep Gaussian of width ``delta_sigma`` scaled by
    ``delta_coeff`` (0 disables the delta term -> pure sign-product grads).
    """
    return mf_correlate_ref(x, w)


def _mf_fwd(x, w, delta_sigma, delta_coeff):
    return mf_correlate_ref(x, w), (x, w)


def _mf_bwd(delta_sigma, delta_coeff, res, g):
    x, w = res
    sx, ax = jnp.sign(x), jnp.abs(x)
    sw, aw = jnp.sign(w), jnp.abs(w)
    # Collapse leading batch dims of x/g for the weight cotangent.
    g2 = g.reshape(-1, g.shape[-1])
    x2 = x.reshape(-1, x.shape[-1])
    dx = sx * (g @ sw.T)
    dw = sw * (jnp.sign(x2).T @ g2)
    if delta_coeff != 0.0:
        dx = dx + 2.0 * delta_coeff * _gauss_delta(x, delta_sigma) * (g @ aw.T)
        dw = dw + 2.0 * delta_coeff * _gauss_delta(w, delta_sigma) * (
            jnp.abs(x2).T @ g2)
    dx = dx.astype(x.dtype)
    dw = dw.astype(w.dtype)
    return dx, dw


mf_matmul.defvjp(_mf_fwd, _mf_bwd)


def mf_conv2d(x: jax.Array, w: jax.Array, *, stride: tuple[int, int] = (1, 1),
              padding: str = "SAME", delta_sigma: float = 0.5,
              delta_coeff: float = 1.0) -> jax.Array:
    """MF 2-D convolution via patch extraction + MF matmul.

    Unlike a linear matmul, the MF operator does not commute with the
    convolution lowering tricks XLA uses, so we materialise patches
    (im2col) and run the MF correlation per patch — exactly how the
    hardware maps a conv channel onto a µArray (flattened filter across
    columns).

    x: (B, H, W, Cin) NHWC; w: (kh, kw, Cin, Cout).
    """
    kh, kw, cin, cout = w.shape
    patches = jax.lax.conv_general_dilated_patches(
        x, filter_shape=(kh, kw), window_strides=stride, padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    # conv_general_dilated_patches returns feature dim ordered as
    # (Cin, kh, kw); reorder w to match.
    w2 = jnp.transpose(w, (2, 0, 1, 3)).reshape(cin * kh * kw, cout)
    b, oh, ow, _ = patches.shape
    flat = patches.reshape(b * oh * ow, cin * kh * kw)
    out = mf_matmul(flat, w2, delta_sigma, delta_coeff)
    return out.reshape(b, oh, ow, cout)


# ---------------------------------------------------------------------------
# Eq. 2 hardware reformulation (used by the CIM path; exposed for tests).
# ---------------------------------------------------------------------------

def step(v: jax.Array) -> jax.Array:
    """step() in Eq. 2: 1 for v >= 0 else 0 (matches hw_sign convention)."""
    return (v >= 0).astype(v.dtype)


def mf_correlate_step_form(x: jax.Array, w: jax.Array) -> jax.Array:
    """Eq. 2 step()-reformulated MF correlation (identical to hw ref).

    sum sign(w)|x| = 2*sum step(w)|x| - sum|x|   (residue: dummy-ones row)
    sum sign(x)|w| = 2*sum step(x)|w| - sum|w|   (residue: weight statistic)
    """
    ax, aw = jnp.abs(x), jnp.abs(w)
    s1 = 2.0 * (step(x) @ aw) - jnp.sum(aw, axis=0)          # sign(x)|w|
    s2 = 2.0 * (ax @ step(w)) - jnp.sum(ax, axis=-1, keepdims=True)
    return s1 + s2


@jax.custom_vjp
def bnn_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """Binarized-weight correlation x @ sign(w) with straight-through
    gradients (the BNN baseline the paper compares against in Table I)."""
    return x @ hw_sign(w)


def _bnn_fwd(x, w):
    return x @ hw_sign(w), (x, w)


def _bnn_bwd(res, g):
    x, w = res
    g2 = g.reshape(-1, g.shape[-1])
    x2 = x.reshape(-1, x.shape[-1])
    dx = (g @ hw_sign(w).T).astype(x.dtype)
    dw = (x2.T @ g2).astype(w.dtype)          # STE through sign()
    return dx, dw


bnn_matmul.defvjp(_bnn_fwd, _bnn_bwd)


# ---------------------------------------------------------------------------
# Layer-level primitives used by the model zoo.
# ---------------------------------------------------------------------------

def mf_dense_init(key: jax.Array, in_dim: int, out_dim: int,
                  dtype: Any = jnp.float32) -> dict:
    """Params for an MF neuron: phi(alpha * (x (+) w) + b), alpha per-channel.

    alpha is initialised to 1/sqrt(2K) so the MF output (std ~ sqrt(K*(s_w^2
    + s_x^2)), dominated by the |x| term) starts at unit scale.
    """
    kw, = jax.random.split(key, 1)
    w = jax.random.normal(kw, (in_dim, out_dim), dtype) / math.sqrt(in_dim)
    alpha = jnp.full((out_dim,), 1.0 / math.sqrt(2.0 * in_dim), dtype)
    b = jnp.zeros((out_dim,), dtype)
    return {"w": w, "alpha": alpha, "b": b}


def dense_init(key: jax.Array, in_dim: int, out_dim: int,
               dtype: Any = jnp.float32, use_bias: bool = True) -> dict:
    w = jax.random.normal(key, (in_dim, out_dim), dtype) / math.sqrt(in_dim)
    p = {"w": w}
    if use_bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def apply_projection(params: dict, x: jax.Array, mode: ExecMode | str,
                     *, cim_cfg: Optional[Any] = None,
                     programmed: Optional[Any] = None,
                     delta_sigma: float = 0.5, delta_coeff: float = 1.0,
                     precision=None) -> jax.Array:
    """Uniform weight-activation projection used throughout the model zoo.

    mode=REGULAR: x @ w (+ b). mode=MF/MF_KERNEL/CIM_SIM: the paper's neuron
    ``alpha * (x (+) w) + b`` with the chosen backend. Every projection in
    every architecture funnels through here, so the mixed-mapping policy
    (core/mapping.py) can flip a layer between digital and CIM execution by
    changing ``mode`` alone.

    In CIM_SIM mode a weight-stationary :class:`~repro.core.programmed
    .ProgrammedMacro` is consumed when available — either passed explicitly
    via ``programmed`` or embedded as ``params["prog"]`` by
    ``core.programmed.program_weights`` — serving the projection from the
    frozen macro state (inference-only: no STE backward on that path).

    Calibration taps (``repro.calib``): while a collector is installed,
    the projection input is recorded against the embedded ``obs_id``
    (observe mode), and in CIM_SIM mode the output is additionally scored
    against the float MF reference on the same input (SQNR mode).
    """
    mode = ExecMode(mode)
    # Every MF projection's ops carry the ``mf_projection`` scope in the
    # compiled program's metadata (programmed CIM routes add their own
    # sub-scope), so a profile can total them.
    scope = (contextlib.nullcontext() if mode == ExecMode.REGULAR
             else jax.named_scope("mf_projection"))
    with scope:
        w = params["w"]
        if _calib_tap.stats_active() and mode != ExecMode.REGULAR:
            _calib_tap.record_activation(params.get("obs_id"), x)
        if mode == ExecMode.REGULAR:
            y = x @ w
        elif mode == ExecMode.MF:
            y = mf_matmul(x, w, delta_sigma, delta_coeff)
        elif mode == ExecMode.MF_KERNEL:
            from repro.kernels import ops as kops  # local import: kernels optional
            y = kops.mf_matmul(x, w)
        elif mode == ExecMode.CIM_SIM:
            from repro.core import cim
            assert cim_cfg is not None, "CIM_SIM mode requires a CimConfig"
            prog = programmed if programmed is not None else params.get("prog")
            if prog is not None:
                from repro.core.programmed import (SwappedMacro,
                                                   cim_mf_matmul_programmed,
                                                   cim_mf_matmul_swapped)
                # Per-slot silicon instances (repro.silicon.instance
                # .attach_silicon embeds them as "sil", riding scans exactly
                # like the programmed state they perturb).
                sil = params.get("sil")
                # Fence the projection: the float ops that make x and consume
                # y then compile to the same fusions whichever route serves it
                # (lossless collapse, plane einsum, fused kernel), so they round
                # alike and the routes' tokens agree bitwise on a TPU too.
                x = jax.lax.optimization_barrier(x)
                if isinstance(prog, SwappedMacro):
                    # Fleet too small to pin this projection: round-interleaved
                    # execution re-programs tiles per input stream.
                    y = cim_mf_matmul_swapped(x, w, prog, cim_cfg, silicon=sil)
                else:
                    y = cim_mf_matmul_programmed(x, prog, cim_cfg, silicon=sil,
                                                 silicon_kernel=params.get(
                                                     "silk"))
                from repro.analysis import sanitize
                if sanitize.SANITIZE_REF in params:
                    # REPRO_SANITIZE's paired replay step only.
                    sanitize.check_partials(x, params, cim_cfg)
                y = jax.lax.optimization_barrier(y)
                if "fleet" in jax.sharding.get_abstract_mesh().axis_names:
                    # Fleet-sharded serving (repro.traffic.shard): each device
                    # holds a slice of the output channels. Gather them, so the
                    # float model downstream reduces whole rows in the same
                    # order as on one device.
                    P = jax.sharding.PartitionSpec
                    y = jax.lax.with_sharding_constraint(
                        y, P(*[P.UNCONSTRAINED] * (y.ndim - 1), None))
            else:
                y = cim.cim_mf_matmul_ste(x, w, cim_cfg)
            if _calib_tap.error_active():
                _calib_tap.record_projection_error(
                    params.get("obs_id"), y, mf_correlate_ref(x, w, hw=True))
        elif mode == ExecMode.BNN:
            y = bnn_matmul(x, w)
        else:  # pragma: no cover
            raise ValueError(mode)
        if mode != ExecMode.REGULAR and "alpha" in params:
            y = y * params["alpha"]
        if "b" in params:
            y = y + params["b"]
        return y
