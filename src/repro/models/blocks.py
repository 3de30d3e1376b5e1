"""Shared model blocks: norms, RoPE, MLPs, embeddings.

Every weight-activation projection goes through `core.mf.apply_projection`
so the MF-Net technique (regular | mf | mf_kernel | cim_sim execution) is a
per-layer switch driven by the mixed-mapping policy — the paper's Sec. VI
integration, applied uniformly across all ten architectures.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.mf import ExecMode, apply_projection


# ---------------------------------------------------------------------------
# Projection params. `mf=True` adds the per-channel alpha of the MF neuron.
# ---------------------------------------------------------------------------

def proj_init(key: jax.Array, in_dim: int, out_dim: int, *, bias: bool,
              mf: bool, dtype: Any = jnp.float32,
              scale: Optional[float] = None) -> dict:
    std = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    p = {"w": (jax.random.normal(key, (in_dim, out_dim)) * std).astype(dtype)}
    if mf:
        p["alpha"] = jnp.full((out_dim,), 1.0 / math.sqrt(2.0 * in_dim),
                              dtype)
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def proj_apply(p: dict, x: jax.Array, mode: ExecMode | str = ExecMode.REGULAR,
               *, programmed: Optional[Any] = None, **kw) -> jax.Array:
    """Apply one projection; ``programmed`` (or an embedded ``p["prog"]``
    from ``core.programmed.program_weights``) serves CIM_SIM projections
    from weight-stationary programmed macro state."""
    return apply_projection(p, x, mode, programmed=programmed, **kw)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(dim: int, dtype: Any = jnp.float32) -> dict:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"]).astype(x.dtype)


def layernorm_init(dim: int, dtype: Any = jnp.float32) -> dict:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def norm_init(kind: str, dim: int, dtype: Any = jnp.float32) -> dict:
    return layernorm_init(dim, dtype) if kind == "layernorm" else rmsnorm_init(
        dim, dtype)


def norm_apply(kind: str, p: dict, x: jax.Array) -> jax.Array:
    return layernorm(p, x) if kind == "layernorm" else rmsnorm(p, x)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(v: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """v: (..., T, H, D) rotated pairwise; positions: (..., T)."""
    d = v.shape[-1]
    freqs = rope_freqs(d, theta)                           # (D/2,)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,T,1,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    v1, v2 = v[..., 0::2], v[..., 1::2]
    r1 = v1 * cos - v2 * sin
    r2 = v2 * cos + v1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(v.shape)
    return out.astype(v.dtype)


# ---------------------------------------------------------------------------
# MLPs — the per-family feed-forward variants.
# ---------------------------------------------------------------------------

MLP_GATED = {"silu_glu", "geglu"}


def mlp_init(key: jax.Array, d_model: int, d_ff: int, kind: str, *,
             mf: bool, bias: bool = False, dtype: Any = jnp.float32) -> dict:
    ks = jax.random.split(key, 3)
    p = {"up": proj_init(ks[0], d_model, d_ff, bias=bias, mf=mf, dtype=dtype),
         "down": proj_init(ks[1], d_ff, d_model, bias=bias, mf=mf,
                           dtype=dtype)}
    if kind in MLP_GATED:
        p["gate"] = proj_init(ks[2], d_model, d_ff, bias=bias, mf=mf,
                              dtype=dtype)
    return p


def mlp_apply(p: dict, x: jax.Array, kind: str,
              mode: ExecMode | str = ExecMode.REGULAR, **kw) -> jax.Array:
    up = proj_apply(p["up"], x, mode, **kw)
    if kind == "silu_glu":
        h = jax.nn.silu(proj_apply(p["gate"], x, mode, **kw)) * up
    elif kind == "geglu":
        h = jax.nn.gelu(proj_apply(p["gate"], x, mode, **kw)) * up
    elif kind == "gelu":
        h = jax.nn.gelu(up)
    elif kind == "sq_relu":                      # nemotron-4 squared ReLU
        h = jnp.square(jax.nn.relu(up))
    else:  # pragma: no cover
        raise ValueError(kind)
    return proj_apply(p["down"], h, mode, **kw)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def embed_init(key: jax.Array, vocab: int, d_model: int,
               dtype: Any = jnp.float32) -> dict:
    return {"table": (jax.random.normal(key, (vocab, d_model)) * 0.02
                      ).astype(dtype)}


def embed_apply(p: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def lm_head_apply(p: dict, x: jax.Array, *, tied_table: Optional[jax.Array]
                  = None) -> jax.Array:
    with jax.named_scope("lm_head"):
        if tied_table is not None:
            return x @ tied_table.T
        return proj_apply(p, x)
