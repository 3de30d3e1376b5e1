"""Mixture-of-Experts: top-k routing with shared experts.

Two execution paths sharing one parameter layout:

  * `moe_apply_dense` — reference path: every expert runs over every token,
    masked by combine weights. O(E) compute; used for correctness tests and
    tiny smoke configs.
  * `moe_apply_ep` — production expert-parallel path for use INSIDE
    shard_map: tokens are sequence/batch-sharded, experts sharded over the
    `model` mesh axis. Sort-based dispatch with fixed per-link capacity ->
    `lax.all_to_all` -> per-expert batched matmul -> reverse all_to_all ->
    weighted combine. This is the DeepSeek/GShard pattern with capacity
    drops (tokens over capacity fall back to the shared expert + residual).

MF-Net integration: each expert FFN is the weight-stationary sweet spot of
the paper's µArray mapping (one expert <-> one CIM bank), so expert
projections honour the layer's ExecMode; the router stays digital
(precision-critical, tiny).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.mf import ExecMode
from repro.models import blocks


def moe_init(key: jax.Array, d_model: int, d_ff: int, n_experts: int,
             n_shared: int, top_k: int, *, mf: bool,
             dtype: Any = jnp.float32) -> dict:
    kr, ke, ks = jax.random.split(key, 3)
    std = 1.0 / math.sqrt(d_model)

    def expert(k):
        k1, k2, k3 = jax.random.split(k, 3)
        p = {"up": (jax.random.normal(k1, (d_model, d_ff)) * std).astype(dtype),
             "gate": (jax.random.normal(k2, (d_model, d_ff)) * std
                      ).astype(dtype),
             "down": (jax.random.normal(k3, (d_ff, d_model))
                      * (1.0 / math.sqrt(d_ff))).astype(dtype)}
        return p

    p = {
        "router": {"w": (jax.random.normal(kr, (d_model, n_experts))
                         * std).astype(jnp.float32)},
        "experts": jax.vmap(expert)(jax.random.split(ke, n_experts)),
    }
    if mf:
        p["experts"]["alpha_up"] = jnp.full(
            (n_experts, d_ff), 1.0 / math.sqrt(2.0 * d_model), dtype)
        p["experts"]["alpha_down"] = jnp.full(
            (n_experts, d_model), 1.0 / math.sqrt(2.0 * d_ff), dtype)
    if n_shared:
        p["shared"] = blocks.mlp_init(ks, d_model, n_shared * d_ff,
                                      "silu_glu", mf=mf, dtype=dtype)
    return p


def _router(p: dict, x2: jax.Array, top_k: int
            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x2: (S, d) -> (weights (S,k), ids (S,k), aux load-balance loss)."""
    logits = x2.astype(jnp.float32) @ p["router"]["w"]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balance loss: E * sum_e f_e * P_e.
    e = probs.shape[-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(ids[:, 0], e), axis=0)
    aux = e * jnp.sum(me * ce)
    return weights, ids, aux


def _sel(v, idx):
    """Index a leading-E-stacked value (array or pytree, e.g. a
    ProgrammedMacro); the full slice means 'already sliced, use as-is' —
    which also keeps 0-d leaves (observer ids inside a scan) legal."""
    if isinstance(idx, slice) and idx == slice(None):
        return v
    return jax.tree.map(lambda a: a[idx], v)


def _expert_ffn(experts: dict, idx_or_slice, h: jax.Array,
                mode: ExecMode | str, **kw) -> jax.Array:
    """Apply expert FFN(s). h: (..., d); expert params indexed by leading E.

    Programmed state (``core.programmed.program_weights`` attaches
    ``prog_up/gate/down`` to the expert bank) and calibration observer ids
    (``obs_id_up/...``) thread through to the per-role projection dicts,
    so MoE experts serve weight-stationary and calibrate exactly like
    every other projection.
    """
    up = {"w": _sel(experts["up"], idx_or_slice)}
    gate = {"w": _sel(experts["gate"], idx_or_slice)}
    down = {"w": _sel(experts["down"], idx_or_slice)}
    if "alpha_up" in experts:
        up["alpha"] = _sel(experts["alpha_up"], idx_or_slice)
        gate["alpha"] = _sel(experts["alpha_up"], idx_or_slice)
        down["alpha"] = _sel(experts["alpha_down"], idx_or_slice)
    for role, d in (("up", up), ("gate", gate), ("down", down)):
        if f"prog_{role}" in experts:
            d["prog"] = _sel(experts[f"prog_{role}"], idx_or_slice)
        if f"obs_id_{role}" in experts:
            d["obs_id"] = _sel(experts[f"obs_id_{role}"], idx_or_slice)
        if f"sil_{role}" in experts:
            # Per-slot silicon instances (repro.silicon) slice by expert
            # exactly like the programmed state they perturb.
            d["sil"] = _sel(experts[f"sil_{role}"], idx_or_slice)
        if f"silk_{role}" in experts:
            d["silk"] = _sel(experts[f"silk_{role}"], idx_or_slice)
    z = (jax.nn.silu(blocks.proj_apply(gate, h, mode, **kw))
         * blocks.proj_apply(up, h, mode, **kw))
    return blocks.proj_apply(down, z, mode, **kw)


def moe_apply_dense(p: dict, x: jax.Array, *, top_k: int,
                    mode: ExecMode | str = ExecMode.REGULAR, **kw
                    ) -> tuple[jax.Array, jax.Array]:
    """Reference path: run all experts on all tokens (tests/smokes only)."""
    orig_shape = x.shape
    x2 = x.reshape(-1, x.shape[-1])
    weights, ids, aux = _router(p, x2, top_k)
    n_experts = p["router"]["w"].shape[-1]
    combine = jnp.zeros((x2.shape[0], n_experts), jnp.float32)
    combine = jax.vmap(
        lambda c, i, w: c.at[i].add(w), in_axes=(0, 0, 0))(combine, ids,
                                                           weights)

    def body(carry, ep_and_w):
        ep, cw = ep_and_w
        y = _expert_ffn(ep, slice(None), x2, mode, **kw)
        return carry + cw[:, None] * y.astype(jnp.float32), None

    experts_stacked = jax.tree.map(lambda v: v, p["experts"])
    y0 = jnp.zeros_like(x2, jnp.float32)
    y, _ = jax.lax.scan(
        lambda c, ew: body(c, ew), y0,
        (experts_stacked, combine.T))
    if "shared" in p:
        y = y + blocks.mlp_apply(p["shared"], x2, "silu_glu", mode,
                                 **kw).astype(jnp.float32)
    return y.reshape(orig_shape).astype(x.dtype), aux


def _segment_positions(sorted_seg_ids: jax.Array, n_segments: int
                       ) -> jax.Array:
    """Position of each element within its (sorted) segment."""
    idx = jnp.arange(sorted_seg_ids.shape[0])
    seg_start = jnp.searchsorted(sorted_seg_ids, jnp.arange(n_segments),
                                 side="left")
    return idx - seg_start[sorted_seg_ids]


def moe_apply_ep(p: dict, x: jax.Array, *, top_k: int, ep_axis: str,
                 capacity_factor: float = 1.25,
                 expert_capacity_factor: float = 2.0,
                 mode: ExecMode | str = ExecMode.REGULAR,
                 fuse_single_expert: bool = True, **kw
                 ) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel path. MUST run inside shard_map with ``ep_axis``.

    x: (S_local, d) local token shard; expert params arrive pre-sharded so
    that p['experts'][...] leading dim is E_local = E / n_ep.
    """
    s, d = x.shape
    axes = (ep_axis,) if isinstance(ep_axis, str) else tuple(ep_axis)
    n_ep = 1
    for a in axes:                        # static: reads the axis env
        n_ep *= jax.lax.axis_size(a)
    e_local = p["experts"]["up"].shape[0]
    n_experts = p["router"]["w"].shape[-1]
    assert n_experts == e_local * n_ep, (n_experts, e_local, n_ep)

    weights, ids, aux = _router(p, x, top_k)
    aux = jax.lax.pmean(aux, axes)

    sk = s * top_k
    flat_e = ids.reshape(sk)
    flat_w = weights.reshape(sk)
    flat_tok = jnp.repeat(jnp.arange(s), top_k)

    # ---- stage 1: route token copies to the owning EP shard -------------
    target = flat_e // e_local
    order = jnp.argsort(target, stable=True)
    t_sorted = target[order]
    pos = _segment_positions(t_sorted, n_ep)
    cap = int(8 * math.ceil(sk / n_ep * capacity_factor / 8))
    keep = pos < cap
    dest = jnp.where(keep, t_sorted * cap + pos, n_ep * cap)  # OOB -> drop

    send_tok = jnp.zeros((n_ep * cap, d), x.dtype).at[dest].set(
        x[flat_tok[order]], mode="drop").reshape(n_ep, cap, d)
    send_eid = jnp.full((n_ep * cap,), e_local, jnp.int32).at[dest].set(
        flat_e[order] % e_local, mode="drop").reshape(n_ep, cap)
    # Bookkeeping for the return trip (stays on the source device).
    src_tok = jnp.full((n_ep * cap,), s, jnp.int32).at[dest].set(
        flat_tok[order], mode="drop").reshape(n_ep, cap)
    src_w = jnp.zeros((n_ep * cap,), jnp.float32).at[dest].set(
        flat_w[order], mode="drop").reshape(n_ep, cap)

    recv_tok = jax.lax.all_to_all(send_tok, axes, 0, 0, tiled=False)
    recv_eid = jax.lax.all_to_all(send_eid, axes, 0, 0, tiled=False)

    r = n_ep * cap
    re = recv_eid.reshape(r)                    # e_local == invalid sentinel
    rt = recv_tok.reshape(r, d)

    if e_local == 1 and fuse_single_expert:
        # Wide-EP fast path (one expert per shard): every valid received
        # row belongs to the single local expert — skip the second
        # sort/scatter and the 2x-capacity staging buffer entirely, and
        # run the FFN on the receive buffer in place (§Perf iteration:
        # halves stage-2 FLOPs and removes two scatters + one gather).
        ffn_out = _expert_ffn(p["experts"], 0, rt, mode, **kw)
        out_rows = jnp.where((re < e_local)[:, None], ffn_out, 0.0
                             ).astype(x.dtype)
        back = jax.lax.all_to_all(out_rows.reshape(n_ep, cap, d), axes, 0,
                                  0, tiled=False).reshape(n_ep * cap, d)
        y = jnp.zeros((s + 1, d), jnp.float32).at[src_tok.reshape(-1)].add(
            back.astype(jnp.float32) * src_w.reshape(-1, 1))[:s]
        if "shared" in p:
            y = y + blocks.mlp_apply(p["shared"], x, "silu_glu", mode,
                                     **kw).astype(jnp.float32)
        return y.astype(x.dtype), aux

    # ---- stage 2: group received rows by local expert --------------------
    order2 = jnp.argsort(re, stable=True)
    e_sorted = re[order2]
    pos2 = _segment_positions(e_sorted, e_local + 1)
    cap2 = int(8 * math.ceil(r / e_local * expert_capacity_factor / 8))
    keep2 = (pos2 < cap2) & (e_sorted < e_local)
    dest2 = jnp.where(keep2, e_sorted * cap2 + pos2, e_local * cap2)

    buf = jnp.zeros((e_local * cap2, d), x.dtype).at[dest2].set(
        rt[order2], mode="drop").reshape(e_local, cap2, d)

    # ---- expert compute: batched over local experts ----------------------
    out_buf = jax.vmap(
        lambda ep, h: _expert_ffn(ep, slice(None), h, mode, **kw),
        in_axes=(0, 0))(
            jax.tree.map(lambda v: v, p["experts"]), buf)

    # ---- inverse of stage 2 ----------------------------------------------
    # row r (in sorted order) came from flat position order2[r].
    inv_vals = out_buf.reshape(e_local * cap2, d)
    gathered = jnp.where(keep2[:, None],
                         inv_vals[jnp.clip(dest2, 0, e_local * cap2 - 1)],
                         0.0)
    out_rows = jnp.zeros((r, d), x.dtype).at[order2].set(gathered)

    # ---- reverse all_to_all + weighted combine ---------------------------
    back = jax.lax.all_to_all(out_rows.reshape(n_ep, cap, d), axes, 0, 0,
                              tiled=False)
    back = back.reshape(n_ep * cap, d)
    y = jnp.zeros((s + 1, d), jnp.float32).at[src_tok.reshape(-1)].add(
        back.astype(jnp.float32) * src_w.reshape(-1, 1))[:s]

    if "shared" in p:
        y = y + blocks.mlp_apply(p["shared"], x, "silu_glu", mode,
                                 **kw).astype(jnp.float32)
    return y.astype(x.dtype), aux
