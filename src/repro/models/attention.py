"""Attention: GQA/MQA with RoPE, optional qk-norm/bias/local windows.

Two execution regimes:

  * `blocked_attention` — blockwise online-softmax (flash-style) scan over
    KV chunks. O(T * block) memory instead of O(T^2); this is what makes
    the 32k prefill shapes lowerable, and under sequence sharding each
    device scans only its local KV blocks.
  * `decode_attention` — single-query attention against a (possibly
    sequence-sharded) KV cache, with partial-softmax (max/denominator)
    combine exposed for the shard_map flash-decode path in
    `parallel/collectives.py`.

GQA/MQA is contracted per KV-head group: query head ``h = kv * g + j``
(``g = n_heads // n_kv_heads``) meets KV head ``kv`` in place, so the
cache is never repeated per query head. MHA is the ``g == 1`` case of the
same code.

QKV/O projections run through `core.mf.apply_projection`, so attention
projections participate in the MF mixed mapping like every other layer.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.mf import ExecMode
from repro.models import blocks

NEG_INF = -1e30


def attn_init(key: jax.Array, d_model: int, n_heads: int, n_kv_heads: int,
              head_dim: int, *, qkv_bias: bool, qk_norm: bool, mf: bool,
              dtype: Any = jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "q": blocks.proj_init(ks[0], d_model, n_heads * head_dim,
                              bias=qkv_bias, mf=mf, dtype=dtype),
        "k": blocks.proj_init(ks[1], d_model, n_kv_heads * head_dim,
                              bias=qkv_bias, mf=mf, dtype=dtype),
        "v": blocks.proj_init(ks[2], d_model, n_kv_heads * head_dim,
                              bias=qkv_bias, mf=mf, dtype=dtype),
        "o": blocks.proj_init(ks[3], n_heads * head_dim, d_model, bias=False,
                              mf=mf, dtype=dtype),
    }
    if qk_norm:
        p["q_norm"] = blocks.rmsnorm_init(head_dim, dtype)
        p["k_norm"] = blocks.rmsnorm_init(head_dim, dtype)
    return p


def _axis_size(pctx, axis: str) -> int:
    sizes = dict(zip(pctx.mesh.axis_names, pctx.mesh.devices.shape))
    return sizes.get(axis, 1)


def _split_heads(v: jax.Array, n: int) -> jax.Array:
    b, t, _ = v.shape
    return v.reshape(b, t, n, -1)


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      q_offset: int = 0, block: int = 1024,
                      block_skip: bool = False) -> jax.Array:
    """Online-softmax attention. q: (B,Tq,H,D), k/v: (B,Tk,Hkv,D).

    Scans KV in chunks of ``block`` keeping running (max, denom, out)
    accumulators — the flash-attention recurrence in pure lax. ``q_offset``
    is the absolute position of q[0] (for sequence-sharded queries).
    ``window`` enables sliding-window (local) attention.

    ``block_skip=True`` switches to the 2-D blocked schedule that
    statically skips (q-block, kv-block) pairs that are fully masked —
    ~2x fewer score blocks for causal attention at large T/block, and
    O(window/T) of the work for sliding-window attention (§Perf).
    """
    if block_skip:
        return _blocked_attention_skip(q, k, v, causal=causal,
                                       window=window, q_offset=q_offset,
                                       block=block)
    b, tq, h, d = q.shape
    _, tk, hkv, _ = k.shape
    dv = v.shape[-1]
    g = h // hkv
    scale = 1.0 / math.sqrt(d)
    qf = (q.astype(jnp.float32) * scale).reshape(b, tq, hkv, g, d)

    nblocks = -(-tk // block)
    pad = nblocks * block - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblocks, block, hkv, d)
    vb = v.reshape(b, nblocks, block, hkv, dv)

    q_pos = q_offset + jnp.arange(tq)

    def body(carry, inp):
        m, l, o = carry
        kc, vc, blk_idx = inp
        k_pos = blk_idx * block + jnp.arange(block)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kc.astype(jnp.float32))
        mask = jnp.ones((tq, block), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        mask &= (k_pos < tk)[None, :]            # padding keys
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        # Fully-masked rows have s == m_new == NEG_INF -> exp(0) == 1;
        # zero them explicitly so they contribute nothing.
        p = jnp.where(mask[None, None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32))
        return (m_new, l_new, o_new), None

    m0 = jnp.full((b, hkv, g, tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, tq), jnp.float32)
    o0 = jnp.zeros((b, hkv, g, tq, dv), jnp.float32)
    (m, l, o), _ = jax.lax.scan(
        body, (m0, l0, o0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0),
         jnp.arange(nblocks)))
    out = (o / jnp.maximum(l, 1e-30)[..., None]).reshape(b, h, tq, dv)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)    # (B, Tq, H, D)


def _blocked_attention_skip(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            causal: bool, window: Optional[int],
                            q_offset: int, block: int) -> jax.Array:
    """2-D blocked online softmax over a statically pruned (i, j) pair
    list: pairs whose every (q,k) position is masked never execute."""
    b, tq, h, d = q.shape
    _, tk, hkv, _ = k.shape
    dv = v.shape[-1]
    g = h // hkv
    scale = 1.0 / math.sqrt(d)

    nq, nk = -(-tq // block), -(-tk // block)
    qp = jnp.pad(q, ((0, 0), (0, nq * block - tq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * block - tk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * block - tk), (0, 0), (0, 0)))
    qb = (qp.astype(jnp.float32) * scale).reshape(b, nq, block, hkv, g, d)
    kb = kp.reshape(b, nk, block, hkv, d)
    vb = vp.reshape(b, nk, block, hkv, dv)

    def kv_blocks_for(i: int) -> list[int]:
        q_lo, q_hi = i * block + q_offset, i * block + q_offset + block - 1
        out = []
        for j in range(nk):
            k_lo, k_hi = j * block, j * block + block - 1
            if causal and k_lo > q_hi:
                continue                      # fully above the diagonal
            if window is not None and k_hi < q_lo - window + 1:
                continue                      # fully outside the window
            out.append(j)
        return out

    def partial_block(i: int, j: int, m, l, o):
        kj = kb[:, j]
        vj = vb[:, j]
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb[:, i], kj.astype(jnp.float32))
        q_pos = q_offset + i * block + jnp.arange(block)
        k_pos = j * block + jnp.arange(block)
        mask = jnp.ones((block, block), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        mask &= (k_pos < tk)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vj.astype(jnp.float32))
        return m_new, l_new, o_new

    # Static Python double loop: per-q-block accumulators stay LOCAL
    # (b,hkv,g,block,dv) values — no (nq, ...) gather/scatter buffers whose
    # full-size dynamic-update-slices would dominate bytes accessed.
    outs = []
    for i in range(nq):
        m = jnp.full((b, hkv, g, block), NEG_INF, jnp.float32)
        l = jnp.zeros((b, hkv, g, block), jnp.float32)
        o = jnp.zeros((b, hkv, g, block, dv), jnp.float32)
        for j in kv_blocks_for(i):
            m, l, o = partial_block(i, j, m, l, o)
        outs.append(o / jnp.maximum(l, 1e-30)[..., None])
    out = jnp.concatenate(outs, axis=3)        # (b, hkv, g, nq*block, dv)
    out = out[:, :, :, :tq].reshape(b, h, tq, dv)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)


def decode_attention_partial(q: jax.Array, k: jax.Array, v: jax.Array,
                             valid: jax.Array
                             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token attention partials for flash-decode combine.

    q: (B,H,D); k/v: (B,S,Hkv,D) local cache shard; valid: (B,S) bool.
    Returns (m, l, o): per-head running max (B,H), denom (B,H), and
    unnormalised output (B,H,D) — combinable across shards with the
    standard log-sum-exp merge.
    """
    b, s, hkv, d = k.shape
    h = q.shape[1]
    scale = 1.0 / math.sqrt(d)
    qg = (q.astype(jnp.float32) * scale).reshape(b, hkv, h // hkv, d)
    # HIGHEST keeps the sums f32: at default precision a dot with g > 1
    # query rows goes to the MXU as one bfloat16 pass.
    hi = jax.lax.Precision.HIGHEST
    sco = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32),
                     precision=hi)
    ok = valid[:, None, None, :]
    sco = jnp.where(ok, sco, NEG_INF)
    m = jnp.max(sco, axis=-1)
    p = jnp.exp(sco - m[..., None])
    p = jnp.where(ok, p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32),
                   precision=hi)
    return m.reshape(b, h), l.reshape(b, h), o.reshape(b, h, -1)


def combine_partials(parts: list[tuple[jax.Array, jax.Array, jax.Array]]
                     ) -> jax.Array:
    """Merge flash-decode partials from sequence shards."""
    m = parts[0][0]
    for mp, _, _ in parts[1:]:
        m = jnp.maximum(m, mp)
    l = sum(lp * jnp.exp(mp - m) for mp, lp, _ in parts)
    o = sum(op * jnp.exp(mp - m)[..., None] for mp, _, op in parts)
    return o / jnp.maximum(l, 1e-30)[..., None]


def flash_decode_sharded(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                         cache_k: jax.Array, cache_v: jax.Array,
                         idx: jax.Array, *, mesh, dp, tp: str
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Distributed single-token attention over a sequence-sharded KV cache.

    GSPMD cannot auto-distribute a softmax whose reduction axis is sharded
    — it falls back to all-gathering the (f32-cast) cache every layer,
    which dominates decode collectives (HC3 finding: 2.15 GB/layer/chip
    for a 72B-class model). This shard_map computes flash-decode partials
    (m, l, o) on each shard's local cache slice and merges them with an
    O(B*H) log-sum-exp psum instead.

    q: (B, H, D); k_new/v_new: (B, 1, Hkv, D); caches: (B, S, Hkv, D)
    sequence-sharded over ``tp``; idx: (B,) current lengths.
    Returns (out (B, H, D), new_k_cache, new_v_cache).
    """
    from jax.sharding import PartitionSpec as P

    def local_fn(qL, knL, vnL, kcL, vcL, idxL):
        s_loc = kcL.shape[1]
        off = jax.lax.axis_index(tp) * s_loc
        widx = idxL - off                                   # (B,)
        in_range = (widx >= 0) & (widx < s_loc)
        safe = jnp.clip(widx, 0, s_loc - 1)
        upd_k = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u, (i, 0, 0)))(kcL, knL.astype(kcL.dtype), safe)
        upd_v = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u, (i, 0, 0)))(vcL, vnL.astype(vcL.dtype), safe)
        kc2 = jnp.where(in_range[:, None, None, None], upd_k, kcL)
        vc2 = jnp.where(in_range[:, None, None, None], upd_v, vcL)
        valid = (off + jnp.arange(s_loc))[None, :] < (idxL + 1)[:, None]
        m, l, o = decode_attention_partial(qL, kc2, vc2, valid)
        mg = jax.lax.pmax(m, tp)
        scale = jnp.exp(m - mg)
        lg = jax.lax.psum(l * scale, tp)
        og = jax.lax.psum(o * scale[..., None], tp)
        out = og / jnp.maximum(lg, 1e-30)[..., None]
        return out.astype(qL.dtype), kc2, vc2

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp, None, None), P(dp, None, None, None),
                  P(dp, None, None, None), P(dp, tp, None, None),
                  P(dp, tp, None, None), P(dp)),
        out_specs=(P(dp, None, None), P(dp, tp, None, None),
                   P(dp, tp, None, None)),
        check_vma=False,
    )(q, k_new, v_new, cache_k, cache_v, idx)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array) -> jax.Array:
    """q: (B,1,H,D) vs cache (B,S,Hkv,D); cache_len: (B,) valid prefix."""
    b, s, _, _ = k_cache.shape
    valid = jnp.arange(s)[None, :] < cache_len[:, None]
    m, l, o = decode_attention_partial(q[:, 0], k_cache, v_cache, valid)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out[:, None].astype(q.dtype)


def gqa_apply(p: dict, x: jax.Array, *, n_heads: int, n_kv_heads: int,
              head_dim: int, rope_theta: float, positions: jax.Array,
              mode: ExecMode | str = ExecMode.REGULAR,
              qk_norm: bool = False, causal: bool = True,
              window: Optional[int] = None,
              cache: Optional[dict] = None,
              attn_block: int = 1024, attn_block_skip: bool = False,
              pctx=None, prefill_valid: Optional[jax.Array] = None, **kw
              ) -> tuple[jax.Array, Optional[dict]]:
    """Full GQA block. With ``cache`` (decode): append one token and attend
    against the cache; without: blockwise self-attention over x.

    ``prefill_valid`` (with ``cache``) switches to batched prefill: x is a
    (B, T) slab of prompt tokens, per-batch lengths ``prefill_valid`` of
    which are real; causal self-attention runs over the slab and only the
    valid positions' K/V are written into the cache (slots with
    ``prefill_valid == 0`` — e.g. mid-decode neighbours in a serving batch
    — keep their cache rows and length untouched). Requires fresh slots
    (``cache['len'] == 0`` wherever valid > 0) and a non-ring cache.
    """
    b, t, _ = x.shape
    q = _split_heads(blocks.proj_apply(p["q"], x, mode, **kw), n_heads)
    k = _split_heads(blocks.proj_apply(p["k"], x, mode, **kw), n_kv_heads)
    v = _split_heads(blocks.proj_apply(p["v"], x, mode, **kw), n_kv_heads)
    if qk_norm:
        q = blocks.rmsnorm(p["q_norm"], q)
        k = blocks.rmsnorm(p["k_norm"], k)
    q = blocks.apply_rope(q, positions, rope_theta)
    k = blocks.apply_rope(k, positions, rope_theta)

    if cache is None:
        with jax.named_scope("attention"):
            out = blocked_attention(q, k, v, causal=causal, window=window,
                                    block=attn_block,
                                    block_skip=attn_block_skip)
        new_cache = None
    elif prefill_valid is not None:
        # Batched prefill: fold the whole (B, T) prompt slab through one
        # forward. Causal masking already confines every consumed query
        # position to real prefix keys (padding positions beyond a slot's
        # valid length only feed query rows nobody reads and cache rows
        # the write mask drops), so plain causal attention over the slab
        # is enough — no per-slot key masking needed.
        s = cache["k"].shape[1]
        if window is not None and s <= window:
            raise ValueError("batched prefill does not support ring-buffer "
                             "(local-attention) caches")
        if t > s:
            raise ValueError(f"prefill slab length {t} exceeds cache "
                             f"length {s}")
        with jax.named_scope("attention"):
            out = blocked_attention(q, k, v, causal=causal, window=window,
                                    block=attn_block,
                                    block_skip=attn_block_skip)
        with jax.named_scope("kv_write"):
            mask = (jnp.arange(t)[None, :]
                    < prefill_valid[:, None])[..., None, None]  # (B,T,1,1)
            k_cache = cache["k"].at[:, :t].set(jnp.where(
                mask, k.astype(cache["k"].dtype), cache["k"][:, :t]))
            v_cache = cache["v"].at[:, :t].set(jnp.where(
                mask, v.astype(cache["v"].dtype), cache["v"][:, :t]))
        new_cache = {"k": k_cache, "v": v_cache,
                     "len": cache["len"] + prefill_valid}
    else:
        # Decode: write k/v at cache_len, attend over the whole cache.
        # When the cache is smaller than the sequence (local attention) it
        # is a ring buffer: writes wrap and every resident entry is within
        # the window by construction (RoPE is absolute at write time, so
        # relative scores are unaffected by the ring position).
        idx = cache["len"]                                   # (B,)
        s = cache["k"].shape[1]
        is_ring = window is not None and s <= window
        use_flash_sp = (pctx is not None and getattr(pctx, "active", False)
                        and pctx.cfg.seq_shard_cache and not is_ring
                        and window is None
                        and s % _axis_size(pctx, pctx.cfg.tp_axis) == 0)
        if use_flash_sp:
            dp = (pctx.cfg.dp_axes if len(pctx.cfg.dp_axes) > 1
                  else pctx.cfg.dp_axes[0])
            with jax.named_scope("attention"):
                out, k_cache, v_cache = flash_decode_sharded(
                    q[:, 0], k, v, cache["k"], cache["v"], idx,
                    mesh=pctx.mesh, dp=dp, tp=pctx.cfg.tp_axis)
            out = out[:, None]
            y = blocks.proj_apply(
                p["o"], out.reshape(b, t, n_heads * head_dim), mode, **kw)
            return y, {"k": k_cache, "v": v_cache, "len": idx + 1}
        widx = idx % s if is_ring else idx
        with jax.named_scope("kv_write"):
            k_cache = jax.vmap(
                lambda c, kv, i: jax.lax.dynamic_update_slice(
                    c, kv, (i, 0, 0)))(cache["k"],
                                       k.astype(cache["k"].dtype), widx)
            v_cache = jax.vmap(
                lambda c, kv, i: jax.lax.dynamic_update_slice(
                    c, kv, (i, 0, 0)))(cache["v"],
                                       v.astype(cache["v"].dtype), widx)
        with jax.named_scope("attention"):
            if is_ring:
                pos_ok = (jnp.arange(s)[None, :]
                          < jnp.minimum(idx + 1, s)[:, None])
            else:
                pos_ok = jnp.arange(s)[None, :] < (idx + 1)[:, None]
                if window is not None:
                    pos_ok &= (jnp.arange(s)[None, :]
                               > (idx[:, None] - window))
            m, l, o = decode_attention_partial(q[:, 0], k_cache, v_cache,
                                               pos_ok)
            out = (o / jnp.maximum(l, 1e-30)[..., None])[:, None].astype(
                q.dtype)
        new_cache = {"k": k_cache, "v": v_cache, "len": idx + 1}

    y = blocks.proj_apply(
        p["o"], out.reshape(b, t, n_heads * head_dim), mode, **kw)
    return y, new_cache


def init_kv_cache(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
                  dtype: Any = jnp.bfloat16) -> dict:
    return {
        "k": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }
