"""Attention unit tests: blocked online-softmax vs naive oracle, the
block-skip schedule, GQA grouping, decode partials (also against the
repeated-cache formulation the grouped contraction replaced)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (blocked_attention, combine_partials,
                                    decode_attention,
                                    decode_attention_partial)


def naive_attention(q, k, v, causal=True, window=None):
    b, tq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    qp, kp = jnp.arange(tq), jnp.arange(k.shape[1])
    mask = jnp.ones((tq, k.shape[1]), bool)
    if causal:
        mask &= qp[:, None] >= kp[None, :]
    if window is not None:
        mask &= qp[:, None] - kp[None, :] < window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[None, None], p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _repeat_kv(v, groups):
    """(B, T, Hkv, D) -> (B, T, Hkv*groups, D): the cache repeated per
    query head, as decode attention once materialised it."""
    if groups == 1:
        return v
    b, t, h, d = v.shape
    return jnp.broadcast_to(v[:, :, :, None, :], (b, t, h, groups, d)
                            ).reshape(b, t, h * groups, d)


def repeated_cache_decode_partial(q, k, v, valid):
    """Oracle: decode partials over the repeated cache."""
    b, s, hkv, d = k.shape
    groups = q.shape[1] // hkv
    k = _repeat_kv(k, groups)
    v = _repeat_kv(v, groups)
    scale = 1.0 / math.sqrt(d)
    sco = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32) * scale,
                     k.astype(jnp.float32))
    sco = jnp.where(valid[:, None, :], sco, -1e30)
    m = jnp.max(sco, axis=-1)
    p = jnp.exp(sco - m[..., None])
    p = jnp.where(valid[:, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhs,bshd->bhd", p, v.astype(jnp.float32))
    return m, l, o


def _qkv(b, t, h, hkv, d, with_time=True):
    qshape = (b, t, h, d) if with_time else (b, h, d)
    return (jax.random.normal(jax.random.PRNGKey(0), qshape),
            jax.random.normal(jax.random.PRNGKey(1), (b, t, hkv, d)),
            jax.random.normal(jax.random.PRNGKey(2), (b, t, hkv, d)))


@pytest.mark.parametrize("block_skip", [False, True])
@pytest.mark.parametrize("t,block,causal,window,h,hkv", [
    (130, 32, True, None, 4, 2), (128, 32, True, 48, 4, 2),
    (96, 32, False, None, 4, 2), (64, 128, True, None, 4, 2),
    (130, 32, True, None, 16, 8), (128, 32, True, 48, 16, 8),
])
def test_blocked_vs_naive(t, block, causal, window, h, hkv, block_skip):
    q, k, v = _qkv(2, t, h, hkv, 16)
    out = blocked_attention(q, k, v, causal=causal, window=window,
                            block=block, block_skip=block_skip)
    ref = naive_attention(q, k, v, causal, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


def test_block_skip_matches_dense_schedule():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 260, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 260, 4, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 260, 4, 16))
    a = blocked_attention(q, k, v, block=64, block_skip=False)
    b = blocked_attention(q, k, v, block=64, block_skip=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


HEADS = [(4, 4), (4, 2), (4, 1)]


@pytest.mark.parametrize("h,hkv", HEADS)
def test_decode_matches_naive_last_position(h, hkv):
    t = 33
    q, k, v = _qkv(2, t, h, hkv, 16)
    full = naive_attention(q, k, v, causal=True)
    out = decode_attention(q[:, -1:], k, v,
                           jnp.full((2,), t, jnp.int32))
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(full[:, -1]), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("h,hkv", HEADS)
def test_flash_decode_partial_combine(h, hkv):
    """Sequence-sharded decode: partials from two shards == full answer."""
    s = 64
    q, k, v = _qkv(2, s, h, hkv, 16, with_time=False)
    valid = jnp.ones((2, s), bool)
    m, l, o = decode_attention_partial(q, k, v, valid)
    full = o / l[..., None]
    parts = [decode_attention_partial(q, k[:, :32], v[:, :32],
                                      valid[:, :32]),
             decode_attention_partial(q, k[:, 32:], v[:, 32:],
                                      valid[:, 32:])]
    combined = combine_partials(parts)
    np.testing.assert_allclose(np.asarray(combined), np.asarray(full),
                               rtol=1e-5, atol=1e-6)


def test_decode_gqa_at_qwen3_heads_partly_valid():
    """16 query heads on 8 KV heads, D 128, S 512, each slot's cache
    valid to its own length: every slot matches naive attention over its
    valid prefix."""
    lens = [1, 200, 511, 512]
    b, s = len(lens), 512
    q, k, v = _qkv(b, s, 16, 8, 128, with_time=False)
    out = decode_attention(q[:, None], k, v, jnp.asarray(lens, jnp.int32))
    for i, n in enumerate(lens):
        ref = naive_attention(q[i:i + 1, None], k[i:i + 1, :n],
                              v[i:i + 1, :n], causal=False)
        np.testing.assert_allclose(np.asarray(out[i, 0]),
                                   np.asarray(ref[0, 0]), rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.parametrize("hkv", [16, 8, 2])          # g = 1, 2, 8
def test_decode_partial_matches_repeated_cache(hkv):
    """Grouped contraction == the repeated-cache formulation: same
    (m, l, o) to f32 rounding, with part of the cache masked."""
    b, s, h, d = 3, 96, 16, 32
    q, k, v = _qkv(b, s, h, hkv, d, with_time=False)
    valid = jnp.arange(s)[None, :] < jnp.asarray([5, 60, 96])[:, None]
    got = decode_attention_partial(q, k, v, valid)
    want = repeated_cache_decode_partial(q, k, v, valid)
    for g_arr, w_arr in zip(got, want):
        assert g_arr.shape == w_arr.shape
        np.testing.assert_allclose(np.asarray(g_arr), np.asarray(w_arr),
                                   rtol=1e-5, atol=1e-6)
