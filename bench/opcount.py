"""Operations and bytes of the served model's work, from shapes alone.

Counted from the configuration and the design point, never from a
kernel's grid or a compiled program, so a rewritten kernel or step is read
against the same work.
"""

from __future__ import annotations

import math


def mf_projections(sizes: dict) -> list[tuple[int, int]]:
    """(K, N) of every multiplication-free projection of one layer."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    q = sizes["num_attention_heads"] * hd
    kv = sizes["num_key_value_heads"] * hd
    ff = sizes["intermediate_size"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, ff), (d, ff), (ff, d)]


def mf_ops(k: int, n: int) -> int:
    """Operations of one row through one MF projection: two correlations
    (sign(x)|w| and |x|sign(w)), each K*N multiply-adds of 2 operations.
    The CIM planes are not counted: the lossless collapse skips them."""
    return 4 * k * n


def token_ops(sizes: dict, cache_len: int) -> int:
    """Operations of one output token at ``cache_len`` positions attended:
    every layer's MF projections and attention (scores and values, 2
    operations per multiply-add each), then the float LM head."""
    per_layer = sum(mf_ops(k, n) for k, n in mf_projections(sizes))
    attn = 2 * 2 * sizes["num_attention_heads"] * sizes["head_dim"] * cache_len
    head = 2 * sizes["hidden_size"] * sizes["vocab_size"]
    return sizes["num_hidden_layers"] * (per_layer + attn) + head


def mav_sil_work(k: int, n: int, rows: int, w_bits: int, x_bits: int,
                 m_columns: int) -> tuple[int, int]:
    """(operations, bytes) of the fused silicon MAV kernel over one (K, N)
    projection for ``rows`` input rows, from the design point.

    Per chunk of M columns and output channel, each row's sign gates meet
    the Pw = W_P - 1 magnitude planes and each of its Px = X_P - 1 input
    planes meets the weight sign gates (one multiply-add per cell, two
    operations). Bytes: the int8 planes and gates (Pw + 1 per cell), their
    float32 cap fold (4 bytes per plane and gate cell), the float32 input
    rows, and the float32 (B, N) outputs of the three partial sums.
    """
    pw, px = w_bits - 1, x_bits - 1
    cells = math.ceil(k / m_columns) * m_columns * n
    ops = 2 * rows * cells * (pw + px)
    weight_bytes = (pw + 1) * cells * (1 + 4)
    return ops, weight_bytes + 4 * rows * k + 3 * 4 * rows * n
