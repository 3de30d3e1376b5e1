"""Operations and bytes of a kernel's work, from shapes alone.

Counted from the design point, never from a kernel's grid or a compiled
program, so a rewritten kernel is read against the same work. A
configuration's operations per token are its reference's ``token_ops``
(``configs/<reference>.py``).
"""

from __future__ import annotations

import math


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
