"""Operations and bytes from shapes, against hand counts at a small
shape; the peaks table refuses an unknown device."""

import pytest

from bench import opcount
from bench.peaks import PEAKS, peaks_for

SIZES = {"hidden_size": 8, "head_dim": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 16,
         "num_hidden_layers": 3, "vocab_size": 10}


def test_token_ops_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8, up/gate 8x16, down 16x8:
    # K*N = 64 + 32 + 32 + 64 + 128 + 128 + 128 = 576; 4 ops per cell.
    per_layer = 4 * 576
    attn = 2 * 2 * 4 * 2 * 5            # 5 cached positions
    head = 2 * 8 * 10
    assert opcount.token_ops(SIZES, 5) == 3 * (per_layer + attn) + head


def test_mav_sil_work_by_hand():
    # K=40 -> 2 chunks of M=31 -> 62 cells per output; N=3, 2 rows,
    # W_P=X_P=8: 7 + 7 planes.
    ops, nbytes = opcount.mav_sil_work(40, 3, 2, 8, 8, 31)
    assert ops == 2 * 2 * (62 * 3) * 14
    assert nbytes == 8 * 62 * 3 * 5 + 4 * 2 * 40 + 12 * 2 * 3


def test_peaks_are_keyed_by_device_kind():
    assert peaks_for("TPU v5 lite").bf16_flops == 197e12
    assert "TPU v5e" in PEAKS["TPU v5 lite"].source
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("cpu")
