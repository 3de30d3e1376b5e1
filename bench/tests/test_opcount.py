"""Operations and bytes from shapes, against hand counts at a small
shape; the peaks table refuses an unknown device. A configuration's
operations per token come from its reference module (``token_ops``)."""

import json

import pytest

from bench import harness, opcount
from bench.peaks import PEAKS, peaks_for

SIZES = {"hidden_size": 8, "head_dim": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 16,
         "num_hidden_layers": 3, "vocab_size": 10}
QWEN3 = harness.load_module(harness.BENCH / "configs" / "qwen3_ref.py")


def test_token_ops_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8, up/gate 8x16, down 16x8:
    # K*N = 64 + 32 + 32 + 64 + 128 + 128 + 128 = 576; 4 ops per cell.
    per_layer = 4 * 576
    attn = 2 * 2 * 4 * 2 * 5            # 5 cached positions
    head = 2 * 8 * 10
    assert QWEN3.token_ops(SIZES, 5) == 3 * (per_layer + attn) + head


def old_token_ops(sizes: dict, cache_len: int) -> int:
    """``bench/opcount.token_ops`` as it was before the count moved to
    the configuration's reference: the oracle."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    q = sizes["num_attention_heads"] * hd
    kv = sizes["num_key_value_heads"] * hd
    ff = sizes["intermediate_size"]
    projections = [(d, q), (d, kv), (d, kv), (q, d), (d, ff), (d, ff),
                   (ff, d)]
    per_layer = sum(4 * k * n for k, n in projections)
    attn = 2 * 2 * sizes["num_attention_heads"] * sizes["head_dim"] * cache_len
    head = 2 * sizes["hidden_size"] * sizes["vocab_size"]
    return sizes["num_hidden_layers"] * (per_layer + attn) + head


def test_qwen3_token_ops_are_the_former_count_at_the_cells_sizes():
    conf = json.loads((harness.BENCH / "configs" /
                       "qwen3-0.6b.cim8x62.pinned.json").read_text())
    sizes = conf["model"]
    for cache_len in range(1, conf["serving"]["max_len"] + 1):
        assert QWEN3.token_ops(sizes, cache_len) == \
            old_token_ops(sizes, cache_len)
    assert not hasattr(opcount, "token_ops")


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
