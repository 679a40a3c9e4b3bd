"""Peaks table and the operations/bytes functions against hand counts."""

import pytest

from bench import peaks

TINY = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 2, "d_ff": 16, "vocab": 32}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_decode_step_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3x8x16 = 64+32+32+64+384
    mm = 576
    attn = 4 * 4 * 2 * 10  # scores and values: 2 flops x H x hd x keys, x2
    head = 2 * 8 * 32
    assert peaks.decode_step_flops(TINY, 3, 10) == 3 * (2 * (2 * mm + attn)
                                                        + head)
    weights = (2 * mm + 32 * 8) * 2
    kv = 2 * 3 * 10 * 2 * 2 * 2 * 2  # layers x B x keys x (K,V) x kvd x 2B
    assert peaks.decode_step_bytes(TINY, 3, 10) == weights + kv


def test_prefill_by_hand():
    S = 5
    attn = 4 * 4 * 2 * (S * (S + 1) // 2)  # causal pairs: 15
    per_seq = 2 * (2 * 576 * S + attn) + 2 * 8 * 32
    assert peaks.prefill_flops(TINY, 2, S) == 2 * per_seq


def test_awrp_select_rows_bytes_by_hand():
    # 2048 rows x 240 lanes -> lanes 256, tile 512 rows (512 KiB / 1 KiB)
    assert peaks.awrp_select_rows_shape(2048, 240) == (2048, 256)
    assert peaks.awrp_select_rows_bytes(2048, 240) == 4 * (3 * 2048 * 256
                                                           + 2 * 2048)
    # 10 rows -> one tile of 16 rows
    assert peaks.awrp_select_rows_shape(10, 8) == (16, 128)


def test_least_seconds_names_its_bound():
    t, bound = peaks.least_seconds(197e12, 1.0, "TPU v5 lite")
    assert (t, bound) == (1.0, "flops")
    t, bound = peaks.least_seconds(1.0, 819e9, "TPU v5 lite")
    assert (t, bound) == (1.0, "bytes")
