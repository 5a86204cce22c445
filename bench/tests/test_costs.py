"""The bytes and FLOPs functions against counts made by hand."""

import pytest

from benchlib import costs, peaks


def test_mlp_flops_forward_times_three():
    # 3 -> 2 -> 1: forward 2*(3*2 + 2*1) = 16, with backward 48
    assert costs.mlp_flops_per_sample((3, 2, 1)) == 48


def test_deepfm_flops_by_hand():
    # 13 numeric + 26 fields x 10 = 273 inputs, 400-400-400-1
    mlp = 3 * 2 * (273 * 400 + 400 * 400 + 400 * 400 + 400)
    fm = 3 * (3 * 26 * 10 + 3 * 10)
    wide = 3 * 26
    assert costs.deepfm_flops_per_sample(13, 26, 10, [400, 400, 400]) == \
        mlp + fm + wide


def test_sparse_bytes_rows_times_row_bytes_times_passes():
    # one row of 10 floats under Adagrad: pull 40+4; push reads and writes
    # the row and its accumulator (4 x 40), reads the gradient (40), index 4
    assert costs.sparse_bytes_per_step(1, 10, "adagrad") == 44 + 204
    assert costs.sparse_bytes_per_step(1000, 10, "adagrad") == 248000
    assert costs.sparse_bytes_per_step(1, 1, "sgd") == 8 + 16


def test_sparse_bytes_do_not_see_the_table_size():
    # a table-sized scatter is charged the rows touched, not the table
    rows = 16384 * 26
    need = costs.sparse_bytes_per_step(rows, 10) \
        + costs.sparse_bytes_per_step(rows, 1)
    assert need < 0.03 * (2 * 67108864 * 11 * 4)


def test_lm_params_gpt2_xl_at_twelve_layers():
    p = costs.lm_params(1600, 12, 50257, 1024)
    assert p["block"] == 12 * 1600 * 1600 + 4 * 1600
    assert p["total"] == 12 * p["block"] + 50257 * 1600 + 1024 * 1600 + 3200
    assert p["total"] == 450769600      # what the program's tree holds


def test_lm_flops_per_token_by_hand():
    d, L, V, T = 1600, 12, 50257, 1024
    matmul = 6 * (L * 12 * d * d + V * d)
    attn = L * 6 * T * d
    assert costs.lm_flops_per_token(d, L, V, T) == matmul + attn


def test_attention_flops_per_step_by_hand():
    # one head, one layer, batch 1: forward 2 products of 2*T*T*hd, halved
    # by the causal mask; backward four: three times the forward in all
    T, hd = 1024, 64
    fwd = 2 * 2 * T * T * hd / 2
    assert costs.attention_flops_per_step(1, T, 1, hd, 1) == 3 * fwd
    assert costs.attention_flops_per_step(16, T, 25, hd, 12) == \
        16 * 25 * 12 * 3 * fwd


def test_peaks_known_kind_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
