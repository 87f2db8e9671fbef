"""FLOP and byte counts against hand counts, and the peak table."""
import json
import pathlib

import pytest

import flops
import peaks

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def test_mobilenetv2_tiny_macs_per_image():
    c = json.loads((CONFIGS / "mobilenetv2-tiny.json").read_text())
    assert len(flops.cnn_convs(c)) == 52
    assert flops.cnn_macs(c) == 352_110_592            # 352.1 M


def test_cnn_macs_hand_count_reduced():
    # stem 3x3 s1 3->8 on 8x8, one block t=1 (dw 3x3 8ch, project 8->8),
    # head 1x1 8->8, fc 8->10.
    c = {"image_size": 8, "channels": 3, "width": 1.0, "stem_channels": 8,
         "stem_stride": 1, "head_channels": 8, "num_classes": 10,
         "plan": [[1, 8, 1, 1]]}
    stem = 64 * 9 * 3 * 8
    dw = 64 * 9 * 1 * 8
    project = 64 * 8 * 8
    head = 64 * 8 * 8
    assert flops.cnn_macs(c) == stem + dw + project + head + 8 * 10
    assert flops.cnn_train_flops(c, 4) == 3 * 2 * 4 * flops.cnn_macs(c)


def test_cnn_stride_plan_halves_maps():
    c = {"image_size": 64, "channels": 3, "width": 1.0, "stem_channels": 32,
         "stem_stride": 1, "head_channels": 1280, "num_classes": 200,
         "plan": [[6, 24, 2, 2]]}
    convs = flops.cnn_convs(c)
    dw = [cv for cv in convs if cv["name"].endswith(".dw")]
    assert [cv["h_out"] for cv in dw] == [32, 32]
    assert dw[0]["h_in"] == 64


LM = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv": 2, "head_dim": 2,
      "d_ff": 16, "vocab": 32, "mlp_kind": "gelu", "sliding_window": 3}


def test_lm_linear_params_hand_count():
    per_layer = 8 * 8 + 2 * (8 * 4) + 8 * 8 + 2 * (8 * 16)
    assert flops.lm_linear_params(LM) == 2 * per_layer + 8 * 32
    assert flops.lm_linear_params(LM, head=False) == 2 * per_layer


def test_attention_pairs_sliding_and_causal():
    assert flops.attn_pairs(5, 3) == 1 + 2 + 3 + 3 + 3
    assert flops.attn_pairs(5, None) == 15


def test_lm_train_flops_hand_count():
    seq, batch = 5, 2
    fwd = 2 * batch * seq * flops.lm_linear_params(LM) \
        + batch * 4 * 2 * 4 * 2 * (1 + 2 + 3 + 3 + 3)
    assert flops.lm_train_flops(LM, seq, batch) == 3 * fwd


def test_starcoder2_4l_matches_hand_count():
    c = json.loads((CONFIGS / "starcoder2-3b-4l.json").read_text())
    assert flops.lm_linear_params(c) == 534_773_760
    per_token = flops.lm_train_flops(c, 4096, 4) / (4 * 4096)
    assert per_token == pytest.approx(3.51e9, rel=1e-3)


def test_decode_bytes_and_least_time():
    ctx, batch = 2, 3
    kv = 2 * 2 * 2 * 2 * ctx * batch * 2           # L, k+v, KV, hd, ctx, B, 2 B
    assert flops.lm_decode_bytes(LM, ctx, batch, 2) == \
        flops.lm_linear_params(LM) + kv
    p = {"int8_ops_per_s": 10.0, "hbm_bytes_per_s": 2.0}
    assert flops.least_seconds([(100, 10), (10, 100)], p) == 10.0 + 50.0


def test_peak_table_known_and_unknown():
    assert peaks.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
