"""Operations and bytes from shapes, at both configurations' published
widths, against numbers worked out by hand."""
from __future__ import annotations

import json

import pytest

import harness
import roofline


def _model(name):
    path = harness.BENCH / "configs" / f"{name}.json"
    return json.loads(path.read_text())["model"]


DANUBE, QWEN = _model("danube-1.8b"), _model("qwen3-8b-8L")


def test_weights_danube():
    # q and o: 2560 x 2560 each; k and v: 2560 x 640 each; MLP 3 x 2560 x 6912
    assert roofline.layer_weights(DANUBE) == (
        2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912)
    assert 24 * roofline.layer_weights(DANUBE) == 1_667_235_840
    assert roofline.head_weights(DANUBE) == 81_920_000


def test_weights_qwen3():
    assert roofline.layer_weights(QWEN) == (
        2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 12288)
    assert roofline.head_weights(QWEN) == 4096 * 18992


def test_decode_tick_danube_one_row():
    # one row feeding position 999, so 1000 keys in each of 24 layers
    w = 1_667_235_840 + 81_920_000
    flops = 2 * w + 4 * 32 * 80 * 1000 * 24
    kv = 2 * 8 * 80 * 2 * 24                  # bytes of one cached position
    assert roofline.decode_tick(DANUBE, [999]) == (flops,
                                                   2 * w + 1000 * kv + kv)


def test_decode_tick_qwen3_four_rows():
    w = 8 * roofline.layer_weights(QWEN) + 4096 * 18992
    ctx = 4096 + 5001 + 6001 + 8192
    kv = 2 * 8 * 128 * 2 * 8
    assert roofline.decode_tick(QWEN, [4095, 5000, 6000, 8191]) == (
        2 * 4 * w + 4 * 32 * 128 * 8 * ctx, 2 * w + ctx * kv + 4 * kv)
    assert roofline.decode_tick(QWEN, [4095, 5000, 6000, 8191]) == (
        16_023_027_712, 4_005_888_000)


def test_window_caps_attention():
    assert roofline.decode_tick(DANUBE, [5000]) == \
        roofline.decode_tick(DANUBE, [4095])
    assert roofline.decode_tick(QWEN, [5000]) != \
        roofline.decode_tick(QWEN, [4095])


def test_prefill_chunk_danube():
    # 256 queries at positions 256..511 attend to 257..512 keys
    keys = sum(range(257, 513))
    assert keys == 98_432
    flops, by = roofline.prefill_chunk(DANUBE, 256, 256, last_row=False)
    assert flops == 2 * 1_667_235_840 * 256 + 4 * 32 * 80 * 24 * keys
    kv = 2 * 8 * 80 * 2 * 24
    assert by == 2 * 1_667_235_840 + 512 * kv + 256 * kv
    f2, b2 = roofline.prefill_chunk(DANUBE, 256, 256, last_row=True)
    assert (f2 - flops, b2 - by) == (2 * 81_920_000, 2 * 81_920_000)


def test_attention_and_projection_calls():
    assert roofline.attention(QWEN, [9]) == (4 * 32 * 128 * 10 * 8,
                                             2 * 8 * 128 * 2 * 10 * 8)
    f, b = roofline.projection(QWEN, 3)
    assert (f, b) == (2 * 3 * 8 * roofline.layer_weights(QWEN),
                      2 * 8 * roofline.layer_weights(QWEN))


def test_bound_names_its_peak():
    peak = roofline.peaks("TPU v5 lite")
    assert (peak["flops_per_s"], peak["hbm_bytes_per_s"]) == (197e12, 819e9)
    f, b = roofline.decode_tick(DANUBE, [999])
    t, which = roofline.bound_seconds(f, b, peak)
    assert which == "memory" and t == b / 819e9
    f, b = roofline.prefill_chunk(DANUBE, 0, 256, last_row=True)
    assert roofline.bound_seconds(f, b, peak)[1] == "compute"


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("TPU v9 imaginary")
