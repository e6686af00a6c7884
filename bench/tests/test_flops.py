"""Useful FLOPs, needed bytes and peaks against hand-reckoned values."""
import json
import pathlib

import pytest

from bench import device, flops, spec

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def arch(name):
    return spec.arch_of(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_internlm2_per_token():
    a = arch("internlm2_1_8b")
    # per layer: qkv 2048 x (16 + 2*8) x 128 = 8,388,608; o 4,194,304;
    # SwiGLU 3 x 2048 x 8192 = 50,331,648 -> 62,914,560 x 24 layers x 2
    assert flops.matmul_flops_per_token(a) == 2 * 24 * 62_914_560
    assert flops.logits_flops(a) == 2 * 2048 * 92_544
    # a decode token at position 4300 attends over 4301 keys
    assert flops.attention_flops(a, 4300, 4301) == 4 * 24 * 16 * 128 * 4301
    assert flops.decode_flops(a, 4300) == pytest.approx(
        3.0199e9 + 0.3791e9 + 0.8456e9, rel=1e-3)


def test_prefill_attention_is_causal():
    a = arch("internlm2_1_8b")
    # positions 0..3 attend over 1+2+3+4 keys; splitting the range at a
    # resident-history boundary changes nothing
    assert flops.attention_flops(a, 0, 4) == 4 * 24 * 16 * 128 * 10
    assert (flops.attention_flops(a, 0, 1000)
            == flops.attention_flops(a, 0, 600)
            + flops.attention_flops(a, 600, 1000))
    assert flops.prefill_flops(a, 10, 10) == 0.0
    assert flops.prefill_flops(a, 0, 2) == (
        2 * flops.matmul_flops_per_token(a) + flops.attention_flops(a, 0, 2)
        + flops.logits_flops(a))


def test_decode_kernel_bytes_internlm2():
    a = arch("internlm2_1_8b")
    # 4300 tokens: 16 flushed pages; a page of one head holds 256 x 128
    # int8 bytes and one 128-wide f32 scale row, for K and for V
    page = 2 * 8 * (256 * 128 + 128 * 4)
    per_layer = 16 * page + 16 * 128 * 2 + 16 * 130 * 4
    assert flops.decode_attn_bytes(a, 4300) == 24 * per_layer
    # 12 rows at ~4.3k tokens read ~2.5 GB of pages a step
    assert 12 * flops.decode_attn_bytes(a, 4300) == pytest.approx(2.457e9,
                                                                  rel=1e-3)
    assert flops.decode_attn_bytes(a, 4300, "int4") < \
        0.55 * flops.decode_attn_bytes(a, 4300)
    # the partial page lives in the fp residual, not in the pool
    assert flops.decode_attn_bytes(a, 255) == flops.decode_attn_bytes(a, 0)


def test_qwen_stage_per_token():
    a = arch("qwen2_5_32b_pp8")
    assert (a["heads"], a["kv_heads"], a["d_ff"], a["layers"]) == \
        (40, 8, 27648, 8)
    # per layer: qkv 5120 x 56 x 128 + o 5120^2 + 3 x 5120 x 27648, so
    # ~7.8 GFLOP a token over the stage's 8 layers (3.9e9 weights)
    per_layer = 36_700_160 + 26_214_400 + 424_673_280
    assert flops.matmul_flops_per_token(a) == 2 * 8 * per_layer
    assert flops.logits_flops(a) == 2 * 5120 * 19008
    # group 5: 40 query heads read each of the 8 KV heads' pages once
    assert flops.decode_attn_bytes(a, 8192) == 8 * (
        32 * 2 * 8 * (256 * 128 + 128 * 4) + 40 * 128 * 2 + 40 * 130 * 4)


def test_peaks_table():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in json.loads(
        (CONFIGS.parent / "peaks.json").read_text())["source"]
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
