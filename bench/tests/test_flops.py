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


# every count of the two cells as the harness computed it before the
# counts knew experts and windows: (function, arguments) -> value
GOLDEN = {
    "internlm2_1_8b": {
        ("matmul_flops_per_token",): 3019898880.0,
        ("logits_flops",): 379060224.0,
        ("attention_flops", 0, 1): 196608.0,
        ("attention_flops", 1024, 5000): 2354911641600.0,
        ("attention_flops", 4300, 4301): 845611008.0,
        ("prefill_flops", 1024, 5000): 14362408648704.0,
        ("prefill_flops", 0, 6144): 22266093502464.0,
        ("decode_flops", 4300): 4244570112.0,
        ("decode_flops", 8191): 5009571840.0,
        ("decode_attn_bytes", 4300): 204770304.0,
        ("decode_attn_bytes", 8192): 409242624.0,
        ("decode_attn_bytes", 4300, "int4"): 104107008.0,
    },
    "qwen2_5_32b_pp8": {
        ("matmul_flops_per_token",): 7801405440.0,
        ("logits_flops",): 194641920.0,
        ("attention_flops", 0, 1): 163840.0,
        ("attention_flops", 1024, 5000): 1962426368000.0,
        ("attention_flops", 4300, 4301): 704675840.0,
        ("prefill_flops", 1024, 5000): 32981009039360.0,
        ("prefill_flops", 0, 6144): 51024909434880.0,
        ("decode_flops", 4300): 8700723200.0,
        ("decode_flops", 8191): 9338224640.0,
        ("decode_attn_bytes", 4300): 68405760.0,
        ("decode_attn_bytes", 8192): 136563200.0,
        ("decode_attn_bytes", 4300, "int4"): 34851328.0,
    },
}


@pytest.mark.parametrize("name,call", [(n, c) for n in sorted(GOLDEN)
                                       for c in GOLDEN[n]])
def test_cells_count_as_before(name, call):
    fn, *args = call
    got = getattr(flops, fn)(arch(name), *args)
    assert got == GOLDEN[name][call] and type(got) is float


def data_arch(name, **keys):
    cfg = json.loads((CONFIGS.parent / "tests" / "data" / f"{name}.json")
                     .read_text())
    return spec.arch_of(dict(cfg, **keys))


def test_expert_layer_by_hand():
    a = data_arch("qwen2_moe_small")
    # attention 64 x (4 + 2*4) x 16 + 4 x 16 x 64 = 16,384; the expert
    # layer: router 64 x 8, shared SwiGLU 3 x 64 x 64 and its gate 64,
    # 2 of 8 routed experts 3 x 64 x 32 each -> 25,152; 4 layers
    assert flops.matmul_flops_per_token(a) == 2 * 4 * (16_384 + 25_152)
    # holding 4 of the 8 experts: half the routed share is computed here
    held = data_arch("qwen2_moe_small", num_experts=4,
                     reduced=["num_experts"], published={"num_experts": 8})
    assert flops.matmul_flops_per_token(held) == \
        2 * 4 * (16_384 + 25_152 - 6_144)
    # a leading dense layer: SwiGLU 3 x 64 x 128
    first = data_arch("qwen2_moe_small", first_k_dense_replace=1)
    assert flops.matmul_flops_per_token(first) == \
        2 * (16_384 + 24_576) + 2 * 3 * (16_384 + 25_152)
    # experts change no attention
    assert flops.attention_flops(a, 0, 10) == 4 * 4 * 4 * 16 * 55


MIXED = ["sliding_attention"] * 3 + ["full_attention"]


def test_window_attention_by_hand():
    a = data_arch("window_small")
    # window 48: positions 0-47 see 1..48 keys (1,176 in all), positions
    # 48-99 see 48 each (2,496); 4 layers x 4 heads x 16 wide
    assert flops.attention_flops(a, 0, 100) == 4 * 4 * 4 * 16 * 3_672
    assert flops.attention_flops(a, 60, 61) == 4 * 4 * 4 * 16 * 48
    assert flops.attention_flops(a, 0, 100) == \
        flops.attention_flops(a, 0, 30) + flops.attention_flops(a, 30, 100)
    # 3 sliding layers and one full one, which sees all 5,050
    mixed = data_arch("window_small", layer_types=MIXED)
    assert flops.attention_flops(mixed, 0, 100) == \
        4 * 4 * 16 * (3 * 3_672 + 5_050)
    # below the window every layer sees every position
    assert flops.attention_flops(a, 0, 48) == \
        flops.attention_flops(data_arch("window_small", sliding_window=None,
                                        layer_types=["full_attention"] * 4),
                              0, 48)


def test_window_decode_bytes_by_hand():
    a = data_arch("window_small")
    # 100 tokens: 6 flushed pages of 16; the window holds positions 52-99,
    # on pages 3-5. A page of one KV head: 16 x 16 int8 bytes and a
    # 16-wide f32 scale row, for K and V over 2 heads; query 4 x 16 bf16,
    # partials 4 x 18 f32
    page, rest = 2 * 2 * (16 * 16 + 16 * 4), 4 * 16 * 2 + 4 * 18 * 4
    assert flops.decode_attn_bytes(a, 100) == 4 * (3 * page + rest)
    mixed = data_arch("window_small", layer_types=MIXED)
    assert flops.decode_attn_bytes(mixed, 100) == \
        3 * (3 * page + rest) + (6 * page + rest)
    # a window that reaches back past the first page reads them all
    assert flops.decode_attn_bytes(a, 40) == 4 * (2 * page + rest)
