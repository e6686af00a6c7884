"""Useful operations and needed bytes of a dense decoder, from its shapes.

`arch` is the dict `spec.arch_of` makes from a configuration file: layers,
d_model, heads, kv_heads, head_dim, d_ff, vocab (the rows this chip
holds), page (tokens per KV page). Nothing here reads the program.

A multiply-add counts as two operations. Useful work only: a token's
matmuls, its attention over the positions before it and itself, and the
logits where a token is produced (once per prefilled request, once per
decoded token). Recomputed or padded work does not count.
"""
from __future__ import annotations


def matmul_flops_per_token(a: dict) -> float:
    """Projections and SwiGLU MLP of every layer, for one token."""
    d, hd = a["d_model"], a["head_dim"]
    qkvo = d * (a["heads"] + 2 * a["kv_heads"]) * hd + a["heads"] * hd * d
    mlp = 3 * d * a["d_ff"]
    return 2.0 * a["layers"] * (qkvo + mlp)


def attention_flops(a: dict, lo: int, hi: int) -> float:
    """Scores and weighted values of the tokens at positions [lo, hi),
    each attending causally over every position up to its own."""
    if hi <= lo:
        return 0.0
    keys = (hi * (hi + 1) - lo * (lo + 1)) / 2          # sum of (p + 1)
    return 4.0 * a["layers"] * a["heads"] * a["head_dim"] * keys


def logits_flops(a: dict) -> float:
    return 2.0 * a["d_model"] * a["vocab"]


def prefill_flops(a: dict, lo: int, hi: int) -> float:
    """Prefill of prompt positions [lo, hi) with the resident history
    [0, lo) already cached, and the logits of the last position."""
    if hi <= lo:
        return 0.0
    return ((hi - lo) * matmul_flops_per_token(a)
            + attention_flops(a, lo, hi) + logits_flops(a))


def decode_flops(a: dict, pos: int) -> float:
    """One decode step of a row whose new token sits at position `pos`."""
    return (matmul_flops_per_token(a) + attention_flops(a, pos, pos + 1)
            + logits_flops(a))


def kv_dtype_bytes(kv_dtype: str) -> float:
    return {"int8": 1.0, "fp8_e4m3": 1.0, "int4": 0.5}[kv_dtype]


def decode_attn_bytes(a: dict, length: int, kv_dtype: str = "int8") -> float:
    """Bytes the paged decode kernel needs for one row at one step, over
    all layers: the row's flushed pages of K and V (a row of `length`
    tokens has length // page of them; the partial page is the fp residual,
    which the kernel does not read) with one f32 scale row per page, head
    and tensor; the bf16 query; and the f32 output partials (o, m, l)."""
    pages = length // a["page"]
    hd, hkv, h = a["head_dim"], a["kv_heads"], a["heads"]
    page_bytes = 2 * hkv * (a["page"] * hd * kv_dtype_bytes(kv_dtype)
                            + hd * 4)
    q_bytes = h * hd * 2
    out_bytes = h * (hd + 2) * 4
    return a["layers"] * (pages * page_bytes + q_bytes + out_bytes)

