"""Useful operations and needed bytes of a decoder, from its shapes.

`arch` is the dict `spec.arch_of` makes from a configuration file: layers,
d_model, heads, kv_heads, head_dim, d_ff, vocab (the rows this chip
holds), page (tokens per KV page), and per layer its kind in
`layer_kinds`: ("full" or "sliding" attention, "dense" or "moe" FFN),
with the experts' shapes (experts_total, experts_held, top_k, moe_d_ff,
shared_d_ff, shared_gate) and the attention `window`. Nothing here reads
the program.

A multiply-add counts as two operations. Useful work only: a token's
matmuls, its attention over the positions it may see (every position up
to its own; in a sliding layer the last `window` of them, its own
included), and the logits where a token is produced (once per prefilled
request, once per decoded token). Recomputed or padded work does not
count.

An expert layer counts its router over every expert (experts_total), its
shared experts, and the routed experts' expected share: a token picks
top_k of experts_total, and under a router drawn at random from the seed
each pick falls on an expert held here with chance
experts_held / experts_total, so top_k * experts_held / experts_total
experts' SwiGLU per token. Tokens routed to experts held elsewhere cost
this chip nothing.
"""
from __future__ import annotations


def _count(a: dict, slot: int, kind: str) -> int:
    return sum(1 for k in a["layer_kinds"] if k[slot] == kind)


def matmul_flops_per_token(a: dict) -> float:
    """Projections and SwiGLU MLP (or expert layer) of every layer, for
    one token."""
    d, hd = a["d_model"], a["head_dim"]
    qkvo = d * (a["heads"] + 2 * a["kv_heads"]) * hd + a["heads"] * hd * d
    mlp = 3 * d * a["d_ff"]
    n_moe = _count(a, 1, "moe")
    flops = 2.0 * (a["layers"] - n_moe) * (qkvo + mlp)
    if n_moe:
        moe = (d * a["experts_total"] + 3 * d * a["shared_d_ff"]
               + d * a["shared_gate"]
               + 3 * d * a["moe_d_ff"] * a["top_k"] * a["experts_held"]
               / a["experts_total"])
        flops += 2.0 * n_moe * (qkvo + moe)
    return flops


def _window_keys(lo: int, hi: int, window: int) -> float:
    """Sum over positions p in [lo, hi) of min(p + 1, window)."""
    m = max(lo, min(hi, window))
    return (m * (m + 1) - lo * (lo + 1)) / 2 + (hi - m) * window


def attention_flops(a: dict, lo: int, hi: int) -> float:
    """Scores and weighted values of the tokens at positions [lo, hi),
    each attending causally over every position up to its own, or over
    the last `window` of them in a sliding layer."""
    if hi <= lo:
        return 0.0
    keys = (hi * (hi + 1) - lo * (lo + 1)) / 2          # sum of (p + 1)
    n_sliding = _count(a, 0, "sliding")
    flops = 4.0 * (a["layers"] - n_sliding) * a["heads"] * a["head_dim"] \
        * keys
    if n_sliding:
        flops += 4.0 * n_sliding * a["heads"] * a["head_dim"] \
            * _window_keys(lo, hi, a["window"])
    return flops


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
    and tensor; the bf16 query; and the f32 output partials (o, m, l). A
    sliding layer needs only the flushed pages that hold one of the last
    `window` positions, those from length - window on."""
    pages = length // a["page"]
    hd, hkv, h = a["head_dim"], a["kv_heads"], a["heads"]
    page_bytes = 2 * hkv * (a["page"] * hd * kv_dtype_bytes(kv_dtype)
                            + hd * 4)
    q_bytes = h * hd * 2
    out_bytes = h * (hd + 2) * 4
    n_sliding = _count(a, 0, "sliding")
    need = (a["layers"] - n_sliding) * (pages * page_bytes + q_bytes
                                        + out_bytes)
    if n_sliding:
        first = max(0, length - a["window"]) // a["page"]
        need += n_sliding * (max(0, pages - first) * page_bytes + q_bytes
                             + out_bytes)
    return need
