"""Public jit'd kernel API with backend dispatch.

The model and serving layers call through here with ``impl="auto"``, and
`resolve_impl` is the one place that picks the implementation:

    * "pallas"  on a TPU backend — the compiled Mosaic kernels;
    * "xla"     on any other backend — the pure-jnp twins, kept as CPU
      oracles (what the tests and the CPU dry run lower).

``impl="pallas_interpret"`` runs the Pallas kernel bodies in the
interpreter; tests and benchmarks ask for it by name to exercise the
kernels on the CPU. It is refused on a TPU, so a run on the chip never
executes a kernel in interpret mode.
"""
from __future__ import annotations

from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import quant_attention as _qa
from repro.kernels import quant_prefill as _qp
from repro.kernels import quantize as _quant
from repro.kernels import ref as _ref

Impl = Literal["auto", "xla", "pallas", "pallas_interpret"]


def _unpack_int4_axis(q: jax.Array, axis: int) -> jax.Array:
    """Nibble-unpack a packed-int4 array along `axis` (byte rows -> 2 token
    rows, low nibble first), sign-extending via arithmetic shifts. Pure jnp
    twin of the in-kernel unpack in quant_attention.page_dequant."""
    lo = (q << 4) >> 4
    hi = q >> 4
    axis = axis % q.ndim
    st = jnp.stack([lo, hi], axis=axis + 1)
    shape = list(q.shape)
    shape[axis] *= 2
    return st.reshape(shape)


def resolve_impl(impl: Impl = "auto") -> str:
    """"auto" -> "pallas" on a TPU backend, "xla" elsewhere. An explicit
    "pallas_interpret" on a TPU backend raises."""
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        return "pallas" if on_tpu else "xla"
    if impl not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    if impl == "pallas_interpret" and on_tpu:
        raise ValueError("impl='pallas_interpret' on a TPU backend: the "
                         "chip runs the compiled kernels only")
    return impl


# -- quantization ------------------------------------------------------------

def quantize_per_channel(x: jax.Array, *, impl: Impl = "auto"):
    """(T, D) -> (int8 (T, D), f32 (D,)); paper Eq. 5-7."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return _ref.quantize_fused_ref(x)
    return _quant.quantize_per_channel(x, interpret=impl == "pallas_interpret")


def quantize_blocked(x: jax.Array, block_size: int = 256, *, impl: Impl = "auto"):
    """(T, D) -> (int8 (T, D), f32 (T//B, D)); fused single-pass."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return _ref.quantize_blocked_ref(x, block_size)
    return _quant.quantize_blocked(x, block_size,
                                   interpret=impl == "pallas_interpret")


def dequantize(x_q: jax.Array, scales: jax.Array, *, out_dtype=jnp.float32,
               impl: Impl = "auto"):
    impl = resolve_impl(impl)
    if impl == "xla":
        return _ref.dequantize_ref(x_q, scales if scales.ndim == 2 else scales[None],
                                   dtype=out_dtype)
    return _quant.dequantize(x_q, scales, out_dtype=out_dtype,
                             interpret=impl == "pallas_interpret")


# -- fused attention ---------------------------------------------------------

def quant_attention_decode(q, k_q, k_s, v_q, v_s, length, *, window=None,
                           impl: Impl = "auto"):
    """One-token decode attention over the INT8 cache.

    q (B, H, D); k_q/v_q (B, Hkv, T, D) int8; k_s/v_s (B, Hkv, nb, D) f32;
    length () or (B,) — absolute tokens written (ring caches: may exceed T);
    window — sliding-window size for ring caches (None = full).
    The Pallas path is ONE flat-grid launch for the whole batch with
    dead-block DMA skipping past each row's length (DESIGN.md §2).
    Returns (B, H, D) f32.
    """
    impl = resolve_impl(impl)
    if impl == "xla":
        o, m, l = _decode_partials_xla(q, k_q, k_s, v_q, v_s, length, window)
        return o / jnp.maximum(l, 1e-30)
    return _qa.quant_attention_decode(q, k_q, k_s, v_q, v_s, length,
                                      window=window,
                                      interpret=impl == "pallas_interpret")


def quant_attention_decode_partials(q, k_q, k_s, v_q, v_s, length, *,
                                    window=None, impl: Impl = "auto"):
    """Flash partials (o_unnormalized, m, l) over the INT8 cache — used to
    merge with the exact fp residual tail in blocked-scale decode. One
    pallas_call over a (B, Hkv, NT) grid; no Python/vmap fan-out."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return _decode_partials_xla(q, k_q, k_s, v_q, v_s, length, window)
    return _qa.quant_attention_decode_partials(
        q, k_q, k_s, v_q, v_s, length, window=window,
        interpret=impl == "pallas_interpret")


# -- paged attention ---------------------------------------------------------

def paged_attention_decode_partials(q, pool_kq, pool_ks, pool_vq, pool_vs,
                                    page_table, lengths, *,
                                    kv_dtype: str = "int8",
                                    impl: Impl = "auto"):
    """Flash partials over a quantized page pool through per-row page tables.

    q (B, H, D); pool_kq/vq (P, Hkv, ps_packed, D) in ``kv_dtype`` storage
    (int8 / fp8_e4m3 / int4-packed, where int4 packs two tokens per byte so
    ps_packed = ps // 2 — DESIGN.md §9); pool_ks/vs (P, Hkv, 1, D) f32;
    page_table (B, NT) int32; lengths (B,) int32 — per-row valid tokens
    (pass the flushed prefix count; the residual tail merges separately).
    Lengths also bound each row's page walk: the kernel never streams pages
    (or reads table entries) past ceil(length / ps).
    Returns (o_unnormalized (B, H, D), m (B, H, 1), l (B, H, 1)).
    """
    impl = resolve_impl(impl)
    if impl == "xla":
        from repro.core.paging import gather_pages
        k_q, k_s, v_q, v_s = gather_pages(
            pool_kq, pool_ks, pool_vq, pool_vs, page_table)
        if kv_dtype == "int4":
            # gathered packed bytes concatenate page-contiguously, so one
            # unpack of the token axis restores logical token order
            k_q = _unpack_int4_axis(k_q, -2)
            v_q = _unpack_int4_axis(v_q, -2)
        return _decode_partials_xla(q, k_q, k_s, v_q, v_s, lengths, None)
    return _qa.paged_attention_decode_partials(
        q, pool_kq, pool_ks, pool_vq, pool_vs, page_table, lengths,
        kv_dtype=kv_dtype, interpret=impl == "pallas_interpret")


def paged_attention_decode(q, pool_kq, pool_ks, pool_vq, pool_vs, page_table,
                           lengths, *, kv_dtype: str = "int8",
                           impl: Impl = "auto"):
    """Normalized paged decode attention: (B, H, D) f32."""
    o, m, l = paged_attention_decode_partials(
        q, pool_kq, pool_ks, pool_vq, pool_vs, page_table, lengths,
        kv_dtype=kv_dtype, impl=impl)
    return o / jnp.maximum(l, 1e-30)


def paged_attention_prefill(q, k, v, pool_kq, pool_ks, pool_vq, pool_vs,
                            page_table, hist_len, valid=None, *,
                            hist_blocks: int, kv_dtype: str = "int8",
                            impl: Impl = "auto"):
    """Fused varlen chunk-prefill attention over the quantized page pool.

    q (B, H, C, D) chunk queries; k/v (B, Hkv, C, D) the chunk's own fp
    K/V; pool_kq/vq (P, Hkv, ps_packed, D) in ``kv_dtype`` storage
    (int8 / fp8_e4m3 / int4-packed); pool_ks/vs (P, Hkv, 1, D) f32;
    page_table (B, NT) int32; hist_len (B,) int32 per-row resident history
    (page-aligned); valid (B,) int32 per-row true chunk tokens (None = C).
    `hist_blocks` (static) bounds the history walk to the dispatch group's
    pow2 cursor bound. The Pallas path is ONE pallas_call over a
    (B, Hkv, hist_blocks + 1) grid — INT8 pages stream through the
    page-table index_map with dead-block DMA skipping, dequant fused into
    the online softmax, no fp32 history tensor in HBM (DESIGN.md §7). The
    XLA path is its structural twin: split history/chunk partials with a
    flash merge over a bounded `page_table[:, :hist_blocks]` gather
    (leaner than the retired concat-softmax oracle, which survives as
    `models/attention._chunk_attention` for parity tests).
    Returns normalized (B, H, C, D) f32; outputs past `valid` are garbage
    the caller discards."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return _prefill_fused_xla(q, k, v, pool_kq, pool_ks, pool_vq,
                                  pool_vs, page_table, hist_len, valid,
                                  hist_blocks, kv_dtype)
    return _qp.paged_attention_prefill(
        q, k, v, pool_kq, pool_ks, pool_vq, pool_vs, page_table, hist_len,
        valid, hist_blocks=hist_blocks, kv_dtype=kv_dtype,
        interpret=impl == "pallas_interpret")


def _hist_partials(qg, pool_kq, pool_ks, pool_vq, pool_vs, kv_dtype, tbl,
                   hist_len):
    """Flash partials (o, s, m) of chunk queries over `tbl`'s history pages.

    Pages keep their native (nb, Hkv, ps, D) layout — dequant multiplies
    the per-page scale row in place and the einsums contract it directly
    (no (B, H, T, D) transpose/reshape). Masking is an additive bias folded
    into the logits BEFORE exp, and there is no post-exp mask multiply: a
    masked position's exp(l - m) underflows to exactly 0 whenever the row
    has any live position (m finite), and a fully-masked row (cursor 0
    inside a deep-history dispatch) keeps m == -1e30 so the caller's merge
    weight exp(m - mx) zeroes its entire contribution."""
    kq, vq = pool_kq[tbl], pool_vq[tbl]          # (B, nb, Hkv, ps_eff, D)
    if kv_dtype == "int4":
        kq = _unpack_int4_axis(kq, 3)                  # token axis is 3 here
        vq = _unpack_int4_axis(vq, 3)
    kh = kq.astype(jnp.float32) * \
        pool_ks[tbl].astype(jnp.float32)               # (B, nb, Hkv, ps, D)
    vh = vq.astype(jnp.float32) * pool_vs[tbl].astype(jnp.float32)
    nb, ps = kh.shape[1], kh.shape[3]
    lh = jnp.einsum("bhgcd,bnhpd->bhgcnp", qg, kh)
    pos = (jnp.arange(nb, dtype=jnp.int32)[:, None] * ps +
           jnp.arange(ps, dtype=jnp.int32)[None])             # (nb, ps)
    mh = pos[None] < jnp.asarray(hist_len, jnp.int32)[:, None, None]
    bias = jnp.where(mh, 0.0, _NEG_INF)                       # (B, nb, ps)
    lh = lh + bias[:, None, None, None]
    mxh = jnp.max(lh, axis=(-2, -1), keepdims=True)
    ph = jnp.exp(lh - mxh)
    sh = jnp.sum(ph, axis=(-2, -1))[..., None]
    oh = jnp.einsum("bhgcnp,bnhpd->bhgcd", ph, vh)
    return oh, sh, mxh[..., 0]                                # (..., c, 1)


def _prefill_fused_xla(q, k, v, pool_kq, pool_ks, pool_vq, pool_vs,
                       page_table, hist_len, valid, hist_blocks,
                       kv_dtype="int8"):
    """XLA twin of the fused prefill kernel: f32 split history/chunk flash
    partials merged once — no (HT+C)-wide concat softmax, no transposes of
    the gathered pages, and the Pallas kernel's dead-block DMA skip
    mirrored structurally: a `lax.switch` ladder sizes the history
    computation to the batch's deepest live page (4-block rungs), so the
    pow2 dispatch bound's over-approximation costs a branch select instead
    of dense masked FLOPs over pages nobody occupies."""
    B, H, C, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    scale = jax.lax.rsqrt(jnp.asarray(D, jnp.float32))
    qg = q.reshape(B, Hkv, G, C, D).astype(jnp.float32) * scale
    # chunk partials: causal + per-row valid masking
    lc = jnp.einsum("bhgcd,bhtd->bhgct", qg, k.astype(jnp.float32))
    kpos = jnp.arange(C, dtype=jnp.int32)
    mc = kpos[None, :] <= kpos[:, None]                       # (C, C) causal
    if valid is not None:
        mc = mc[None] & (kpos[None, None, :] <
                         jnp.asarray(valid, jnp.int32)[:, None, None])
        mc = mc[:, None, None]                                # (B,1,1,C,C)
    else:
        mc = mc[None, None, None]
    lc = jnp.where(mc, lc, _NEG_INF)
    mxc = jnp.max(lc, axis=-1, keepdims=True)
    # exp runs on the MASKED logits, so masked entries underflow to exactly
    # 0 (every real query sees at least itself: mxc is finite); a valid==0
    # padding row degenerates to finite garbage the caller discards
    pc = jnp.exp(lc - mxc)
    sc = jnp.sum(pc, axis=-1, keepdims=True)
    oc = jnp.einsum("bhgct,bhtd->bhgcd", pc, v.astype(jnp.float32))
    if hist_blocks == 0:
        out = oc / jnp.maximum(sc, 1e-30)
        return out.reshape(B, H, C, D)
    ps = pool_kq.shape[2] * (2 if kv_dtype == "int4" else 1)  # logical tokens
    hist_len = jnp.asarray(hist_len, jnp.int32)
    # dead-block skip, XLA edition: pick the smallest ladder rung covering
    # ceil(max(hist_len) / ps) and run the history partials at that static
    # width. Rungs every 4 blocks bound the trace count while matching the
    # chunk cursor stride exactly (chunks advance whole pages, C = 4 pages
    # in the serving default), so uniform-cursor dispatches — the steady
    # state — run zero dead blocks.
    rungs = sorted(set(range(4, hist_blocks, 4)) | {hist_blocks})
    hist = partial(_hist_partials, qg, pool_kq, pool_ks, pool_vq, pool_vs,
                   kv_dtype)
    if len(rungs) == 1:
        oh, sh, mxh = hist(page_table[:, :hist_blocks], hist_len)
    else:
        live = jnp.max(-(-jnp.minimum(hist_len, hist_blocks * ps) // ps))
        idx = jnp.searchsorted(jnp.asarray(rungs, jnp.int32), live)
        oh, sh, mxh = jax.lax.switch(
            idx, [partial(hist, page_table[:, :r]) for r in rungs],
            hist_len)
    # flash merge of the two partial sets (history may be fully masked for
    # rows at cursor 0: its mx stays _NEG_INF and its weight underflows to 0)
    mx = jnp.maximum(mxc, mxh)
    ch, cc = jnp.exp(mxh - mx), jnp.exp(mxc - mx)
    l = sh * ch + sc * cc
    out = (oh * ch + oc * cc) / jnp.maximum(l, 1e-30)
    return out.reshape(B, H, C, D)


_NEG_INF = -1e30


def _decode_partials_xla(q, k_q, k_s, v_q, v_s, length, window=None):
    B, H, D = q.shape
    _, Hkv, T, _ = k_q.shape
    G = H // Hkv
    nb = k_s.shape[2]
    # dequantize to bf16: halves the dequant-buffer traffic vs f32 (the
    # Pallas kernel on TPU never materializes it at all — §Perf iteration 9)
    k = _deq4(k_q, k_s, nb, jnp.bfloat16)
    v = _deq4(v_q, v_s, nb, jnp.bfloat16)
    qg = q.reshape(B, Hkv, G, D).astype(jnp.bfloat16)
    logits = jnp.einsum("bhgd,bhtd->bhgt", qg, k,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.asarray(D, jnp.float32))
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32),
                               (B,))[:, None, None, None]
    slots = jnp.arange(T)[None, None, None, :]
    mask = slots < jnp.minimum(lengths, T)
    if window is not None:
        # ring-slot age: slot s last held the token (length-1-s) mod T ago
        w = jnp.broadcast_to(jnp.asarray(window, jnp.int32),
                             (B,))[:, None, None, None]
        age = jnp.remainder(lengths - 1 - slots, T)
        mask &= age < w
    logits = jnp.where(mask, logits, -1e30)
    m = jnp.maximum(jnp.max(logits, axis=-1, keepdims=True), -1e30)
    p = jnp.where(mask, jnp.exp(logits - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgt,bhtd->bhgd", p.astype(jnp.bfloat16), v,
                   preferred_element_type=jnp.float32)
    rs = lambda a: a.reshape(B, H, a.shape[-1])
    return rs(o), rs(m), rs(l)


def _deq4(x_q, s, nb, dtype=jnp.float32):
    B, Hkv, T, D = x_q.shape
    xb = x_q.reshape(B, Hkv, nb, T // nb, D).astype(jnp.float32)
    return (xb * s[:, :, :, None]).astype(dtype).reshape(B, Hkv, T, D)
