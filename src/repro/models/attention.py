"""GQA/MQA/MHA attention with a pluggable (quantized or fp) KV cache.

Three entry points per layer:
    train(...)    — full causal (optionally sliding-window) attention, no cache
    prefill(...)  — causal attention over the prompt; quantizes K/V into cache
    decode(...)   — one token vs the INT8 cache via the fused kernel (ops.py);
                    both cache backends resolve to ONE flat-grid kernel launch
                    for the whole batch with per-row dead-block DMA skipping

RoPE / M-RoPE applied to q,k before caching (rotated keys are what the paper
quantizes in serving systems: dequantized keys are directly dot-producted).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import kvcache as KV
from repro.core import paging as PG
from repro.core import quantization as Q
from repro.kernels import ops
from repro.models import flash
from repro.models.common import act_shard, apply_mrope, apply_rope, dense_init


def init(cfg: ModelConfig, key) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    ks = jax.random.split(key, 4)
    dt = cfg.activation_dtype
    p = {
        "wq": dense_init(ks[0], d, nq, dt),
        "wk": dense_init(ks[1], d, nkv, dt),
        "wv": dense_init(ks[2], d, nkv, dt),
        "wo": dense_init(ks[3], nq, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq,), dt)
        p["bk"] = jnp.zeros((nkv,), dt)
        p["bv"] = jnp.zeros((nkv,), dt)
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions):
    """x (B, S, d) -> q (B, H, S, hd), k/v (B, Hkv, S, hd), RoPE applied."""
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    # context parallelism: queries sequence-sharded over "model"; K/V gathered
    # (GQA keeps them small). Head counts (12/24/40/48) need not divide the
    # model axis this way — DESIGN.md §4. RoPE runs on the *sharded* tensors
    # and the gather moves the bf16 result (§Perf iteration 3: gathering
    # before RoPE made XLA hoist the gather into RoPE's f32 intermediates).
    if S > 1:
        q = act_shard(q, "batch", None, "seq_shard", None)
        k = act_shard(k, "batch", None, "seq_shard", None)
        v = act_shard(v, "batch", None, "seq_shard", None)
    if cfg.mrope_sections is not None:
        pos3 = positions if positions.ndim == 3 else jnp.broadcast_to(
            positions[:, None], (B, 3, S))
        q = apply_mrope(q, pos3, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, pos3, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if S > 1:
        k = _gather_seq(k)
        v = _gather_seq(v)
    return q, k, v


def _gather_seq(x):
    """Explicit context-parallel K/V gather inside shard_map: guarantees the
    collective moves the bf16 storage dtype (GSPMD hoisted it above f32
    intermediates), and its transpose is a bf16 psum_scatter for dK/dV
    (§Perf iteration 11). Falls back to a sharding constraint when the mesh
    or shapes don't apply."""
    from repro.parallel.shard import current_mesh, current_rules
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return act_shard(x, "batch", None, None, None)
    ntp = mesh.shape["model"]
    B, Hkv, S, D = x.shape
    rules = current_rules()
    if (ntp == 1 or S % ntp or rules.get("seq_shard") != ("model",)
            or "model" in rules.get("batch", ())):
        return act_shard(x, "batch", None, None, None)
    from jax.sharding import PartitionSpec as P
    fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    nf = 1
    for a in fsdp:
        nf *= mesh.shape[a]
    batch_ax = fsdp if fsdp and B % nf == 0 else ()
    in_spec = P(batch_ax if batch_ax else None, None, "model", None)
    out_spec = P(batch_ax if batch_ax else None, None, None, None)
    return jax.shard_map(
        lambda xl: jax.lax.all_gather(xl, "model", axis=2, tiled=True),
        mesh=mesh, in_specs=(in_spec,), out_specs=out_spec,
        check_vma=False)(x)


def _sdpa(q, k, v, cfg: ModelConfig, *, causal: bool, window: int | None,
          kv_offset: int = 0):
    """Blocked flash-style attention (see models/flash.py)."""
    return flash.flash_attention(q, k, v, causal, window, kv_offset)


def _merge_heads(p, out, cfg: ModelConfig, dtype):
    B, H, S, hd = out.shape
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd).astype(dtype)
    return act_shard(out @ p["wo"], "batch", "seq_shard", None)


# -- training ---------------------------------------------------------------

def train(p, x, cfg: ModelConfig, positions, *, local: bool = False,
          causal: bool = True):
    q, k, v = _project_qkv(p, x, cfg, positions)
    window = cfg.sliding_window if (cfg.sliding_window or local) else None
    out = _sdpa(q, k, v, cfg, causal=causal, window=window)
    return _merge_heads(p, out, cfg, x.dtype)


def cross_train(p, x, kv_src, cfg: ModelConfig):
    """Encoder-decoder cross attention (train/prefill): queries from x,
    keys/values from kv_src (encoder output). No RoPE, no mask."""
    B, S, _ = x.shape
    zeros_q = jnp.zeros((B, S), jnp.int32)
    zeros_k = jnp.zeros((B, kv_src.shape[1]), jnp.int32)
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = (kv_src @ p["wk"]).reshape(B, -1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = (kv_src @ p["wv"]).reshape(B, -1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    out = _sdpa(q, k, v, cfg, causal=False, window=None)
    return _merge_heads(p, out, cfg, x.dtype), (k, v)


def cross_decode(p, x, cfg: ModelConfig, cache: KV.QuantizedKVCache,
                 *, impl: str = "auto"):
    """Decode-time cross attention over the (per-channel) quantized encoder
    K/V — the paper's ideal case: the whole matrix is known upfront, scales
    computed once (Eq. 5), never updated."""
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    out = ops.quant_attention_decode(
        q[:, :, 0], cache.k_q, cache.k_s, cache.v_q, cache.v_s,
        cache.valid_len, impl=impl)
    return _merge_heads(p, out[:, :, None].astype(x.dtype), cfg, x.dtype)


# -- serving ------------------------------------------------------------------

def prefill(p, x, cfg: ModelConfig, positions, cache, *, local: bool = False,
            row_mask=None):
    """Prompt pass: causal attention + quantize K/V into the cache.

    `row_mask` (B,) bool is a paged-cache feature: only masked rows' caches
    are written, so the scheduler can prefill mid-stream admissions while
    other rows are mid-decode (DESIGN.md §6)."""
    q, k, v = _project_qkv(p, x, cfg, positions)
    window = cfg.sliding_window if (cfg.sliding_window or local) else None
    out = _sdpa(q, k, v, cfg, causal=True, window=window)
    if isinstance(cache, PG.PagedQuantizedKVCache):
        cache = cache.prefill(k.astype(jnp.float32), v.astype(jnp.float32),
                              row_mask=row_mask)
    else:
        if row_mask is not None:
            raise ValueError("row-masked prefill requires the paged cache "
                             "(the contiguous cache has one shared length)")
        cache = cache.prefill(k.astype(jnp.float32), v.astype(jnp.float32))
    return _merge_heads(p, out, cfg, x.dtype), cache


def prefill_chunk(p, x, cfg: ModelConfig, positions, cache, *, row_mask=None,
                  hist_blocks: int | None = None, valid=None,
                  use_fused: bool = True, impl: str = "auto",
                  oracle_hist_dtype=jnp.float32):
    """One prompt chunk under varlen chunked prefill (DESIGN.md §7).

    The chunk's queries attend causally within the chunk *plus* over the
    row's already-resident prefix read straight from its INT8 pages — so a
    chunk computes identically whether the pages before it were cache hits
    or were filled by this prompt's earlier chunks, which is what makes
    hit and miss prefills bitwise-equal. The chunk's K/V are then
    quantized into pages at the row's block cursor
    (`PagedQuantizedKVCache.prefill_at`).

    `x` (B, C, d) with C a multiple of page_size — the *dispatch width*;
    `valid` (B,) int32 is each row's true token count in the chunk
    (None = fully valid). Tokens past `valid` are dispatch padding, not
    prompt padding: causal masking already hides them from valid queries
    (they sit strictly *after* every valid position), their cache writes
    are masked off inside `prefill_at`, and their outputs are garbage the
    caller discards — so a final partial chunk needs no extra mask plumbing
    beyond the write path. `positions` (B, C) absolute positions —
    positions[:, 0] is each row's resident-history length (page-aligned by
    construction). `row_mask` (B,) bool as in `prefill`. `hist_blocks`
    (static) bounds the history read: only that many leading blocks are
    walked — the scheduler passes the dispatch group's cursor bound so a
    chunk never materializes max_len; None reads the full table, 0 skips
    history entirely (first chunk).

    `use_fused=True` (the default) routes attention through
    `ops.paged_attention_prefill` — the fused varlen flash-prefill that
    consumes INT8 pages directly (Pallas kernel on TPU, split flash-merge
    twin under XLA). `use_fused=False` keeps the original
    `dequantized_prefix` + `_chunk_attention` concat-softmax path, pinned
    as the parity oracle; `oracle_hist_dtype` picks the dtype the oracle
    dequantizes history into (bf16 halves the gathered buffer)."""
    if not isinstance(cache, PG.PagedQuantizedKVCache):
        raise ValueError("chunked prefill requires the paged cache")
    q, k, v = _project_qkv(p, x, cfg, positions)
    hist_len = positions[:, 0].astype(jnp.int32)            # (B,)
    nb = cache.max_blocks if hist_blocks is None else \
        min(hist_blocks, cache.max_blocks)
    if use_fused:
        out = ops.paged_attention_prefill(
            q, k, v, cache.pool.k_q, cache.pool.k_s, cache.pool.v_q,
            cache.pool.v_s, cache.page_table, hist_len, valid,
            hist_blocks=nb, kv_dtype=cache.pool.kv_dtype, impl=impl)
    else:
        hk = hv = None
        if nb:
            hk, hv = cache.dequantized_prefix(nb, oracle_hist_dtype)
        out = _chunk_attention(q, k, v, hk, hv, hist_len)
    cache = cache.prefill_at(k.astype(jnp.float32), v.astype(jnp.float32),
                             hist_len // cache.page_size, row_mask=row_mask,
                             valid=valid)
    return _merge_heads(p, out.astype(x.dtype), cfg, x.dtype), cache


def _chunk_attention(q, k, v, hk, hv, hist_len):
    """Exact fp attention of chunk queries over (resident history ‖ chunk).

    PARITY ORACLE for the fused prefill path: this is the retired serving
    hot path (one softmax over a gathered, dequantized history concat),
    kept deliberately naive so `ops.paged_attention_prefill` has an
    independent reference to match — tests compare the two, production
    traffic takes the fused path (`prefill_chunk(use_fused=True)`).

    q (B, H, C, hd); k/v (B, Hkv, C, hd) the chunk's own keys; hk/hv
    (B, Hkv, HT, hd) the dequantized history view (None when the dispatch
    has no resident history; any fp dtype — logits accumulate in f32);
    hist_len (B,) tokens of real history per row, <= HT. One softmax over
    the concatenated key axis — history masked by hist_len, chunk masked
    causally."""
    B, H, C, hd = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qg = q.reshape(B, Hkv, G, C, hd).astype(jnp.float32) * scale
    lc = jnp.einsum("bhgcd,bhtd->bhgct", qg, k.astype(jnp.float32))
    mc = (jnp.arange(C)[None, :] <= jnp.arange(C)[:, None])  # (C, C) causal
    lc = jnp.where(mc[None, None, None], lc, -1e30)
    if hk is None:
        logits, vs = lc, v.astype(jnp.float32)
    else:
        HT = hk.shape[2]
        lh = jnp.einsum("bhgcd,bhtd->bhgct", qg, hk.astype(jnp.float32))
        mh = (jnp.arange(HT)[None, :] < hist_len[:, None])   # (B, HT)
        lh = jnp.where(mh[:, None, None, None, :], lh, -1e30)
        logits = jnp.concatenate([lh, lc], axis=-1)          # (..., HT+C)
        vs = jnp.concatenate([hv.astype(jnp.float32),
                              v.astype(jnp.float32)], axis=2)
    m = jnp.max(logits, axis=-1, keepdims=True)
    pexp = jnp.exp(logits - m)
    pexp = jnp.where(logits <= -1e30 / 2, 0.0, pexp)
    l = jnp.sum(pexp, axis=-1, keepdims=True)
    out = jnp.einsum("bhgct,bhtd->bhgcd", pexp / jnp.maximum(l, 1e-30), vs)
    return out.reshape(B, H, C, hd)


def decode(p, x, cfg: ModelConfig, positions, cache,
           *, local: bool = False, impl: str = "auto", row_mask=None):
    """One-token step against the INT8 cache (fused dequant attention).

    `row_mask` (B,) bool freezes unmasked rows' paged caches (used by the
    scheduler so empty rows between requests never advance)."""
    q, k, v = _project_qkv(p, x, cfg, positions)          # S == 1
    if isinstance(cache, PG.PagedQuantizedKVCache):
        cache = cache.append(k.astype(jnp.float32), v.astype(jnp.float32),
                             row_mask=row_mask)
    else:
        if row_mask is not None:
            raise ValueError("row-masked decode requires the paged cache")
        cache = cache.append(k.astype(jnp.float32), v.astype(jnp.float32))
    B, H, _, hd = q.shape
    window = cfg.sliding_window if (cfg.sliding_window or local) else None
    if isinstance(cache, PG.PagedQuantizedKVCache):
        out = _decode_paged(q[:, :, 0], cache, impl=impl)
    elif cache.per_channel:
        out = ops.quant_attention_decode(
            q[:, :, 0], cache.k_q, cache.k_s, cache.v_q, cache.v_s,
            cache.length, window=window if cache.ring else None, impl=impl)
    else:
        # quantized prefix via the fused kernel + exact fp residual tail,
        # combined with a softmax merge (flash partials)
        out = _decode_blocked(q[:, :, 0], cache,
                              window=window if cache.ring else None,
                              impl=impl)
    out = out[:, :, None]                                  # (B, H, 1, hd)
    return _merge_heads(p, out.astype(x.dtype), cfg, x.dtype), cache


def _decode_blocked(q, cache: KV.QuantizedKVCache, *, window=None,
                    impl="auto"):
    """Merge fused-kernel attention over flushed blocks with exact attention
    over the bf16 residual tail."""
    B, H, hd = q.shape
    bs = cache.block_size
    # quantized slots hold the flushed prefix; the newest n_tail tokens live
    # unquantized in the residual buffer
    flushed = (cache.length // bs) * bs          # absolute flushed count
    n_tail = cache.length % bs
    # ages in the quantized buffer are relative to `flushed`; the window
    # budget left for it excludes the n_tail newest (residual) tokens
    win_q = None if window is None else jnp.maximum(window - n_tail, 0)
    # partials over the quantized prefix (fused kernel on TPU)
    o1, m1, l1 = ops.quant_attention_decode_partials(
        q, cache.k_q, cache.k_s, cache.v_q, cache.v_s, flushed,
        window=win_q, impl=impl)
    # partials over the residual tail (exact, fp)
    m2, l2, o2 = _decode_partials_fp(q, cache.resid_k, cache.resid_v, n_tail)
    return _merge_partials(o1, m1, l1, o2, m2, l2)


def _decode_paged(q, cache: PG.PagedQuantizedKVCache, *, impl="auto"):
    """Paged analogue of _decode_blocked: fused page-table kernel over each
    row's flushed pages + exact fp residual tail, merged per row (rows flush
    independently — lengths are per-row, and the kernel walks only each
    row's live pages, never the table tail)."""
    ps = cache.page_size
    flushed = (cache.length // ps) * ps          # (B,) flushed per row
    n_tail = cache.length % ps
    o1, m1, l1 = ops.paged_attention_decode_partials(
        q, cache.pool.k_q, cache.pool.k_s, cache.pool.v_q, cache.pool.v_s,
        cache.page_table, flushed, kv_dtype=cache.pool.kv_dtype,
        layer=cache.layer, impl=impl)
    m2, l2, o2 = _decode_partials_fp(q, cache.resid_k, cache.resid_v, n_tail)
    return _merge_partials(o1, m1, l1, o2, m2, l2)


def _merge_partials(o1, m1, l1, o2, m2, l2):
    """Softmax-merge two sets of flash partials into normalized outputs."""
    m = jnp.maximum(m1, m2)
    c1, c2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
    l = l1 * c1 + l2 * c2
    return (o1 * c1 + o2 * c2) / jnp.maximum(l, 1e-30)


def _decode_partials_fp(q, rk, rv, n_tail):
    B, H, hd = q.shape
    Hkv, bs = rk.shape[1], rk.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, hd).astype(jnp.float32)
    logits = jnp.einsum("bhgd,bhtd->bhgt", qg, rk.astype(jnp.float32))
    logits = logits / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    nt = jnp.broadcast_to(jnp.asarray(n_tail, jnp.int32), (B,))
    mask = jnp.arange(bs)[None, None, None, :] < nt[:, None, None, None]
    logits = jnp.where(mask, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    m = jnp.maximum(m, -1e30 / 2)
    pexp = jnp.where(mask, jnp.exp(logits - m), 0.0)
    l = jnp.sum(pexp, axis=-1, keepdims=True)
    o = jnp.einsum("bhgt,bhtd->bhgd", pexp, rv.astype(jnp.float32))
    return (m.reshape(B, H, 1), l.reshape(B, H, 1), o.reshape(B, H, hd))
