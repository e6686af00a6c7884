"""Fused flash-decode attention directly over the INT8 KV cache.

This is the beyond-paper kernel (DESIGN.md §2): the paper stops at standalone
quantize/dequantize kernels, but on TPU a standalone dequantize would write
the bf16 cache back to HBM and re-read it for attention — negating the
bandwidth win. Here the int8 K/V tiles are dequantized *in VMEM* inside the
attention kernel, so HBM attention traffic is 1 byte/element instead of 2
(bf16) or 4 (f32): the paper's "reduce memory transactions" conclusion,
realized at the attention level.

Flat-grid launch (DESIGN.md §2): ONE `pallas_call` serves the whole batch —
the grid is (B, Hkv, NT) with the token-block axis innermost, and per-row
lengths/windows ride in SMEM via `PrefetchScalarGridSpec`. The former
per-(batch × kv-head) `vmap` fan-out survives only as the benchmark baseline
(`quant_attention_decode_partials_vmap`).

Length-aware DMA skipping: grid steps beyond a row's live blocks have their
`index_map` *clamped to the last live block*. The pipeline only issues a DMA
when a block's index changes between steps, so the clamped steps re-use the
tile already resident in VMEM — masked steps cost zero new HBM traffic — and
`pl.when` skips their compute. (Under `vmap`, the seed path's `pl.when`
degraded to a select that still computed every block; the flat grid keeps it
a real branch.)

Per-(row, head) online-softmax state (m, l, acc) lives in VMEM scratch
across the token-block steps; outputs are unnormalized flash partials
(acc, m, l) so callers can merge with the fp residual tail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Length-aware grid helpers
# ---------------------------------------------------------------------------

def _num_live_blocks(length, block: int, max_len: int):
    """Blocks holding live cache slots: ceil(min(length, max_len) / block)."""
    return (jnp.minimum(length, max_len) + block - 1) // block


def _dead_clamp(t, length, block: int, max_len: int):
    """Clamp grid step `t` to the row's last live block.

    Steps past a row's length revisit that block: the index_map returns the
    same block index as the previous step, the pipeline elides the DMA (the
    tile is already resident in VMEM), and `pl.when` skips the compute — a
    fully-masked step streams nothing from HBM.
    """
    return jnp.minimum(
        t, jnp.maximum(_num_live_blocks(length, block, max_len) - 1, 0))


def live_blocks(lengths, block: int, max_len: int):
    """Per-row count of token blocks the clamped index_map actually streams
    (host-side numpy mirror of `_num_live_blocks`; the clamp floor means
    even a length-0 row revisits one block)."""
    import numpy as np
    lens = np.minimum(np.asarray(lengths, np.int64), max_len)
    return np.maximum(-(-lens // block), 1)


# ---------------------------------------------------------------------------
# Shared online-softmax tile update
# ---------------------------------------------------------------------------

def _attn_update(q, k, v, pos0, n_slots, length, window, max_len,
                 m_scr, l_scr, acc_scr):
    """Accumulate one dequantized (bt, D) K/V tile into the flash state."""
    d = q.shape[-1]
    logits = jax.lax.dot_general(                       # (G, bt)
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jax.lax.rsqrt(
            jnp.asarray(d, jnp.float32))
    pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    # ring-slot age: slot s last held token (length-1-s) mod max_len ago
    age = jnp.remainder(length - 1 - pos, max_len)
    mask = (pos < n_slots) & (age < window)
    logits = jnp.where(mask, logits, _NEG_INF)
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
    alpha = jnp.exp(m_prev - m_new)
    m_scr[...] = m_new
    l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Flat-grid contiguous kernel: one launch for the whole batch
# ---------------------------------------------------------------------------

def _flat_decode_kernel(len_ref, win_ref, q_ref, kq_ref, ks_ref, vq_ref,
                        vs_ref, o_ref, m_ref, l_ref,
                        m_scr, l_scr, acc_scr, *, block_t: int, max_len: int,
                        per_block: bool):
    b = pl.program_id(0)
    t = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]       # absolute tokens written (ring: may be > max_len)
    window = win_ref[b]       # sliding window (== max_len when unwindowed)
    n_slots = jnp.minimum(length, max_len)

    @pl.when(t * block_t < n_slots)      # dead block: DMA clamped + no compute
    def _step():
        # the row's scale rows sit whole in VMEM: pick this block's row
        # (per-block) or the single per-channel row
        row = pl.ds(t, 1) if per_block else pl.ds(0, 1)
        # dequantize K/V tiles in VMEM (int8 -> f32 multiply by scale row)
        k = (kq_ref[0, 0].astype(jnp.float32) *
             ks_ref[0, 0, row, :].astype(jnp.float32))
        v = (vq_ref[0, 0].astype(jnp.float32) *
             vs_ref[0, 0, row, :].astype(jnp.float32))
        _attn_update(q_ref[0, 0].astype(jnp.float32), k, v, t * block_t,
                     n_slots, length, window, max_len, m_scr, l_scr, acc_scr)

    @pl.when(t == nt - 1)
    def _finish():
        # emit flash partials: unnormalized acc + (m, l) so callers can merge
        # with the fp residual tail (blocked mode) or normalize directly
        o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


@functools.partial(jax.jit,
                   static_argnames=("block_t", "skip_dead", "interpret"))
def _decode_flat(qg, k_q, k_s, v_q, v_s, lengths, windows, *, block_t: int,
                 skip_dead: bool, interpret: bool):
    """qg (B, Hkv, Gp, D); k_q/v_q (B, Hkv, T, D) int8; k_s/v_s
    (B, Hkv, nb, D) f32; lengths/windows (B,) int32.
    Returns (o (B, Hkv, Gp, D), m (B, Hkv, Gp, 1), l (B, Hkv, Gp, 1))."""
    B, Hkv, Gp, D = qg.shape
    T = k_q.shape[2]
    nb = k_s.shape[2]
    if T % block_t:
        raise ValueError(f"block_t={block_t} must divide T={T} (a floored "
                         f"grid would silently drop the cache tail)")
    nt = T // block_t
    if skip_dead:
        t_idx = lambda t, ln: _dead_clamp(t, ln, block_t, T)
    else:
        t_idx = lambda t, ln: t
    # scale rows: per-block (nb == nt) or per-channel (nb == 1). A row's
    # whole (nb, D) scale slab is one block — its last two dims are the
    # array's, so Mosaic takes it at any nb — fetched once per (row, head);
    # the kernel picks the block's row in VMEM.
    if nb not in (1, nt):
        raise ValueError(f"scale rows {nb} incompatible with {nt} token blocks")

    kernel = functools.partial(_flat_decode_kernel, block_t=block_t, max_len=T,
                               per_block=nb > 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,               # per-row lengths + windows (SMEM)
        grid=(B, Hkv, nt),                   # token blocks innermost
        in_specs=[
            pl.BlockSpec((1, 1, Gp, D), lambda b, h, t, ln, wn: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_t, D),
                         lambda b, h, t, ln, wn: (b, h, t_idx(t, ln[b]), 0)),
            pl.BlockSpec((1, 1, nb, D), lambda b, h, t, ln, wn: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_t, D),
                         lambda b, h, t, ln, wn: (b, h, t_idx(t, ln[b]), 0)),
            pl.BlockSpec((1, 1, nb, D), lambda b, h, t, ln, wn: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Gp, D), lambda b, h, t, ln, wn: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Gp, 1), lambda b, h, t, ln, wn: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Gp, 1), lambda b, h, t, ln, wn: (b, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Gp, 1), jnp.float32),
            pltpu.VMEM((Gp, 1), jnp.float32),
            pltpu.VMEM((Gp, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, Gp, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, Gp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, Gp, 1), jnp.float32)],
        interpret=interpret,
    )(lengths.astype(jnp.int32), windows.astype(jnp.int32),
      qg, k_q, k_s, v_q, v_s)


def _group_queries(q, Hkv):
    """(B, H, D) -> (B, Hkv, Gp, D) with the GQA group padded to the
    8-sublane minimum; returns (qg, G)."""
    B, H, D = q.shape
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    Gp = max(8, G)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    return qg, G


def _default_block_t(T, nb):
    return T // nb if nb > 1 else (256 if T % 256 == 0 else T)


def quant_attention_decode_partials(q, k_q, k_s, v_q, v_s, length, *,
                                    interpret: bool, window=None,
                                    block_t: int | None = None,
                                    skip_dead: bool = True):
    """Batched fused decode partials: q (B, H, D) over int8 cache
    (B, Hkv, T, D) — ONE pallas_call over a (B, Hkv, NT) grid (no Python or
    vmap fan-out). `length` () or (B,): per-row valid tokens; blocks beyond a
    row's length are skipped at the DMA level (`skip_dead`). `window` masks
    ring slots by token age (sliding-window caches); None = no window.
    Returns (o_unnormalized (B, H, D), m (B, H, 1), l (B, H, 1))."""
    B, H, D = q.shape
    _, Hkv, T, _ = k_q.shape
    qg, G = _group_queries(q, Hkv)
    if block_t is None:
        block_t = _default_block_t(T, k_s.shape[2])
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    if window is None:
        window = T
    windows = jnp.broadcast_to(jnp.asarray(window, jnp.int32), (B,))
    o, m, l = _decode_flat(qg, k_q, k_s, v_q, v_s, lengths, windows,
                           block_t=block_t, skip_dead=skip_dead,
                           interpret=interpret)
    trim = lambda a: a[:, :, :G].reshape(B, H, a.shape[-1])
    return trim(o), trim(m), trim(l)


def quant_attention_decode(q, k_q, k_s, v_q, v_s, length, *, interpret: bool,
                           window=None, block_t: int | None = None,
                           skip_dead: bool = True):
    """Normalized fused decode attention: (B, H, D) f32."""
    o, m, l = quant_attention_decode_partials(
        q, k_q, k_s, v_q, v_s, length, window=window, block_t=block_t,
        skip_dead=skip_dead, interpret=interpret)
    return o / jnp.maximum(l, 1e-30)


# ---------------------------------------------------------------------------
# Seed baseline: per-(batch, kv-head) vmap fan-out. Kept ONLY as the
# benchmark reference (benchmarks/e2e_decode.py) — under vmap the pl.when
# compute-skip lowers to a select that evaluates both branches, so masked
# blocks still burn compute and DMA; the flat grid above is the production
# path.
# ---------------------------------------------------------------------------

def _decode_kernel(len_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                   o_ref, m_ref, l_ref,
                   m_scr, l_scr, acc_scr, *, block_t: int, max_len: int):
    t = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[0]
    window = len_ref[1]
    n_slots = jnp.minimum(length, max_len)

    @pl.when(t * block_t < n_slots)         # compute-skip only (no DMA skip)
    def _step():
        k = kq_ref[...].astype(jnp.float32) * ks_ref[...].astype(jnp.float32)
        v = vq_ref[...].astype(jnp.float32) * vs_ref[...].astype(jnp.float32)
        _attn_update(q_ref[...].astype(jnp.float32), k, v, t * block_t,
                     n_slots, length, window, max_len, m_scr, l_scr, acc_scr)

    @pl.when(t == nt - 1)
    def _finish():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)
        m_ref[...] = m_scr[...]
        l_ref[...] = l_scr[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def _decode_single(q, k_q, k_s, v_q, v_s, length, window, *, block_t: int,
                   interpret: bool):
    G, D = q.shape
    T = k_q.shape[0]
    nb = k_s.shape[0]
    if T % block_t:
        raise ValueError(f"block_t={block_t} must divide T={T}")
    nt = T // block_t
    if nb == 1:
        s_map = lambda t: (0, 0)
    elif nb == nt:
        s_map = lambda t: (t, 0)
    else:
        raise ValueError(f"scale rows {nb} incompatible with {nt} token blocks")

    kernel = functools.partial(_decode_kernel, block_t=block_t, max_len=T)
    return pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # [length, window]
            pl.BlockSpec((G, D), lambda t: (0, 0)),          # q resident
            pl.BlockSpec((block_t, D), lambda t: (t, 0)),    # K tile
            pl.BlockSpec((1, D), s_map),                     # K scale row
            pl.BlockSpec((block_t, D), lambda t: (t, 0)),    # V tile
            pl.BlockSpec((1, D), s_map),                     # V scale row
        ],
        out_specs=[pl.BlockSpec((G, D), lambda t: (0, 0)),
                   pl.BlockSpec((G, 1), lambda t: (0, 0)),
                   pl.BlockSpec((G, 1), lambda t: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((G, D), jnp.float32),
                   jax.ShapeDtypeStruct((G, 1), jnp.float32),
                   jax.ShapeDtypeStruct((G, 1), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.stack([length, window]).astype(jnp.int32), q, k_q, k_s, v_q, v_s)


def quant_attention_decode_partials_vmap(q, k_q, k_s, v_q, v_s, length, *,
                                         interpret: bool, window=None,
                                         block_t: int | None = None):
    """SEED BASELINE (benchmarks only): one kernel launch per (batch ×
    kv-head) via nested vmap. See module docstring for why the flat grid
    replaced it."""
    B, H, D = q.shape
    _, Hkv, T, _ = k_q.shape
    qg, G = _group_queries(q, Hkv)
    if block_t is None:
        block_t = _default_block_t(T, k_s.shape[2])
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    if window is None:
        window = T
    windows = jnp.broadcast_to(jnp.asarray(window, jnp.int32), (B,))
    f = functools.partial(_decode_single, block_t=block_t, interpret=interpret)
    o, m, l = jax.vmap(                                     # over batch
        jax.vmap(f, in_axes=(0, 0, 0, 0, 0, None, None)),   # over kv heads
        in_axes=(0, 0, 0, 0, 0, 0, 0))(qg, k_q, k_s, v_q, v_s, lengths,
                                       windows)
    trim = lambda a: a[:, :, :G].reshape(B, H, a.shape[-1])
    return trim(o), trim(m), trim(l)


# ---------------------------------------------------------------------------
# Page-table-aware variant (DESIGN.md §5): the grid iterates *logical* token
# blocks per (row, kv head); the index_map gathers the physical page id from
# the scalar-prefetched page table, so the DMA streams exactly the pages a
# row owns — no contiguous copy of the cache ever exists. One scale row per
# page streams alongside its page (page_size == quant block size). The
# logical-block axis is bounded per row by the prefetched lengths: steps past
# `ceil(length/ps)` clamp to the row's last live page, so short rows never
# stream the page-table tail (nor the sentinel page).
# ---------------------------------------------------------------------------

def page_dequant(q_tile, scale_row, kv_dtype: str, page_size: int):
    """Dequantize one streamed page tile inside a kernel (DESIGN.md §9):
    ``q_tile`` (ps_packed, D) in the pool's storage dtype, ``scale_row``
    (1, D) f32. int8/fp8 cast straight to f32; int4 sign-extends both
    nibbles via arithmetic shifts and interleaves them back to token order
    (token 2i = low nibble of byte i, 2i+1 = high). Returns (page_size, D)
    f32. Plain jnp ops, so the same code serves Pallas kernel bodies and
    the XLA twins."""
    if kv_dtype == "int4":
        # widen before shifting: Mosaic has no shifts on int8 vectors
        x = q_tile.astype(jnp.int32)     # sign-extends the packed byte
        lo = (x << 28) >> 28             # sign-extend low nibble (arith shift)
        hi = x >> 4                      # arithmetic shift keeps sign
        q_tile = jnp.stack([lo, hi], axis=1).reshape(page_size,
                                                     q_tile.shape[-1])
    return q_tile.astype(jnp.float32) * scale_row.astype(jnp.float32)


def _paged_decode_kernel(pt_ref, len_ref, q_ref, kq_ref, ks_ref, vq_ref,
                         vs_ref, o_ref, m_ref, l_ref,
                         m_scr, l_scr, acc_scr, *, page_size: int,
                         kv_dtype: str):
    b = pl.program_id(0)
    t = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]                      # this row's valid tokens
    max_len = nt * page_size

    @pl.when(t * page_size < length)     # dead page: DMA clamped + no compute
    def _step():
        k = page_dequant(kq_ref[0, 0], ks_ref[0, 0], kv_dtype, page_size)
        v = page_dequant(vq_ref[0, 0], vs_ref[0, 0], kv_dtype, page_size)
        _attn_update(q_ref[0, 0].astype(jnp.float32), k, v, t * page_size,
                     length, length, max_len, max_len, m_scr, l_scr, acc_scr)

    @pl.when(t == nt - 1)
    def _finish():
        o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


@functools.partial(jax.jit, static_argnames=("skip_dead", "interpret",
                                             "kv_dtype"))
def _paged_decode(qg, pool_kq, pool_ks, pool_vq, pool_vs, page_table,
                  lengths, *, skip_dead: bool, interpret: bool,
                  kv_dtype: str):
    """qg (B, Hkv, Gp, D); pool_* (P, Hkv, ps_packed, D) in the pool's
    storage dtype (int8 / fp8_e4m3 / int4-packed: ps_packed = ps // 2) /
    (P, Hkv, 1, D) f32 scales; page_table (B, NT) int32; lengths (B,) int32.
    Returns (o (B, Hkv, Gp, D), m (B, Hkv, Gp, 1), l (B, Hkv, Gp, 1))."""
    B, Hkv, Gp, D = qg.shape
    ps_eff = pool_kq.shape[2]            # packed token rows per page
    ps = 2 * ps_eff if kv_dtype == "int4" else ps_eff   # logical tokens
    NT = page_table.shape[1]
    if skip_dead:
        # bound the logical-block walk by the row's live page count: the
        # table tail past ceil(length/ps) is never even read, and the DMA
        # revisits the last live page instead of streaming dead ones
        t_idx = lambda t, ln: _dead_clamp(t, ln, ps, NT * ps)
    else:
        t_idx = lambda t, ln: t
    page = lambda b, h, t, pt, ln: (pt[b, t_idx(t, ln[b])], h, 0, 0)
    kernel = functools.partial(_paged_decode_kernel, page_size=ps,
                               kv_dtype=kv_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,               # page table + lengths in SMEM
        grid=(B, Hkv, NT),
        in_specs=[
            pl.BlockSpec((1, 1, Gp, D), lambda b, h, t, pt, ln: (b, h, 0, 0)),
            # physical page gather: logical block t of row b -> pt[b, t];
            # heads-major pages make each block's last two dims the
            # array's own (ps_packed, D) and (1, D), as Mosaic requires
            pl.BlockSpec((1, 1, ps_eff, D), page),
            pl.BlockSpec((1, 1, 1, D), page),
            pl.BlockSpec((1, 1, ps_eff, D), page),
            pl.BlockSpec((1, 1, 1, D), page),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Gp, D), lambda b, h, t, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Gp, 1), lambda b, h, t, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Gp, 1), lambda b, h, t, pt, ln: (b, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Gp, 1), jnp.float32),
            pltpu.VMEM((Gp, 1), jnp.float32),
            pltpu.VMEM((Gp, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, Gp, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, Gp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, Gp, 1), jnp.float32)],
        interpret=interpret,
        name="_paged_decode",        # the kernel's name in a device trace
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, pool_kq, pool_ks, pool_vq, pool_vs)


def paged_attention_decode_partials(q, pool_kq, pool_ks, pool_vq, pool_vs,
                                    page_table, lengths, *, interpret: bool,
                                    skip_dead: bool = True,
                                    kv_dtype: str = "int8"):
    """Batched paged decode partials: q (B, H, D) over a page pool
    (P, Hkv, ps_packed, D) in ``kv_dtype`` storage (int8 / fp8_e4m3 /
    int4-packed — DESIGN.md §9) through per-row page tables (B, NT).
    `lengths` (B,) masks each row independently (pass the *flushed* prefix
    count; the fp residual tail is merged by the caller) and bounds each
    row's page walk (`skip_dead`). Returns (o_unnormalized (B, H, D),
    m (B, H, 1), l (B, H, 1))."""
    B, H, D = q.shape
    Hkv = pool_kq.shape[1]
    qg, G = _group_queries(q, Hkv)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    o, m, l = _paged_decode(qg, pool_kq, pool_ks, pool_vq, pool_vs,
                            page_table, lengths, skip_dead=skip_dead,
                            interpret=interpret, kv_dtype=kv_dtype)
    trim = lambda a: a[:, :, :G].reshape(B, H, a.shape[-1])
    return trim(o), trim(m), trim(l)
