"""Fused varlen flash-prefill attention directly over the INT8 page pool.

The prefill analogue of quant_attention.py's paged decode kernel
(DESIGN.md §5/§7): one chunk of C prompt tokens per row attends over the
row's resident history pages *and* causally within the chunk in a single
`pallas_call` — INT8 page tiles and their scale rows stream straight into
VMEM through the page-table index_map, dequantization is fused into the
online-softmax inner loop, and no fp32 history tensor is ever
materialized in HBM (the former `dequantized_prefix` + `_chunk_attention`
path gathered and dequantized every resident page per layer per chunk).

Grid (B, Hkv, NT + 1) with NT = hist_blocks (the dispatch group's static
pow2 cursor bound): steps t < NT walk the row's history pages via
`PrefetchScalarGridSpec` — the index_map gathers the physical page id
from the scalar-prefetched page table, exactly like the decode kernel —
and the final step t == NT processes the chunk's own fp K/V tile with
causal + per-row `valid` masking. The GQA group's queries for the whole
chunk ride as one (G*C, D) resident block (row r is query position
r % C of head-group lane r // C), so the per-(row, kv-head) flash state
(m, l, acc) in VMEM scratch covers every chunk query at once.

Varlen ragged edge, all in SMEM scalars:
  * per-row `hist_len` masks history positions and bounds the page walk —
    steps past ceil(hist_len / ps) clamp to the row's last live page
    (`_dead_clamp`, PR 2's trick), so the pipeline re-issues no DMA and
    `pl.when` skips the compute; a row admitted at cursor 0 inside a
    deep-history dispatch streams nothing extra.
  * per-row `valid` masks the chunk's dispatch-padding keys; queries past
    `valid` produce garbage the caller discards (same contract as the
    XLA path — causality already hides padding from valid queries).

History is page-aligned by construction (chunk cursors advance in page
multiples), so there is no residual tail to merge: the kernel emits
normalized outputs directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_attention import _dead_clamp, page_dequant

_NEG_INF = -1e30

# Scoped VMEM the kernel asks Mosaic for. A v5e core has 128 MiB of VMEM;
# the compiler's default scope (16 MiB) is what the (G*C, D) query, output
# and flash-state blocks outgrow first.
PREFILL_VMEM_LIMIT = 64 * 2**20


def _key_tile(chunk: int, page_size: int) -> int:
    """Keys per inner step of the chunk walk: one page's worth."""
    return page_size if chunk % page_size == 0 else chunk


def prefill_vmem_bytes(group: int, chunk: int, page_size: int,
                       head_dim: int) -> int:
    """Estimated VMEM of one dispatch: G*C query rows, each a lane-padded
    f32 row in the double-buffered query and output blocks and the flash
    state (acc, m, l), plus the double-buffered chunk K/V blocks and the
    f32 temporaries of one (G*C, key_tile) logit tile."""
    rows, lanes = group * chunk, -(-head_dim // 128) * 128
    kt = _key_tile(chunk, page_size)
    row_bytes = 4 * (2 * 2 * lanes + lanes + 2 * 128 + 4 * kt)
    return rows * row_bytes + 4 * (2 * 2 * chunk * lanes
                                   + 4 * page_size * lanes)


def check_prefill_fits(group: int, chunk: int, page_size: int,
                       head_dim: int) -> None:
    """Refuse a chunk width the kernel cannot hold in VMEM: the footprint
    grows with G*C, so the largest chunk shrinks as the GQA group grows."""
    need = prefill_vmem_bytes(group, chunk, page_size, head_dim)
    if need > PREFILL_VMEM_LIMIT:
        fit = page_size
        while prefill_vmem_bytes(group, 2 * fit, page_size,
                                 head_dim) <= PREFILL_VMEM_LIMIT:
            fit *= 2
        raise ValueError(
            f"prefill chunk of {chunk} tokens needs ~{need / 2**20:.0f} MiB "
            f"of VMEM for {group} query heads per KV head (limit "
            f"{PREFILL_VMEM_LIMIT / 2**20:.0f} MiB); use a prefill chunk of "
            f"at most {fit} tokens")


def _update(logits, mask, v, m_scr, l_scr, acc_scr):
    """Online-softmax accumulate of one masked (GC, bt) logit tile."""
    logits = jnp.where(mask, logits, _NEG_INF)
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
    alpha = jnp.exp(m_prev - m_new)
    m_scr[...] = m_new
    l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _prefill_kernel(pt_ref, hl_ref, vd_ref, q_ref, kc_ref, vc_ref,
                    kq_ref, ks_ref, vq_ref, vs_ref, o_ref,
                    m_scr, l_scr, acc_scr, *, page_size: int, chunk: int,
                    key_tile: int, kv_dtype: str):
    b = pl.program_id(0)
    t = pl.program_id(2)
    nt = pl.num_programs(2)          # NT history steps + 1 chunk step

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    hist_len = hl_ref[b]             # this row's resident history tokens
    valid = vd_ref[b]                # this row's true tokens in the chunk

    # -- history step: one quantized page, dequantized in VMEM -------------
    # (int8 / fp8 cast, int4 nibble-unpack — DESIGN.md §9)
    @pl.when(jnp.logical_and(t < nt - 1, t * page_size < hist_len))
    def _hist():                     # dead page: DMA clamped + no compute
        k = page_dequant(kq_ref[0, 0], ks_ref[0, 0], kv_dtype, page_size)
        v = page_dequant(vq_ref[0, 0], vs_ref[0, 0], kv_dtype, page_size)
        logits = jax.lax.dot_general(            # (GC, ps)
            q_ref[0, 0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        pos = t * page_size + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        _update(logits, pos < hist_len, v, m_scr, l_scr, acc_scr)

    # -- chunk step: the chunk's own fp K/V, causal + valid masked ---------
    # Walked in (key_tile, D) key tiles, so the live logit tile is
    # (GC, key_tile) rather than (GC, C): VMEM grows linearly in C.
    @pl.when(t == nt - 1)
    def _chunk():
        def tile(j, carry):
            start = pl.multiple_of(j * key_tile, key_tile)
            k = kc_ref[0, 0, pl.ds(start, key_tile), :]      # (kt, D) f32
            v = vc_ref[0, 0, pl.ds(start, key_tile), :]
            logits = jax.lax.dot_general(                    # (GC, kt)
                q_ref[0, 0], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            # query row r is chunk position r % C of its head-group lane
            qpos = jax.lax.rem(
                jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0), chunk)
            kpos = start + jax.lax.broadcasted_iota(jnp.int32,
                                                    logits.shape, 1)
            _update(logits, (kpos <= qpos) & (kpos < valid), v,
                    m_scr, l_scr, acc_scr)
            return carry

        jax.lax.fori_loop(0, chunk // key_tile, tile, 0)
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("hist_blocks", "skip_dead",
                                             "interpret", "kv_dtype"))
def _paged_prefill(qg, kc, vc, pool_kq, pool_ks, pool_vq, pool_vs,
                   page_table, hist_len, valid, *, hist_blocks: int,
                   skip_dead: bool, interpret: bool, kv_dtype: str):
    """qg (B, Hkv, G*C, D) f32 pre-scaled queries; kc/vc (B, Hkv, C, D) f32
    chunk K/V; pool_* (P, Hkv, ps_packed, D) in ``kv_dtype`` storage
    (int4: ps_packed = ps // 2) / (P, Hkv, 1, D) f32 scales; page_table
    (B, >=max(hist_blocks, 1)) int32; hist_len/valid (B,) int32.
    Returns normalized (B, Hkv, G*C, D) f32."""
    B, Hkv, GC, D = qg.shape
    C = kc.shape[2]
    ps_eff = pool_kq.shape[2]            # packed token rows per page
    ps = 2 * ps_eff if kv_dtype == "int4" else ps_eff   # logical tokens
    check_prefill_fits(GC // C, C, ps, D)
    NT = hist_blocks
    pt = page_table[:, :max(NT, 1)]
    if skip_dead:
        t_idx = lambda t, ln: _dead_clamp(t, ln, ps, max(NT, 1) * ps)
    else:
        t_idx = lambda t, ln: jnp.minimum(t, max(NT - 1, 0))
    # the chunk step (t == NT) revisits the previous step's page so the
    # pipeline issues no DMA for the unused pool tiles on the final step
    p_idx = lambda t, ln: t_idx(jnp.minimum(t, max(NT - 1, 0)), ln)

    page = lambda b, h, t, pt, hl, vd: (pt[b, p_idx(t, hl[b])], h, 0, 0)
    kernel = functools.partial(_prefill_kernel, page_size=ps, chunk=C,
                               key_tile=_key_tile(C, ps), kv_dtype=kv_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,       # page table + hist lens + valids (SMEM)
        grid=(B, Hkv, NT + 1),
        in_specs=[
            pl.BlockSpec((1, 1, GC, D),
                         lambda b, h, t, pt, hl, vd: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, C, D),
                         lambda b, h, t, pt, hl, vd: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, C, D),
                         lambda b, h, t, pt, hl, vd: (b, h, 0, 0)),
            # physical page gather: logical history block t -> pt[b, t]
            # (heads-major pages: (ps_packed, D) and (1, D) tiles)
            pl.BlockSpec((1, 1, ps_eff, D), page),
            pl.BlockSpec((1, 1, 1, D), page),
            pl.BlockSpec((1, 1, ps_eff, D), page),
            pl.BlockSpec((1, 1, 1, D), page),
        ],
        out_specs=pl.BlockSpec((1, 1, GC, D),
                               lambda b, h, t, pt, hl, vd: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((GC, 1), jnp.float32),
            pltpu.VMEM((GC, 1), jnp.float32),
            pltpu.VMEM((GC, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, GC, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=PREFILL_VMEM_LIMIT),
        interpret=interpret,
        name="_paged_prefill",       # the kernel's name in a device trace
    )(pt.astype(jnp.int32), hist_len.astype(jnp.int32),
      valid.astype(jnp.int32), qg, kc, vc,
      pool_kq, pool_ks, pool_vq, pool_vs)


def paged_attention_prefill(q, k, v, pool_kq, pool_ks, pool_vq, pool_vs,
                            page_table, hist_len, valid=None, *,
                            hist_blocks: int, interpret: bool,
                            skip_dead: bool = True, kv_dtype: str = "int8"):
    """Fused varlen chunk-prefill attention over a quantized page pool.

    q (B, H, C, D) chunk queries; k/v (B, Hkv, C, D) the chunk's own fp
    K/V; pool_* (P, Hkv, ps_packed, D) in ``kv_dtype`` storage (int8 /
    fp8_e4m3 / int4-packed — DESIGN.md §9) / (P, Hkv, 1, D) f32 scales;
    page_table (B, NT) int32; hist_len (B,) int32 resident history tokens
    per row (page-aligned); valid (B,) int32 true chunk tokens per row
    (None = C). `hist_blocks` (static) bounds the history walk — ONE
    pallas_call over a (B, Hkv, hist_blocks + 1) grid serves the whole
    dispatch. Returns normalized (B, H, C, D) f32; outputs at query
    positions past `valid` are garbage the caller discards."""
    B, H, C, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    scale = jax.lax.rsqrt(jnp.asarray(D, jnp.float32))
    qg = (q.reshape(B, Hkv, G * C, D).astype(jnp.float32) * scale)
    if valid is None:
        valid = jnp.full((B,), C, jnp.int32)
    hist_len = jnp.broadcast_to(jnp.asarray(hist_len, jnp.int32), (B,))
    valid = jnp.broadcast_to(jnp.asarray(valid, jnp.int32), (B,))
    out = _paged_prefill(qg, k.astype(jnp.float32), v.astype(jnp.float32),
                         pool_kq, pool_ks, pool_vq, pool_vs, page_table,
                         hist_len, valid, hist_blocks=hist_blocks,
                         skip_dead=skip_dead, interpret=interpret,
                         kv_dtype=kv_dtype)
    return out.reshape(B, H, C, D)
