"""Pallas TPU kernel: flash-attention forward (prefill / training fwd).

The prefill-time analogue of quant_attention.py: blocked online-softmax
attention that keeps logits in VMEM. On TPU this is the fwd inside
models/flash.py's custom_vjp (the jnp scan body is its oracle and the
backward recompute); here it is validated in interpret mode against
kernels/ref.py-style math.

Layout (single (batch, kv-head) pair; batch × kv-heads via vmap):
    q   (G·S, D)   — the GQA group's query heads stacked along rows
                     (S % block_q == 0 keeps blocks within one head)
    k,v (T, D)
    out (G·S, D) f32

Grid (nq, nk): kv is the inner (sequential) axis; scratch (m, l, acc) is
revisited across the kv loop for each q block. Causal + sliding-window
masking by absolute positions; fully-masked kv blocks are skipped twice
over: pl.when drops the compute, and the k/v index maps clamp dead block
indices to the q block's causal frontier (`min(j, last_live_block)`, the
same clamp-to-last-live trick as quant_attention.py's page walk), so the
pipeline re-reads the resident block instead of streaming HBM for kv
blocks entirely in the causal future.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                block_q: int, block_k: int, seq_q: int, seq_kv: int,
                causal: bool, window: int, kv_offset: int):
    iq = pl.program_id(0)
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute query positions of this block's rows (rows stay in one head)
    row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
    qpos = kv_offset + jax.lax.rem(row, seq_q)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)

    # causal block skip: earliest query in block vs first kv of block
    first_q = kv_offset + (iq * block_q) % seq_q
    # (conservative: the whole kv block is in the future of every row)
    run = jnp.logical_or(jnp.logical_not(causal),
                         ik * block_k <= first_q + block_q - 1)

    @pl.when(run)
    def _step():
        q = q_ref[...]
        k = k_ref[...]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jax.lax.rsqrt(
                jnp.asarray(q_ref.shape[-1], jnp.float32))
        mask = kpos < seq_kv
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, _NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] /
                      jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_prefill(q, k, v, *, causal: bool = True, window: int | None = None,
                  kv_offset: int = 0, block_q: int = 256, block_k: int = 256,
                  skip_dead: bool = True, interpret: bool):
    """Batched flash forward: q (B, H, S, D); k/v (B, Hkv, T, D) ->
    (B, H, S, D) f32. GQA via vmap over (B, Hkv), G folded into q rows.

    ``skip_dead`` (causal only) clamps the k/v index maps to each q
    block's causal frontier, so kv blocks wholly in the future — whose
    compute pl.when already drops — stream no DMA either: the pipeline
    sees a repeated block index and re-uses the resident tile. Invisible
    to results (those blocks were fully masked)."""
    B, H, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    G = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    if S % block_q or T % block_k:
        raise ValueError(f"S={S} % block_q={block_q} or T={T} % "
                         f"block_k={block_k} != 0")
    qg = q.reshape(B, Hkv, G * S, D)
    nq, nk = (G * S) // block_q, T // block_k

    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, seq_q=S, seq_kv=T,
        causal=causal, window=window or 0, kv_offset=kv_offset)

    if causal and skip_dead:
        # last kv block any row of q block i can see; rem() keeps the
        # frontier per-head (q rows are G stacked heads of S rows each)
        def kv_map(i, j):
            last_live = (kv_offset + jax.lax.rem(i * block_q, S)
                         + block_q - 1) // block_k
            return (jnp.minimum(j, last_live), 0)
    else:
        def kv_map(i, j):
            return (j, 0)

    def one(qh, kh, vh):
        return pl.pallas_call(
            kernel,
            grid=(nq, nk),
            in_specs=[pl.BlockSpec((block_q, D), lambda i, j: (i, 0)),
                      pl.BlockSpec((block_k, D), kv_map),
                      pl.BlockSpec((block_k, D), kv_map)],
            out_specs=pl.BlockSpec((block_q, D), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((G * S, D), jnp.float32),
            scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, D), jnp.float32)],
            interpret=interpret,
        )(qh, kh, vh)

    out = jax.vmap(jax.vmap(one))(qg, k, v)           # (B, Hkv, G*S, D)
    return out.reshape(B, H, S, D)
