"""Pallas TPU kernels: fused per-channel / per-block INT8 quantization.

TPU adaptation of the paper's CUDA kernel family (DESIGN.md §2):

* The paper's *vectorized* kernel (its best variant) maps to lane-aligned
  BlockSpec tiling: the channel axis is blocked in multiples of 128 lanes and
  the token axis in multiples of 8 sublanes, so every VMEM transaction is a
  full native tile — the TPU's equivalent of float4/char4 loads.
* The paper's two-pass structure (Alg. 1 scale pass + Eq. 7 quantize pass) is
  *fused* where the scale granularity allows: `quantize_blocked_kernel` does
  absmax + quantize in a single HBM read per element (the paper's CUDA code
  reads K twice). For whole-matrix per-channel scales the reduction is global
  over T, so a grid-revisited accumulator pass runs first, then a quantize
  pass — still 2 reads + 1 write, matching the paper's traffic.

Every entry point takes ``interpret`` explicitly: True runs the kernel body
in the Pallas interpreter (CPU validation), False compiles it with Mosaic.
These standalone kernels do not compile for a TPU yet: their (1, bd) scale
blocks are not tile-aligned. Only the benchmarks call them; the serving
path quantizes inside the cache update.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

QMAX = 127.0
_NEG_EPS = 1e-30


# ---------------------------------------------------------------------------
# Pass 1 (per-channel mode): grid-revisited absmax accumulator over T
# ---------------------------------------------------------------------------

def _absmax_kernel(x_ref, out_ref):
    # grid = (nd, nt): d outer so each (1, bd) output block is revisited by
    # consecutive t-steps and stays resident in VMEM (TPU output revisiting).
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    blk_max = jnp.max(jnp.abs(x_ref[...].astype(jnp.float32)), axis=0,
                      keepdims=True)
    out_ref[...] = jnp.maximum(out_ref[...], blk_max)


def _quantize_with_scales_kernel(x_ref, s_ref, q_ref):
    s = jnp.maximum(s_ref[...].astype(jnp.float32), _NEG_EPS)   # (1, bd)
    q = jnp.round(x_ref[...].astype(jnp.float32) / s)
    q_ref[...] = jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)


# ---------------------------------------------------------------------------
# Fused single-pass kernel (per-block mode): absmax + quantize in one read
# ---------------------------------------------------------------------------

def _quantize_blocked_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                           # (bs, bd)
    max_abs = jnp.maximum(jnp.max(jnp.abs(x), axis=0, keepdims=True), _NEG_EPS)
    s = max_abs / QMAX                                           # (1, bd)
    s_ref[...] = s
    q_ref[...] = jnp.clip(jnp.round(x / s), -QMAX, QMAX).astype(jnp.int8)


def _dequantize_kernel(q_ref, s_ref, o_ref):
    s = s_ref[...].astype(jnp.float32)                           # (1, bd)
    o_ref[...] = (q_ref[...].astype(jnp.float32) * s).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call builders
# ---------------------------------------------------------------------------

def _pick_block_d(D: int) -> int:
    # lane-dim alignment: full 128-lane multiples (the "vectorized" analogue)
    for bd in (512, 256, 128):
        if D % bd == 0:
            return bd
    return D  # small/unaligned D: single block (interpret handles any shape)


def _pick_block_t(T: int) -> int:
    for bt in (512, 256, 128, 8):
        if T % bt == 0:
            return bt
    return T


@functools.partial(jax.jit, static_argnames=("block_t", "block_d", "interpret"))
def quantize_per_channel(x: jax.Array, *, block_t: int | None = None,
                         block_d: int | None = None,
                         interpret: bool) -> tuple[jax.Array, jax.Array]:
    """Paper-faithful whole-matrix per-channel quantization of (T, D).

    Returns (int8 (T, D), f32 scales (D,)).
    """
    T, D = x.shape
    bt = block_t or _pick_block_t(T)
    bd = block_d or _pick_block_d(D)
    nt, nd = pl.cdiv(T, bt), pl.cdiv(D, bd)

    max_abs = pl.pallas_call(
        _absmax_kernel,
        grid=(nd, nt),
        in_specs=[pl.BlockSpec((bt, bd), lambda d, t: (t, d))],
        out_specs=pl.BlockSpec((1, bd), lambda d, t: (0, d)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
    )(x)
    scales = jnp.maximum(max_abs, _NEG_EPS) / QMAX               # (1, D)

    q = pl.pallas_call(
        _quantize_with_scales_kernel,
        grid=(nt, nd),
        in_specs=[pl.BlockSpec((bt, bd), lambda t, d: (t, d)),
                  pl.BlockSpec((1, bd), lambda t, d: (0, d))],
        out_specs=pl.BlockSpec((bt, bd), lambda t, d: (t, d)),
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.int8),
        interpret=interpret,
    )(x, scales)
    return q, scales[0]


@functools.partial(jax.jit, static_argnames=("block_size", "block_d", "interpret"))
def quantize_blocked(x: jax.Array, block_size: int = 256, *,
                     block_d: int | None = None,
                     interpret: bool) -> tuple[jax.Array, jax.Array]:
    """Fused single-pass per-(token-block, channel) quantization of (T, D).

    One HBM read + int8 write per element (beats the paper's 2-read CUDA
    pipeline). Returns (int8 (T, D), f32 scales (T//block_size, D)).
    """
    T, D = x.shape
    if T % block_size:
        raise ValueError(f"T={T} not multiple of block_size={block_size}")
    bd = block_d or _pick_block_d(D)
    nb, nd = T // block_size, pl.cdiv(D, bd)

    q, scales = pl.pallas_call(
        _quantize_blocked_kernel,
        grid=(nb, nd),
        in_specs=[pl.BlockSpec((block_size, bd), lambda b, d: (b, d))],
        out_specs=[pl.BlockSpec((block_size, bd), lambda b, d: (b, d)),
                   pl.BlockSpec((1, bd), lambda b, d: (b, d))],
        out_shape=[jax.ShapeDtypeStruct((T, D), jnp.int8),
                   jax.ShapeDtypeStruct((nb, D), jnp.float32)],
        interpret=interpret,
    )(x)
    return q, scales


@functools.partial(jax.jit, static_argnames=("block_d", "out_dtype", "interpret"))
def dequantize(x_q: jax.Array, scales: jax.Array, *,
               block_d: int | None = None, out_dtype=jnp.float32,
               interpret: bool) -> jax.Array:
    """int8 (T, D) × f32 scales (nb, D) -> (T, D) out_dtype. nb=1 => per-channel."""
    T, D = x_q.shape
    if scales.ndim == 1:
        scales = scales[None]
    nb = scales.shape[0]
    block_size = T // nb
    bd = block_d or _pick_block_d(D)
    nd = pl.cdiv(D, bd)
    return pl.pallas_call(
        _dequantize_kernel,
        grid=(nb, nd),
        in_specs=[pl.BlockSpec((block_size, bd), lambda b, d: (b, d)),
                  pl.BlockSpec((1, bd), lambda b, d: (b, d))],
        out_specs=pl.BlockSpec((block_size, bd), lambda b, d: (b, d)),
        out_shape=jax.ShapeDtypeStruct((T, D), out_dtype),
        interpret=interpret,
    )(x_q, scales)
