"""Compile the serving path's Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology it is only
told about (`jax.experimental.topologies`), at internlm2_1_8b widths
(8 KV heads x 128, GQA group 2, page 256, a 64-page pool, batch 8,
max_len 2048). A kernel whose blocks Mosaic refuses — a tile that is not
(8, 128)-aligned, a shift it cannot legalize, a VMEM overflow — fails
here instead of on the chip. Nothing runs, so these tests say nothing
about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file. All such compiles live in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import quantization as Q
from repro.kernels import quant_attention as QA
from repro.kernels import quant_prefill as QP

KV_HEADS, GROUP, HEAD_DIM, PAGE = 8, 2, 128, 256
BATCH, MAX_BLOCKS, POOL_PAGES = 8, 8, 64
CHUNK = 4 * PAGE                  # the scheduler's default prefill chunk
KV_DTYPES = ["int8", "fp8_e4m3", "int4"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    a compile for a described chip is written to it but cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _pool(spec, kv_dtype):
    """(values, scales) shapes of one heads-major page pool."""
    packed = Q.packed_tokens(PAGE, kv_dtype)
    return (spec((POOL_PAGES, KV_HEADS, packed, HEAD_DIM),
                 Q.kv_storage_dtype(kv_dtype)),
            spec((POOL_PAGES, KV_HEADS, 1, HEAD_DIM), jnp.float32))


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the Mosaic kernel
    return compiled


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_paged_decode_compiles(one_chip, kv_dtype):
    spec = _spec(one_chip)
    kq, ks = _pool(spec, kv_dtype)

    def decode(q, kq, ks, vq, vs, table, lengths):
        return QA.paged_attention_decode_partials(
            q, kq, ks, vq, vs, table, lengths, interpret=False,
            kv_dtype=kv_dtype)

    compiled = _compile(decode, spec((BATCH, KV_HEADS * GROUP, HEAD_DIM),
                                     jnp.bfloat16),
                        kq, ks, kq, ks, spec((BATCH, MAX_BLOCKS), jnp.int32),
                        spec((BATCH,), jnp.int32))
    # the kernel's name in a device trace, where the benchmark reads it
    assert "%_paged_decode." in compiled.as_text()


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_fused_prefill_compiles(one_chip, kv_dtype):
    spec = _spec(one_chip)
    kq, ks = _pool(spec, kv_dtype)

    def prefill(q, k, v, kq, ks, vq, vs, table, hist_len, valid):
        return QP.paged_attention_prefill(
            q, k, v, kq, ks, vq, vs, table, hist_len, valid,
            hist_blocks=4, interpret=False, kv_dtype=kv_dtype)

    chunk = lambda heads: spec((BATCH, heads, CHUNK, HEAD_DIM), jnp.bfloat16)
    compiled = _compile(prefill, chunk(KV_HEADS * GROUP), chunk(KV_HEADS),
                        chunk(KV_HEADS), kq, ks, kq, ks,
                        spec((BATCH, MAX_BLOCKS), jnp.int32),
                        spec((BATCH,), jnp.int32), spec((BATCH,), jnp.int32))
    assert "%_paged_prefill." in compiled.as_text()


def test_contiguous_decode_compiles(one_chip):
    spec = _spec(one_chip)
    T = MAX_BLOCKS * PAGE
    vals = spec((BATCH, KV_HEADS, T, HEAD_DIM), jnp.int8)
    scales = spec((BATCH, KV_HEADS, MAX_BLOCKS, HEAD_DIM), jnp.float32)

    def decode(q, kq, ks, vq, vs, lengths):
        return QA.quant_attention_decode_partials(q, kq, ks, vq, vs, lengths,
                                                  interpret=False)

    _compile(decode, spec((BATCH, KV_HEADS * GROUP, HEAD_DIM), jnp.bfloat16),
             vals, scales, vals, scales, spec((BATCH,), jnp.int32))
