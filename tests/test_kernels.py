"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode.

Every kernel in src/repro/kernels is asserted allclose against ref.py
across a sweep of shapes and dtypes (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantization as Q
from repro.kernels import ops, ref
from repro.kernels import quant_attention as QA
from repro.kernels import quantize as QK

jax.config.update("jax_platform_name", "cpu")

SHAPES = [(8, 128), (256, 128), (512, 256), (96, 72), (1024, 512), (16, 8)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_quantize_per_channel_matches_ref(shape, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), shape) * 2).astype(dtype)
    q, s = QK.quantize_per_channel(x, interpret=True)
    qr, sr = ref.quantize_fused_ref(x)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)
    # rounding at .5 boundaries may differ by 1 ulp between paths
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32) - qr.astype(jnp.int32)))) <= 1


@pytest.mark.parametrize("shape,block", [((256, 128), 64), ((512, 256), 128),
                                         ((128, 512), 8), ((1024, 128), 256)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_quantize_blocked_matches_ref(shape, block, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(1), shape) * 3).astype(dtype)
    q, s = QK.quantize_blocked(x, block, interpret=True)
    qr, sr = ref.quantize_blocked_ref(x, block)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32) - qr.astype(jnp.int32)))) <= 1


@pytest.mark.parametrize("shape,nb", [((256, 128), 1), ((256, 128), 4),
                                      ((512, 512), 8)])
@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_dequantize_matches_ref(shape, nb, out_dtype):
    T, D = shape
    x = jax.random.normal(jax.random.PRNGKey(2), shape)
    if nb == 1:
        q, s = ref.quantize_fused_ref(x)
        s2 = s[None]
    else:
        q, s2 = ref.quantize_blocked_ref(x, T // nb)
    d = QK.dequantize(q, s2, out_dtype=out_dtype, interpret=True)
    dr = ref.dequantize_ref(q, s2, dtype=out_dtype)
    np.testing.assert_allclose(np.asarray(d, np.float32),
                               np.asarray(dr, np.float32), rtol=1e-2)


DECODE_CASES = [
    # (B, Hkv, G, T, D, block)
    (1, 1, 1, 128, 64, 64),
    (2, 4, 3, 512, 128, 128),
    (2, 2, 8, 256, 128, 256),     # per-channel-like single block
    (1, 8, 1, 1024, 256, 256),
]


@pytest.mark.parametrize("B,Hkv,G,T,D,block", DECODE_CASES)
def test_fused_decode_matches_ref(B, Hkv, G, T, D, block):
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(keys[0], (B, Hkv * G, D))
    k = jax.random.normal(keys[1], (B, Hkv, T, D))
    v = jax.random.normal(keys[2], (B, Hkv, T, D))
    kq, ks = Q.quantize_blocked(k, block)
    vq, vs = Q.quantize_blocked(v, block)
    length = jnp.asarray(np.random.RandomState(0).randint(1, T + 1, (B,)),
                         jnp.int32)
    out = QA.quant_attention_decode(q, kq, ks, vq, vs, length,
                                    interpret=True)

    def ref_one(qb, kqb, ksb, vqb, vsb, lb):
        return jax.vmap(lambda qg, kk, kss, vv, vss:
                        ref.quant_attention_decode_ref(qg, kk, kss, vv, vss,
                                                       lb))(
            qb.reshape(Hkv, G, D), kqb, ksb, vqb, vsb)
    expect = jax.vmap(ref_one)(q, kq, ks, vq, vs, length).reshape(
        B, Hkv * G, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=3e-5, atol=3e-5)


def test_fused_decode_per_channel_scales():
    B, Hkv, G, T, D = 2, 2, 2, 256, 64
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(keys[0], (B, Hkv * G, D))
    k = jax.random.normal(keys[1], (B, Hkv, T, D))
    v = jax.random.normal(keys[2], (B, Hkv, T, D))
    kq, ks = Q.quantize_matrix(k)
    vq, vs = Q.quantize_matrix(v)
    out = QA.quant_attention_decode(q, kq, ks[:, :, None], vq, vs[:, :, None],
                                    jnp.asarray(200), interpret=True)
    expect = ops.quant_attention_decode(q, kq, ks[:, :, None], vq,
                                        vs[:, :, None], jnp.asarray(200),
                                        impl="xla")
    # xla path runs bf16 dequant+dots (production numerics); kernel is f32
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-2, atol=2e-2)


def test_partials_merge_equals_full():
    """Merging kernel partials over two halves == attention over the whole."""
    B, Hkv, G, T, D = 1, 2, 2, 256, 64
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (B, Hkv * G, D))
    k = jax.random.normal(keys[1], (B, Hkv, T, D))
    v = jax.random.normal(keys[2], (B, Hkv, T, D))
    kq, ks = Q.quantize_blocked(k, 128)
    vq, vs = Q.quantize_blocked(v, 128)
    full = ops.quant_attention_decode(q, kq, ks, vq, vs, jnp.asarray(T),
                                      impl="pallas_interpret")
    o1, m1, l1 = ops.quant_attention_decode_partials(
        q, kq[:, :, :128], ks[:, :, :1], vq[:, :, :128], vs[:, :, :1],
        jnp.asarray(128), impl="pallas_interpret")
    o2, m2, l2 = ops.quant_attention_decode_partials(
        q, kq[:, :, 128:], ks[:, :, 1:], vq[:, :, 128:], vs[:, :, 1:],
        jnp.asarray(128), impl="pallas_interpret")
    m = jnp.maximum(m1, m2)
    c1, c2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
    merged = (o1 * c1 + o2 * c2) / (l1 * c1 + l2 * c2)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               rtol=3e-5, atol=3e-5)


# -- length-aware flat-grid decode (ISSUE 2) --------------------------------

def _quantized_cache(B=4, Hkv=2, G=3, T=256, D=64, block=64, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Hkv * G, D))
    k = jax.random.normal(ks[1], (B, Hkv, T, D))
    v = jax.random.normal(ks[2], (B, Hkv, T, D))
    kq, kss = Q.quantize_blocked(k, block)
    vq, vs = Q.quantize_blocked(v, block)
    return q, kq, kss, vq, vs


@pytest.mark.parametrize("length", [0, 1, 63, 64, 256])   # {0,1,bt-1,bt,max}
def test_flat_decode_length_edges_match_xla(length):
    """Normalized flat-grid output vs the XLA reference at the block-edge
    lengths where the index_map clamp changes behaviour (bt=64, T=256)."""
    q, kq, kss, vq, vs = _quantized_cache()
    ln = jnp.asarray(length, jnp.int32)
    out = QA.quant_attention_decode(q, kq, kss, vq, vs, ln, interpret=True)
    expect = ops.quant_attention_decode(q, kq, kss, vq, vs, ln, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-2, atol=2e-2)
    if length == 0:
        np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_flat_decode_ragged_lengths_match_seed_kernel():
    """Ragged per-row lengths through ONE flat-grid launch must match the
    seed per-(row, head) vmap fan-out bit-for-bit (same kernel math; only
    the launch geometry and DMA schedule changed)."""
    q, kq, kss, vq, vs = _quantized_cache()
    lengths = jnp.asarray([0, 1, 200, 256], jnp.int32)
    o, m, l = QA.quant_attention_decode_partials(q, kq, kss, vq, vs, lengths,
                                                 interpret=True)
    ov, mv, lv = QA.quant_attention_decode_partials_vmap(
        q, kq, kss, vq, vs, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(ov))
    np.testing.assert_array_equal(np.asarray(m), np.asarray(mv))
    np.testing.assert_array_equal(np.asarray(l), np.asarray(lv))


def test_flat_decode_ring_wrap_window_matches_xla():
    """Ring caches: absolute lengths beyond T with a sliding window — age
    masking must survive the flat grid + DMA clamp (clamping is by live
    *slots*, which is all of T once the ring wraps)."""
    q, kq, kss, vq, vs = _quantized_cache()
    lengths = jnp.asarray([300, 257, 256, 512], jnp.int32)   # all wrapped
    out = QA.quant_attention_decode(q, kq, kss, vq, vs, lengths, window=100,
                                    interpret=True)
    expect = ops.quant_attention_decode(q, kq, kss, vq, vs, lengths,
                                        window=100, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-2, atol=2e-2)


def test_flat_decode_skip_dead_is_invisible():
    """DMA-level dead-block skipping must be numerically invisible: clamped
    steps stream a stale tile but never compute on it."""
    q, kq, kss, vq, vs = _quantized_cache()
    lengths = jnp.asarray([0, 1, 100, 192], jnp.int32)
    a = QA.quant_attention_decode_partials(q, kq, kss, vq, vs, lengths,
                                           skip_dead=True, interpret=True)
    b = QA.quant_attention_decode_partials(q, kq, kss, vq, vs, lengths,
                                           skip_dead=False, interpret=True)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_paged_decode_ragged_lengths_and_bounded_walk():
    """Paged kernel at ragged flushed lengths (incl. 0) vs the XLA gather
    reference, and skip_dead (bounded page walk) must be invisible."""
    from repro.core.paging import scatter_to_pool
    q, kq, kss, vq, vs = _quantized_cache()
    pk, pks, pv, pvs, table = scatter_to_pool(kq, kss, vq, vs)
    flushed = jnp.asarray([0, 64, 128, 256], jnp.int32)
    o, m, l = QA.paged_attention_decode_partials(q, pk, pks, pv, pvs, table,
                                                 flushed, interpret=True)
    out = o / jnp.maximum(l, 1e-30)
    expect = ops.paged_attention_decode(q, pk, pks, pv, pvs, table, flushed,
                                        impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)   # length-0 row
    o2, m2, l2 = QA.paged_attention_decode_partials(
        q, pk, pks, pv, pvs, table, flushed, skip_dead=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(l), np.asarray(l2))


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_paged_decode_reads_a_stacked_pool_at_its_layer(impl):
    """A layer-stacked pool read at layer 1 gives bit for bit what that
    layer's own pool gives; the other layers' pages are never read."""
    from repro.core.paging import scatter_to_pool
    q, kq, kss, vq, vs = _quantized_cache()
    pools = scatter_to_pool(kq, kss, vq, vs)
    table = pools[-1]
    flushed = jnp.asarray([0, 64, 128, 256], jnp.int32)
    stacked = [jnp.stack([-a, a, a * 2]) for a in pools[:4]]
    one = ops.paged_attention_decode_partials(q, *pools[:4], table, flushed,
                                              impl=impl)
    at = ops.paged_attention_decode_partials(q, *stacked, table, flushed,
                                             layer=jnp.int32(1), impl=impl)
    for x, y in zip(one, at):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    other = ops.paged_attention_decode_partials(
        q, *stacked, table, flushed, layer=jnp.int32(2), impl=impl)
    assert not np.array_equal(np.asarray(other[0]), np.asarray(one[0]))


def _pallas_grids(closed_jaxpr):
    """Grid of every pallas_call in a traced function, nested jaxprs
    included."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield tuple(eqn.params["grid_mapping"].grid)
                continue
            for p in eqn.params.values():
                sub = getattr(p, "jaxpr", None)
                if sub is not None and hasattr(sub, "eqns"):
                    yield from walk(sub)
    return list(walk(closed_jaxpr.jaxpr))


def test_flat_decode_is_single_pallas_call():
    """Acceptance: quant_attention_decode_partials issues exactly ONE
    pallas_call for the whole batch — no Python/vmap fan-out."""
    q, kq, kss, vq, vs = _quantized_cache()
    lengths = jnp.asarray([1, 2, 3, 4], jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: QA.quant_attention_decode_partials(*a, interpret=True))(
        q, kq, kss, vq, vs, lengths)
    # and the whole batch flows through it: the (B, Hkv, NT) grid, not vmap
    B, Hkv, nb = kss.shape[:3]           # one token block per scale row
    assert _pallas_grids(jaxpr) == [(B, Hkv, nb)]


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_ops_dispatch_consistency(impl):
    x = jax.random.normal(jax.random.PRNGKey(6), (256, 128))
    q, s = ops.quantize_per_channel(x, impl=impl)
    qr, sr = ref.quantize_fused_ref(x)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)


FLASH_CASES = [
    # (B, Hkv, G, S, T, D, bq, bk, causal, window)
    (1, 1, 1, 16, 16, 8, 8, 8, True, None),
    (2, 2, 3, 32, 32, 16, 8, 8, True, None),
    (2, 2, 2, 32, 48, 16, 16, 16, False, None),
    (1, 2, 2, 64, 64, 32, 16, 16, True, 12),
    (1, 1, 4, 32, 32, 128, 32, 32, True, None),
]


@pytest.mark.parametrize("B,Hkv,G,S,T,D,bq,bk,causal,window", FLASH_CASES)
def test_flash_prefill_kernel_matches_jnp(B, Hkv, G, S, T, D, bq, bk,
                                          causal, window):
    """Pallas flash forward (interpret) vs the jnp flash oracle."""
    from repro.kernels.flash_fwd import flash_prefill
    from repro.models.flash import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    q = jax.random.normal(ks[0], (B, Hkv * G, S, D))
    k = jax.random.normal(ks[1], (B, Hkv, T, D))
    v = jax.random.normal(ks[2], (B, Hkv, T, D))
    o1 = flash_prefill(q, k, v, causal=causal, window=window,
                       block_q=bq, block_k=bk, interpret=True)
    o2 = flash_attention(q, k, v, causal, window, 0, bk)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=3e-5, atol=3e-5)
