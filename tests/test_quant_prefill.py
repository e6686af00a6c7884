"""Fused varlen flash-prefill over the INT8 page pool (DESIGN.md §5/§7).

`_chunk_attention` (models/attention.py) is the pinned parity oracle: the
retired dequantize-gather concat-softmax. These tests drive BOTH fused
implementations — the Pallas kernel in interpret mode and its XLA
split-flash twin — against it across the varlen ragged edge a chunked
dispatch actually sees: per-row history depths from 0 through the pow2
dispatch bound, per-row `valid` chunk widths from 1 through C, all mixed
inside ONE dispatch. Plus the structural acceptance asserts: one
pallas_call per dispatch, dead-page DMA clamping invisible to results,
the DMA-skip metric, the oracle's bf16 history option, and the scheduler
never serving a stale trace after the fused toggle flips mid-process.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import paging as PG
from repro.core import quantization as Q
from repro.kernels import ops
from repro.kernels import quant_prefill as QP
from repro.models import attention

jax.config.update("jax_platform_name", "cpu")

B, HKV, G, C, D, PS = 4, 2, 3, 16, 32, 8
NB = 4                       # history pages per row in the pool fixture
H = HKV * G

# per-row ragged edge, all inside one dispatch (hist_blocks = NB = pow2):
# hist_len 0 / one page / partial cursor / the pow2 boundary;
# valid C / 1 / C-1 / C
HIST_LEN = np.asarray([0, PS, 2 * PS, NB * PS], np.int32)
VALID = np.asarray([C, 1, C - 1, C], np.int32)


def _fixture(seed=0):
    """Chunk q/k/v plus a paged INT8 history pool with NB pages per row."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, H, C, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, HKV, C, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, HKV, C, D), jnp.float32)
    hk = jax.random.normal(ks[3], (B, HKV, NB * PS, D), jnp.float32)
    hv = jax.random.normal(ks[4], (B, HKV, NB * PS, D), jnp.float32)
    kq, kss = Q.quantize_blocked(hk, PS)
    vq, vs = Q.quantize_blocked(hv, PS)
    pk, pks, pv, pvs, table = PG.scatter_to_pool(kq, kss, vq, vs)
    return q, k, v, (pk, pks, pv, pvs, table)


def _oracle(q, k, v, pool, hist_len, nb):
    """The retired path, verbatim: gather + dequantize + concat softmax."""
    pk, pks, pv, pvs, table = pool
    hk = hv = None
    if nb:
        gkq, gks, gvq, gvs = PG.gather_pages(pk, pks, pv, pvs,
                                             table[:, :nb])
        hk = Q.dequantize_blocked(gkq, gks)
        hv = Q.dequantize_blocked(gvq, gvs)
    return attention._chunk_attention(q, k, v, hk, hv,
                                      jnp.asarray(hist_len, jnp.int32))


def _assert_valid_rows_close(out, expect, valid, **tol):
    """Outputs at query positions past `valid` are garbage by contract —
    compare only each row's true chunk tokens."""
    for b in range(out.shape[0]):
        np.testing.assert_allclose(np.asarray(out[b, :, :valid[b]]),
                                   np.asarray(expect[b, :, :valid[b]]),
                                   **tol)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("hist_blocks", [0, 1, 3, NB])
def test_fused_prefill_parity_mixed_ragged(impl, hist_blocks):
    """Both fused impls vs the concat-softmax oracle, with every ragged
    case (hist 0 / one page / partial cursor / pow2 boundary x valid
    1 / C-1 / C) riding in ONE dispatch, at history bounds 0 (first
    chunk), 1, non-pow2 3, and the full pool."""
    q, k, v, pool = _fixture()
    hist_len = np.minimum(HIST_LEN, hist_blocks * PS)
    out = ops.paged_attention_prefill(
        q, k, v, *pool, jnp.asarray(hist_len), jnp.asarray(VALID),
        hist_blocks=hist_blocks, impl=impl)
    expect = _oracle(q, k, v, pool, hist_len, hist_blocks)
    _assert_valid_rows_close(out, expect, VALID, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_fused_prefill_valid_none_is_full_chunk(impl):
    q, k, v, pool = _fixture(1)
    out = ops.paged_attention_prefill(q, k, v, *pool,
                                      jnp.asarray(HIST_LEN), None,
                                      hist_blocks=NB, impl=impl)
    expect = _oracle(q, k, v, pool, HIST_LEN, NB)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


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


def test_fused_prefill_is_single_pallas_call():
    """Acceptance: one chunk-prefill dispatch is exactly ONE pallas_call
    over the (B, Hkv, hist_blocks + 1) grid — no vmap/Python fan-out
    (mirror of the decode kernel's assert in test_kernels.py)."""
    q, k, v, pool = _fixture()
    jaxpr = jax.make_jaxpr(
        lambda *a: QP.paged_attention_prefill(*a, hist_blocks=NB,
                                              interpret=True))(
        q, k, v, *pool, jnp.asarray(HIST_LEN), jnp.asarray(VALID))
    assert _pallas_grids(jaxpr) == [(B, HKV, NB + 1)]


def test_fused_prefill_skip_dead_invisible():
    """The index_map clamp re-streams a resident page for dead history
    steps; pl.when drops their compute — results must be bit-identical
    with the clamp off."""
    q, k, v, pool = _fixture(2)
    a = QP.paged_attention_prefill(q, k, v, *pool, jnp.asarray(HIST_LEN),
                                   jnp.asarray(VALID), hist_blocks=NB,
                                   skip_dead=True, interpret=True)
    b = QP.paged_attention_prefill(q, k, v, *pool, jnp.asarray(HIST_LEN),
                                   jnp.asarray(VALID), hist_blocks=NB,
                                   skip_dead=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flash_prefill_skip_dead_invisible():
    """Satellite: the same clamp ported to the dense flash-prefill kernel
    (kernels/flash_fwd.py) — causally-dead kv blocks stop streaming, with
    bit-identical outputs."""
    from repro.kernels import flash_fwd as FF
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, 4, 32, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, 2, 32, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, 2, 32, 16), jnp.float32)
    a = FF.flash_prefill(q, k, v, block_q=8, block_k=8, skip_dead=True,
                         interpret=True)
    b = FF.flash_prefill(q, k, v, block_q=8, block_k=8, skip_dead=False,
                         interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_oracle_accepts_bf16_history():
    """Satellite: `dequantized_prefix` gathers into a caller-chosen dtype —
    bf16 halves the oracle's HBM footprint while logits still accumulate
    in f32 inside `_chunk_attention`."""
    q, k, v, pool = _fixture(3)
    pk, pks, pv, pvs, table = pool
    pool_obj = PG.PagePool(k_q=pk, v_q=pv, k_s=pks, v_s=pvs,
                           free_stack=jnp.arange(pk.shape[0], dtype=jnp.int32),
                           n_free=jnp.asarray(0, jnp.int32), page_size=PS)
    resid = jnp.zeros((B, HKV, PS, D), jnp.float32)
    cache = PG.PagedQuantizedKVCache(pool_obj, table, resid,
                                     jnp.copy(resid),
                                     jnp.asarray(HIST_LEN))
    hk32, hv32 = cache.dequantized_prefix(NB, jnp.float32)
    hkbf, hvbf = cache.dequantized_prefix(NB, jnp.bfloat16)
    assert hkbf.dtype == jnp.bfloat16 and hvbf.dtype == jnp.bfloat16
    out32 = attention._chunk_attention(q, k, v, hk32, hv32,
                                       jnp.asarray(HIST_LEN))
    outbf = attention._chunk_attention(q, k, v, hkbf, hvbf,
                                       jnp.asarray(HIST_LEN))
    np.testing.assert_allclose(np.asarray(outbf), np.asarray(out32),
                               rtol=2e-2, atol=2e-2)


# -- scheduler integration: the fused toggle and trace identity ------------

def _serving_model():
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config("internlm2_1_8b", smoke=True)
    return cfg, T.init_params(cfg, jax.random.PRNGKey(2))


def _run_one(b, prompt, uid):
    from repro.serving import Request
    b.submit(Request(uid=uid, prompt=np.asarray(prompt, np.int32),
                     max_new_tokens=4))
    done = b.run_to_completion(max_ticks=400)
    assert len(done) == 1
    return done[0].generated


def test_fused_toggle_no_stale_trace():
    """Satellite: `use_fused_prefill` is part of the chunk-prefill-fn cache
    key — flipping it on a live scheduler compiles a fresh trace for the
    same hist_blocks bucket instead of serving the stale one, and greedy
    output is identical either way."""
    from repro.serving import ContinuousBatcher, EngineConfig
    cfg, params = _serving_model()
    assert EngineConfig().use_fused_prefill is True      # fused is default-on
    b = ContinuousBatcher(params, cfg, EngineConfig(
        batch=1, max_len=64, paged=True, prefill_chunk=8))
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, cfg.vocab, (29,)).astype(np.int32)
    got_fused = _run_one(b, prompt, 0)
    fused_keys = set(b._chunk_prefill_fns)
    assert fused_keys and all(f is True for _, f, _dt in fused_keys)
    b.config.use_fused_prefill = False
    got_oracle = _run_one(b, prompt, 1)
    oracle_keys = set(b._chunk_prefill_fns) - fused_keys
    assert oracle_keys and all(f is False for _, f, _dt in oracle_keys)
    # same hist_blocks buckets were re-traced, not reused
    assert {hb for hb, _, _ in oracle_keys} <= \
        {hb for hb, _, _ in fused_keys}
    assert got_fused == got_oracle


def test_hit_equals_miss_with_fused_prefill():
    """Satellite: prefix-cache hit vs miss stays bitwise-equal with the
    fused path explicitly on — a hit chunk attends over adopted pages
    through the same kernel a miss chunk uses for self-filled pages."""
    from repro.serving import ContinuousBatcher, EngineConfig
    cfg, params = _serving_model()
    ecfg = lambda: EngineConfig(batch=1, max_len=64, paged=True,
                                prefix_cache=True, prefill_chunk=8,
                                use_fused_prefill=True)
    rng = np.random.RandomState(11)
    shared = rng.randint(0, cfg.vocab, (16,)).astype(np.int32)
    pb = np.concatenate([shared, rng.randint(0, cfg.vocab, (5,))]) \
        .astype(np.int32)
    b_hit = ContinuousBatcher(params, cfg, ecfg())
    _run_one(b_hit, np.concatenate(
        [shared, rng.randint(0, cfg.vocab, (3,))]).astype(np.int32), 0)
    h0 = b_hit.allocator.hits
    got_hit = _run_one(b_hit, pb, 1)
    assert b_hit.allocator.hits > h0
    b_miss = ContinuousBatcher(params, cfg, ecfg())
    got_miss = _run_one(b_miss, pb, 0)
    assert got_hit == got_miss
