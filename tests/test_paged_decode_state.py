"""Paged decode threads the layer-stacked KV pool through the layer scan's
carry and reads and writes it in place (DESIGN.md §5).

* `decode_scan` over a paged state must equal, bit for bit, the layer loop
  that slices each layer's params and cache out of the stack as scan
  xs/ys and re-stacks them (the oracle below): the same pages are read,
  the same token appended, the same page flushed at the same step.
* The scheduler's decode-scan program must hold no copy, broadcast or
  dynamic slice of a whole stacked pool or of one layer's pool.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import PagedQuantizedKVCache
from repro.models import transformer as T
from repro.serving import ContinuousBatcher, EngineConfig

KV_DTYPES = ["int8", "fp8_e4m3", "int4"]
B, MAX_LEN, STEPS = 4, 32, 4
# rows 0-2 flush a page at steps 1, 2 and 3; row 3 is masked out
LENGTHS = np.asarray([6, 13, 20, 0], np.int32)
ROW_MASK = np.asarray([True, True, True, False])


def _cfg():
    """Two layer groups of two attention blocks plus one tail block."""
    return dataclasses.replace(get_config("internlm2_1_8b", smoke=True),
                               block_pattern=("attn", "attn"), n_layers=5)


def _oracle_serve(params, tok, cfg, state, positions, mode, row_mask=None,
                  hist_blocks=None, valid=None, use_fused=True):
    """The layer loop as scan xs/ys: each layer's params and cache are
    sliced out of the stack, run through `_block_serve`, and re-stacked."""
    x, positions = T._embed(params, tok, cfg, positions)
    period, n_groups, _ = T._pattern_layout(cfg)
    per_layer = []
    for g in range(n_groups):
        gp = jax.tree.map(lambda a: a[g], params["blocks"])
        out = {}
        for i, kind in enumerate(cfg.block_pattern):
            cache = jax.tree.map(lambda a: a[g], state[f"p{i}"])
            x, out[f"p{i}"] = T._block_serve(
                gp[f"p{i}"], x, kind, cfg, positions, cache, mode, row_mask)
        per_layer.append(out)
    new_state = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    new_state["tail"] = []
    for j, bp in enumerate(params["tail"]):
        kind = cfg.block_kind(n_groups * period + j)
        x, c = T._block_serve(bp, x, kind, cfg, positions, state["tail"][j],
                              mode, row_mask)
        new_state["tail"].append(c)
    return T._head(params, x, cfg), new_state


def _fill(cache: PagedQuantizedKVCache, key) -> PagedQuantizedKVCache:
    """Random pages, scales and residuals; row b maps blocks to pages
    1 + b * NT + t, and holds LENGTHS[b] tokens."""
    ks = jax.random.split(key, 6)
    pool = cache.pool

    def vals(k, a):
        if a.dtype == jnp.int8:
            return jax.random.randint(k, a.shape, -128, 128).astype(a.dtype)
        return jax.random.normal(k, a.shape).astype(a.dtype)

    scale = lambda k, a: jax.random.uniform(k, a.shape, minval=0.01,
                                            maxval=0.1)
    nt = cache.page_table.shape[-1]
    table = 1 + np.arange(B * nt, dtype=np.int32).reshape(B, nt)
    resid = lambda k, a: jax.random.normal(k, a.shape).astype(a.dtype)
    return dataclasses.replace(
        cache,
        pool=dataclasses.replace(pool, k_q=vals(ks[0], pool.k_q),
                                 v_q=vals(ks[1], pool.v_q),
                                 k_s=scale(ks[2], pool.k_s),
                                 v_s=scale(ks[3], pool.v_s)),
        page_table=jnp.broadcast_to(table, cache.page_table.shape),
        resid_k=resid(ks[4], cache.resid_k),
        resid_v=resid(ks[5], cache.resid_v),
        length=jnp.broadcast_to(LENGTHS, cache.length.shape))


def _state(cfg, kv_dtype, key):
    state = T.init_decode_state(cfg, B, MAX_LEN, paged=True,
                                n_pages=1 + B * MAX_LEN // 8,
                                kv_cache_dtype=kv_dtype)
    keys = iter(jax.random.split(key, 8))
    return jax.tree.map(
        lambda c: _fill(c, next(keys)), state,
        is_leaf=lambda c: isinstance(c, PagedQuantizedKVCache))


@pytest.mark.parametrize("sampled", [False, True])
@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_decode_scan_matches_sliced_layer_loop(monkeypatch, kv_dtype,
                                               sampled):
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    state = _state(cfg, kv_dtype, jax.random.PRNGKey(1))
    assert state["p0"].pool.k_q.shape[0] == 2 and len(state["tail"]) == 1
    tok = jnp.asarray([[3], [17], [99], [5]], jnp.int32)
    pos = jnp.asarray(LENGTHS)
    sampling = None
    if sampled:
        sampling = {"temperature": jnp.asarray([0.0, 0.9, 1.2, 0.7]),
                    "top_k": jnp.asarray([0, 12, 0, 5], jnp.int32),
                    "top_p": jnp.asarray([1.0, 0.9, 1.0, 0.8]),
                    "key": jax.random.bits(jax.random.PRNGKey(2), (B, 2),
                                           jnp.uint32),
                    "step": jnp.ones((B,), jnp.int32)}

    def scan(p, t, s, q, m, sm):
        return T.decode_scan(p, t, cfg, s, q, steps=STEPS, row_mask=m,
                             sampling=sm)

    args = (params, tok, state, pos, jnp.asarray(ROW_MASK), sampling)
    got = jax.jit(scan)(*args)
    with monkeypatch.context() as mp:
        mp.setattr(T, "_serve", _oracle_serve)
        want = jax.jit(scan)(*args)
    # the scan flushed pages and left the masked row alone
    lengths = np.asarray(got[1]["p0"].length)
    assert (lengths == LENGTHS + STEPS * ROW_MASK).all()
    flat_got, tree_got = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree_got == tree_want
    for a, b in zip(flat_got, flat_want):
        assert a.dtype == b.dtype and a.shape == b.shape
        bits = lambda x: np.atleast_1d(np.asarray(x)).view(np.uint8)
        np.testing.assert_array_equal(bits(a), bits(b))


def _pool_shapes(state):
    """HLO shape strings of the stacked pools and of one layer's pool."""
    names = {jnp.dtype(jnp.int8): "s8", jnp.dtype(jnp.float32): "f32",
             jnp.dtype(jnp.float8_e4m3fn): "f8e4m3fn"}
    out = set()
    for a in (state["p0"].pool.k_q, state["p0"].pool.k_s):
        for shape in (a.shape, a.shape[1:]):
            out.add(f"{names[jnp.dtype(a.dtype)]}"
                    f"[{','.join(map(str, shape))}]")
    return out


def test_decode_scan_program_never_copies_the_pool():
    """Compiled for the CPU, the scheduler's decode scan (`jit_run`) has no
    copy, broadcast or dynamic slice producing a whole stacked pool or one
    layer's pool: the state is donated and the pools are carried whole."""
    cfg = get_config("internlm2_1_8b", smoke=True)
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    b = ContinuousBatcher(params, cfg, EngineConfig(batch=2, max_len=64,
                                                    paged=True))
    state = jax.eval_shape(lambda: b._init_state(2))
    S = jax.ShapeDtypeStruct
    i32 = lambda *shape: S(shape, jnp.int32)
    hlo = b._chunk_fn(4).lower(params, i32(2, 1), state, i32(2),
                               S((2,), bool), None).compile().as_text()
    shapes = _pool_shapes(state)
    assert len(shapes) == 4
    ops_on_pool = [
        line.strip() for line in hlo.splitlines()
        if re.search(r"=\s*(\S+)\{?[^ ]*\s+(copy|broadcast|dynamic-slice)\(",
                     line)
        and re.search(r"=\s*(\S+?)(\{|\s)", line).group(1) in shapes]
    assert not ops_on_pool, "\n".join(ops_on_pool)
