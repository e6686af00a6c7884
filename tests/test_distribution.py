"""Distribution: logical sharding rules, spec assignment, and a real
multi-device lowering on a small forced-host-device mesh."""
import os

import numpy as np
import pytest

# 8 fake devices for THIS test module only (runs in its own process under
# pytest-forked? no — guard: skip if jax already initialized with 1 device)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.parallel.shard import logical_spec, mesh_context, act_shard
from repro.launch.specs import (batch_shardings, cache_shardings,
                                param_shardings)

jax.config.update("jax_platform_name", "cpu")

needs_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs --xla_force_host_platform_device_count=8")


def _mesh():
    return make_mesh((2, 4), ("data", "model"))


@needs_devices
class TestLogicalSpec:
    def test_basic_mapping(self):
        mesh = _mesh()
        spec = logical_spec(("batch", None, "ffn"), (16, 32, 64), mesh)
        assert spec == P("data", None, "model")

    def test_divisibility_fallback(self):
        mesh = _mesh()
        # 3 doesn't divide model=4 -> replicated
        spec = logical_spec(("batch", "heads"), (16, 3), mesh)
        assert spec == P("data", None)

    def test_axis_used_once(self):
        mesh = _mesh()
        # both want "model": first dim wins, second replicates
        spec = logical_spec(("seq_shard", "ffn"), (16, 64), mesh)
        assert spec == P("model", None)

    def test_pod_axis_composes(self):
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        spec = logical_spec(("batch", None), (16, 8), mesh)
        assert spec == P(("pod", "data"), None)

    def test_batch_one_replicates(self):
        mesh = _mesh()
        spec = logical_spec(("batch", None), (1, 8), mesh)
        assert spec == P(None, None)


@needs_devices
class TestParamShardings:
    def test_param_rules_applied(self):
        from repro.configs import get_config
        from repro.models import transformer as T
        mesh = _mesh()
        cfg = get_config("llama3_2_3b", smoke=True)
        sds = jax.eval_shape(lambda k: T.init_params(cfg, k),
                             jax.random.PRNGKey(0))
        sh = param_shardings(sds, mesh)
        # stacked block wq: (n_groups, d, H*hd): trailing dims (fsdp, heads)
        wq = sh["blocks"]["p0"]["attn"]["wq"]
        assert wq.spec == P(None, "data", "model")
        # norms replicated (P() and P(None,) are equivalent)
        assert all(a is None for a in sh["final_norm"]["scale"].spec)
        # embed (Vp, d): vocab -> model, d -> fsdp(data)
        assert sh["embed"].spec == P("model", "data")

    def test_cache_rules_applied(self):
        from repro.configs import get_config
        from repro.models import transformer as T
        mesh = _mesh()
        cfg = get_config("llama3_2_3b", smoke=True)
        sds = jax.eval_shape(lambda: T.init_decode_state(cfg, 8, 64))
        sh = cache_shardings(sds, mesh)
        kq = sh["p0"].k_q      # (n_groups, B, Hkv, T, D)
        assert kq.spec == P(None, "data", None, "model", None)

    def test_paged_pool_specs(self):
        """Page pool: pages replicated (any row may map any page), kv_heads
        over model; tables/lengths batch-sharded; free list replicated."""
        from repro.core import PagedQuantizedKVCache, QuantConfig
        from repro.parallel.shard import paged_cache_specs
        mesh = _mesh()
        cfgq = QuantConfig(granularity="per_block", block_size=8)
        cache = PagedQuantizedKVCache.init(8, 4, 64, 16, cfgq, n_pages=32)
        specs = paged_cache_specs(cache, mesh)
        assert specs.pool.k_q == P(None, "model", None, None)
        assert specs.pool.k_s == P(None, "model", None, None)
        assert specs.pool.free_stack == P(None)
        assert specs.page_table == P("data", None)
        assert specs.length == P("data")

    def test_paged_pool_device_put(self):
        from repro.core import PagedQuantizedKVCache, QuantConfig
        from repro.parallel.shard import paged_cache_shardings
        mesh = _mesh()
        cfgq = QuantConfig(granularity="per_block", block_size=8)
        cache = PagedQuantizedKVCache.init(8, 4, 64, 16, cfgq, n_pages=32)
        sharded = jax.device_put(cache, paged_cache_shardings(cache, mesh))
        assert sharded.pool.k_q.sharding.spec == P(None, "model", None, None)
        assert sharded.page_table.sharding.spec == P("data", None)


@needs_devices
def test_sharded_train_step_runs():
    """End-to-end: jit a train step with explicit shardings on 8 devices and
    actually execute it (not just lower)."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.optim import AdamWConfig
    from repro.training.step import init_opt_state, make_train_step

    mesh = _mesh()
    cfg = get_config("llama3_2_3b", smoke=True)
    with mesh_context(mesh):
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        step = make_train_step(cfg, AdamWConfig(warmup_steps=1, total_steps=4))
        p_sh = param_shardings(params, mesh)
        o_sh = param_shardings(opt, mesh)
        batch = {"tokens": jnp.zeros((16, 32), jnp.int32),
                 "labels": jnp.zeros((16, 32), jnp.int32)}
        b_sh = batch_shardings(batch, mesh)
        params = jax.device_put(params, p_sh)
        opt = jax.device_put(opt, o_sh)
        batch = jax.device_put(batch, b_sh)
        fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh))
        p2, o2, metrics = fn(params, opt, batch)
        assert jnp.isfinite(metrics["loss"])
        # params stayed sharded per spec
        wq = p2["blocks"]["p0"]["attn"]["wq"]
        assert wq.sharding.spec == P(None, "data", "model")


@needs_devices
def test_sharded_decode_step_runs():
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving.engine import make_serve_fns

    mesh = _mesh()
    cfg = get_config("internlm2_1_8b", smoke=True)
    with mesh_context(mesh):
        params = T.init_params(cfg, jax.random.PRNGKey(1))
        init_state, prefill_fn, decode_fn = make_serve_fns(cfg, max_len=32)
        state = init_state(8)
        p_sh = param_shardings(params, mesh)
        s_sh = cache_shardings(state, mesh)
        params = jax.device_put(params, p_sh)
        state = jax.device_put(state, s_sh)
        toks = jnp.zeros((8, 16), jnp.int32)
        logits, state = jax.jit(prefill_fn)(params, {"tokens": toks}, state)
        tok = jnp.argmax(logits[..., :cfg.vocab], -1)[:, None]
        logits2, state = jax.jit(decode_fn)(params, tok, state,
                                            jnp.full((8,), 16, jnp.int32))
        assert not bool(jnp.any(jnp.isnan(logits2)))


@needs_devices
def test_int8_gradient_compression_numerics():
    """Compressed DP gradients converge to the same direction: error feedback
    keeps the accumulated bias bounded."""
    from repro.optim import compression as C
    key = jax.random.PRNGKey(3)
    g = {"w": jax.random.normal(key, (64, 128))}
    err = C.init_error_state(g)
    # accumulated compressed sum over steps ~ accumulated true sum
    acc_c = jnp.zeros((64, 128))
    acc_t = jnp.zeros((64, 128))
    for i in range(20):
        gi = {"w": jax.random.normal(jax.random.PRNGKey(i), (64, 128))}
        comp, err = C.compress_with_feedback(gi, err)
        acc_c += comp["w"]
        acc_t += gi["w"]
    resid = float(jnp.max(jnp.abs(acc_c - acc_t)))
    # residual bounded by one step's quantization error (feedback property)
    assert resid < 0.05, resid
