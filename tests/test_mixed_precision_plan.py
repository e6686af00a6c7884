"""Adaptive per-layer mixed-precision plans (DESIGN.md §10).

Pins the plan lifecycle end to end: `PrecisionPlan` round-trips and
validation, uniform-plan collapse (an all-int8 plan IS the default
engine, bitwise), per-layer pool parity on an {int8, int4} alternating
plan (each dtype's quantization path inside a mixed stack is exactly the
uniform path — first-layer pools compare bitwise against the uniform
engines, per-layer page geometry matches the corresponding uniform
pools), flip/retrace semantics (mid-flight plan flips raise like uniform
flips; idle flips rebuild and match a freshly-born plan engine), and the
submit-time contract (a request declaring any uniform dtype contradicts
a mixed plan and is rejected before mutation)."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.paging as PG
import repro.core.quantization as Q
from repro.configs import get_config
from repro.models import transformer as Tm
from repro.serving import engine as E

jax.config.update("jax_platform_name", "cpu")

PLAN2 = ("int8", "int4")                 # the smoke model's 2 layers


@pytest.fixture(scope="module")
def serving_model():
    cfg = get_config("internlm2_1_8b", smoke=True)
    return cfg, Tm.init_params(cfg, jax.random.PRNGKey(2))


def _prompts(cfg, n=2, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab, (11,)).astype(np.int32)
            for _ in range(n)]


def _run_requests(b, prompts, uid0=0, max_new=5):
    from repro.serving import Request, SamplingParams
    for i, p in enumerate(prompts):
        b.submit(Request(uid=uid0 + i, prompt=np.asarray(p, np.int32),
                         sampling=SamplingParams.greedy(
                             max_new_tokens=max_new)))
    done = b.run_to_completion(max_ticks=400)
    assert len(done) == len(prompts)
    return {r.uid - uid0: r.generated for r in done}


# -- PrecisionPlan: schema, validation, resolver -----------------------------

def test_precision_plan_roundtrip_and_validation(tmp_path):
    plan = Q.PrecisionPlan(PLAN2, ppl_budget_pct=1.0,
                           measured_delta_pct=0.01)
    rt = Q.PrecisionPlan.from_json(plan.to_json())
    assert rt.layer_dtypes == PLAN2 and rt.ppl_budget_pct == 1.0
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(plan.to_json()))
    assert Q.PrecisionPlan.load(str(p)).layer_dtypes == PLAN2

    with pytest.raises(Q.QuantizationError, match="unknown kv dtype"):
        Q.PrecisionPlan(("int8", "int3"))
    with pytest.raises(Q.QuantizationError, match="0..1"):
        Q.PrecisionPlan.from_json(
            {"layers": [{"layer": 0, "kv_dtype": "int8"},
                        {"layer": 2, "kv_dtype": "int4"}]})
    with pytest.raises(Q.QuantizationError, match="not found"):
        Q.PrecisionPlan.load(str(tmp_path / "missing.json"))


def test_resolver_collapses_uniform_and_validates_layers(tmp_path):
    # uniform collapse: plans with one dtype ARE that dtype downstream
    assert Q.resolve_kv_dtype_spec(("int4", "int4")) == "int4"
    assert Q.resolve_kv_dtype_spec(Q.PrecisionPlan(("int8",) * 3)) == "int8"
    assert Q.resolve_kv_dtype_spec(PLAN2) == PLAN2
    assert Q.resolve_kv_dtype_spec(
        {"layer_dtypes": list(PLAN2)}, n_layers=2) == PLAN2
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(Q.PrecisionPlan(PLAN2).to_json()))
    assert Q.resolve_kv_dtype_spec(str(p)) == PLAN2
    with pytest.raises(Q.QuantizationError, match="2 layers"):
        Q.resolve_kv_dtype_spec(PLAN2, n_layers=4)
    with pytest.raises(Q.QuantizationError, match="unknown kv_cache_dtype"):
        Q.resolve_kv_dtype_spec("itn8")
    assert Q.layer_kv_dtypes("int8", 3) == ("int8",) * 3
    assert Q.layer_kv_dtypes(PLAN2, 2) == PLAN2


def test_engine_config_accepts_every_plan_form(tmp_path):
    from repro.serving import EngineConfig
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(Q.PrecisionPlan(PLAN2).to_json()))
    for spec in (PLAN2, list(PLAN2), Q.PrecisionPlan(PLAN2),
                 {"layer_dtypes": list(PLAN2)}, str(p)):
        ec = EngineConfig(paged=True, kv_cache_dtype=spec)
        assert ec.kv_cache_dtype == PLAN2
    # uniform plans collapse at construction — all-int8 needs no paged
    assert EngineConfig(kv_cache_dtype=("int8", "int8")).kv_cache_dtype \
        == "int8"
    with pytest.raises(ValueError, match="paged"):
        EngineConfig(kv_cache_dtype=PLAN2)            # mixed needs paged
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        EngineConfig(kv_cache_dtype="itn8")


# -- mixed state: per-layer pools, alternating-plan parity -------------------

def _ident_tables(c, batch):
    nt = c.max_len // c.pool.page_size
    tbl = (1 + jnp.arange(batch * nt, dtype=jnp.int32)).reshape(batch, nt)
    return dataclasses.replace(c, page_table=tbl)


def _drive(cfg, params, spec, toks):
    """Chunk-prefill + two decode steps over identity-mapped tables;
    returns the final state (uniform: stacked; mixed: lists)."""
    B, S = toks.shape
    state = Tm.init_decode_state(cfg, B, 64, paged=True,
                                 kv_cache_dtype=spec)
    if isinstance(state["p0"], list):
        state = {"p0": [_ident_tables(c, B) for c in state["p0"]],
                 "tail": []}
    else:
        sk = state["p0"]
        unstacked = [_ident_tables(jax.tree.map(lambda a: a[g], sk), B)
                     for g in range(sk.page_table.shape[0])]
        state = {"p0": jax.tree.map(lambda *xs: jnp.stack(xs), *unstacked),
                 "tail": []}
    fn = E.make_chunk_prefill_fn(cfg, hist_blocks=4, kv_cache_dtype=spec)
    rm = jnp.ones((B,), bool)
    logits, state = jax.jit(fn)(params, toks, state,
                                jnp.zeros((B,), jnp.int32),
                                jnp.full((B,), S, jnp.int32), rm)
    # decode a FIXED token stream (not argmax): layer-0 inputs then only
    # depend on the tokens, so layer-0 writes stay comparable across
    # engines whose deeper layers (and hence logits) differ
    for i in range(2):
        tok = jnp.full((B, 1), 7 + i, jnp.int32)
        logits, state = Tm.decode_step(params, tok, cfg, state,
                                       jnp.full((B,), S + i, jnp.int32),
                                       row_mask=rm)
    return state


def _layer_cache(state, g):
    v = state["p0"]
    return v[g] if isinstance(v, list) else jax.tree.map(lambda a: a[g], v)


@pytest.mark.parametrize("plan", [("int8", "int4"), ("int4", "int8")])
def test_alternating_plan_first_layer_bitwise_vs_uniform(serving_model,
                                                         plan):
    """Each dtype inside a mixed stack quantizes exactly like its uniform
    engine: layer 0 sees identical inputs in the mixed and uniform runs,
    so its pool contents (pages, scales, residual) must compare BITWISE
    against the same-dtype uniform engine's layer 0."""
    cfg, params = serving_model
    toks = jnp.asarray(np.random.RandomState(5).randint(
        0, cfg.vocab, (2, 16)), jnp.int32)
    mixed = _drive(cfg, params, plan, toks)
    uni = _drive(cfg, params, plan[0], toks)
    got, want = _layer_cache(mixed, 0), _layer_cache(uni, 0)
    for field in ("k_q", "k_s", "v_q", "v_s"):
        # page 0 is the reserved sentinel: non-flushing decode scatters
        # redirect there, so its contents are garbage by design and
        # depend on scatter ordering (scan vs the mixed unrolled loop)
        a = np.asarray(getattr(got.pool, field))[1:]
        b = np.asarray(getattr(want.pool, field))[1:]
        assert a.dtype == b.dtype and np.array_equal(a, b), \
            f"layer 0 pool.{field} diverged from uniform {plan[0]}"
    for field in ("resid_k", "resid_v", "length"):
        assert np.array_equal(np.asarray(getattr(got, field)),
                              np.asarray(getattr(want, field))), \
            f"layer 0 {field} diverged from uniform {plan[0]}"


def test_alternating_plan_per_layer_pool_geometry(serving_model):
    """Every layer's pool in a mixed stack is structurally the
    corresponding uniform pool: same storage dtype, same packed token
    axis, same per-page bytes as a pool built uniformly at that layer's
    dtype."""
    cfg, params = serving_model
    state = Tm.init_decode_state(cfg, 2, 64, paged=True,
                                 kv_cache_dtype=PLAN2)
    for g, dt in enumerate(PLAN2):
        c = state["p0"][g]
        u = Tm.init_decode_state(cfg, 2, 64, paged=True,
                                 kv_cache_dtype=dt)["p0"]
        uc = jax.tree.map(lambda a: a[g], u)
        assert c.pool.kv_dtype == dt
        assert c.pool.k_q.dtype == uc.pool.k_q.dtype
        assert c.pool.k_q.shape == uc.pool.k_q.shape
        ps = c.pool.page_size
        # (n_pages, H_kv, tokens_packed, D)
        assert c.pool.k_q.shape[2] == Q.packed_tokens(ps, dt)
        assert PG.page_bytes_for(ps, cfg.n_kv_heads, cfg.head_dim, dt) \
            == PG.page_bytes_for(ps, cfg.n_kv_heads, cfg.head_dim,
                                 uc.pool.kv_dtype)


def test_all_int8_plan_is_bitwise_default_engine(serving_model):
    """Uniform collapse acceptance: an all-int8 plan generates exactly
    what the default engine does — same trace-cache keys, same tokens."""
    from repro.serving import ContinuousBatcher, EngineConfig
    cfg, params = serving_model
    prompts = _prompts(cfg)
    got_plan = _run_requests(ContinuousBatcher(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, prefill_chunk=8,
        kv_cache_dtype=("int8", "int8"))), prompts)
    got_default = _run_requests(ContinuousBatcher(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, prefill_chunk=8)), prompts)
    assert got_plan == got_default


# -- serving: flips, trace keys, submit contract, prefix cache ---------------

def test_mixed_plan_serves_and_keys_traces_on_spec(serving_model):
    """A mixed engine drains requests; its chunk/decode trace caches key
    on the full per-layer tuple (so a flip back to uniform reuses nothing
    stale), and pool_report carries the weighted capacity metrics."""
    from repro.serving import ContinuousBatcher, EngineConfig
    cfg, params = serving_model
    b = ContinuousBatcher(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, prefill_chunk=8,
        kv_cache_dtype=PLAN2))
    got = _run_requests(b, _prompts(cfg))
    assert all(len(v) == 5 for v in got.values())
    assert {dt for _, _, dt in b._chunk_prefill_fns} == {PLAN2}
    assert {dt for _, dt in b._chunk_fns} == {PLAN2}
    rep = b.pool_report()
    assert rep["kv_cache_dtype"] == "mixed"
    assert rep["kv_cache_layer_dtypes"] == list(PLAN2)
    pb = lambda dt: PG.page_bytes_for(b.page_size, cfg.n_kv_heads,
                                      cfg.head_dim, dt)
    want_ratio = 2 * pb("int8") / (pb("int8") + pb("int4"))
    assert rep["pages_vs_int8_equal_hbm"] == pytest.approx(want_ratio)
    assert rep["kv_page_bytes_saved_vs_int8_frac"] == pytest.approx(
        1 - (pb("int8") + pb("int4")) / (2 * pb("int8")))
    # deterministic: a fresh engine born on the same plan matches
    fresh = ContinuousBatcher(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, prefill_chunk=8,
        kv_cache_dtype=PLAN2))
    assert got == _run_requests(fresh, _prompts(cfg))


def test_plan_flip_midflight_raises_idle_rebuilds(serving_model):
    """A plan flip is a backend flip: with rows resident it raises like
    the uniform flip; on an idle engine it rebuilds, and post-flip output
    matches an engine born on the plan."""
    from repro.serving import (ContinuousBatcher, EngineConfig, Request,
                               SamplingParams)
    cfg, params = serving_model
    prompts = _prompts(cfg)
    b = ContinuousBatcher(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, chunk=1))
    b.submit(Request(uid=0, prompt=prompts[0],
                     sampling=SamplingParams.greedy(max_new_tokens=8)))
    b.step()
    b.step()
    assert any(r is not None for r in b.rows)
    b.config.kv_cache_dtype = PLAN2
    with pytest.raises(RuntimeError, match="resident"):
        b.step()
    b.config.kv_cache_dtype = "int8"     # flip back: drains normally
    b.run_to_completion(max_ticks=400)
    # idle now: the plan flip takes effect and matches a plan-born engine
    b.config.kv_cache_dtype = PLAN2
    got_flip = _run_requests(b, prompts, uid0=10)
    fresh = ContinuousBatcher(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, kv_cache_dtype=PLAN2))
    assert got_flip == _run_requests(fresh, prompts, uid0=10)


def test_submit_rejects_dtype_contradicting_plan(serving_model):
    """A mixed engine owns layer precision: ANY uniform SamplingParams
    dtype contradicts the plan and is rejected before mutation — even
    a dtype the plan uses somewhere."""
    from repro.serving import (ContinuousBatcher, EngineConfig, Request,
                               SamplingParams)
    cfg, params = serving_model
    b = ContinuousBatcher(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, kv_cache_dtype=PLAN2))
    for dt in Q.KV_DTYPES:
        with pytest.raises(ValueError, match="plan"):
            b.submit(Request(uid=0, prompt=_prompts(cfg)[0],
                             sampling=SamplingParams.greedy(
                                 max_new_tokens=4, kv_cache_dtype=dt)))
        assert not b.queue               # validation-before-mutation
    # None defers to the plan and is accepted
    b.submit(Request(uid=1, prompt=_prompts(cfg)[0],
                     sampling=SamplingParams.greedy(max_new_tokens=4)))
    assert b.run_to_completion(max_ticks=400)


def test_prefix_hit_equals_miss_on_mixed_plan(serving_model):
    """Prefix-cache hit and miss stay bitwise-equal on a mixed stack —
    shared pages live per-layer in same-dtype pools, so the hash chain
    and CoW invariants hold unchanged (DESIGN.md §10)."""
    from repro.serving import ContinuousBatcher, EngineConfig
    cfg, params = serving_model
    prompt = _prompts(cfg, n=1)[0]
    b = ContinuousBatcher(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, prefix_cache=True,
        prefill_chunk=8, kv_cache_dtype=PLAN2))
    miss = _run_requests(b, [prompt], uid0=0)
    hits0 = b.allocator.hits
    hit = _run_requests(b, [prompt], uid0=5)
    assert b.allocator.hits > hits0, "second run must hit the prefix"
    assert miss[0] == hit[0], "hit and miss diverged on the mixed stack"
