"""Tiered KV cache (DESIGN.md §11): host-memory swap tier, pluggable
eviction, swap-vs-recompute cost model, preempt-by-swap, and the
bitwise swap-restore guarantee — every path driven deterministically
(forced preemption, forced reclaim, seeded fault injection), never by
hoped-for pressure."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import tiering as TIER
from repro.core.paging import HostPageAllocator, PoolFaultInjector
from repro.models import transformer as T
from repro.serving import (ContinuousBatcher, EngineConfig, Request,
                           SamplingParams, kv_cache_memory_report)

jax.config.update("jax_platform_name", "cpu")

PAGE = 8


@pytest.fixture(scope="module")
def model():
    cfg = get_config("internlm2_1_8b", smoke=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return params, cfg


def _alloc_invariant(a: HostPageAllocator) -> bool:
    """free + live + evictable + deferred + in-flight partitions the
    pool (the 5-population accounting, DESIGN.md §11)."""
    pops = [set(a.free), set(a.ref), set(a.lru), set(a.deferred),
            set(a.inflight)]
    total = sum(len(p) for p in pops)
    return total == a.n_pages - 1 and len(set().union(*pops)) == total


# -- evictor policies ------------------------------------------------------
def test_lru_evictor_oldest_first():
    ev = TIER.make_evictor("lru")
    for p in (4, 7, 2):
        ev.cache(p)
    assert 7 in ev and len(ev) == 3 and set(ev) == {4, 7, 2}
    assert ev.pop_victim() == 4          # oldest cached goes first
    ev.uncache(7)                        # adoption = a hit, not an eviction
    assert ev.pop_victim() == 2
    assert len(ev) == 0


def test_freq_evictor_keeps_hit_dense_pages():
    ev = TIER.make_evictor("freq")
    ev.cache(1)
    ev.cache(2)
    for _ in range(3):                   # page 1 is adopted repeatedly
        ev.uncache(1)
        ev.cache(1)
    assert ev.hits_of(1) == 3 and ev.hits_of(2) == 0
    assert ev.pop_victim() == 2          # lowest hits/byte, not oldest
    assert ev.pop_victim() == 1
    # eviction resets stats: the physical page will hold new content
    ev.cache(1)
    assert ev.hits_of(1) == 0


def test_freq_evictor_size_aware_tiebreak():
    ev = TIER.FreqSizeEvictor()
    ev.cache(1, nbytes=1024)             # same hits, more bytes held
    ev.cache(2, nbytes=64)
    for p in (1, 2):
        ev.uncache(p)
        ev.cache(p, nbytes=1024 if p == 1 else 64)
    # equal hit counts: the big page has the lower hit density
    assert ev.pop_victim() == 1


def test_make_evictor_validates():
    with pytest.raises(ValueError, match="unknown evictor"):
        TIER.make_evictor("mru")


# -- host tier -------------------------------------------------------------
def _payload(rng):
    q = rng.randint(-128, 128, (PAGE, 2, 4)).astype(np.int8)
    s = rng.rand(2, 4).astype(np.float32)
    return [(q, s, q.copy(), s.copy())]


def test_host_tier_put_get_drop_and_capacity():
    rng = np.random.RandomState(0)
    t = TIER.HostTier(2)
    assert t.put(b"a", _payload(rng), ["int8"])
    assert t.put(b"b", _payload(rng), ["int8"])
    assert not t.put(b"a", _payload(rng), ["int8"])   # refresh, no re-copy
    assert t.demotions == 2 and len(t) == 2 and t.nbytes > 0
    t.put(b"c", _payload(rng), ["int8"])              # overflow: b is coldest
    assert t.host_evictions == 1 and b"b" not in t and b"a" in t
    rec = t.get(b"a")
    assert rec.hits == 1 and t.promotions == 1
    assert t.run_length([b"a", b"c", b"zz"]) == 2
    t.drop(b"a")
    assert t.lost == 1 and b"a" not in t
    t.drop(b"a")                                      # idempotent
    assert t.lost == 1
    with pytest.raises(ValueError):
        TIER.HostTier(0)


# -- swap-vs-recompute cost model ------------------------------------------
def test_cost_model_flips_with_copy_cost():
    cm = TIER.SwapCostModel(page_size=PAGE)
    assert cm.swap_cost(3) == 3.0 and cm.recompute_cost(3) == 3 * PAGE
    assert cm.prefer_swap(1) and cm.prefer_swap(10)
    flipped = TIER.SwapCostModel(page_size=PAGE, copy_cost_tokens=2 * PAGE)
    assert not flipped.prefer_swap(1)     # copies priced past recompute


# -- host recompression (PackKV-style) -------------------------------------
def test_repack_same_dtype_is_bitwise_and_cross_dtype_bounded():
    from repro.core import quantization as Q
    rng = np.random.RandomState(7)
    x = rng.randn(2, PAGE, 4).astype(np.float32)      # (H, ps, D)
    q8, s8 = Q.quantize_page_matrix(x, "int8")        # (H, tp, D), (H, D)
    # pool layout: heads-major values, one (1, D) scale row per head
    qp = np.asarray(q8)
    sp = np.asarray(s8)[..., None, :]
    q_same, s_same = TIER.repack_page(qp, sp, "int8", "int8")
    assert np.array_equal(q_same, qp) and np.array_equal(s_same, sp)
    # int8 -> int4 -> int8 round trip: error bounded by the sum of both
    # dtypes' analytic per-channel bounds (DESIGN.md §9, §11 caveat)
    q4, s4 = TIER.repack_page(qp, sp, "int8", "int4")
    qb, sb = TIER.repack_page(q4, s4, "int4", "int8")
    dq = lambda q, s, dt: np.asarray(Q.dequantize_pages(
        np.asarray(q), np.asarray(s), dt))
    x8, x48 = dq(qp, sp, "int8"), dq(qb, sb, "int8")
    amax = np.abs(x).max(axis=-2, keepdims=True)
    bound = amax / 127.0 + amax / 7.0 + amax / 127.0   # int8 + int4 + int8
    assert np.all(np.abs(x48 - x8) <= bound + 1e-6)


# -- engine: hit == miss through the host tier -----------------------------
def _grouped_run(model, host_pages, evictor="lru", host_tier_dtype=None,
                 n_pages=10, groups=3, rounds=2):
    """Sequential shared-prefix requests through a pool too small to keep
    every group resident: revisits either promote from the host tier
    (host_pages set) or recompute (tier off). Returns (outputs, report)."""
    params, cfg = model
    b = ContinuousBatcher(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, n_pages=n_pages, chunk=1,
        prefix_cache=True, prefill_chunk=8, watermark=1,
        host_pages=host_pages, evictor=evictor,
        host_tier_dtype=host_tier_dtype))
    rng = np.random.RandomState(0)
    shared = [rng.randint(0, cfg.vocab, (24,)).astype(np.int32)
              for _ in range(groups)]
    outs, uid = {}, 0
    for _ in range(rounds):
        for g in shared:
            p = np.concatenate([g, rng.randint(0, cfg.vocab, (4,))
                                .astype(np.int32)])
            b.submit(Request(uid=uid, prompt=p,
                             sampling=SamplingParams.greedy(
                                 max_new_tokens=4)))
            uid += 1
            for _t in range(300):
                for r in b.step():
                    outs[r.uid] = list(r.generated)
                if uid == len(outs):
                    break
            assert _alloc_invariant(b.allocator)
    return outs, b


def test_hit_equals_miss_through_host_tier(model):
    """The §11 analogue of the prefix-cache hit==miss property: a prompt
    served through demote + prefetch + promote emits the same tokens as
    one recomputed from scratch, for both evictor policies."""
    base, _ = _grouped_run(model, host_pages=None)
    for evictor in ("lru", "freq"):
        tiered, b = _grouped_run(model, host_pages=32, evictor=evictor)
        assert tiered == base
        rep = b.pool_report()
        assert rep["demotions"] > 0 and rep["promotions"] > 0
        assert rep["prefetch_page_hits"] > 0
        assert rep["page_hits"] > 0          # promoted pages became hits


def test_pool_and_memory_report_split_tiers(model):
    """Satellite: device vs host bytes split — each tier's utilization is
    against its OWN capacity (≤1), a demoted page's bytes are counted on
    exactly one tier, and `kv_cache_memory_report` carries the host keys
    (DESIGN.md §11)."""
    _, b = _grouped_run(model, host_pages=32)
    rep = b.pool_report()
    assert 0 <= rep["utilization"] <= 1
    assert 0 <= rep["host_utilization"] <= 1
    assert rep["host_pages_used"] <= rep["host_pages_capacity"] == 32
    assert rep["host_bytes"] > 0 and rep["device_bytes_live"] >= 0
    # populations partition the device pool: no page on both tiers' books
    assert rep["pages_free"] + rep["pages_cached"] + rep["pages_allocated"] \
        + rep["pages_inflight"] <= rep["pages_total"]
    assert rep["evictor"] == "lru" and rep["host_tier_dtype"] is None
    assert rep["prefetch_hit_rate"] <= 1.0
    assert rep["est_prefill_tokens_saved_by_swap"] > 0
    _, cfg = model
    mem = kv_cache_memory_report(cfg, 2, 64, scheduler=b)
    assert mem["host_tier_pages_used"] == rep["host_pages_used"]
    assert mem["host_tier_bytes"] == rep["host_bytes"]
    assert 0 <= mem["host_tier_utilization"] <= 1


def test_host_tier_dtype_recompression_runs(model):
    """`host_tier_dtype="int4"` (PackKV-style at-rest recompression): the
    engine completes and the tier reports the cheaper dtype; restores are
    lossy so token parity is NOT asserted — the §11 caveat."""
    outs, b = _grouped_run(model, host_pages=32, host_tier_dtype="int4")
    assert len(outs) == 6
    rep = b.pool_report()
    assert rep["host_tier_dtype"] == "int4"
    assert rep["demotions"] > 0 and rep["promotions"] > 0


# -- bitwise swap-restore (the tentpole guarantee) -------------------------
def _force_swap_restore(model, inj=None, host_pages=32):
    """Drive two rows (greedy + seeded) mid-decode, preempt BOTH, then
    reclaim every cached device page so re-admission cannot fast-resume
    from device residency — with a host tier the resume must swap-restore,
    without one (or with swap faults) it must recompute. Returns
    ({uid: tokens}, batcher)."""
    params, cfg = model
    b = ContinuousBatcher(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, n_pages=24, chunk=1,
        prefix_cache=True, watermark=1, host_pages=host_pages,
        fault_injector=inj))
    rng = np.random.RandomState(3)
    p0, p1 = (rng.randint(0, cfg.vocab, (n,)).astype(np.int32)
              for n in (17, 19))
    b.submit(Request(uid=0, prompt=p0,
                     sampling=SamplingParams.greedy(max_new_tokens=16)))
    b.submit(Request(uid=1, prompt=p1, sampling=SamplingParams(
        temperature=0.9, seed=7, max_new_tokens=16)))
    outs = {}
    for _ in range(200):                  # both rows decoding, >1 page deep
        for r in b.step():
            outs[r.uid] = list(r.generated)
        rows = [r for r in b.rows if r is not None]
        if len(rows) == 2 and not b.prefilling \
                and all(len(r.generated) >= 10 for r in rows):
            break
    assert not outs, "rows finished before the forced preemption"
    for i in (0, 1):
        b._preempt_row(i)
    a = b.allocator
    # reclaim every evictable page: device copies die, host copies survive
    a.release(a.alloc(len(a.free) + len(a.lru)))
    assert _alloc_invariant(a)
    for _ in range(400):
        for r in b.step():
            outs[r.uid] = list(r.generated)
        if len(outs) == 2:
            break
    assert len(outs) == 2, "preempted requests did not complete"
    return outs, b


def test_swap_restore_bitwise_parity_greedy_and_seeded(model):
    """Swap-restored preempted requests are bitwise-identical to a run
    never preempted at all — greedy AND seeded decode (the §11 restore
    guarantee: verbatim page bytes, restored residual + pending token,
    draw-index-invariant sampling)."""
    params, cfg = model
    # unpreempted baseline: same prompts/sampling, no interference
    b = ContinuousBatcher(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, n_pages=24, chunk=1,
        prefix_cache=True, watermark=1))
    rng = np.random.RandomState(3)
    p0, p1 = (rng.randint(0, cfg.vocab, (n,)).astype(np.int32)
              for n in (17, 19))
    b.submit(Request(uid=0, prompt=p0,
                     sampling=SamplingParams.greedy(max_new_tokens=16)))
    b.submit(Request(uid=1, prompt=p1, sampling=SamplingParams(
        temperature=0.9, seed=7, max_new_tokens=16)))
    base = {r.uid: list(r.generated)
            for r in b.run_to_completion(max_ticks=600)}
    assert len(base) == 2

    swapped, bs = _force_swap_restore(model)
    assert swapped == base                  # bitwise: greedy and seeded
    rep = bs.pool_report()
    assert rep["preempt_by_swap"] >= 1      # the preempt-by-swap arm ran
    assert rep["preempt_swap_restores"] >= 1
    assert rep["promotions"] >= 1

    # same forced scenario with NO host tier: the device pages are gone,
    # so resume must recompute — streams still match (pending-token
    # restore), but no swap restore is possible
    recomputed, br = _force_swap_restore(model, host_pages=None)
    assert recomputed == base
    assert br.pool_report()["preempt_recompute_resumes"] >= 1


def test_swap_fault_falls_back_to_recompute(model):
    """p_swap_fail=1: every prefetch attempt loses the host record — the
    resume falls back to recompute-resume instead of stalling, and the
    streams still match the unpreempted run (DESIGN.md §11)."""
    inj = PoolFaultInjector(seed=5, p_swap_fail=1.0)
    faulted, bf = _force_swap_restore(model, inj=inj)
    clean, _ = _force_swap_restore(model)
    assert faulted == clean
    rep = bf.pool_report()
    assert rep["injected_swap_faults"] >= 1
    assert rep["host_lost_records"] >= 1
    assert rep["preempt_swap_restores"] == 0
    assert rep["preempt_recompute_resumes"] >= 1


def test_swap_delay_rides_inflight_population(model):
    """swap_delay > 0: promotion copies park in the in-flight population
    (neither free, cached, referenced, nor deferred) and the request
    swap-waits — visible in the stuck report — until `tick` completes
    them; the restored stream is unchanged (DESIGN.md §11)."""
    inj = PoolFaultInjector(seed=5, swap_delay=3)
    params, cfg = model
    b = ContinuousBatcher(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, n_pages=24, chunk=1,
        prefix_cache=True, watermark=1, host_pages=32,
        fault_injector=inj))
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, cfg.vocab, (17,)).astype(np.int32)
    b.submit(Request(uid=0, prompt=prompt,
                     sampling=SamplingParams.greedy(max_new_tokens=16)))
    for _ in range(200):
        b.step()
        r = b.rows[0]
        if r is not None and 0 not in b.prefilling \
                and len(r.generated) >= 10:
            break
    b._preempt_row(0)
    a = b.allocator
    a.release(a.alloc(len(a.free) + len(a.lru)))
    saw_wait = False
    outs = {}
    for _ in range(400):
        for r in b.step():
            outs[r.uid] = list(r.generated)
        if a.inflight:
            assert _alloc_invariant(a)
            assert "swap-wait" in b._stuck_report()
            saw_wait = True
        if outs:
            break
    assert saw_wait, "delayed prefetch never rode the in-flight population"
    assert not a.inflight
    clean, _ = _force_swap_restore(model)
    assert outs[0] == clean[0]


def test_deterministic_demote_promote_interleaving(model):
    """Deterministic mirror of the hypothesis interleaving (runs on bare
    containers too): demote/promote cycles through a delayed-swap injector
    keep the 5-population partition exact at every step and the in-flight
    population always drains (DESIGN.md §11)."""
    inj = PoolFaultInjector(seed=9, swap_delay=2)
    _, b = _grouped_run(model, host_pages=32)
    a, tier = b.allocator, b._tiering
    a.injector = inj
    for step in range(12):
        if step % 3 == 0 and len(a.lru):           # eager demote
            page = next(iter(a.lru))
            b._demote_to_host(page, a.hash_of[page])
        elif step % 3 == 1:                        # delayed promote
            for h in list(tier.pages):
                if h not in a.index and h not in a.inflight_digests \
                        and a.available > 0:
                    b._issue_prefetch([h], 0, 1)
                    break
        else:
            a.tick()
        assert _alloc_invariant(a)
    for _ in range(6):
        a.tick()
    assert not a.inflight and _alloc_invariant(a)


# -- config validation -----------------------------------------------------
def test_engine_config_validates_tiering_fields():
    with pytest.raises(ValueError, match="prefix_cache"):
        EngineConfig(batch=1, max_len=32, paged=True, host_pages=8)
    with pytest.raises(ValueError, match="evictor"):
        EngineConfig(batch=1, max_len=32, paged=True, prefix_cache=True,
                     host_pages=8, evictor="mru")
    with pytest.raises(ValueError, match="host_pages"):
        EngineConfig(batch=1, max_len=32, paged=True, prefix_cache=True,
                     host_tier_dtype="int4")
    with pytest.raises(ValueError):
        EngineConfig(batch=1, max_len=32, paged=True, prefix_cache=True,
                     host_pages=8, host_tier_dtype="intX")
    cfgd = EngineConfig(batch=1, max_len=32, paged=True, prefix_cache=True,
                        host_pages=8, evictor="freq",
                        host_tier_dtype="int4")
    assert cfgd.host_pages == 8
