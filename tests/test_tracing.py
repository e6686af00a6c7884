"""Host spans and counters of the serving tick (runtime/trace.py,
DESIGN.md §6): span arithmetic on a stub clock, the engine's cumulative
counters over a few ticks of a tiny paged engine, the first-token stamp
at delivery, the spans in a profiler trace recorded on the CPU, and the
names the programs and kernels carry into a device trace."""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.runtime import trace as TR
from repro.serving import EngineConfig, LLMEngine, SamplingParams

jax.config.update("jax_platform_name", "cpu")

COUNTERS = ("ticks", "tick_host_s", "admissions", "queue_wait_s",
            "first_token_s", "first_token_scan_s",
            "prefill_tokens_computed", "prefill_tokens_dispatched")
# (prompt length, decode budget): more requests than rows, so some wait
REQUESTS = [(20, 5), (9, 8), (30, 3), (12, 6), (17, 4)]


def _engine(prefix_cache=True):
    cfg = get_config("internlm2_1_8b", smoke=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, LLMEngine(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, prefix_cache=prefix_cache))


def _submit(eng, cfg, seed=0):
    rng = np.random.RandomState(seed)
    reqs = []
    for n, m in REQUESTS:
        uid = eng.add_request(rng.randint(0, cfg.vocab, (n,)),
                              SamplingParams.greedy(max_new_tokens=m))
        reqs.append(eng._live[uid])
    return reqs


def test_span_seconds_and_host_seconds_on_stub_clock():
    """Nested spans add their durations by name; a span's host seconds
    leave out the wait.* and dispatch.* spans inside it, however deep."""
    ticks = iter([0.0, 1.0, 1.5, 2.5, 3.0, 4.0, 4.5, 6.0, 7.0, 7.5, 7.5,
                  10.0])
    sp = TR.Spans(clock=lambda: next(ticks))
    with sp.span("engine.step", tick=1) as step:          # 0 .. 10
        with sp.span("sched.decode", n=4, rows=2) as dec:  # 1 .. 3
            with sp.span("dispatch.decode"):                  # 1.5 .. 2.5
                pass
        with sp.span("wait.decode"):                      # 4 .. 4.5
            pass
        with sp.span("sched.bookkeeping") as bk:          # 6 .. 7.5
            with sp.span("wait.prefill"):                 # 7 .. 7.5
                pass
    assert step.seconds == 10.0
    assert step.host_seconds == 10.0 - 1.0 - 0.5 - 0.5
    assert dec.seconds == 2.0 and dec.host_seconds == 1.0
    assert bk.seconds == 1.5 and bk.host_seconds == 1.0
    assert sp.seconds["sched.decode"] == 2.0
    assert sp.seconds["dispatch.decode"] == 1.0
    assert sp.held == 2.0
    assert sp.total("sched.decode", "wait.decode", "sched.bookkeeping",
                    "absent") == 2.0 + 0.5 + 1.5


@pytest.fixture(scope="module")
def served():
    """Every request of REQUESTS served to the end, with the engine's
    pool_report after each step, for each step its start, the end of
    each decode tick in it, and its end, and the batcher."""
    cfg, eng = _engine()
    b = eng.batcher
    decode_ends = []
    decode_tick = b._decode_tick

    def timed(*a, **kw):
        out = decode_tick(*a, **kw)
        decode_ends.append(time.perf_counter())
        return out
    b._decode_tick = timed
    reqs = _submit(eng, cfg)
    reports, steps = [eng.pool_report()], []
    for _ in range(200):
        if not eng.has_unfinished():
            break
        t0, k = time.perf_counter(), len(decode_ends)
        eng.step()
        steps.append((t0, decode_ends[k:], time.perf_counter()))
        reports.append(eng.pool_report())
    assert not eng.has_unfinished()
    return reqs, reports, steps, b


def test_counters_only_grow(served):
    _, reports, _, _ = served
    for key in COUNTERS:
        seq = [r[key] for r in reports]
        assert all(b >= a for a, b in zip(seq, seq[1:])), key
    last = reports[-1]
    assert last["ticks"] == len(reports) - 1
    assert last["admissions"] == len(REQUESTS)
    assert last["tick_host_s"] > 0 and last["queue_wait_s"] > 0


def test_prefill_dispatches_cover_the_prompts(served):
    _, reports, _, _ = served
    last = reports[-1]
    assert last["prefill_tokens_computed"] == sum(n for n, _ in REQUESTS)
    assert last["prefill_tokens_dispatched"] >= \
        last["prefill_tokens_computed"]


def test_first_token_scan_time_within_ttft(served):
    reqs, reports, _, _ = served
    last = reports[-1]
    assert 0 < last["first_token_scan_s"] <= last["first_token_s"]
    assert last["first_token_s"] == pytest.approx(
        sum(r.first_token_time - r.submit_time for r in reqs))


def test_tick_host_time_leaves_out_calls_and_waits(served):
    """tick_host_s is the steps' time less their program calls and their
    waits on device results; the first tokens' scan time leaves out the
    scan's call too."""
    _, reports, _, b = served
    seconds = b.spans.seconds
    held = sum(v for k, v in seconds.items()
               if k.startswith((TR.WAIT, TR.DISPATCH)))
    assert seconds["dispatch.prefill"] > 0 and seconds["dispatch.decode"] > 0
    assert reports[-1]["tick_host_s"] == pytest.approx(
        seconds["engine.step"] - held)
    assert b._scan_s() == pytest.approx(
        seconds["sched.decode"] - seconds["dispatch.decode"]
        + seconds["wait.decode"] + seconds["sched.bookkeeping"])


def test_first_token_stamped_at_delivery(served):
    """The first-token time falls in the step that appended the token,
    after that step's decode scan returned; the finish time is stamped
    with it, so no request decodes for a negative time."""
    reqs, _, steps, _ = served
    for r in reqs:
        t0, decode_ends, t1 = next(s for s in steps
                                   if s[0] <= r.first_token_time <= s[2])
        assert decode_ends and r.first_token_time >= decode_ends[-1]
        assert r.finish_time >= r.first_token_time
        assert r.generated


def test_preemption_requeues_and_keeps_the_first_token():
    """A preempted request waits in the queue again and is admitted
    again, so every preemption adds an admission; its first token was
    delivered once and keeps its time (DESIGN.md §8)."""
    from repro.core.paging import PoolFaultInjector
    from repro.serving import ContinuousBatcher, Request
    cfg = get_config("internlm2_1_8b", smoke=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    inj = PoolFaultInjector(seed=1)
    b = ContinuousBatcher(params, cfg, EngineConfig(
        batch=2, max_len=64, paged=True, n_pages=24, chunk=1,
        prefix_cache=True, watermark=1, fault_injector=inj))
    rng = np.random.RandomState(0)
    reqs = [Request(uid=u, prompt=rng.randint(0, cfg.vocab, (n,)).astype(
        np.int32), sampling=SamplingParams.greedy(max_new_tokens=20))
        for u, n in ((0, 9), (1, 11))]
    for r in reqs:
        b.submit(r)
    first, done = {}, 0
    for t in range(400):
        inj.hold_pages = 18 if 12 <= t < 30 else 0   # pool pressure
        done += len(b.step())
        for r in reqs:
            if r.first_token_time is not None:
                first.setdefault(r.uid, r.first_token_time)
        if done == 2:
            break
    rep = b.pool_report()
    assert rep["preemptions"] >= 1
    assert rep["admissions"] == 2 + rep["preemptions"]
    assert {r.uid: r.first_token_time for r in reqs} == first
    assert rep["first_token_s"] == pytest.approx(
        sum(r.first_token_time - r.submit_time for r in reqs))


def test_program_spans_in_a_profiler_trace(tmp_path):
    """A tick traced on the CPU: the program's spans sit on the host
    plane inside the caller's `step` span, the admission carries its uids
    and the prefill its shape, and none is named as the caller's."""
    from jax.profiler import ProfileData, TraceAnnotation
    cfg, eng = _engine()
    _submit(eng, cfg, seed=1)
    while eng.has_unfinished():          # compile every shape first
        eng.step()
    reqs = _submit(eng, cfg, seed=2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TraceAnnotation("step"):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               dict(ev.stats))
              for p in ProfileData.from_file(str(path)).planes
              if p.name.startswith("/host:")
              for line in p.lines for ev in line.events]
    outer = [e for e in events if e[0] == "step"]
    assert len(outer) == 1
    assert not [e for e in events if e[0] in ("submit", "bookkeeping")]
    _, lo, hi, _ = outer[0]
    ours = {}
    for name, s, e, stats in events:
        if name.split(".")[0] in ("engine", "sched", "cache", "wait",
                                  "dispatch"):
            assert lo <= s <= e <= hi, name
            ours.setdefault(name, stats)
    assert {"engine.step", "engine.outputs", "sched.admit", "cache.hash",
            "sched.sync", "sched.prefill", "dispatch.prefill",
            "wait.prefill"} <= set(ours)
    assert ours["engine.step"]["tick"] == eng.batcher.ticks
    admitted = str(ours["sched.admit"]["uids"]).split()
    assert admitted == [str(r.uid) for r in reqs[:2]]
    assert {"width", "hist_blocks", "rows"} <= set(ours["sched.prefill"])


@pytest.fixture(scope="module")
def lowered():
    """The three serving programs of a tiny paged engine, lowered for a
    TPU with the Pallas kernels selected (lowering needs no chip): the
    texts by program name."""
    from repro.kernels import ops
    from repro.serving import ContinuousBatcher
    cfg = get_config("internlm2_1_8b", smoke=True)
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    b = ContinuousBatcher(params, cfg, EngineConfig(batch=2, max_len=64,
                                                    paged=True))
    state = jax.eval_shape(lambda: b._init_state(2))
    S = jax.ShapeDtypeStruct
    i32 = lambda *shape: S(shape, jnp.int32)
    calls = {
        "jit_run": (b._chunk_fn(4), (params, i32(2, 1), state, i32(2),
                                     S((2,), bool), None)),
        "jit_decode_fn": (b._decode, (params, i32(2, 1), state, i32(2),
                                      S((2,), bool))),
        "jit_chunk_prefill": (b._chunk_prefill_fn(4), (
            params, i32(2, 32), state, i32(2), i32(2), S((2,), bool))),
    }
    auto = ops.resolve_impl
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "resolve_impl",
                   lambda impl="auto": "pallas" if impl == "auto"
                   else auto(impl))
        return {name: fn.trace(*args).lower(lowering_platforms=("tpu",))
                .as_text(debug_info=True)
                for name, (fn, args) in calls.items()}


@pytest.mark.parametrize("program,kernel", [
    ("jit_run", "_paged_decode"),
    ("jit_decode_fn", "_paged_decode"),
    ("jit_chunk_prefill", "_paged_prefill")])
def test_programs_and_kernels_carry_their_trace_names(lowered, program,
                                                      kernel):
    """The device trace names a program's executions after its module
    and a kernel after its pallas_call's name; the benchmark's readers
    look them up by these names."""
    text = lowered[program]
    assert re.search(rf"module @{program}\b", text)
    assert re.search(rf'loc\("{kernel}/pallas_call"', text)
