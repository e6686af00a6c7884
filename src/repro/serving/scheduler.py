"""Host-side continuous-batching scheduler (CPU logic, no jax tracing).

Maintains a fixed pool of `batch` decode rows; finished/empty rows are
refilled from a request queue between device steps. Two backends:

  * contiguous (default): the cache has one shared scalar length, so every
    row must sit at the same position. Admissions therefore *rebuild* the
    batch: all active rows' histories (prompt + generated so far) are
    left-padded to a common length and re-prefilled together with the new
    rows. This fixes the two historical bugs — rows admitted after the first
    tick were never prefilled (decoding garbage from an empty cache), and a
    finished row's cache slice leaked into the next request — at the cost of
    recomputing prefill for rows that were mid-decode.

  * paged (``paged=True``): the cache is a page pool with per-row page
    tables and per-row lengths (core/paging.py), so rows live on independent
    timelines. The scheduler allocates pages on admission (enough for the
    *unpadded* prompt plus max_new_tokens), frees them on completion, and
    admits by free-page budget instead of row count alone. Admission is
    always per-row varlen chunked prefill (below) — no prompt is ever
    padded, and mid-stream admissions write through a row mask so rows that
    are mid-decode are untouched. This is the production path
    (DESIGN.md §6).

Request lifecycle (DESIGN.md §6): queued -> prefilling -> decoding ->
finished/aborted. Each `Request` carries `SamplingParams`; the scheduler
compiles the active rows' params into per-row (B,) arrays + per-request
PRNG keys that ride INSIDE the jitted decode scan
(models/sampling.sample_at_step), so mixed greedy/sampled rows share one
dispatch per chunk and a request's tokens depend only on (prompt, params,
seed) — never on its neighbors. Stop token ids finish a row when the next
sampled token matches (the token is suppressed, as eos_id always was);
stop strings are matched host-side at chunk boundaries with post-stop
chunk tokens causally discarded. `abort(uid)` cancels queued or running
requests through the normal release path, so partially generated pages
still feed the prefix cache.

The device-side step functions are row-independent (engine.make_serve_fns),
so all of this is host bookkeeping plus cheap device_put pushes of page
tables / lengths between steps.

Chunked scanned decode: instead of one device dispatch per token, a tick
scans up to `chunk` decode steps in one `jax.lax.scan`
(models/transformer.decode_scan) and post-processes the emitted tokens on
the host. The chunk never exceeds the smallest remaining decode budget
among active rows, so no row outruns its reservation; rows that hit EOS
mid-chunk simply have their trailing tokens discarded (greedy decode is
causal, so tokens before the EOS are unaffected by what was appended
after). `chunk=None` (default) scans to the next completion boundary;
`chunk=1` restores per-token ticks (tick == token, used by tests that
observe scheduler state between individual tokens, and by the encoder-
decoder family which has no scan path).

Varlen chunked prefill + automatic prefix caching (paged, DESIGN.md §7):
every admitted prompt enters *unpadded* and is fed in chunks of
``prefill_chunk`` tokens (default 4 pages) interleaved with decode ticks,
so one long prompt never stalls the running batch and rows of arbitrary
lengths admit together. Full chunks are page-aligned; the final partial
chunk dispatches at a pow2 page width with a per-row valid length — its
full pages are scattered and its sub-page tail lands in the row's fp
residual, so decode continues mid-page and no pad token ever exists.
Rows whose next chunk needs the same dispatch width share one dispatch
(the compile set of chunk shapes is the pow2 widths up to
``prefill_chunk``). ``prefix_cache=True`` additionally resolves the *full
pages* of each new prompt's unpadded token stream against a content-hash
index (`core.paging.HostPageAllocator`): hit pages are adopted by
refcount instead of recomputed and their chunks are skipped outright —
two prompts sharing a prefix share pages at ANY lengths (no length-mod-
page_size congruence, the pre-varlen alignment caveat); completed
requests' pages are released into an evictable LRU rather than freed, so
future identical prefixes keep hitting until pool pressure reclaims them.

The contiguous backend is pad-retaining legacy: its single scalar cache
length structurally requires a common (left-padded) history length per
rebuild, so it keeps the padded layout and is excluded from prefix
caching. The paged path is the production one.

Overload survival (DESIGN.md §8): with `EngineConfig.watermark` set,
admission reserves only the prompt's pages plus a watermark of decode
headroom instead of the worst-case prompt+max_new — decode then *grows*
a row's reservation page by page, and when growth would exhaust the pool
the scheduler preempts a victim (lowest priority, then latest arrival):
its pages release through the promotion/LRU path so the prefix stays
hittable, the row's fp residual + pending token are snapshotted, and the
request re-queues. Re-admission adopts the still-resident pages and
restores the snapshot — bitwise-identical to a never-preempted run — or,
if pages were reclaimed, re-prefills (prompt + generated) with prefix
hits and restores the pending token so no token is ever redrawn.
Priorities with anti-starvation aging order admission and victim choice;
a preemption-loop detector (`PoolExhaustedError`) and a tick-level stall
watchdog (`StallError`, via runtime/fault.StallWatchdog) make the
failure modes diagnostic rather than livelocks.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
import warnings
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import paging as PG
from repro.core import quantization as Q
from repro.core.paging import PagedQuantizedKVCache
from repro.runtime import trace as TR
from repro.runtime.fault import StallWatchdog
from repro.serving.params import (EngineConfig, SamplingParams,
                                  default_detokenize, request_key,
                                  sampling_arrays)


class PoolExhaustedError(RuntimeError):
    """The scheduler preempted repeatedly without any request advancing —
    the pool cannot serve the committed working set (DESIGN.md §8). The
    message lists every page holder (uid -> pages held), queue depth and
    injector state, so the operator sees *who* owns the pool instead of a
    livelocked preempt/resume loop. Raised only after
    `EngineConfig.preempt_loop_limit` fruitless preemptions; the
    forward-progress rule (never preempt the last running request) makes
    it unreachable without fault injection or external page pressure."""


class StallError(RuntimeError):
    """No request advanced for `EngineConfig.stall_ticks` consecutive
    ticks with work in flight (DESIGN.md §8). Carries the per-uid
    stuck-state (queued / preempted / mid-prefill cursor / decoding
    position) plus pool occupancy, replacing the old practice of waiting
    for `run_to_completion`'s bare max_ticks RuntimeError to notice an
    admission deadlock."""


def pages_for_request(prompt_len: int, max_new: int, page_size: int) -> int:
    """Pages one request reserves in paged mode: its *unpadded* prompt plus
    the full decode budget, rounded up to whole pages (DESIGN.md §6) —
    varlen prefill means the prompt's partial final page and the first
    decode tokens share one page. The single source for this policy —
    submit() validation and benchmark pool sizing both use it. Prefix-cache
    hits reduce what admission actually *allocates*, never what submit()
    validates against (worst case: no hits)."""
    return -(-(max(prompt_len, 1) + max_new) // page_size)


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle record (DESIGN.md §6):
    prompt (S,) int32, a decode budget, per-request `SamplingParams`
    (default: exact greedy — the historical semantics), and the decoded
    output accumulated in `generated`.

    Lifecycle: queued -> prefilling -> decoding -> finished/aborted. On
    completion `finish_reason` is one of `serving.params.FINISH_REASONS`
    ("stop_token" | "stop_string" | "length" | "aborted") and the
    timestamps record submit / first-token (TTFT) / finish times
    (`time.perf_counter` seconds, host clock). A token is delivered when
    the `ContinuousBatcher.step` that appended it to `generated` returns,
    so the first-token and finish times are stamped at the end of that
    tick, after its decode scan, not at the prefill boundary where the
    first token is sampled.

    `max_new_tokens=None` takes the budget from
    `sampling.max_new_tokens` (resolved at submit) — there is ONE
    authoritative decode budget per request, and an explicit Request
    value overrides the SamplingParams one. `priority=None` likewise
    resolves from `sampling.priority` at submit (DESIGN.md §8): higher
    priorities admit first and are preempted last; anti-starvation aging
    raises a queued request's *effective* priority over time."""
    uid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int | None = None
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams.greedy)
    priority: int | None = None
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: str | None = None
    submit_time: float | None = None
    first_token_time: float | None = None
    finish_time: float | None = None


class ContinuousBatcher:
    """Continuous batching over a fixed pool of `batch` rows, with
    per-request SamplingParams compiled into the decode scan
    (DESIGN.md §6). Configured by ONE `EngineConfig` (`config=`); the
    historical kwarg sprawl survives one release as a deprecated shim.
    Backends: contiguous (pad-retaining legacy — rebuild on admit) and
    paged (`EngineConfig.paged`: page-budget admission over *unpadded*
    prompts, per-row timelines, varlen chunked prefill — `prefill_chunk`
    sizes the chunk, `prefix_cache` adds automatic prefix caching,
    DESIGN.md §7). `submit` queues requests; `step` runs one scheduler
    tick; `abort` cancels a queued/running uid; `run_to_completion` drains
    the queue and returns finished `Request`s (see `LLMEngine` for the
    streaming-output facade)."""

    _LEGACY_KWARGS = ("batch", "max_len", "eos_id", "paged", "n_pages",
                      "chunk", "prefix_cache", "prefill_chunk")

    def __init__(self, params, cfg, config: EngineConfig | None = None,
                 **legacy):
        from repro.serving.engine import make_serve_fns
        if config is None:
            # deprecated shim (one release): the historical kwarg sprawl
            # maps 1:1 onto EngineConfig fields. No config AND no kwargs
            # stays an error — it always was one — rather than silently
            # building a default-sized batcher with a misleading warning.
            if not legacy:
                raise TypeError("ContinuousBatcher requires "
                                "config=EngineConfig(...) (or the "
                                "deprecated legacy kwargs)")
            bad = set(legacy) - set(self._LEGACY_KWARGS)
            if bad:
                raise TypeError(f"unknown ContinuousBatcher kwargs: {bad}")
            warnings.warn(
                "ContinuousBatcher(batch=..., max_len=..., ...) kwargs are "
                "deprecated; pass config=EngineConfig(...) (or use the "
                "LLMEngine facade)", DeprecationWarning, stacklevel=2)
            config = EngineConfig(**legacy)
        elif legacy:
            raise TypeError("pass either config=EngineConfig(...) or the "
                            f"legacy kwargs, not both (got {set(legacy)})")
        self.config = config
        batch, max_len = config.batch, config.max_len
        paged, n_pages, chunk = config.paged, config.n_pages, config.chunk
        prefix_cache, prefill_chunk = config.prefix_cache, \
            config.prefill_chunk
        self.params, self.cfg = params, cfg
        self.batch, self.max_len = batch, max_len
        self.eos_id = config.eos_id
        self.paged = paged
        self.detokenize = config.detokenize or default_detokenize
        # request-lifecycle bookkeeping (DESIGN.md §6): uids queued or on a
        # row (duplicates rejected at submit), abort counter, and recorded
        # per-request TTFTs for the pool_report percentiles and sum
        self._inflight_uids: set[int] = set()
        self.aborted_requests = 0
        self._ttfts: list[float] = []
        # host spans of the tick and the cumulative counters made from
        # them (runtime/trace.py; exported by pool_report): admissions and
        # their queue wait, and the part of the first tokens' time from
        # submission that the ticks' decode scans took
        self.spans = TR.Spans()
        self.admissions = 0
        self.queue_wait_s = 0.0
        self.first_token_scan_s = 0.0
        # decode tokens per device dispatch: None = scan to the next
        # completion boundary; 1 = per-token ticks (also forced for encdec,
        # which has no transformer decode_scan path)
        self.chunk = 1 if cfg.family == "encdec" else chunk
        # (steps, kv dtype spec) -> jitted decode-scan chunk fn (one
        # signature; jit's None-vs-pytree structure keying separates
        # greedy/sampled traces; the dtype key makes the §9 stale-trace
        # guarantee explicit — a mixed plan keys on its full per-layer
        # tuple, so same-dtype layers share one trace per spec, §10)
        self._chunk_fns: dict[tuple[int, str | tuple], Any] = {}
        # host-side sampling entry (first token after prefill, per-token
        # ticks): the SAME sample_at_step the scan body runs, jitted once
        from repro.models import sampling as _SMP
        import functools as _ft
        self._sample_fn = jax.jit(
            _ft.partial(_SMP.sample_at_step, vocab=cfg.vocab))
        self.ticks = 0
        self.block = (cfg.quant.block_size
                      if cfg.quant.granularity == "per_block" else 8)
        self.prefix_cache = bool(prefix_cache)
        if (prefix_cache or prefill_chunk) and not paged:
            raise ValueError("prefix caching / chunked prefill require the "
                             "paged backend (paged=True)")
        # overload controls (DESIGN.md §8)
        if (config.watermark is not None
                or config.fault_injector is not None) and not paged:
            raise ValueError("watermark admission / pool fault injection "
                             "require the paged backend (paged=True)")
        if config.watermark is not None and config.watermark < 0:
            raise ValueError(f"watermark must be >= 0 "
                             f"(got {config.watermark})")
        self.watermark = config.watermark
        self.aging_ticks = int(config.aging_ticks or 0)
        self.preempt_loop_limit = config.preempt_loop_limit
        self._watchdog = StallWatchdog(config.stall_ticks)
        self._seq = 0               # arrival order for priority tie-breaks
        self._progressed = False
        self._preempts_since_progress = 0
        self.preemptions = 0
        self.preempt_fast_resumes = 0
        self.preempt_recompute_resumes = 0
        self.decode_stall_ticks = 0
        self.prefill_tokens_computed = 0
        self.prefill_tokens_dispatched = 0      # batch x width per chunk
        self.decode_tokens_computed = 0
        # tiered KV cache (DESIGN.md §11): host swap tier + cost model +
        # per-uid swap-wait state; inert (None/empty) without host_pages
        self._tiering = None
        self._swap_cost = None
        self._swap_wait: dict[int, int] = {}   # uid -> pages in flight
        self.preempt_by_swap = 0
        self.preempt_swap_restores = 0
        if paged:
            self.page_size = cfg.quant.block_size
            self.max_blocks = max_len // self.page_size
            if n_pages is None:   # dense capacity; pass less to oversubscribe
                n_pages = batch * self.max_blocks + 1
            self.n_pages = n_pages
            if config.host_pages is not None:
                from repro.core import tiering as TIER
                self._tiering = TIER.HostTier(
                    config.host_pages, dtype=config.host_tier_dtype)
                self._swap_cost = TIER.SwapCostModel(self.page_size)
            # host-authoritative allocator (free list + refcounts + prefix
            # index), mirrored to the device pytree on change
            self.allocator = PG.HostPageAllocator(
                n_pages, prefix_cache=self.prefix_cache,
                injector=config.fault_injector,
                evictor=config.evictor, host_tier=self._tiering)
            if self._tiering is not None:
                self.allocator.demote_hook = self._demote_to_host
            self.tables = np.zeros((batch, self.max_blocks), np.int32)
            self.row_pages: list[list[int]] = [[] for _ in range(batch)]
            # preemption-by-recompute state (DESIGN.md §8): uid -> suspend
            # snapshot (pending token, fp residual, full token stream and
            # its hash chain); per-row base into `generated` marking where
            # this residency's decoding started (promotion must not
            # re-extend over tokens already inside the stream); rows whose
            # re-prefill must restore a pending token instead of sampling
            self._suspended: dict[int, dict] = {}
            self.gen_base = [0] * batch
            self._resume_tok: dict[int, int] = {}
            # copy-on-write scan before decode: armed only when something
            # can actually share a flush target (fork_row wiring) — the
            # scheduler itself never forks, so scanning every tick would
            # guard a structurally impossible case (DESIGN.md §7)
            self.cow_armed = False
            # paged admission is ALWAYS per-row varlen chunked prefill
            # (DESIGN.md §7) — there is no padded group-prefill path left
            pc = prefill_chunk or 4 * self.page_size
            self.prefill_chunk_tokens = -(-pc // self.page_size) * \
                self.page_size
            # a chunk the fused prefill kernel cannot hold in VMEM fails
            # here, at construction, not at its first dispatch on the chip
            from repro.kernels.quant_prefill import check_prefill_fits
            check_prefill_fits(cfg.n_heads // cfg.n_kv_heads,
                               self.prefill_chunk_tokens, self.page_size,
                               cfg.head_dim)
            # one jitted chunk fn per (static history bound, fused-toggle,
            # kv dtype spec); the bound set is pow2, the toggle and dtype
            # read live from self.config per dispatch (DESIGN.md §9; a
            # mixed plan's spec is its per-layer dtype tuple, §10)
            self._chunk_prefill_fns: dict[tuple[int, bool, str | tuple],
                                          Any] = {}
            # req.uid -> (toks, chain): computed once per request, not once
            # per tick while admission is blocked on pool pressure. Keyed by
            # uid, NOT id(request): CPython reuses a collected object's id,
            # so an id-keyed memo could hand a new request a dead request's
            # (toks, chain). Entries drop on admission and on abort; submit()
            # rejects duplicate in-flight uids so the key is unambiguous.
            self._admit_memo: dict[int, tuple] = {}
            # rows mid-prompt: row -> {"toks", "cursor", "S"}
            self.prefilling: dict[int, dict] = {}
            # per-row *unpadded* token stream + the hash chain over its full
            # pages, kept until release for decode-page promotion (prefix)
            self.streams: list[np.ndarray | None] = [None] * batch
            self.row_chain: list[list[bytes] | None] = [None] * batch
            self._pf_rr = 0     # round-robin cursor over prefilling rows
        # the pools' storage format — a dtype string (uniform, §9) or a
        # per-layer dtype tuple (mixed plan, §10); config.kv_cache_dtype is
        # the *wanted* spec — the two diverge only between a config flip
        # and the next idle rebuild (_ensure_backend_dtype)
        self.kv_cache_dtype = self._want_dtype_spec()
        init_state, prefill, decode = make_serve_fns(
            cfg, max_len=max_len, paged=paged, n_pages=n_pages,
            kv_cache_dtype=self.kv_cache_dtype)
        self._prefill = jax.jit(prefill)
        # the state is donated to both decode programs, as to chunk
        # prefill: the caller replaces self.state with the result in the
        # same statement, and the pools are then updated in place
        self._decode = TR.program(decode, "decode_fn", donate_argnums=(2,))
        self._init_state = init_state
        self.queue: deque[Request] = deque()
        self.rows: list[Request | None] = [None] * batch
        self.pos = np.zeros((batch,), np.int32)
        self.tok = np.zeros((batch, 1), np.int32)
        self.state = None

    @property
    def free_pages(self) -> list[int]:
        """Truly-free page ids (host authoritative; excludes evictable
        cached pages — see `HostPageAllocator`)."""
        return self.allocator.free

    def _want_dtype_spec(self):
        """The dtype spec `config.kv_cache_dtype` currently asks for,
        resolved to its canonical form (DESIGN.md §10): a dtype string for
        a uniform engine, a per-layer dtype tuple for a mixed plan. Raw
        config values (plan paths/dicts, `PrecisionPlan`s) resolve here so
        live config mutation behaves like construction; the plan length is
        validated against the model's layer count."""
        return Q.resolve_kv_dtype_spec(
            getattr(self.config, "kv_cache_dtype", "int8"),
            n_layers=self.cfg.n_layers)

    def submit(self, req: Request):
        """Queue a request (DESIGN.md §6). Rejects impossible requests here
        — once queued, admission must never fail, or earlier candidates
        popped in the same tick would be stranded. Duplicate in-flight uids
        are rejected too: the uid is the lifecycle handle (`abort`,
        admission memo, streaming outputs), so two live requests must never
        share one. Paged capacity is unpadded (varlen prefill); the legacy
        contiguous backend still pads to a block multiple and validates
        accordingly.

        Every check runs before ANY state mutates — scheduler or request —
        so a rejected submit leaves the queue, the pool report, and the
        request object byte-identical to before the call (DESIGN.md §8).
        The worst-case page bound is validated even under watermark
        admission: a request that fits the pool *alone* underpins the
        forward-progress guarantee (the last running row can always grow
        to its full budget)."""
        if req.uid in self._inflight_uids:
            raise ValueError(f"request uid {req.uid} is already in flight "
                             f"(queued or running); uids are the lifecycle "
                             f"handle and must be unique until completion")
        want_dtype = req.sampling.kv_cache_dtype
        engine_spec = self._want_dtype_spec()
        if want_dtype is not None and want_dtype != engine_spec:
            if isinstance(engine_spec, str):
                raise ValueError(
                    f"request {req.uid}: kv_cache_dtype={want_dtype!r} does "
                    f"not match the engine's pool backend "
                    f"({engine_spec!r}); the pool carries ONE storage "
                    f"format — flip EngineConfig.kv_cache_dtype on an idle "
                    f"engine instead (DESIGN.md §9)")
            raise ValueError(
                f"request {req.uid}: kv_cache_dtype={want_dtype!r} "
                f"contradicts the engine's mixed per-layer precision plan "
                f"({'/'.join(engine_spec)}); plan-driven engines accept "
                f"only requests with kv_cache_dtype=None — the plan, not "
                f"the request, owns layer precision (DESIGN.md §10)")
        budget = (req.max_new_tokens if req.max_new_tokens is not None
                  else req.sampling.max_new_tokens)
        if self.paged:
            if len(req.prompt) < 1:
                raise ValueError(f"request {req.uid}: empty prompt")
            if len(req.prompt) + budget > self.max_len:
                raise ValueError(f"request {req.uid}: prompt+max_new exceeds "
                                 f"max_len={self.max_len}")
            if pages_for_request(len(req.prompt), budget,
                                 self.page_size) > self.n_pages - 1:
                raise ValueError(f"request {req.uid} needs more pages than "
                                 f"the pool holds ({self.n_pages - 1}); "
                                 f"raise n_pages")
        elif self._pad(len(req.prompt)) + budget > self.max_len:
            raise ValueError(f"request {req.uid}: prompt+max_new exceeds "
                             f"max_len={self.max_len}")
        # -- commit: nothing above mutated scheduler or request state ------
        req.max_new_tokens = budget         # single source: SamplingParams
        if req.priority is None:
            req.priority = req.sampling.priority
        req.submit_time = time.perf_counter()
        req._queued_at = req.submit_time    # queue wait, until admission
        req._scan_mark = self._scan_s()     # decode-scan seconds so far
        req._submit_tick = self.ticks       # aging clock (DESIGN.md §8)
        req._arrival = self._seq            # priority tie-break: FCFS
        self._seq += 1
        self._inflight_uids.add(req.uid)
        self.queue.append(req)

    # -- shared helpers ----------------------------------------------------
    def _pad(self, n: int) -> int:
        return -(-max(n, 1) // self.block) * self.block

    # -- priorities + anti-starvation aging (DESIGN.md §8) -----------------
    def _queue_priority(self, r: Request) -> int:
        """Effective priority of a QUEUED request: its static priority
        plus one point per `aging_ticks` waited, so a low-priority request
        blocked behind a stream of high-priority arrivals eventually
        outranks them (no starvation; aging off when aging_ticks=0).
        Running rows never age — victim selection uses static priority."""
        p = r.priority if r.priority is not None else 0
        if self.aging_ticks:
            p += (self.ticks - getattr(r, "_submit_tick", self.ticks)) \
                // self.aging_ticks
        return p

    def _next_candidate_index(self) -> int:
        """Queue index of the next admission candidate: highest effective
        priority first, FCFS (arrival sequence) within a priority. The
        head candidate does NOT yield to smaller later requests when it is
        blocked on pool pressure — bypass would re-introduce starvation
        exactly where aging removes it (DESIGN.md §8)."""
        return min(range(len(self.queue)),
                   key=lambda k: (-self._queue_priority(self.queue[k]),
                                  getattr(self.queue[k], "_arrival", k)))

    def _sample(self, logits) -> np.ndarray:
        """Pure-greedy batch argmax — the fast path when no active row
        samples (zero behavior/perf change vs the pre-lifecycle code)."""
        return np.asarray(jnp.argmax(logits[..., :self.cfg.vocab], -1))

    # -- per-request sampling (DESIGN.md §6) -------------------------------
    def _req_key(self, r: Request) -> np.ndarray:
        k = getattr(r, "_base_key", None)
        if k is None:
            k = request_key(r.uid, r.sampling)
            r._base_key = k
        return k

    def _needs_sampling(self, idxs) -> bool:
        """True when any of the rows whose draw will actually be READ
        samples — a sampled request merely mid-prefill (masked out of the
        decode) must not knock greedy decoders off the argmax fast
        path."""
        return any(self.rows[i] is not None
                   and not self.rows[i].sampling.is_greedy for i in idxs)

    def _sampling_arrays(self, offset: int) -> dict:
        """Per-row sampling arrays for the whole batch (empty rows greedy):
        `offset` is added to each row's generated count to form the token
        index of its NEXT draw — 0 when sampling the first token from
        prefill logits, 1 during decode (the pending token, already drawn,
        holds index len(generated))."""
        sps, keys, steps = [], [], []
        for r in self.rows:
            sps.append(r.sampling if r is not None
                       else SamplingParams.greedy())
            keys.append(self._req_key(r)
                        if r is not None and not r.sampling.is_greedy
                        else None)              # cached once per request
            steps.append((len(r.generated) if r is not None else 0) + offset)
        arrs = sampling_arrays(sps, steps=steps, keys=keys)
        return {k: jnp.asarray(v) for k, v in arrs.items()}

    def _sample_rows(self, logits, idxs, *, offset: int) -> np.ndarray:
        """Draw the next token for every row honoring per-request
        SamplingParams — the host-boundary twin of the scan body's
        on-device draw (same `sample_at_step`, same key indexing). `idxs`
        are the rows whose draw the caller will read (fast-path gate)."""
        if not self._needs_sampling(idxs):
            return self._sample(logits)
        s = self._sampling_arrays(offset)
        return np.asarray(self._sample_fn(logits, s["temperature"],
                                          s["top_k"], s["top_p"], s["key"],
                                          s["step"]))

    # -- lifecycle helpers (DESIGN.md §6) ----------------------------------
    def _scan_s(self) -> float:
        """Seconds the ticks' decode scans have taken so far, from the
        scan's launch to the end of its tokens' bookkeeping: the host's
        part of `sched.decode`, the wait for the tokens and their
        bookkeeping. The program call (`dispatch.decode`) is left out: the
        runtime can hold it until the tick's chunk prefill, enqueued
        before it, has finished (DESIGN.md §6)."""
        sp = self.spans
        return sp.total("sched.decode", "wait.decode",
                        "sched.bookkeeping") - sp.total("dispatch.decode")

    def _deliver(self, done: list[Request]):
        """End of a tick: what it appended to `generated` is delivered
        now, when `step` returns it (DESIGN.md §6). Stamps the finish time
        of the requests it completed and the first-token time of those
        whose first token it appended, and adds each first token to the
        counters: its time from submission, and the part of that time the
        decode scans of the ticks in between took. A resumed request
        (DESIGN.md §8) keeps the first-token time it already has."""
        now, scan = time.perf_counter(), self._scan_s()
        for r in done:
            r.finish_time = now
        for r in itertools.chain(done, self.rows):
            if r is not None and r.first_token_time is None and r.generated:
                r.first_token_time = now
                self._ttfts.append(now - r.submit_time)
                self.first_token_scan_s += scan - r._scan_mark

    def _finish(self, r: Request, reason: str):
        r.done = True
        r.finish_reason = reason
        r.finish_time = time.perf_counter()
        self._inflight_uids.discard(r.uid)

    def _stop_ids(self, r: Request) -> frozenset:
        ids = getattr(r, "_stop_ids", None)     # built once per request,
        if ids is None:                         # checked once per token
            ids = frozenset(r.sampling.stop_token_ids)
            if self.eos_id is not None:
                ids = ids | {self.eos_id}
            r._stop_ids = ids
        return ids

    def _stop_string_hit(self, r: Request) -> bool:
        """True when the detokenized generated stream contains one of the
        request's stop strings. Checked host-side after each appended
        token inside the chunk's bookkeeping loop — tokens past a
        mid-chunk stop are never appended, i.e. causally discarded
        exactly like post-EOS chunk tails (DESIGN.md §6). Only a suffix
        window is detokenized and scanned: a match ending at the newest
        token spans at most `max(len(stop))` tokens *provided every token
        renders to >= 1 character* — the documented `EngineConfig.
        detokenize` contract (zero-width tokens would let a match escape
        the window) — so generation stays O(n), not O(n^2)."""
        stops = r.sampling.stop
        if not stops:
            return False
        window = getattr(r, "_stop_window", None)
        if window is None:
            window = r._stop_window = max(len(s) for s in stops)
        text = self.detokenize(r.generated[-window:])
        return any(s in text for s in stops)

    def abort(self, uid: int) -> Request | None:
        """Cancel a queued or running request (DESIGN.md §6). Running rows
        release through the normal `_release_row` path — pages free (or
        park on the prefix-cache LRU) and fully-flushed decode pages are
        still promoted, so a later prompt sharing the aborted prefix keeps
        hitting. Returns the request marked `finish_reason="aborted"` with
        its partial `generated`, or None if the uid is not in flight."""
        for idx, r in enumerate(self.queue):
            if r.uid == uid:
                del self.queue[idx]
                if self.paged:
                    self._admit_memo.pop(uid, None)
                    self._suspended.pop(uid, None)
                    self._swap_wait.pop(uid, None)
                self._finish(r, "aborted")
                self.aborted_requests += 1
                return r
        for i, r in enumerate(self.rows):
            if r is not None and r.uid == uid:
                self._finish(r, "aborted")
                self._release_row(i)
                if self.paged:
                    self._sync_device()   # freed tables/lengths live now
                self.aborted_requests += 1
                return r
        return None

    def lifecycle_report(self) -> dict:
        """Abort/streaming observability (DESIGN.md §6): abort count and
        per-request TTFT percentiles over every request that produced a
        first token (0.0 until one has)."""
        ts = np.asarray(self._ttfts, np.float64)
        pct = (lambda q: float(np.percentile(ts, q))) if ts.size else \
            (lambda q: 0.0)
        return {"aborted_requests": self.aborted_requests,
                "ttft_s_p50": pct(50),
                "ttft_s_p90": pct(90),
                "ttft_s_p99": pct(99)}

    def step(self) -> list[Request]:
        """One scheduler tick: admit, prefill admitted rows, decode one
        chunk (up to `chunk` tokens, one device dispatch) for all active
        rows. Returns requests completed this tick. `self.ticks` counts
        ticks taken since construction (tokens/dispatch telemetry).

        A tick-level stall watchdog (runtime/fault.StallWatchdog,
        DESIGN.md §8) observes every tick: if no request advances for
        `EngineConfig.stall_ticks` consecutive ticks while work is in
        flight, the tick raises `StallError` with per-uid stuck-state —
        an admission deadlock surfaces as a structured diagnostic instead
        of a silent spin."""
        self.ticks += 1
        self._progressed = False
        self._ensure_backend_dtype()
        done = self._step_paged() if self.paged else self._step_contiguous()
        self._deliver(done)
        if done:
            self._progressed = True
        if self._progressed:
            self._preempts_since_progress = 0
        busy = bool(self.queue) or any(r is not None for r in self.rows)
        if self._watchdog.observe(self._progressed, busy):
            raise StallError(
                f"scheduler stalled: no request advanced in "
                f"{self._watchdog.limit} consecutive ticks with work in "
                f"flight; {self._stuck_report()}")
        return done

    def _stuck_report(self) -> str:
        """Per-uid lifecycle state plus pool occupancy, for the watchdog
        and run_to_completion diagnostics (DESIGN.md §8): queued (and
        whether a preemption snapshot is waiting), mid-prefill with its
        cursor, or decoding with its position."""
        parts = []
        for r in self.queue:
            tag = ("queued(preempted)" if self.paged
                   and r.uid in self._suspended else "queued")
            if self.paged and r.uid in self._swap_wait:
                tag += (f" swap-wait:{self._swap_wait[r.uid]} "
                        f"page(s) in flight")
            parts.append(f"uid {r.uid}: {tag}")
        for i, r in enumerate(self.rows):
            if r is None:
                continue
            if self.paged and i in self.prefilling:
                st = self.prefilling[i]
                parts.append(f"uid {r.uid}: mid-prefill "
                             f"{st['cursor']}/{st['S']}")
            else:
                parts.append(f"uid {r.uid}: decoding pos={int(self.pos[i])} "
                             f"generated={len(r.generated)}")
        rep = "per-request state: [" + "; ".join(parts) + "]"
        if self.paged:
            a = self.allocator
            rep += (f"; pool: available={a.available} free={a.n_free} "
                    f"cached={a.n_cached} preemptions={self.preemptions}")
            if self._tiering is not None:
                rep += (f"; host tier: hosted={len(self._tiering)} "
                        f"inflight={len(a.inflight)}")
            if a.injector is not None:
                rep += (f"; injector: fault_ticks="
                        f"{a.injector.alloc_fault_ticks} "
                        f"held={a.injector.hold_pages} "
                        f"deferred={len(a.deferred)} "
                        f"swap_faults={a.injector.swap_faults}")
        return rep

    def _ensure_backend_dtype(self):
        """Honor a live flip of `EngineConfig.kv_cache_dtype` (DESIGN.md §9)
        — including flips to/from/between per-layer precision plans (§10):
        a plan flip is a full backend flip, never an in-place relabel.

        The pool's storage format is baked into every page, every allocator
        index entry, and the device pytree's structure, so a flip cannot be
        served in place: on the next tick with NO work in flight the serve
        fns, decode state, and host allocator are rebuilt for the new dtype
        (the jitted chunk/decode fn caches are keyed on dtype, so old
        traces stay valid if the config flips back). A flip with pool
        state in use (rows running, mid-prefill, or preempt snapshots
        waiting) raises — silently re-quantizing resident pages through a
        second lossy format would corrupt live streams; merely *queued*
        requests hold no pages yet and ride the rebuild."""
        want = self._want_dtype_spec()     # validates dtype names + plan len
        if want == self.kv_cache_dtype:
            return
        if not self.paged:
            raise RuntimeError(
                f"kv_cache_dtype={want!r} requires the paged backend")
        if (any(r is not None for r in self.rows) or self.prefilling
                or self._suspended):
            raise RuntimeError(
                f"cannot flip kv_cache_dtype to {want!r} with rows "
                f"resident in the pool; drain the engine first "
                f"(DESIGN.md §9, §10)")
        from repro.serving.engine import make_serve_fns
        self.kv_cache_dtype = want
        init_state, prefill, decode = make_serve_fns(
            self.cfg, max_len=self.max_len, paged=True,
            n_pages=self.n_pages, kv_cache_dtype=want)
        self._prefill = jax.jit(prefill)
        self._decode = TR.program(decode, "decode_fn", donate_argnums=(2,))
        self._init_state = init_state
        self.state = None                      # rebuilt lazily next tick
        # indexed/cached pages hold bytes in the OLD format — a fresh
        # allocator drops them (chain hashes are token-content keyed, so a
        # stale hit would alias wrong-format pages into a new row's table);
        # the host tier's demoted payloads are stale the same way, so the
        # tier rebuilds empty too (DESIGN.md §11)
        if self._tiering is not None:
            from repro.core import tiering as TIER
            self._tiering = TIER.HostTier(self.config.host_pages,
                                          dtype=self.config.host_tier_dtype)
        self.allocator = PG.HostPageAllocator(
            self.n_pages, prefix_cache=self.prefix_cache,
            injector=self.config.fault_injector,
            evictor=self.config.evictor, host_tier=self._tiering)
        if self._tiering is not None:
            self.allocator.demote_hook = self._demote_to_host
        self._swap_wait.clear()
        self.tables[:] = 0
        self.row_pages = [[] for _ in range(self.batch)]
        self.streams = [None] * self.batch
        self.row_chain = [None] * self.batch
        self.gen_base = [0] * self.batch
        self._suspended.clear()
        self._admit_memo.clear()
        self._resume_tok.clear()

    def run_to_completion(self, max_ticks: int = 10_000) -> list[Request]:
        """Drain the queue; returns naturally finished requests (aborted
        ones are returned by `abort` itself). Raises RuntimeError when
        `max_ticks` is exhausted with requests still queued or active —
        the old behavior silently returned partial results, losing the
        stranded requests without a trace; the message carries the per-uid
        stuck-state (`_stuck_report`, DESIGN.md §8) so admission
        deadlocks are debuggable. A genuine no-progress spin raises
        `StallError` from `step` itself long before max_ticks."""
        out = []
        for _ in range(max_ticks):
            out.extend(self.step())
            if not self.queue and all(r is None for r in self.rows):
                return out
        stranded = sorted([r.uid for r in self.queue] +
                          [r.uid for r in self.rows if r is not None])
        raise RuntimeError(
            f"run_to_completion: max_ticks={max_ticks} exhausted with "
            f"{len(stranded)} request(s) still in flight (uids {stranded}); "
            f"{self._stuck_report()}; raise max_ticks or check for an "
            f"admission deadlock")

    def _check_stop(self, r: Request, nxt: int) -> str | None:
        """Finish reason for the request after appending a token, given the
        next (already-sampled, not-yet-fed) token — or None to continue.
        Precedence: a stop string completed by the appended token, then
        the decode budget, then a stop token about to be emitted (the stop
        token itself is suppressed, the convention eos_id always had)."""
        if self._stop_string_hit(r):
            return "stop_string"
        if len(r.generated) >= r.max_new_tokens:
            return "length"
        if int(nxt) in self._stop_ids(r):
            return "stop_token"
        return None

    def _finish_tick(self, active: list[int], nxt: np.ndarray) -> list[Request]:
        done, ended = [], []
        for i in active:
            r = self.rows[i]
            r.generated.append(int(self.tok[i, 0]))
            self.tok[i, 0] = nxt[i]
            self.pos[i] += 1
            reason = self._check_stop(r, int(nxt[i]))
            if reason is not None:
                self._finish(r, reason)
                done.append(r)
                ended.append(i)
        self._release_rows(ended, done)
        return done

    # -- chunked scanned decode --------------------------------------------
    _EOS_CHUNK_CAP = 8

    def _chunk_len(self, active: list[int]) -> int:
        """Decode steps for this tick's scan: bounded by the smallest
        remaining budget among active rows (no row outruns its page
        reservation / max_new), then rounded down to a power of two so the
        set of compiled scan lengths stays O(log max_new). With any stop
        condition configured (engine eos_id, per-request stop token ids or
        stop strings), rows can finish long before their budget — discarded
        scan tail + slot held past the stop — so the auto chunk is
        additionally capped to bound that waste."""
        rem = min(self.rows[i].max_new_tokens - len(self.rows[i].generated)
                  for i in active)
        n = rem if self.chunk is None else min(self.chunk, rem)
        stops_possible = self.eos_id is not None or any(
            self.rows[i].sampling.stop_token_ids or self.rows[i].sampling.stop
            for i in active)
        if stops_possible and self.chunk is None:
            n = min(n, self._EOS_CHUNK_CAP)
        n = max(n, 1)
        return 1 << (n.bit_length() - 1)

    def _chunk_fn(self, n: int):
        """Jitted n-step decode-scan fn, one signature for every mode:
        `row_mask`/`sampling` are None when unused (jit re-traces on the
        None-vs-pytree structure change, so greedy and sampled chunks
        still get their own compiled variants). Threading the sampling
        arrays into the SAME scan is what keeps mixed per-row params at
        one dispatch per chunk (DESIGN.md §6). Keyed on (n, kv dtype):
        the pool dtype is a pytree meta field, so jit would re-trace
        anyway — the explicit key makes the stale-trace guarantee
        inspectable (DESIGN.md §9) and keeps old traces when the config
        flips back."""
        key = (n, self.kv_cache_dtype)
        fn = self._chunk_fns.get(key)
        if fn is None:
            from repro.models import transformer as T
            cfg = self.cfg

            def run(params, tok, state, pos, row_mask, sampling):
                return T.decode_scan(params, tok, cfg, state, pos, steps=n,
                                     row_mask=row_mask, sampling=sampling)
            fn = self._chunk_fns[key] = TR.program(run, "run",
                                                   donate_argnums=(2,))
        return fn

    def _finish_chunk(self, active: list[int], toks: np.ndarray,
                      pending: np.ndarray) -> list[Request]:
        """Host bookkeeping after an n-step scan: `toks` (n, B) are the
        tokens fed at each step (the generated stream), `pending` (B, 1) the
        next not-yet-fed sample. Rows completing mid-chunk (stop token /
        stop string / budget) release immediately; their trailing chunk
        tokens are discarded — decode is causal, so tokens before the stop
        are unaffected by what was appended after (DESIGN.md §6)."""
        n = toks.shape[0]
        done, ended = [], []
        for i in active:
            r = self.rows[i]
            finished = False
            for j in range(n):
                r.generated.append(int(toks[j, i]))
                nxt = toks[j + 1, i] if j + 1 < n else pending[i, 0]
                reason = self._check_stop(r, int(nxt))
                if reason is not None:
                    self._finish(r, reason)
                    finished = True
                    done.append(r)
                    ended.append(i)
                    break
            if not finished:
                self.tok[i, 0] = pending[i, 0]
                self.pos[i] += n
        self._release_rows(ended, done)
        return done

    def _decode_tick(self, active: list[int],
                     row_mask: np.ndarray | None = None,
                     n: int | None = None) -> list[Request]:
        """Decode one chunk for the active rows and run host bookkeeping.
        When any active row samples, the chunk runs the sampled scan
        variant — still ONE device dispatch for the whole mixed batch.
        ``n`` lets the paged growth pass (`_ensure_decode_pages`,
        DESIGN.md §8) pin the chunk length it sized page reservations
        for; None computes it here (the historical behavior)."""
        if n is None:
            n = self._chunk_len(active)
        self._progressed = True
        self.decode_tokens_computed += n * len(active)
        if self.paged and self.cow_armed and self._cow_retarget(active, n):
            self._sync_device()          # retargeted tables before the scan
        sp = self.spans
        if n == 1:          # per-token path (chunk=1 / encdec)
            with sp.span("sched.decode", n=1, rows=len(active)):
                args = (self.params, jnp.asarray(self.tok), self.state,
                        jnp.asarray(self.pos))
                if row_mask is not None:
                    args += (jnp.asarray(row_mask),)
                with sp.span("dispatch.decode"):
                    logits, self.state = self._decode(*args)
            with sp.span("wait.decode"):
                nxt = self._sample_rows(logits, active, offset=1)
            with sp.span("sched.bookkeeping"):
                return self._finish_tick(active, nxt)
        with sp.span("sched.decode", n=n, rows=len(active)):
            sampling = (self._sampling_arrays(1)
                        if self._needs_sampling(active) else None)
            args = (self.params, jnp.asarray(self.tok), self.state,
                    jnp.asarray(self.pos),
                    jnp.asarray(row_mask) if row_mask is not None else None,
                    sampling)
            with sp.span("dispatch.decode"):
                pending, self.state, toks = self._chunk_fn(n)(*args)
        with sp.span("wait.decode"):
            toks, pending = np.asarray(toks), np.asarray(pending)
        with sp.span("sched.bookkeeping"):
            return self._finish_chunk(active, toks, pending)

    def _release_rows(self, rows: list[int], reqs: list[Request]):
        """`_release_row` for the rows a tick's bookkeeping finished
        (their requests `reqs`), under one span (DESIGN.md §6)."""
        if rows:
            with self.spans.span("sched.release", uids=TR.uids(reqs)):
                for i in rows:
                    self._release_row(i)

    def _release_row(self, i: int):
        """Return row ``i`` to the pool. Paged: decref-with-reclaim — in
        prefix mode the row's kept, fully-flushed decode pages are first
        promoted into the hash index, then every page reference is dropped
        (`HostPageAllocator.release`): pages still shared survive, indexed
        pages park on the evictable LRU, the rest go back to the free list
        (DESIGN.md §7)."""
        if self.paged and self.prefix_cache:
            self._promote_on_release(i)
        self.rows[i] = None
        self.pos[i] = 0
        self.tok[i, 0] = 0
        if self.paged:
            self.allocator.release(self.row_pages[i])
            self.row_pages[i] = []
            self.tables[i, :] = 0
            # device table/length stay stale until the next _sync_device
            # (before any page is reallocated) — the dead row's output is
            # discarded in the meantime
            self.prefilling.pop(i, None)
            self.streams[i] = None
            self.row_chain[i] = None
            self.gen_base[i] = 0
            self._resume_tok.pop(i, None)

    def _promote_on_release(self, i: int):
        """Publish the completing row's decode pages under the prompt's
        extended hash chain, so a future prompt that continues this
        conversation (unpadded old prompt + generated tokens + new turn)
        hits them at any length. The prompt's hash chain covers only its
        full pages, so the extension stream starts at the prompt's partial
        tail (those tokens share their page with the first generated ones).
        Only blocks whose ps tokens are all *kept* are promoted — a block
        reaching into tokens discarded after an EOS mid-scan holds KV the
        request never acknowledged. For a resumed row (DESIGN.md §8) the
        stream already contains the pre-preemption generated tokens, so
        the extension starts at `gen_base` — promoting the full
        `generated` again would double-count those tokens. DESIGN.md §7."""
        r, stream, chain = self.rows[i], self.streams[i], self.row_chain[i]
        if r is None or stream is None:
            return
        ps = self.page_size
        gb = self.gen_base[i]
        S, nb = len(stream), len(stream) // ps       # nb = full stream pages
        kept = S + len(r.generated) - gb
        if kept // ps <= nb:
            return
        ext = np.concatenate([stream[nb * ps:],
                              np.asarray(r.generated[gb:], np.int32)])
        ext = ext[:(kept // ps) * ps - nb * ps]
        parent = chain[-1] if chain else None        # S < ps: seed the chain
        with self.spans.span("cache.hash", pages=len(ext) // ps):
            hashes = PG.chain_hashes(ext, ps, parent=parent)
        for j, h in enumerate(hashes):
            self.allocator.register(int(self.tables[i, nb + j]), h)

    # -- contiguous backend ------------------------------------------------
    def _admit_rows(self) -> list[int]:
        """Fill empty rows, deferring candidates that would overflow the
        cache after a rebuild: the rebuild restarts *every* active row at the
        group's padded history length S, so each row's S + remaining decode
        budget must fit max_len — a long-prompt candidate can push a
        mid-decode row (or itself) past the end otherwise."""
        active = [r for r in self.rows if r is not None]
        new = []
        free = [i for i in range(self.batch) if self.rows[i] is None]
        while free[len(new):] and self.queue:
            k = self._next_candidate_index()     # priority order, FCFS ties
            cand = self.queue[k]                 # validated at submit()
            group = active + [self.rows[i] for i in new] + [cand]
            S = self._pad(max(len(r.prompt) + len(r.generated)
                              for r in group))
            remaining = lambda r: r.max_new_tokens - len(r.generated)
            if any(S + remaining(r) > self.max_len for r in group):
                break                      # defer until rows free up
            i = free[len(new)]
            del self.queue[k]
            self.rows[i] = cand
            new.append(i)
        if new:
            self._progressed = True
        return new

    def _step_contiguous(self) -> list[Request]:
        newly = self._admit_rows()
        active = [i for i, r in enumerate(self.rows) if r is not None]
        done0: list[Request] = []        # first-draw-is-stop completions
        if not active:
            return done0
        if newly:
            # Rebuild: the contiguous cache has ONE scalar length, so every
            # row must share a position. Re-prefill all active histories
            # (prompt + generated) left-padded to a common block multiple;
            # this prefills mid-stream admissions and scrubs recycled rows.
            self.state = self._init_state(self.batch)
            hist = {i: np.concatenate(
                [self.rows[i].prompt,
                 np.asarray(self.rows[i].generated, np.int32)])
                for i in active}
            S = self._pad(max(len(h) for h in hist.values()))
            toks = np.zeros((self.batch, S), np.int32)
            for i, h in hist.items():
                toks[i, S - len(h):] = h          # left-pad
            logits, self.state = self._prefill(
                self.params, {"tokens": jnp.asarray(toks)}, self.state)
            nxt = self._sample_rows(logits, active, offset=0)
            for i in active:
                r = self.rows[i]
                if not r.generated and int(nxt[i]) in self._stop_ids(r):
                    # first draw is a stop token: suppressed, empty output
                    self._finish(r, "stop_token")
                    done0.append(r)
                    self._release_row(i)
                    continue
                self.tok[i, 0] = nxt[i]
                self.pos[i] = S
            active = [i for i in active if self.rows[i] is not None]
        if not active:
            return done0
        return done0 + self._decode_tick(active)

    # -- paged backend -----------------------------------------------------
    def _pages_needed(self, prompt_len: int, max_new: int) -> int:
        # delegates to the module-level single source of the reservation
        # policy (unpadded prompt + decode budget, in whole pages)
        return pages_for_request(prompt_len, max_new, self.page_size)

    def _sync_device(self):
        """Push host allocator state (page tables, per-row lengths, free
        list) into every layer's cache leaf. Lengths: active rows mirror
        self.pos; freed rows reset to 0."""
        with self.spans.span("sched.sync"):
            lengths = np.where(np.asarray([r is not None for r in self.rows]),
                               self.pos, 0).astype(np.int32)
            stack = np.zeros((self.n_pages,), np.int32)
            stack[:len(self.free_pages)] = self.free_pages
            n_free = np.int32(len(self.free_pages))
            tables = self.tables

            def upd(c: PagedQuantizedKVCache) -> PagedQuantizedKVCache:
                pool = dataclasses.replace(
                    c.pool,
                    free_stack=jnp.broadcast_to(jnp.asarray(stack),
                                                c.pool.free_stack.shape),
                    n_free=jnp.broadcast_to(jnp.asarray(n_free),
                                            c.pool.n_free.shape))
                return dataclasses.replace(
                    c, pool=pool,
                    page_table=jnp.broadcast_to(jnp.asarray(tables),
                                                c.page_table.shape),
                    length=jnp.broadcast_to(jnp.asarray(lengths),
                                            c.length.shape))

            def rec(x):
                if isinstance(x, PagedQuantizedKVCache):
                    return upd(x)
                if isinstance(x, dict):
                    return {k: rec(v) for k, v in x.items()}
                if isinstance(x, (list, tuple)):
                    return type(x)(rec(v) for v in x)
                return x

            self.state = rec(self.state)

    # -- varlen chunked admission + prefix caching (DESIGN.md §7) ----------
    def _cap_hits(self, match_pages: int, prompt_len: int) -> int:
        """Usable hit length in *tokens* for an unpadded prompt of
        ``prompt_len`` tokens, given a ``match_pages``-deep index match
        over its full pages. Hits are rounded down to a chunk boundary (so
        the remaining chunks land on the same grid a miss run uses — the
        bitwise hit==miss property needs identical chunking) and capped
        below the prompt's final chunk (it must always compute: it produces
        the last-valid-position logits the first token is sampled from)."""
        cp = self.prefill_chunk_tokens
        cpp = cp // self.page_size
        n_chunks = -(-prompt_len // cp)
        hit_chunks = min(match_pages // cpp, n_chunks - 1)
        return max(hit_chunks, 0) * cp

    def _initial_pages(self, stream_len: int, max_new: int) -> int:
        """Pages reserved at admission (DESIGN.md §8). Worst-case mode
        (`watermark=None`): the full `pages_for_request` reservation — the
        pool can never exhaust mid-decode and preemption stays cold.
        Optimistic mode: the stream's own pages plus `watermark` pages of
        decode headroom (never more than the worst case) — requests that
        stop early release pages they never reserved, and decode grows the
        reservation page by page (`_ensure_decode_pages`)."""
        total = self._pages_needed(stream_len, max_new)
        if self.watermark is None:
            return total
        return min(total,
                   -(-max(stream_len, 1) // self.page_size) + self.watermark)

    def _admit_chunked(self) -> bool:
        """Admit queued requests into free rows, one at a time (no length
        grouping of any kind — rows prefill independently). Candidates are
        taken in effective-priority order (aging included, FCFS within a
        priority, DESIGN.md §8); a blocked head does not yield to later
        candidates. For each candidate: hash the *unpadded* prompt's full
        pages, match the chain against the index, adopt hit pages by
        refcount, allocate the rest of the initial reservation
        (`_initial_pages`; reclaiming evictable cached pages LRU-first
        under pressure), and start its prefill cursor past the hits.
        Preempted requests re-admit through `_admit_resume` instead.
        Admission is gated by `HostPageAllocator.available_after_adopt`.
        Returns the requests admitted; page tables changed (device sync
        required) when there are any. Each adds its wait since it was
        (re)queued to the counters. DESIGN.md §7."""
        ps = self.page_size
        admitted = []
        for i in range(self.batch):
            if self.rows[i] is not None or not self.queue:
                continue
            k = self._next_candidate_index()
            cand = self.queue[k]                 # validated at submit()
            if cand.uid in self._suspended:
                if not self._admit_resume(i, k, cand):
                    break                        # wait for releases
                admitted.append(cand)
                continue
            S = len(cand.prompt)                 # true length — no padding
            nb = S // ps                         # hashable full pages
            init = self._initial_pages(S, cand.max_new_tokens)
            if cand.uid in self._admit_memo:     # blocked-head retry
                toks, chain = self._admit_memo[cand.uid]
            else:
                toks = np.asarray(cand.prompt, np.int32)
                chain = []
                if self.prefix_cache:
                    with self.spans.span("cache.hash", pages=nb):
                        chain = PG.chain_hashes(toks[:nb * ps], ps)
                self._admit_memo[cand.uid] = (toks, chain)
            # host-tier prefetch at hash-match time (DESIGN.md §11): start
            # swap-in copies for the chain's hosted continuation; while
            # they are in flight the head swap-waits (cheaper than
            # recomputing those pages, per the cost model)
            if self._tiering is not None and \
                    self._prefetch_for_admission(cand.uid, chain, S):
                break                            # copies still in flight
            hit_toks = self._cap_hits(self.allocator.match(chain), S) \
                if self.prefix_cache else 0
            hit = hit_toks // ps                 # adopted pages
            # gate on what is allocatable AFTER adoption: hit pages sitting
            # on the LRU stop being evictable the moment they are adopted
            if init - hit > self.allocator.available_after_adopt(chain[:hit]):
                break                            # wait for releases
            del self.queue[k]
            self._admit_memo.pop(cand.uid, None)
            ids = (self.allocator.adopt(chain[:hit]) if hit else []) \
                + self.allocator.alloc(init - hit)
            if self.prefix_cache:
                self.allocator.misses += nb - hit
            self.rows[i] = cand
            self.row_pages[i] = ids
            self.tables[i, :] = 0
            self.tables[i, :init] = ids
            self.streams[i] = toks
            self.row_chain[i] = chain
            self.gen_base[i] = 0
            self.prefilling[i] = {"toks": toks, "cursor": hit_toks, "S": S}
            self.pos[i] = hit_toks
            self.tok[i, 0] = 0
            admitted.append(cand)
        if admitted:
            self._progressed = True
            now = time.perf_counter()
            self.admissions += len(admitted)
            self.queue_wait_s += sum(now - r._queued_at for r in admitted)
        return admitted

    def _admit_resume(self, i: int, k: int, cand: Request) -> bool:
        """Re-admit a preempted request into row ``i`` (DESIGN.md §8).

        Fast path — every full page of the suspended stream
        (prompt + generated) is still resident in the prefix index and the
        fp-residual snapshot survives: adopt all of them, restore the
        residual and the pending token, and rejoin decode with NO
        prefill. Bitwise-identical to a never-preempted run: the physical
        pages are the very ones the row flushed, the residual is restored
        literally, and seeded sampling is draw-index invariant (token i is
        always drawn at fold_in(key, i) — `generated` is preserved across
        the preemption).

        Swap-restore (DESIGN.md §11, host tier attached): when reclaimed
        pages of the stream survive on the host tier, promotion copies
        are issued and the request swap-waits instead of falling to
        recompute — once they land, the SAME fast path below adopts them,
        so a swap-restored resume is bitwise-identical too (verbatim page
        bytes, restored residual/pending token, draw-index-invariant
        seeded sampling; `host_tier_dtype` recompression is the lossy
        exception, see §11).

        Recompute path — some pages were reclaimed (or no prefix cache):
        re-prefill the full stream with whatever hits remain; the pending
        token is restored at the prefill boundary instead of being
        redrawn, so the emitted stream never forks even though the
        recomputed cache may differ at quantization-noise scale
        (DESIGN.md §7's chunk-grid caveat). Returns False when the pool
        cannot host the resume yet (the caller waits, aging guarantees
        the retry wins eventually)."""
        ps = self.page_size
        snap = self._suspended[cand.uid]
        full, fchain = snap["full_toks"], snap["full_chain"]
        Sf, nbf = len(full), len(full) // ps
        rem = cand.max_new_tokens - len(cand.generated)
        init = self._initial_pages(Sf, rem)
        resident = self.allocator.match(fchain) if self.prefix_cache else 0
        if (self._tiering is not None and resident < nbf
                and snap["resid"] is not None):
            dev, swap = self.allocator.match_tiered(fchain)
            if dev + swap >= nbf and swap > 0 \
                    and self._swap_cost.prefer_swap(nbf - dev):
                # fully restorable without recompute: promote the hosted
                # run; swap-wait while copies are in flight (§11)
                snap["swapped"] = True
                if self._prefetch_for_admission(cand.uid, fchain, Sf,
                                                want_pages=nbf):
                    return False         # swap-wait: copies in flight
                resident = self.allocator.match(fchain)
        if resident >= nbf and snap["resid"] is not None:
            if init - nbf > self.allocator.available_after_adopt(fchain):
                return False
            ids = self.allocator.adopt(fchain) \
                + self.allocator.alloc(init - nbf)
            del self.queue[k]
            self.rows[i] = cand
            self.row_pages[i] = ids
            self.tables[i, :] = 0
            self.tables[i, :init] = ids
            self.streams[i] = full
            self.row_chain[i] = fchain
            self.gen_base[i] = len(cand.generated)
            self.pos[i] = Sf
            self.tok[i, 0] = snap["pending"]
            self._restore_resid(i, snap["resid"])
            del self._suspended[cand.uid]
            self._swap_wait.pop(cand.uid, None)
            self.preempt_fast_resumes += 1
            if snap.get("swapped"):
                self.preempt_swap_restores += 1
            return True
        hit_toks = self._cap_hits(resident, Sf) if self.prefix_cache else 0
        hit = hit_toks // ps
        if init - hit > self.allocator.available_after_adopt(fchain[:hit]):
            return False
        ids = (self.allocator.adopt(fchain[:hit]) if hit else []) \
            + self.allocator.alloc(init - hit)
        if self.prefix_cache:
            self.allocator.misses += nbf - hit
        del self.queue[k]
        self.rows[i] = cand
        self.row_pages[i] = ids
        self.tables[i, :] = 0
        self.tables[i, :init] = ids
        self.streams[i] = full
        self.row_chain[i] = fchain
        self.gen_base[i] = len(cand.generated)
        self.prefilling[i] = {"toks": full, "cursor": hit_toks, "S": Sf}
        self.pos[i] = hit_toks
        self.tok[i, 0] = 0
        self._resume_tok[i] = snap["pending"]
        del self._suspended[cand.uid]
        self._swap_wait.pop(cand.uid, None)
        self.preempt_recompute_resumes += 1
        return True

    def _hist_blocks(self, max_start: int) -> int:
        """Static history-walk bound of a chunk dispatch whose deepest
        cursor is ``max_start`` tokens: the cursor in blocks rounded up to
        a power of two (compile set stays O(log max_blocks); masking trims
        the over-approximation), so a chunk never materializes max_len of
        history (DESIGN.md §7)."""
        blocks = -(-max_start // self.page_size)
        return 0 if blocks == 0 else min(1 << (blocks - 1).bit_length(),
                                         self.max_blocks)

    def _chunk_prefill_fn(self, hb: int):
        """Jitted chunk fn for a dispatch with history bound ``hb``
        (`_hist_blocks`), compiled as the program ``jit_chunk_prefill``.

        Keyed on (bound, use_fused_prefill, kv_cache_dtype) — the toggle
        and dtype are read from the live config at every dispatch, so
        flipping either mid-process compiles the other attention path /
        pool format instead of serving a stale trace (DESIGN.md §9)."""
        fused = bool(getattr(self.config, "use_fused_prefill", True))
        key = (hb, fused, self.kv_cache_dtype)
        fn = self._chunk_prefill_fns.get(key)
        if fn is None:
            from repro.serving.engine import make_chunk_prefill_fn
            # donate the incoming state: the caller immediately replaces
            # self.state with the result, and donation lets XLA update the
            # page pool in place instead of copying every pool buffer per
            # chunk dispatch (the scatter in prefill_at would otherwise
            # clone ~MBs of quantized pages each tick)
            fn = self._chunk_prefill_fns[key] = TR.program(
                make_chunk_prefill_fn(self.cfg, hist_blocks=hb,
                                      use_fused=fused,
                                      kv_cache_dtype=self.kv_cache_dtype),
                "chunk_prefill", donate_argnums=(2,))
        return fn

    def _chunk_width(self, rem: int) -> int:
        """Dispatch width (tokens) for a row whose prompt has ``rem`` tokens
        left: full chunks use the configured chunk size; a final partial
        chunk is rounded up to a power-of-two page count (capped at the
        chunk size), so the compile set of chunk shapes stays
        O(log chunk_pages) instead of one shape per possible remainder —
        the varlen analogue of the padded path's fixed grid (DESIGN.md §7).
        Tokens between ``rem`` and the width are dispatch padding: masked
        out of every write and never part of any row's stream."""
        cp = self.prefill_chunk_tokens
        if rem >= cp:
            return cp
        pages = -(-rem // self.page_size)
        return min(self.page_size * (1 << (pages - 1).bit_length()), cp)

    def _advance_prefill(self) -> list[Request]:
        """Advance one prompt chunk for the mid-prefill rows; returns
        requests that finished AT the prefill boundary (their very first
        draw was a stop token, so they complete with empty output).

        Every prefilling row whose next chunk needs the same dispatch
        *width* as the round-robin head's rides the same dispatch — per-row
        ``start`` cursors and ``valid`` lengths make one traced shape serve
        rows at different offsets AND different final-chunk lengths (rows
        only wait for their own tick when their pow2 width differs). Each
        chunk attends over its row's resident pages — cache hits included —
        and its freshly *completed* pages are published to the hash index
        immediately, so a concurrent identical prompt shares them while
        this one is still prefilling; a final chunk's partial page stays
        unpublished (it lives in the fp residual, still mutable). A row's
        final chunk yields its last-valid-position logits; the row then
        joins the decode set in the same tick. DESIGN.md §7."""
        if not self.prefilling:
            return []
        ps = self.page_size
        order = sorted(self.prefilling)
        head = order[self._pf_rr % len(order)]
        self._pf_rr += 1
        rem_of = {i: st["S"] - st["cursor"]
                  for i, st in self.prefilling.items()}
        w = self._chunk_width(rem_of[head])
        group = [i for i in order if self._chunk_width(rem_of[i]) == w]
        hb = self._hist_blocks(max(self.prefilling[i]["cursor"]
                                   for i in group))
        with self.spans.span("sched.prefill", width=w, hist_blocks=hb,
                             rows=len(group)):
            toks = np.zeros((self.batch, w), np.int32)
            start = np.zeros((self.batch,), np.int32)
            valid = np.zeros((self.batch,), np.int32)
            mask = np.zeros((self.batch,), bool)
            for i in group:
                st = self.prefilling[i]
                c = min(self.prefill_chunk_tokens, rem_of[i])
                toks[i, :c] = st["toks"][st["cursor"]:st["cursor"] + c]
                start[i] = st["cursor"]
                valid[i] = c
                mask[i] = True
            args = (self.params, jnp.asarray(toks), self.state,
                    jnp.asarray(start), jnp.asarray(valid), jnp.asarray(mask))
            with self.spans.span("dispatch.prefill"):
                logits, self.state = self._chunk_prefill_fn(hb)(*args)
        self._progressed = True
        self.prefill_tokens_computed += int(valid.sum())
        self.prefill_tokens_dispatched += toks.size
        # resumed rows (DESIGN.md §8) restore their pre-preemption pending
        # token instead of redrawing — they are not "finishing" rows
        finishing = [i for i in group
                     if rem_of[i] <= self.prefill_chunk_tokens
                     and i not in self._resume_tok]
        if finishing:                    # token index 0 for finishing rows
            with self.spans.span("wait.prefill"):
                sampled = self._sample_rows(logits, finishing, offset=0)
        done: list[Request] = []
        for i in group:
            st = self.prefilling[i]
            c = int(valid[i])
            if self.prefix_cache:
                # only pages fully covered by [cursor, cursor + c) are
                # immutable and publishable; a trailing partial page is not
                for b in range(st["cursor"] // ps, (st["cursor"] + c) // ps):
                    self.allocator.register(int(self.tables[i, b]),
                                            self.row_chain[i][b])
            st["cursor"] += c
            self.pos[i] = st["cursor"]
            if st["cursor"] == st["S"]:
                rtok = self._resume_tok.pop(i, None)
                if rtok is not None:
                    # recompute-resume complete: the pending token was drawn
                    # before preemption (first token already delivered) — do
                    # not redraw, do not re-record TTFT (DESIGN.md §8)
                    del self.prefilling[i]
                    self.tok[i, 0] = rtok
                    continue
                del self.prefilling[i]
                r = self.rows[i]
                if int(sampled[i]) in self._stop_ids(r):
                    # the very first draw is a stop token: suppressed like
                    # any other (DESIGN.md §6) — finish with empty output
                    self._finish(r, "stop_token")
                    done.append(r)
                    self._release_row(i)
                    continue
                # the first token: this tick's decode scan appends it to
                # `generated`, and the tick's end delivers it
                self.tok[i, 0] = sampled[i]
        return done

    # -- tiered KV cache: demotion / promotion copies (DESIGN.md §11) ------
    def _cache_leaves(self) -> list[PagedQuantizedKVCache]:
        """The state's paged cache leaves in deterministic pytree traversal
        order — the SAME order `_snapshot_resid` uses, and the order host
        tier payload lists are keyed by (DESIGN.md §11)."""
        out: list[PagedQuantizedKVCache] = []

        def rec(x):
            if isinstance(x, PagedQuantizedKVCache):
                out.append(x)
            elif isinstance(x, dict):
                for v in x.values():
                    rec(v)
            elif isinstance(x, (list, tuple)):
                for v in x:
                    rec(v)
        rec(self.state)
        return out

    def _demote_to_host(self, page: int, digest: bytes) -> bool:
        """Demote one indexed device page to the host tier (DESIGN.md §11):
        copy its quantized values + scale rows out of every cache leaf
        (page axis -4 — stacked uniform state carries leading layer-group
        dims) and store them under the chain digest,
        recompressing to `host_tier_dtype` when set. Installed as the
        allocator's ``demote_hook`` (reclaim-time demotion) and called
        eagerly by the preempt-by-swap arm. Skips when the digest is
        already hosted (registered pages are immutable — the first copy
        is the only copy needed) or the cost model says the copy isn't
        worth a page of recompute."""
        from repro.core import tiering as TIER
        tier = self._tiering
        if tier is None or self.state is None or digest in tier:
            return False
        if not self._swap_cost.prefer_swap(1):
            return False
        payloads, dtypes = [], []
        for leaf in self._cache_leaves():
            dt = leaf.pool.kv_dtype
            host_dt = tier.dtype or dt
            kq, ks = TIER.repack_page(leaf.pool.k_q[..., page, :, :, :],
                                      leaf.pool.k_s[..., page, :, :, :],
                                      dt, host_dt)
            vq, vs = TIER.repack_page(leaf.pool.v_q[..., page, :, :, :],
                                      leaf.pool.v_s[..., page, :, :, :],
                                      dt, host_dt)
            payloads.append((kq, ks, vq, vs))
            dtypes.append(host_dt)
        return tier.put(digest, payloads, dtypes)

    def _demote_chain(self, chain) -> int:
        """Eagerly demote every device-resident page of ``chain`` to the
        host tier (the preempt-by-swap arm, DESIGN.md §11): the victim's
        pages gain a host copy BEFORE pool pressure can reclaim them, so
        re-admission swap-restores instead of dropping to recompute even
        if the device copies die meanwhile. Returns pages copied."""
        n = 0
        for h in chain:
            page = self.allocator.index.get(h)
            if page is not None and self._demote_to_host(page, h):
                n += 1
        return n

    def _write_host_pages(self, pages: list[int], recs) -> None:
        """Scatter host-tier records into the device pools at ``pages``
        (the promotion copy, DESIGN.md §11): one batched `.at[].set` per
        leaf array, dispatched asynchronously — decode ticks overlap the
        copies, which is what makes a swap-in hit cost a copy rather than
        a re-prefill. Payloads stored in a cheaper host dtype repack to
        the pool's dtype here (lossy round trip — the §11 caveat)."""
        from repro.core import tiering as TIER
        ids = jnp.asarray(np.asarray(pages, np.int32))
        li = [0]

        def upd(x: PagedQuantizedKVCache) -> PagedQuantizedKVCache:
            k = li[0]
            li[0] += 1
            dt = x.pool.kv_dtype
            quads = []
            for rec_ in recs:
                kq, ks, vq, vs = rec_.payloads[k]
                src = rec_.dtypes[k]
                if src != dt:
                    kq, ks = TIER.repack_page(kq, ks, src, dt)
                    vq, vs = TIER.repack_page(vq, vs, src, dt)
                quads.append((kq, ks, vq, vs))
            kq, ks, vq, vs = (np.stack(a, axis=-4) for a in zip(*quads))
            pool = dataclasses.replace(
                x.pool,
                k_q=x.pool.k_q.at[..., ids, :, :, :].set(jnp.asarray(kq)),
                k_s=x.pool.k_s.at[..., ids, :, :, :].set(jnp.asarray(ks)),
                v_q=x.pool.v_q.at[..., ids, :, :, :].set(jnp.asarray(vq)),
                v_s=x.pool.v_s.at[..., ids, :, :, :].set(jnp.asarray(vs)))
            return dataclasses.replace(x, pool=pool)

        def rec(x):
            if isinstance(x, PagedQuantizedKVCache):
                return upd(x)
            if isinstance(x, dict):
                return {kk: rec(vv) for kk, vv in x.items()}
            if isinstance(x, (list, tuple)):
                return type(x)(rec(v) for v in x)
            return x
        self.state = rec(self.state)

    def _issue_prefetch(self, chain, lo: int, n: int) -> int:
        """Start swap-in copies for the host-resident digests
        ``chain[lo:lo+n]`` (DESIGN.md §11): claim a staging page per
        digest (`HostPageAllocator.begin_prefetch`), write the host
        payload into the pools, and let the allocator publish the page —
        immediately, or after the injector's ``swap_delay`` ticks via the
        in-flight population. An injected swap fault (``p_swap_fail``)
        LOSES the host record instead: the digest stops matching and the
        requester falls back to recompute — never a stall. Returns the
        number of copies started."""
        a, tier = self.allocator, self._tiering
        inj = a.injector
        pages, recs = [], []
        for h in chain[lo:lo + n]:
            if h in a.index or h in a.inflight_digests:
                continue                 # already device-resident / staging
            if h not in tier.pages or a.available < 1:
                break
            if inj is not None and inj.swap_fault():
                tier.drop(h)             # lost record: run ends here
                break
            delay = inj.swap_delay if inj is not None else 0
            pages.append(a.begin_prefetch(h, delay))
            recs.append(tier.get(h))
        if pages:
            self._write_host_pages(pages, recs)
        return len(pages)

    def _prefetch_for_admission(self, uid: int, chain, prompt_len: int,
                                want_pages: int | None = None) -> bool:
        """Prefetch the host-tier continuation of ``chain`` for a
        candidate at hash-match time, ahead of admission (DESIGN.md §11).
        ``want_pages`` caps how deep a hit is useful (`_cap_hits` grid for
        fresh prompts; the full stream for a suspended resume). Returns
        True while usable copies are still in flight — the candidate
        swap-waits (tracked per uid for the stuck report) instead of
        recomputing pages whose restore the cost model prices below a
        re-prefill."""
        a = self.allocator
        dev, swap = a.match_tiered(chain)
        if want_pages is None:
            want_pages = self._cap_hits(dev + swap, prompt_len) \
                // self.page_size
        want_pages = min(want_pages, dev + swap)
        if want_pages <= dev or \
                not self._swap_cost.prefer_swap(want_pages - dev):
            self._swap_wait.pop(uid, None)
            return False
        self._issue_prefetch(chain, dev, want_pages - dev)
        in_flight = sum(1 for h in chain[dev:want_pages]
                        if h in a.inflight_digests)
        if in_flight:
            self._swap_wait[uid] = in_flight
            return True
        self._swap_wait.pop(uid, None)
        return False

    # -- preemption-by-recompute (DESIGN.md §8) ----------------------------
    def _snapshot_resid(self, i: int) -> list:
        """Pull row ``i``'s per-layer fp residuals (the mutable partial
        page) to host numpy, in the deterministic pytree traversal order
        `_restore_resid` replays. Together with the pending token this is
        the row's entire non-page state — flushed pages are immutable and
        survive in the pool/index (DESIGN.md §8).

        Residuals are (..., B, H, ps, D) — unstacked per-layer caches
        (mixed plans, tail blocks) have no leading dim, the uniform
        stacked state carries a leading group dim — so the row is indexed
        on the batch axis (-4), never axis 0."""
        out = []

        def rec(x):
            if isinstance(x, PagedQuantizedKVCache):
                out.append((np.asarray(x.resid_k)[..., i, :, :, :],
                            np.asarray(x.resid_v)[..., i, :, :, :]))
            elif isinstance(x, dict):
                for v in x.values():
                    rec(v)
            elif isinstance(x, (list, tuple)):
                for v in x:
                    rec(v)
        rec(self.state)
        return out

    def _restore_resid(self, i: int, snaps: list) -> None:
        """Write a `_snapshot_resid` snapshot back into row ``i``'s cache
        leaves (fast resume, DESIGN.md §8). Same traversal order as the
        snapshot, so layer k's residual lands back in layer k."""
        it = iter(snaps)

        def rec(x):
            if isinstance(x, PagedQuantizedKVCache):
                k, v = next(it)
                return dataclasses.replace(
                    x,
                    resid_k=x.resid_k.at[..., i, :, :, :].set(
                        jnp.asarray(k)),
                    resid_v=x.resid_v.at[..., i, :, :, :].set(
                        jnp.asarray(v)))
            if isinstance(x, dict):
                return {kk: rec(vv) for kk, vv in x.items()}
            if isinstance(x, (list, tuple)):
                return type(x)(rec(v) for v in x)
            return x
        self.state = rec(self.state)

    def _pick_victim(self) -> int | None:
        """Preemption victim among running rows: lowest static priority
        first, then latest arrival (LIFO within a priority — the newest
        request re-queues, the oldest keeps its progress). Never the last
        running row: the sole survivor must be able to grow to its full
        budget (its worst case fits the pool alone, validated at submit),
        which is the forward-progress guarantee (DESIGN.md §8)."""
        running = [i for i, r in enumerate(self.rows) if r is not None]
        if len(running) <= 1:
            return None
        return min(running,
                   key=lambda i: (self.rows[i].priority
                                  if self.rows[i].priority is not None else 0,
                                  -getattr(self.rows[i], "_arrival", i)))

    def _preempt_row(self, i: int) -> None:
        """Suspend row ``i`` and re-queue its request (DESIGN.md §8).

        Mid-decode rows snapshot (pending token, fp residuals, the full
        token stream and its hash chain) for `_admit_resume`; release then
        runs the normal promotion path, so the row's flushed pages park on
        the evictable LRU still indexed — the fast (bitwise) resume adopts
        exactly those pages back. Mid-prefill rows have no decode state:
        they re-queue plainly (restart prefill, prefix hits make it
        near-free), except a resume-in-progress, which keeps carrying its
        pending token. The preemption-loop detector counts preemptions
        since the last global progress and raises `PoolExhaustedError`
        past the configured limit instead of livelocking."""
        r = self.rows[i]
        swap_chain = None            # mid-decode chain for preempt-by-swap
        self._preempts_since_progress += 1
        if self._preempts_since_progress > self.preempt_loop_limit:
            holders = {rr.uid: len(self.row_pages[j])
                       for j, rr in enumerate(self.rows) if rr is not None}
            raise PoolExhaustedError(
                f"pool exhausted: {self._preempts_since_progress} "
                f"preemption(s) without any request advancing (limit "
                f"{self.preempt_loop_limit}); page holders "
                f"(uid -> pages): {holders}; "
                f"available={self.allocator.available} of "
                f"{self.n_pages - 1}; {self._stuck_report()}")
        self.preemptions += 1
        ps = self.page_size
        if i in self.prefilling:
            rtok = self._resume_tok.pop(i, None)
            if rtok is not None:     # resume-in-progress: keep its snapshot
                self._suspended[r.uid] = {
                    "pending": rtok, "resid": None,
                    "full_toks": self.streams[i],
                    "full_chain": list(self.row_chain[i])}
        else:
            stream, gb = self.streams[i], self.gen_base[i]
            full = np.concatenate(
                [stream, np.asarray(r.generated[gb:], np.int32)])
            fchain = (PG.chain_hashes(full[:(len(full) // ps) * ps], ps)
                      if self.prefix_cache else [])
            self._suspended[r.uid] = {
                "pending": int(self.tok[i, 0]),
                "resid": self._snapshot_resid(i),
                "full_toks": full,
                "full_chain": fchain}
            swap_chain = fchain
        self._release_row(i)         # promote -> LRU: prefix stays hittable
        if (swap_chain and self._tiering is not None
                and self._swap_cost.prefer_swap(len(swap_chain))):
            # preempt-by-swap (DESIGN.md §11): the victim's freshly
            # promoted pages gain host copies now, so even if pool
            # pressure reclaims the device copies before re-admission,
            # resume swap-restores (bitwise) instead of recomputing
            if self._demote_chain(swap_chain):
                self.preempt_by_swap += 1
        r._submit_tick = self.ticks  # aging clock restarts at preemption
        r._queued_at = time.perf_counter()
        self.queue.append(r)

    def _ensure_decode_pages(self, active: list[int]
                             ) -> tuple[list[int], int, bool]:
        """Optimistic-admission growth pass before a decode chunk
        (DESIGN.md §8): every block the n-step scan can flush into
        (`append` flushes block pos//ps at page boundaries — an unmapped
        entry would silently lose the page to the sentinel) must be mapped
        BEFORE the dispatch. Grows each active row's reservation to cover
        pos+n; when the pool cannot cover the growth, preempts victims
        (`_pick_victim`) until it can, and when no victim remains
        (forward-progress rule) stalls the lowest-priority needy rows for
        this tick — they keep their pages and retry next tick. Returns
        (active rows to decode, chunk length n, tables changed)."""
        ps = self.page_size
        changed = False
        for _ in range(4 * self.batch + 8):      # paranoia bound
            if not active:
                return active, 0, changed
            n = self._chunk_len(active)
            need = {}
            for i in active:
                want = -(-(int(self.pos[i]) + n) // ps)
                have = len(self.row_pages[i])
                if want > have:
                    need[i] = want - have
            if not need:
                return active, n, changed
            if sum(need.values()) <= self.allocator.available:
                # deterministic order: highest priority grows first
                for i in sorted(need,
                                key=lambda j: (-(self.rows[j].priority or 0),
                                               getattr(self.rows[j],
                                                       "_arrival", j))):
                    ids = self.allocator.alloc(need[i])
                    have = len(self.row_pages[i])
                    self.tables[i, have:have + len(ids)] = ids
                    self.row_pages[i].extend(ids)
                return active, n, True
            victim = self._pick_victim()
            if victim is None:
                # no preemptable victim: stall the lowest-priority needy
                # rows this tick until the rest fits (they hold pages and
                # retry next tick); re-loop — n can change with the set
                order = sorted(need,
                               key=lambda j: ((self.rows[j].priority or 0),
                                              -getattr(self.rows[j],
                                                       "_arrival", j)))
                while order and sum(need[j] for j in order) \
                        > self.allocator.available:
                    drop = order.pop(0)
                    self.decode_stall_ticks += 1
                    active = [i for i in active if i != drop]
                continue
            self._preempt_row(victim)
            changed = True
            active = [i for i in active if self.rows[i] is not None]
        return [], 0, changed                    # bound hit: stall the tick

    def _cow_retarget(self, active: list[int], n: int) -> bool:
        """Copy-on-write gate before an n-step decode scan: any block the
        scan will flush must be privately owned — a shared or indexed page
        is immutable (another row, or a future hit, reads it). Structurally
        the scheduler's own decode always flushes into the row's private
        reservation pages, so this runs only when `cow_armed` is set by a
        caller that wired `fork_row` sharing into the batch (beam-search-
        style); the check is O(active · blocks-per-scan) host work.
        Returns True if tables changed."""
        ps = self.page_size
        changed = False
        for i in active:
            pos = int(self.pos[i])
            for b in range(pos // ps, (pos + n) // ps):
                page = int(self.tables[i, b])
                if page == PG.SENTINEL_PAGE:
                    continue
                new = self.allocator.ensure_private(page)
                if new is not None:
                    self.row_pages[i][self.row_pages[i].index(page)] = new
                    self.tables[i, b] = new
                    changed = True
        return changed

    def _step_paged(self) -> list[Request]:
        """One paged tick — always varlen chunked admission (DESIGN.md §7):
        admit (hash-match + adopt + alloc), advance one prefill chunk, then
        decode one scanned chunk for the rows that are past prefill.
        Prefill and decode interleave tick by tick, so a long prompt never
        stalls running decodes.

        Under optimistic admission (`watermark` set, DESIGN.md §8) a growth
        pass runs between prefill and decode: it maps every block the
        decode scan can flush into, preempting victims when the pool can't
        cover the growth. With `watermark=None` the worst-case reservation
        makes growth impossible and the pass is skipped entirely — the
        preemption machinery costs nothing when disabled."""
        if self.state is None:
            self.state = self._init_state(self.batch)
        self.allocator.tick()        # fault-injection clock + deferred drain
        with self.spans.span("sched.admit") as sp:
            admitted = self._admit_chunked()
            if admitted:
                sp.set(uids=TR.uids(admitted))
        if admitted:
            self._sync_device()      # hit pages + cursors live before use
        done = self._advance_prefill()   # first-draw-is-stop completions
        active = [i for i, r in enumerate(self.rows)
                  if r is not None and i not in self.prefilling]
        n = None
        if active and self.watermark is not None:
            with self.spans.span("sched.grow"):
                active, n, grew = self._ensure_decode_pages(active)
            if grew:
                self._sync_device()  # new/changed tables live before decode
        if active:
            row_mask = np.zeros((self.batch,), bool)
            row_mask[active] = True
            done = done + self._decode_tick(active, row_mask, n=n)
        if done:
            self._sync_device()
        return done

    # -- introspection -----------------------------------------------------
    def pool_report(self) -> dict:
        """Pool occupancy + prefix-cache counters, plus request-lifecycle
        observability (DESIGN.md §6): both backends report
        ``aborted_requests`` and per-request TTFT percentiles
        (`lifecycle_report`); the paged backend adds the page populations.

        ``pages_allocated`` counts referenced pages, ``pages_cached`` the
        evictable LRU population (refcount 0, still hittable), and the two
        never overlap; ``pages_live`` counts *distinct physical* pages
        holding tokens (`core.paging.live_page_count` — prefix hits alias
        one page into several rows, so a per-row sum would double-count).
        Prefix mode adds the
        `HostPageAllocator` counters (hits / misses / reclaims /
        cow_retargets) and the page hit rate.

        The tick's cumulative counters (DESIGN.md §6): ``ticks``;
        ``admissions`` and ``queue_wait_s``, their summed wait from
        (re)queueing to admission; ``first_token_s``, the first tokens'
        summed time from submission to delivery (the sum of the TTFTs the
        percentiles are taken over), and ``first_token_scan_s``, the part
        of it from the ticks' scan launches to their tokens' bookkeeping
        (`_scan_s`);
        ``prefill_tokens_dispatched``, batch x width over the chunk
        dispatches, beside the valid ``prefill_tokens_computed``.

        With a host tier attached (DESIGN.md §11) the report splits
        device vs host bytes — ``device_bytes_live`` counts HBM-resident
        page bytes only, the ``host_*`` keys count the swap tier, and
        each tier's utilization is computed against its OWN capacity so
        a demoted page is never double-counted and utilization stays ≤1
        per tier. Swap traffic counters (demotions / promotions /
        prefetch hit rate / preempt-by-swap) quantify the
        swap-vs-recompute tradeoff the §11 cost model prices.

        ``pages_vs_int8_equal_hbm`` /
        ``kv_page_bytes_saved_vs_int8_frac`` report the memory/accuracy
        curve position (DESIGN.md §9): for a uniform engine, the
        single-pool ratio; for a mixed per-layer plan (§10), the
        page-bytes-weighted mean over the stack, with the per-layer
        assignment itself under ``kv_cache_layer_dtypes``."""
        if not self.paged:
            return self.lifecycle_report()
        lengths = [int(self.pos[i]) if r is not None else 0
                   for i, r in enumerate(self.rows)]
        live = PG.live_page_count(self.tables, lengths, self.page_size)
        a = self.allocator
        allocated = (self.n_pages - 1) - a.n_free - a.n_cached \
            - len(a.deferred) - len(a.inflight)
        # memory/accuracy curve metric (DESIGN.md §9): how many pages this
        # dtype fits into the HBM an int8 pool of the same geometry takes —
        # int4 packs two tokens per byte, so ~2x minus the unshrunk f32
        # scale rows (1.94x at page_size 128)
        pb = lambda dt: PG.page_bytes_for(self.page_size,
                                          self.cfg.n_kv_heads,
                                          self.cfg.head_dim, dt)
        spec = self.kv_cache_dtype
        layer_dts = Q.layer_kv_dtypes(spec, self.cfg.n_layers)
        stack_bytes = sum(pb(dt) for dt in layer_dts)
        int8_bytes = pb("int8") * len(layer_dts)
        rep = {"kv_cache_dtype": (spec if isinstance(spec, str)
                                  else "mixed"),
               # uniform: single-pool ratio; mixed plan: per-layer-weighted
               # mean over the stack (§10) — same number for uniform specs
               "pages_vs_int8_equal_hbm": int8_bytes / stack_bytes,
               "kv_page_bytes_saved_vs_int8_frac":
                   1.0 - stack_bytes / int8_bytes,
               "pages_total": self.n_pages - 1,
               "pages_free": a.n_free,
               "pages_cached": a.n_cached,
               "pages_allocated": allocated,
               "pages_inflight": len(a.inflight),
               "pages_live": live,
               "utilization": live / max(allocated, 1),
               # device-tier bytes only: a demoted page's bytes move to
               # the host_* keys below, never both (DESIGN.md §11)
               "device_bytes_live": live * stack_bytes,
               "preemptions": self.preemptions,
               "preempt_fast_resumes": self.preempt_fast_resumes,
               "preempt_recompute_resumes": self.preempt_recompute_resumes,
               "decode_stall_ticks": self.decode_stall_ticks,
               "prefill_tokens_computed": self.prefill_tokens_computed,
               "prefill_tokens_dispatched": self.prefill_tokens_dispatched,
               "decode_tokens_computed": self.decode_tokens_computed,
               "ticks": self.ticks,
               "admissions": self.admissions,
               "queue_wait_s": self.queue_wait_s,
               "first_token_s": sum(self._ttfts),
               "first_token_scan_s": self.first_token_scan_s,
               **self.lifecycle_report()}
        if not isinstance(spec, str):
            rep["kv_cache_layer_dtypes"] = list(layer_dts)
        if self.prefix_cache:
            rep.update({
                "page_hits": a.hits,
                "page_misses": a.misses,
                "page_hit_rate": a.hits / max(a.hits + a.misses, 1),
                "reclaims": a.reclaims,
                "cow_retargets": a.cow_retargets,
            })
        if self._tiering is not None:
            t, cm = self._tiering, self._swap_cost
            rep.update({
                "host_pages_capacity": t.capacity,
                "host_pages_used": len(t),
                "host_utilization": len(t) / max(t.capacity, 1),
                "host_bytes": t.nbytes,
                "host_tier_dtype": t.dtype,
                "evictor": self.config.evictor,
                "demotions": t.demotions,
                "promotions": t.promotions,
                "host_evictions": t.host_evictions,
                "host_lost_records": t.lost,
                "prefetch_issued": a.prefetch_issued,
                "prefetch_page_hits": a.promote_hits,
                "prefetch_hit_rate":
                    a.promote_hits / max(a.prefetch_issued, 1),
                "preempt_by_swap": self.preempt_by_swap,
                "preempt_swap_restores": self.preempt_swap_restores,
                "swap_cost_tokens_per_page": cm.swap_cost(1),
                "recompute_cost_tokens_per_page": cm.recompute_cost(1),
                "est_prefill_tokens_saved_by_swap":
                    a.promote_hits * (cm.recompute_cost(1)
                                      - cm.swap_cost(1)),
            })
        if a.injector is not None:
            rep.update({
                "injected_alloc_fault_ticks": a.injector.alloc_fault_ticks,
                "injected_delayed_releases": a.injector.delayed_releases,
                "injected_held_pages": a.injector.hold_pages,
                "injected_swap_faults": a.injector.swap_faults,
                "pages_deferred": len(a.deferred),
            })
        return rep
