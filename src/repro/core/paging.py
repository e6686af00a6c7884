"""Paged INT8 KV cache — block-pool allocator + page-table views.

The contiguous `QuantizedKVCache` reserves ``batch × max_len`` slots per
layer, so serving capacity is bounded by the *worst-case* sequence length.
Paging (vLLM-style) breaks the cache into fixed-size pages owned by a shared
pool; each sequence holds a page table mapping logical token blocks to
physical pages, so capacity is bounded by *actual* tokens (DESIGN.md §5).

Two device pytrees plus one host-side policy object:

``PagePool`` — the physical storage + allocator state (DESIGN.md §5):
    k_q, v_q    int8  (n_pages, H_kv, page_size, D)   heads-major pages
    k_s, v_s    f32   (n_pages, H_kv, 1, D)   one scale row per page+head
    free_stack  int32 (n_pages,)            free page ids; top = n_free-1
    n_free      int32 ()

``PagedQuantizedKVCache`` — a batched *view* into one pool (DESIGN.md §5):
    pool        PagePool
    page_table  int32 (B, max_blocks)       physical page per logical block
    resid_k/v   ref_dtype (B, H_kv, page_size, D)  unquantized current page
    length      int32 (B,)                  per-row tokens written

``HostPageAllocator`` — the host-authoritative allocation policy
(DESIGN.md §7): free list, per-page refcounts, the content-hash index that
backs automatic prefix caching, and the LRU of evictable cached pages. The
scheduler owns one instance and mirrors its state into the device pytrees
between steps; nothing on the device ever sees a refcount.

Key invariants:
  * page_size == quantization block size: one scale row per page, so scales
    stream with their page through the fused kernel (DESIGN.md §5).
  * Pages are heads-major: a (row, head) kernel step reads one
    (page_size, D) tile and one (1, D) scale row, whose last two dims are
    the array's own — the tiling Mosaic requires on the TPU.
  * Page 0 is a reserved sentinel: it is never allocated, unmapped table
    entries point at it, and masked-out rows scatter into it. Its contents
    are garbage by design and always masked out of attention by `length`.
  * `length` is per-row (unlike the contiguous cache's scalar): rows live on
    independent timelines, which is what makes real continuous batching
    possible (serving/scheduler.py).
  * A page is only ever written by the flush (or prefill scatter) that fills
    it; flushed pages are immutable. Sharing therefore never needs a device
    copy: copy-on-write is a host-side *retarget* of a table entry before
    the flush, and the fp residual already holds the full page content
    (DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
import hashlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantization as Q

SENTINEL_PAGE = 0   # never allocated; unmapped / masked writes land here


# ---------------------------------------------------------------------------
# Content-hash chain + host-side allocator (automatic prefix caching)
# ---------------------------------------------------------------------------

_CHAIN_SEED = b"repro-paged-int8-v1"


def chain_hashes(tokens, page_size: int, parent: bytes | None = None):
    """Hash chain over a page-aligned token stream (DESIGN.md §7).

    ``tokens`` (T,) int array with T a multiple of ``page_size``. Returns a
    list of ``T // page_size`` digests where digest ``i`` commits to *all*
    tokens in pages ``0..i`` — ``h_i = H(h_{i-1} || tokens[i*ps:(i+1)*ps])``
    — so equal digests imply equal full prefixes, which is what lets a page
    be shared purely by digest equality. ``parent`` seeds the chain (pass a
    previous digest to extend a stream, e.g. past the prompt into generated
    tokens).

    Callers hash the *unpadded* token stream: the scheduler digests only a
    prompt's full pages (``tokens[:len(prompt) // page_size * page_size]``),
    so two prompts sharing a prefix produce equal digests at *any* total
    lengths — no pad tokens enter the chain, hence no pad-width (length mod
    page_size) agreement condition. The partial final page is never hashed:
    it lives in the fp residual, mutable until decode fills it."""
    toks = np.ascontiguousarray(np.asarray(tokens, dtype=np.int32))
    if toks.ndim != 1 or toks.size % page_size:
        raise ValueError(f"token stream of shape {toks.shape} is not a "
                         f"multiple of page_size={page_size}")
    h = parent if parent is not None else _CHAIN_SEED
    out = []
    for i in range(toks.size // page_size):
        blk = toks[i * page_size:(i + 1) * page_size].tobytes()
        h = hashlib.blake2b(h + blk, digest_size=16).digest()
        out.append(h)
    return out


class PoolFaultInjector:
    """Deterministic seeded fault injector for `HostPageAllocator`
    (DESIGN.md §8).

    Drives the scheduler's overload-recovery paths from tests and
    benchmarks instead of waiting for production pressure. Three knobs,
    all deterministic given the seed and the tick sequence:

      * ``p_alloc_fail`` — per-tick probability that every admission /
        growth gate reports zero available pages for that tick (a
        transient allocation failure; the draw happens once per
        `HostPageAllocator.tick`, never per query, so repeated gate
        consults within a tick agree).
      * ``hold_pages`` — forced pressure: this many pages are virtually
        withheld from the gates (`available` / `available_after_adopt`).
        Mutable at any time, so tests can squeeze the pool mid-run and
        release it later.
      * ``reclaim_delay`` — delayed reclaim: a page whose refcount hits 0
        is parked for this many ticks before it reaches the LRU / free
        list, modelling deferred host-side cleanup.

    Host-tier faults (DESIGN.md §11) drive the swap fallback paths:

      * ``p_swap_fail`` — per-prefetch probability (seeded draw per
        `swap_fault` call) that a host-tier record is LOST at promotion
        time: the tier drops the record, the digest stops matching, and
        the requester falls back to recompute instead of stalling.
      * ``swap_delay`` — every prefetch's device copy takes this many
        extra ticks to land (the page rides the allocator's in-flight
        population until `HostPageAllocator.tick` completes it),
        modelling a saturated host/device interconnect.

    Faults apply to the *gates* only; `alloc` and copy-on-write check
    physical capacity, preserving the invariant that admission never
    fails after a gate has passed (DESIGN.md §7)."""

    def __init__(self, seed: int = 0, *, p_alloc_fail: float = 0.0,
                 hold_pages: int = 0, reclaim_delay: int = 0,
                 p_swap_fail: float = 0.0, swap_delay: int = 0):
        if not 0.0 <= p_alloc_fail <= 1.0:
            raise ValueError(f"p_alloc_fail={p_alloc_fail} not in [0, 1]")
        if not 0.0 <= p_swap_fail <= 1.0:
            raise ValueError(f"p_swap_fail={p_swap_fail} not in [0, 1]")
        if hold_pages < 0 or reclaim_delay < 0 or swap_delay < 0:
            raise ValueError("hold_pages / reclaim_delay / swap_delay "
                             "must be >= 0")
        self._rng = np.random.RandomState(seed)
        self.p_alloc_fail = p_alloc_fail
        self.hold_pages = hold_pages
        self.reclaim_delay = reclaim_delay
        self.p_swap_fail = p_swap_fail
        self.swap_delay = swap_delay
        self.blocked = False        # is the current tick's gate blocked?
        # counters surfaced via ContinuousBatcher.pool_report
        self.alloc_fault_ticks = 0  # ticks whose gates reported 0 pages
        self.delayed_releases = 0   # pages that took the deferred path
        self.swap_faults = 0        # host-tier records lost at promotion

    def tick(self) -> None:
        """Advance the injector clock one scheduler tick: draw (seeded)
        whether this tick's gates are blocked. Called by
        `HostPageAllocator.tick` (DESIGN.md §8)."""
        self.blocked = (self.p_alloc_fail > 0.0
                        and bool(self._rng.random_sample() < self.p_alloc_fail))
        if self.blocked:
            self.alloc_fault_ticks += 1

    def swap_fault(self) -> bool:
        """Seeded per-prefetch draw: True when this promotion's host-tier
        record is to be lost (`p_swap_fail`, DESIGN.md §11). The caller
        drops the record so the requester falls back to recompute —
        a lost swap must never stall admission."""
        hit = (self.p_swap_fail > 0.0
               and bool(self._rng.random_sample() < self.p_swap_fail))
        if hit:
            self.swap_faults += 1
        return hit


class HostPageAllocator:
    """Host-authoritative page allocator with optional prefix caching
    (DESIGN.md §7) and host-tier swap support (DESIGN.md §11).

    Owns four disjoint populations of the pool's ``n_pages - 1``
    allocatable pages (page 0 is the sentinel and never enters any of them):

      * ``free``     — pages holding nothing; allocation pops from here
                       first.
      * ``ref``      — page -> refcount > 0 for pages referenced by >= 1
                       row.
      * ``lru``      — *cached* pages: refcount 0 but still resident in the
                       content-hash ``index``; a pluggable
                       `tiering.Evictor` policy (oldest-first by default)
                       picks which one ``alloc`` reclaims when free pages
                       run out (decref-with-reclaim, DESIGN.md §11).
      * ``inflight`` — pages staging an in-progress host->device promotion
                       copy (`begin_prefetch`): claimed but neither
                       referenced, cached, nor free until the copy lands
                       (`finish_prefetch`, DESIGN.md §11).

    (`PoolFaultInjector.reclaim_delay` parks a fifth, transient population
    in ``deferred``.) With a `tiering.HostTier` attached, reclaim victims
    are offered to the scheduler's ``demote_hook`` before their index entry
    dies — the digest retargets from a device page id to a host record
    instead of vanishing, and `match_tiered` counts host/in-flight digests
    so admission can prefetch instead of recomputing.

    The content-hash ``index`` maps chain digests (see `chain_hashes`) to
    page ids; ``hash_of`` is its inverse. A registered page's contents must
    never change — `ensure_private` is the copy-on-write gate callers use
    before flushing into a page that is shared (refcount > 1) or indexed.

    All state is plain Python (no jax); the scheduler mirrors it into the
    device `PagePool` pytree between steps (serving/scheduler.py)."""

    def __init__(self, n_pages: int, *, prefix_cache: bool = False,
                 injector: PoolFaultInjector | None = None,
                 evictor=None, host_tier=None):
        from repro.core import tiering as TIER
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the sentinel)")
        self.n_pages = n_pages
        self.prefix_cache = prefix_cache
        self.injector = injector
        self.free: list[int] = list(range(1, n_pages))
        self.ref: dict[int, int] = {}
        self.index: dict[bytes, int] = {}
        self.hash_of: dict[int, bytes] = {}
        # cached population behind a pluggable policy (DESIGN.md §11);
        # "lru" keeps the attribute's historical name and victim order
        if evictor is None:
            evictor = "lru"
        self.lru: TIER.Evictor = (TIER.make_evictor(evictor)
                                  if isinstance(evictor, str) else evictor)
        self.deferred: dict[int, int] = {}   # page -> tick it becomes free
        # host tier + in-flight promotions (DESIGN.md §11)
        self.host_tier = host_tier
        self.demote_hook = None     # set by the scheduler: (page, digest)
        self.inflight: dict[int, tuple[bytes, int]] = {}  # page->(h, ready)
        self.inflight_digests: dict[bytes, int] = {}      # inverse
        self._promoted: set[int] = set()     # device pages of host origin
        self._tick = 0
        # counters surfaced via ContinuousBatcher.pool_report / benchmarks
        self.hits = 0           # pages resolved from the index
        self.misses = 0         # prompt pages that had to be computed
        self.reclaims = 0       # cached pages evicted to satisfy alloc
        self.cow_retargets = 0  # shared pages replaced before a flush
        self.prefetch_issued = 0   # host->device promotion copies started
        self.promote_hits = 0      # promoted pages later adopted by a row

    # -- capacity ----------------------------------------------------------
    @property
    def n_free(self) -> int:
        """Truly-free pages (the device ``free_stack`` mirrors exactly this
        set — cached pages still hold data and are not on the device list)."""
        return len(self.free)

    @property
    def n_cached(self) -> int:
        """Evictable cached pages (refcount 0, still indexed)."""
        return len(self.lru)

    @property
    def _physical(self) -> int:
        """Physically allocatable pages, ignoring injected faults. `alloc`
        and copy-on-write check this, so injection can starve the gates
        without ever making an already-gated allocation raise."""
        return len(self.free) + len(self.lru)

    @property
    def available(self) -> int:
        """Pages an admission may claim: free now + evictable via reclaim.
        An attached `PoolFaultInjector` (DESIGN.md §8) can depress this —
        a blocked tick reports 0, forced pressure withholds ``hold_pages``
        — which is how tests drive the preemption/recovery paths."""
        inj = self.injector
        if inj is not None:
            if inj.blocked:
                return 0
            return max(0, self._physical - inj.hold_pages)
        return self._physical

    def available_after_adopt(self, chain) -> int:
        """Pages allocatable once the digests in ``chain`` are adopted.
        Adopted pages that currently sit on the LRU stop being evictable,
        so gating an admission on plain `available` overcounts by exactly
        those — adopt-then-alloc could raise mid-admission otherwise
        (admission must never fail after a request is popped). Injected
        faults (DESIGN.md §8) depress this exactly like `available`."""
        on_lru = sum(1 for h in chain if self.index.get(h) in self.lru)
        inj = self.injector
        if inj is not None:
            if inj.blocked:
                return 0
            return max(0, self._physical - on_lru - inj.hold_pages)
        return self._physical - on_lru

    def tick(self) -> None:
        """Advance the allocator one scheduler tick: roll the fault
        injector's per-tick draw, return deferred-reclaim pages whose
        delay has elapsed to the LRU / free list (DESIGN.md §8), and
        complete in-flight prefetches whose copy delay has elapsed
        (`finish_prefetch`, DESIGN.md §11)."""
        self._tick += 1
        if self.injector is not None:
            self.injector.tick()
            due = [p for p, t in self.deferred.items() if t <= self._tick]
            for p in due:
                del self.deferred[p]
                self._dispose(p)
        for p in [p for p, (_, t) in self.inflight.items()
                  if t <= self._tick]:
            self.finish_prefetch(p)

    def _dispose(self, page: int) -> None:
        """Final disposition of a refcount-0 page: the evictable cached
        set if still indexed (hittable, reclaimable under pressure), else
        the free list."""
        if page in self.hash_of:
            self.lru.cache(page)              # most-recently-used end
        else:
            self.free.append(page)

    # -- allocation --------------------------------------------------------
    def alloc(self, n: int) -> list[int]:
        """Claim ``n`` pages (refcount 1 each). Free pages first; then the
        cached set is reclaimed in the `tiering.Evictor` policy's victim
        order (oldest-first for the "lru" baseline), un-indexing each
        victim — after offering it to the host tier's ``demote_hook``, so
        a cold prefix page demotes to host RAM instead of vanishing
        (DESIGN.md §11). Raises if ``n`` exceeds physical capacity —
        admission must gate on `available` (which injected faults may
        depress below physical; gated callers therefore never trip this,
        DESIGN.md §8)."""
        if n > self._physical:
            raise ValueError(f"alloc({n}) exceeds available={self._physical}")
        ids = [self.free.pop() for _ in range(min(n, len(self.free)))]
        while len(ids) < n:                    # reclaim cached pages
            page = self.lru.pop_victim()
            digest = self.hash_of.pop(page)
            del self.index[digest]
            self._promoted.discard(page)
            if self.demote_hook is not None and self.host_tier is not None:
                self.demote_hook(page, digest)
            self.reclaims += 1
            ids.append(page)
        for p in ids:
            self.ref[p] = 1
        return ids

    def incref(self, page: int) -> None:
        """Add a reference to an already-referenced page (fork / sharing)."""
        if self.ref.get(page, 0) <= 0:
            raise ValueError(f"incref of unreferenced page {page}")
        self.ref[page] += 1

    def release(self, pages) -> None:
        """Drop one reference per page. A count reaching 0 sends the page to
        the LRU if it is indexed (still hittable, evictable under pressure)
        or back to the free list otherwise — unless a fault injector
        imposes delayed reclaim, in which case the page parks in
        ``deferred`` until `tick` releases it (DESIGN.md §8). A count below
        0 is a refcounting bug and raises."""
        inj = self.injector
        delay = inj.reclaim_delay if inj is not None else 0
        for p in pages:
            c = self.ref.get(p, 0) - 1
            if c < 0:
                raise ValueError(f"refcount underflow on page {p}")
            if c:
                self.ref[p] = c
                continue
            del self.ref[p]
            if delay:
                self.deferred[p] = self._tick + delay
                inj.delayed_releases += 1
            else:
                self._dispose(p)

    # -- prefix cache ------------------------------------------------------
    def match(self, chain) -> int:
        """Longest prefix of ``chain`` (list of digests) resident in the
        index. Pure lookup: no refcounts change."""
        if not self.prefix_cache:
            return 0
        n = 0
        for h in chain:
            if h not in self.index:
                break
            n += 1
        return n

    def match_tiered(self, chain) -> tuple[int, int]:
        """Two-tier prefix match (DESIGN.md §11): ``(dev, swap)`` where
        ``dev`` is the device-resident prefix (`match`) and ``swap`` the
        consecutive run beyond it that is restorable without recompute —
        digests resident on the host tier or already in flight back to the
        device. The scheduler prefetches the ``swap`` run at hash-match
        time; once those copies land, `match` itself covers them and the
        normal adopt path serves the hit. Pure lookup."""
        dev = self.match(chain)
        swap = 0
        if self.prefix_cache and self.host_tier is not None:
            for h in chain[dev:]:
                if h in self.inflight_digests or h in self.host_tier:
                    swap += 1
                else:
                    break
        return dev, swap

    # -- host-tier prefetch (DESIGN.md §11) --------------------------------
    def begin_prefetch(self, digest: bytes, delay: int = 0) -> int:
        """Claim a device page to receive the host-tier record ``digest``
        and park it in the ``inflight`` population (DESIGN.md §11). The
        caller (scheduler) issues the actual async device write; the page
        joins the index via `finish_prefetch` — immediately for
        ``delay=0``, else when `tick` reaches ``delay`` ticks from now
        (injected slow-swap). In-flight pages are neither free, cached,
        referenced, nor matchable by `match` — `match_tiered` reports
        them so admission waits instead of recomputing."""
        page = self.alloc(1)[0]
        del self.ref[page]
        self.inflight[page] = (digest, self._tick + delay)
        self.inflight_digests[digest] = page
        self.prefetch_issued += 1
        if delay <= 0:
            self.finish_prefetch(page)
        return page

    def finish_prefetch(self, page: int) -> bool:
        """Complete an in-flight promotion: publish the staged page under
        its digest and park it on the cached set, ready for adoption
        (DESIGN.md §11). If the digest was re-registered meanwhile (a
        concurrent prefill recomputed the same content and won the
        first-writer race), the staging page is redundant and returns to
        the free list. Returns True iff the page was published."""
        digest, _ = self.inflight.pop(page)
        del self.inflight_digests[digest]
        if self.prefix_cache and digest not in self.index \
                and page not in self.hash_of:
            self.index[digest] = page
            self.hash_of[page] = digest
            self._promoted.add(page)
            self.lru.cache(page)
            return True
        self.free.append(page)
        return False

    def abort_prefetch(self, page: int) -> None:
        """Cancel an in-flight promotion (its host record was lost or the
        requester went away): the staging page returns to the free list
        and the digest stops being in flight (DESIGN.md §11)."""
        digest, _ = self.inflight.pop(page)
        del self.inflight_digests[digest]
        self.free.append(page)

    def adopt(self, chain) -> list[int]:
        """Resolve each digest in ``chain`` to its resident page and take a
        reference — cached (LRU) pages are revived, referenced pages just
        gain a holder. Returns the page ids in chain order."""
        ids = []
        for h in chain:
            p = self.index[h]
            if p in self.lru:
                self.lru.uncache(p)           # counts as a policy hit
                self.ref[p] = 1
            elif p in self.deferred:          # revive a delayed-reclaim page
                del self.deferred[p]
                self.ref[p] = 1
            else:
                self.ref[p] += 1
            if p in self._promoted:           # first adoption after a swap-in
                self._promoted.discard(p)
                self.promote_hits += 1
            ids.append(p)
        self.hits += len(ids)
        return ids

    def register(self, page: int, digest: bytes) -> bool:
        """Publish an immutable (fully flushed) page under its chain digest.
        First writer wins: if the digest is already indexed (an identical
        page exists) the call is a no-op and the caller's page stays a
        private, unindexed duplicate. Returns True iff registered.
        Re-registering a page under a second digest raises — it would
        orphan the first index entry, which would dangle after reclaim and
        resolve future hits to a reallocated page."""
        if not self.prefix_cache or digest in self.index:
            return False
        if page in self.hash_of:
            raise ValueError(f"page {page} is already registered; a page "
                             f"holds exactly one digest (immutable content)")
        self.index[digest] = page
        self.hash_of[page] = digest
        return True

    # -- copy-on-write -----------------------------------------------------
    def ensure_private(self, page: int) -> int | None:
        """Copy-on-write gate: call before a row flushes into ``page``.

        Returns None when the page is exclusively owned and unindexed (the
        common case — flush may proceed in place). Otherwise allocates a
        replacement page, drops this row's reference on the shared one, and
        returns the replacement id; the caller must retarget the row's table
        entry before the flush. No device copy is needed: the flush writes
        the entire page from the row's fp residual (DESIGN.md §7)."""
        if self.ref.get(page, 0) <= 1 and page not in self.hash_of:
            return None
        if not self._physical:
            # admission budgets pages_for_request() exactly; a CoW page is
            # extra. Only fork_row creates flush-shared pages, so forking
            # callers must leave headroom (one page per diverging fork).
            raise ValueError(
                "copy-on-write retarget needs a free page: leave pool "
                "headroom when forking (DESIGN.md §7)")
        new = self.alloc(1)[0]
        self.release([page])
        self.cow_retargets += 1
        return new


def live_page_count(tables, lengths, page_size: int) -> int:
    """Distinct physical pages holding tokens across rows: ``tables``
    (B, NT) int page table, ``lengths`` (B,) tokens per row (0 for empty
    rows). Prefix-cache hits alias one page into several rows' tables, so
    summing per-row block counts would double-count — occupancy reports
    must count distinct pages (DESIGN.md §7). The sentinel never counts."""
    live: set[int] = set()
    for b in range(len(lengths)):
        nb = -(-int(lengths[b]) // page_size)
        live.update(int(p) for p in tables[b][:nb])
    live.discard(SENTINEL_PAGE)
    return len(live)


def scatter_to_pool(k_q, k_s, v_q, v_s):
    """Inverse of `gather_pages` for a dense row-major layout: pack every
    block of a contiguous quantized cache (B, H, T, D) / scales (B, H, nb, D)
    into pool arrays (1 + B*nb pages; page 0 stays the zero sentinel) plus
    the page table mapping row b, logical block t -> page 1 + b*nb + t.
    Used by tests/benchmarks to drive the paged kernel against a cache built
    contiguously; page_size is inferred as T // nb. DESIGN.md §5."""
    B, H, T, D = k_q.shape
    nb = k_s.shape[2]
    ps = T // nb

    def q2p(x):             # (B, H, T, D) -> (B*nb, H, ps, D)
        return x.reshape(B, H, nb, ps, D).transpose(0, 2, 1, 3, 4).reshape(
            B * nb, H, ps, D)

    def s2p(s):             # (B, H, nb, D) -> (B*nb, H, 1, D)
        return s.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
            B * nb, H, 1, D)

    pad = lambda a: jnp.concatenate([jnp.zeros_like(a[:1]), a], axis=0)
    table = (1 + jnp.arange(B * nb, dtype=jnp.int32)).reshape(B, nb)
    return (pad(q2p(k_q)), pad(s2p(k_s)), pad(q2p(v_q)), pad(s2p(v_s)), table)


def gather_pages(pool_kq, pool_ks, pool_vq, pool_vs, page_table):
    """Materialize the contiguous cache layout from a page pool:
    pool int8 (n_pages, H, ps, D) + table (B, NT) -> int8 (B, H, NT*ps, D)
    + f32 scales (B, H, NT, D). Reference path — the fused kernel gathers
    pages via its index_map instead (DESIGN.md §5)."""
    B, NT = page_table.shape
    _, H, ps, D = pool_kq.shape

    def gq(pool_q):
        g = pool_q[page_table]                       # (B, NT, H, ps, D)
        return g.transpose(0, 2, 1, 3, 4).reshape(B, H, NT * ps, D)

    def gs(pool_s):                                  # (B, NT, H, 1, D)
        return pool_s[page_table][:, :, :, 0].transpose(0, 2, 1, 3)

    return gq(pool_kq), gs(pool_ks), gq(pool_vq), gs(pool_vs)


def page_bytes_for(page_size: int, kv_heads: int, head_dim: int,
                   kv_dtype: str = "int8") -> int:
    """Storage cost of ONE page of ``kv_dtype``: K+V value slots (int4 packs
    two tokens per byte) plus their f32 scale rows (DESIGN.md §9). Pure
    arithmetic so reports can compare backends without building pools."""
    ps_eff = Q.packed_tokens(page_size, kv_dtype)
    itemsize = jnp.dtype(Q.kv_storage_dtype(kv_dtype)).itemsize
    return 2 * (ps_eff * kv_heads * head_dim * itemsize
                + kv_heads * head_dim * 4)


@partial(jax.tree_util.register_dataclass,
         data_fields=["k_q", "v_q", "k_s", "v_s", "free_stack", "n_free"],
         meta_fields=["page_size", "kv_dtype"])
@dataclasses.dataclass
class PagePool:
    """Shared physical page storage + functional free-list allocator
    (DESIGN.md §5): k_q/v_q (n_pages, H_kv, tokens_packed, D) in the pool's
    ``kv_dtype`` storage (int8 / fp8_e4m3 / int4-packed-in-int8 — DESIGN.md
    §9; tokens_packed is page_size, or page_size // 2 for int4), k_s/v_s f32
    (n_pages, H_kv, 1, D) — one scale row per page, identical across
    backends —
    plus an int32 free stack. ``kv_dtype`` is a *meta* field: it is part of
    the pytree structure, so jitted functions retrace (never reuse a stale
    trace) when a pool of a different precision flows in. Device-side
    pytree; allocation *policy* (refcounts, prefix caching) lives in the
    host-side `HostPageAllocator` (DESIGN.md §7)."""
    k_q: jax.Array          # kv storage (n_pages, H_kv, tokens_packed, D)
    v_q: jax.Array
    k_s: jax.Array          # f32  (n_pages, H_kv, 1, D)
    v_s: jax.Array
    free_stack: jax.Array   # int32 (n_pages,); entries [0, n_free) are free
    n_free: jax.Array       # int32 ()
    page_size: int
    kv_dtype: str = "int8"

    @staticmethod
    def init(n_pages: int, page_size: int, kv_heads: int,
             head_dim: int, kv_dtype: str = "int8") -> "PagePool":
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the sentinel)")
        if page_size % 8:
            raise ValueError(f"page_size must be a multiple of 8, got {page_size}")
        ps_eff = Q.packed_tokens(page_size, kv_dtype)
        zq = jnp.zeros((n_pages, kv_heads, ps_eff, head_dim),
                       Q.kv_storage_dtype(kv_dtype))
        zs = jnp.full((n_pages, kv_heads, 1, head_dim), Q._EPS,
                      jnp.float32)
        # pages 1..n_pages-1 are allocatable; slot for the sentinel is unused
        stack = jnp.roll(jnp.arange(n_pages, dtype=jnp.int32), -1)
        return PagePool(zq, jnp.zeros_like(zq), zs, jnp.copy(zs), stack,
                        jnp.asarray(n_pages - 1, jnp.int32), page_size,
                        kv_dtype)

    # -- allocator (functional, jit-safe; n is static) ---------------------
    def alloc(self, n: int) -> tuple["PagePool", jax.Array]:
        """Pop `n` pages off the free stack. Caller must ensure n <= n_free
        (the host scheduler admits by free-page budget)."""
        ids = jax.lax.dynamic_slice(self.free_stack, (self.n_free - n,), (n,))
        return dataclasses.replace(self, n_free=self.n_free - n), ids

    def free(self, ids: jax.Array) -> "PagePool":
        """Push page ids back onto the free stack."""
        stack = jax.lax.dynamic_update_slice(self.free_stack,
                                             ids.astype(jnp.int32),
                                             (self.n_free,))
        return dataclasses.replace(self, free_stack=stack,
                                   n_free=self.n_free + ids.shape[0])

    # -- stats -------------------------------------------------------------
    @property
    def n_pages(self) -> int:
        return self.k_q.shape[0]

    @property
    def capacity(self) -> int:
        """Allocatable pages (excludes the sentinel)."""
        return self.n_pages - 1

    @property
    def pages_in_use(self) -> jax.Array:
        return jnp.asarray(self.capacity, jnp.int32) - self.n_free

    @property
    def memory_bytes(self) -> int:
        n = lambda a: a.size * a.dtype.itemsize
        return sum(n(a) for a in (self.k_q, self.v_q, self.k_s, self.v_s))

    @property
    def page_bytes(self) -> int:
        """Storage cost of one page: K+V value slots (in this pool's
        ``kv_dtype`` — int4 packs two tokens per byte) plus scale rows."""
        return self.memory_bytes // self.n_pages

    @property
    def tokens_packed(self) -> int:
        """Storage rows per page along the token axis (page_size, or
        page_size // 2 for the int4 backend — DESIGN.md §9)."""
        return self.k_q.shape[2]


@partial(jax.tree_util.register_dataclass,
         data_fields=["pool", "page_table", "resid_k", "resid_v", "length"],
         meta_fields=[])
@dataclasses.dataclass
class PagedQuantizedKVCache:
    """Per-batch-row page-table view over a shared PagePool (DESIGN.md §5):
    page_table int32 (B, max_blocks), fp residual (B, H_kv, page_size, D)
    holding each row's current partial page, length int32 (B,) per-row
    tokens written.

    Mirrors the contiguous `QuantizedKVCache` interface (prefill / append /
    dequantized / max_len / memory_bytes) so models/attention.py can swap the
    two behind one code path; granularity is always per_block with
    block_size == page_size. `prefill_at` / `fork_row` are the chunked-
    prefill and sharing entry points of DESIGN.md §7.
    """
    pool: PagePool
    page_table: jax.Array   # int32 (B, max_blocks); SENTINEL_PAGE = unmapped
    resid_k: jax.Array      # ref_dtype (B, H_kv, page_size, D)
    resid_v: jax.Array
    length: jax.Array       # int32 (B,) per-row tokens written

    # -- constructors ------------------------------------------------------
    @staticmethod
    def init(batch: int, kv_heads: int, max_len: int, head_dim: int,
             cfg: Q.QuantConfig, *, n_pages: int,
             kv_dtype: str = "int8") -> "PagedQuantizedKVCache":
        if cfg.granularity != "per_block":
            raise ValueError("paged cache requires per_block quantization "
                             "(one scale row per page)")
        ps = cfg.block_size
        if max_len % ps:
            raise ValueError(f"max_len={max_len} not a multiple of page {ps}")
        pool = PagePool.init(n_pages, ps, kv_heads, head_dim, kv_dtype)
        table = jnp.zeros((batch, max_len // ps), jnp.int32)
        resid = jnp.zeros((batch, kv_heads, ps, head_dim), cfg.ref_dtype)
        return PagedQuantizedKVCache(pool, table, resid, jnp.copy(resid),
                                     jnp.zeros((batch,), jnp.int32))

    # -- shape accessors ---------------------------------------------------
    @property
    def page_size(self) -> int:
        return self.pool.page_size

    @property
    def kv_dtype(self) -> str:
        """The pool's page precision ∈ {int8, fp8_e4m3, int4} (DESIGN.md
        §9). A *meta* field of the pool pytree, so it is static under jit."""
        return self.pool.kv_dtype

    @property
    def block_size(self) -> int:     # interface parity with QuantizedKVCache
        return self.pool.page_size

    @property
    def max_blocks(self) -> int:
        return self.page_table.shape[-1]

    @property
    def max_len(self) -> int:
        return self.max_blocks * self.page_size

    @property
    def valid_len(self) -> jax.Array:
        return jnp.minimum(self.length, self.max_len)

    @property
    def live_pages(self) -> jax.Array:
        """Pages actually holding tokens (ceil(length / page_size), summed
        over rows) — vs `pool.pages_in_use` which counts *reserved* pages."""
        ps = self.page_size
        return jnp.sum(-(-self.valid_len // ps))

    @property
    def memory_bytes(self) -> int:
        n = lambda a: a.size * a.dtype.itemsize
        return (self.pool.memory_bytes +
                sum(n(a) for a in (self.page_table, self.resid_k,
                                   self.resid_v, self.length)))

    # -- prefill -----------------------------------------------------------
    def _scatter_chunk(self, k, v, ids):
        """Quantize a (B, H, T, D) page-aligned chunk and scatter it into
        physical pages ``ids`` (B, T//ps) int32. Returns the updated pool.
        Shared by `prefill` (whole prompt at block 0) and `prefill_at`
        (chunked prefill at a per-row block cursor, DESIGN.md §7)."""
        B, H, T, D = k.shape
        ps = self.page_size
        nb = T // ps
        kv_dtype = self.pool.kv_dtype
        ps_eff = Q.packed_tokens(ps, kv_dtype)     # int4 packs 2 tokens/byte
        k_q, k_s = Q.quantize_pages(k, ps, kv_dtype)   # (B,H,T_eff,D)
        v_q, v_s = Q.quantize_pages(v, ps, kv_dtype)   # scales (B,H,nb,D)
        flat_ids = ids.reshape(-1)                 # (B*nb,)

        def to_pages(x_q):
            # (B, H, nb*ps_eff, D) -> (B*nb, H, ps_eff, D)
            xb = x_q.reshape(B, H, nb, ps_eff, D).transpose(0, 2, 1, 3, 4)
            return xb.reshape(B * nb, H, ps_eff, D)

        def scales_to_pages(s):
            # (B, H, nb, D) -> (B*nb, H, 1, D)
            return s.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
                B * nb, H, 1, D)

        return dataclasses.replace(
            self.pool,
            k_q=self.pool.k_q.at[flat_ids].set(to_pages(k_q)),
            v_q=self.pool.v_q.at[flat_ids].set(to_pages(v_q)),
            k_s=self.pool.k_s.at[flat_ids].set(scales_to_pages(k_s)),
            v_s=self.pool.v_s.at[flat_ids].set(scales_to_pages(v_s)))

    def prefill(self, k: jax.Array, v: jax.Array,
                row_mask: jax.Array | None = None) -> "PagedQuantizedKVCache":
        """Quantize a (B, H, T, D) prefix into this view's mapped pages.

        T must be a multiple of page_size — this is the whole-prompt,
        page-aligned entry point used by direct-API callers and tests; the
        serving scheduler always goes through `prefill_at`, whose per-row
        ``valid`` handles unpadded prompts (varlen, DESIGN.md §7).
        `row_mask` (B,) bool selects which rows are written — unmasked rows
        keep their cache and length untouched, which is what lets a caller
        prefill mid-stream admissions while other rows are mid-decode
        (their scatters are redirected to the sentinel page). The masked
        rows' first T//page_size table entries must be mapped before the
        call. Owned by DESIGN.md §5/§6."""
        B, H, T, D = k.shape
        ps = self.page_size
        if T % ps:
            raise ValueError(f"T={T} not a multiple of page_size={ps}")
        nb = T // ps
        ids = self.page_table[:, :nb]              # (B, nb)
        if row_mask is not None:
            ids = jnp.where(row_mask[:, None], ids, SENTINEL_PAGE)
        pool = self._scatter_chunk(k, v, ids)
        T_arr = jnp.asarray(T, jnp.int32)
        if row_mask is None:
            length = jnp.full_like(self.length, T_arr)
            resid_k = jnp.zeros_like(self.resid_k)
            resid_v = jnp.zeros_like(self.resid_v)
        else:
            length = jnp.where(row_mask, T_arr, self.length)
            keep = row_mask[:, None, None, None]
            resid_k = jnp.where(keep, 0, self.resid_k)
            resid_v = jnp.where(keep, 0, self.resid_v)
        return dataclasses.replace(self, pool=pool, length=length,
                                   resid_k=resid_k, resid_v=resid_v)

    def prefill_at(self, k: jax.Array, v: jax.Array, start_block: jax.Array,
                   row_mask: jax.Array | None = None,
                   valid: jax.Array | None = None
                   ) -> "PagedQuantizedKVCache":
        """Lookup-then-fill chunk write for varlen chunked prefill
        (DESIGN.md §7).

        Quantizes the *full pages* of a (B, H, T, D) chunk (T a page
        multiple — the dispatch width) into logical blocks starting at
        ``start_block`` (B,) int32, each row's page-aligned block cursor
        (cache-hit pages before it are already resident and never
        rewritten). ``valid`` (B,) int32 is each row's true token count in
        the chunk (default T, the fully-valid case): only the
        ``valid // ps`` full pages are scattered — the partial tail
        ``valid % ps`` lands in the row's fp residual at offsets
        ``[0, valid % ps)``, exactly where `append` expects it, so decode
        continues mid-page with no pad tokens anywhere. Masked rows get
        ``length = start_block*ps + valid``; unmasked rows scatter to the
        sentinel and keep their state, exactly as in `prefill`."""
        B, H, T, D = k.shape
        ps = self.page_size
        if T % ps:
            raise ValueError(f"T={T} not a multiple of page_size={ps}")
        nb = T // ps
        blk = start_block[:, None] + jnp.arange(nb, dtype=jnp.int32)[None]
        blk = jnp.minimum(blk, self.max_blocks - 1)   # tail blocks are masked
        ids = jnp.take_along_axis(self.page_table, blk, axis=1)   # (B, nb)
        if valid is None:
            valid_t = jnp.full((B,), T, jnp.int32)
        else:
            valid_t = jnp.asarray(valid, jnp.int32)
        full = valid_t // ps                          # (B,) full chunk pages
        ids = jnp.where(jnp.arange(nb, dtype=jnp.int32)[None] < full[:, None],
                        ids, SENTINEL_PAGE)
        if row_mask is not None:
            ids = jnp.where(row_mask[:, None], ids, SENTINEL_PAGE)
        pool = self._scatter_chunk(k, v, ids)
        # partial tail -> fp residual (page positions [0, valid % ps))
        src = jnp.minimum(full[:, None] * ps +
                          jnp.arange(ps, dtype=jnp.int32)[None], T - 1)
        in_tail = (jnp.arange(ps, dtype=jnp.int32)[None] <
                   (valid_t - full * ps)[:, None])    # (B, ps)
        gat = lambda x: jnp.where(
            in_tail[:, None, :, None],
            jnp.take_along_axis(x.astype(self.resid_k.dtype),
                                src[:, None, :, None], axis=2), 0)
        rk, rv = gat(k), gat(v)
        new_len = start_block.astype(jnp.int32) * ps + valid_t
        if row_mask is None:
            length, resid_k, resid_v = new_len, rk, rv
        else:
            length = jnp.where(row_mask, new_len, self.length)
            keep = row_mask[:, None, None, None]
            resid_k = jnp.where(keep, rk, self.resid_k)
            resid_v = jnp.where(keep, rv, self.resid_v)
        return dataclasses.replace(self, pool=pool, length=length,
                                   resid_k=resid_k, resid_v=resid_v)

    # -- fork (shared pages + copy-on-write) -------------------------------
    def fork_row(self, src: int, dst: int) -> "PagedQuantizedKVCache":
        """Clone row ``src``'s view into row ``dst``: page table row, fp
        residual, and length. Physical pages become shared between the two
        rows — the caller must take references via
        `HostPageAllocator.incref` and, before either row's next flush into
        a still-shared page, retarget through
        `HostPageAllocator.ensure_private` (copy-on-write; the residual
        copy taken here IS the private page content, so no device copy is
        ever needed). DESIGN.md §7."""
        return dataclasses.replace(
            self,
            page_table=self.page_table.at[dst].set(self.page_table[src]),
            resid_k=self.resid_k.at[dst].set(self.resid_k[src]),
            resid_v=self.resid_v.at[dst].set(self.resid_v[src]),
            length=self.length.at[dst].set(self.length[src]))

    # -- decode append -----------------------------------------------------
    def append(self, k: jax.Array, v: jax.Array,
               row_mask: jax.Array | None = None) -> "PagedQuantizedKVCache":
        """Append one token (B, H, 1, D) per row, each at its own offset.

        Tokens accumulate in the per-row residual; when a row's page fills it
        is quantized and scattered to that row's mapped page (rows flush
        independently — unlike the contiguous cache there is no shared
        position). Rows whose current block is unmapped flush to the
        sentinel page. `row_mask` (B,) bool freezes unmasked rows entirely
        (the scheduler masks out empty/finished rows so their lengths stay
        exactly 0 between requests).
        """
        B, H, _, D = k.shape
        ps = self.page_size
        off = self.length % ps                      # (B,)
        blk = jnp.minimum(self.length // ps, self.max_blocks - 1)
        write = (jnp.arange(ps)[None, None, :, None] ==
                 off[:, None, None, None])          # (B,1,ps,1)
        if row_mask is not None:
            write &= row_mask[:, None, None, None]
        resid_k = jnp.where(write, k.astype(self.resid_k.dtype), self.resid_k)
        resid_v = jnp.where(write, v.astype(self.resid_v.dtype), self.resid_v)

        full = off == ps - 1                        # (B,) rows flushing now
        if row_mask is not None:
            full &= row_mask
        kv_dtype = self.pool.kv_dtype               # (B,H,ps_eff,D), (B,H,D)
        fq_k, fs_k = Q.quantize_page_matrix(resid_k, kv_dtype)
        fq_v, fs_v = Q.quantize_page_matrix(resid_v, kv_dtype)
        pid = self.page_table[jnp.arange(B), blk]   # (B,)
        pid = jnp.where(full, pid, SENTINEL_PAGE)   # non-flushing -> sentinel
        # the residual is already heads-major: a flushed page lands as is
        srow = lambda s: s.astype(jnp.float32)[:, :, None]   # (B, H, 1, D)
        pool = dataclasses.replace(
            self.pool,
            k_q=self.pool.k_q.at[pid].set(fq_k),
            v_q=self.pool.v_q.at[pid].set(fq_v),
            k_s=self.pool.k_s.at[pid].set(srow(fs_k)),
            v_s=self.pool.v_s.at[pid].set(srow(fs_v)))
        clear = full[:, None, None, None]
        advance = 1 if row_mask is None else row_mask.astype(jnp.int32)
        return dataclasses.replace(
            self, pool=pool,
            resid_k=jnp.where(clear, 0, resid_k),
            resid_v=jnp.where(clear, 0, resid_v),
            length=self.length + advance)

    # -- read --------------------------------------------------------------
    def gathered(self) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """Contiguous (k_q, k_s, v_q, v_s) view of this cache's pages
        (see `gather_pages`)."""
        return gather_pages(self.pool.k_q, self.pool.k_s, self.pool.v_q,
                            self.pool.v_s, self.page_table)

    def dequantized_prefix(self, n_blocks: int, dtype=jnp.float32
                           ) -> tuple[jax.Array, jax.Array]:
        """Dequantized (k, v) of each row's first ``n_blocks`` logical
        blocks: (B, H_kv, n_blocks*ps, D), no residual overlay.

        PARITY-ORACLE DUTY ONLY. This was chunked prefill's history read
        (DESIGN.md §7) until the fused paged prefill kernel
        (`ops.paged_attention_prefill`) retired the gather-and-dequantize
        hot path — production chunks now stream INT8 pages straight into
        the attention kernel and this HBM materialization never happens.
        It survives as the reference read feeding the
        `attention._chunk_attention` oracle (`prefill_chunk(
        use_fused=False)`), for tests and debugging; keep it naive.

        Cursors are page-aligned so there is no fp tail, and gathering
        only the blocks below the dispatch's cursor bound avoids
        materializing max_len per chunk. ``n_blocks`` is static (the
        scheduler rounds it to a power of two to bound the compile set).
        ``dtype`` is the dequantization target — bf16 halves the gathered
        buffer while the oracle still accumulates logits in f32."""
        k_q, k_s, v_q, v_s = gather_pages(
            self.pool.k_q, self.pool.k_s, self.pool.v_q, self.pool.v_s,
            self.page_table[:, :n_blocks])
        kv_dtype = self.pool.kv_dtype
        return (Q.dequantize_pages(k_q, k_s, kv_dtype, dtype=dtype),
                Q.dequantize_pages(v_q, v_s, kv_dtype, dtype=dtype))

    def dequantized(self, dtype=jnp.float32) -> tuple[jax.Array, jax.Array]:
        """Full cache in `dtype` with the exact residual tail overlaid
        (interface parity with QuantizedKVCache.dequantized)."""
        k_q, k_s, v_q, v_s = self.gathered()
        k = Q.dequantize_pages(k_q, k_s, self.pool.kv_dtype, dtype=dtype)
        v = Q.dequantize_pages(v_q, v_s, self.pool.kv_dtype, dtype=dtype)
        ps = self.page_size
        B, H, _, D = k.shape
        # per-row residual overlay: token t of row b is exact iff it sits in
        # the row's current *partial* page (none when length % ps == 0 —
        # that page was flushed and the residual cleared)
        tail_start = self.length - self.length % ps                # (B,)
        tpos = jnp.arange(self.max_len)[None, :]                   # (1, T)
        in_tail = ((tpos >= tail_start[:, None]) &
                   (tpos < self.length[:, None]))                  # (B, T)
        src = tpos - tail_start[:, None]                           # (B, T)
        src = jnp.clip(src, 0, ps - 1)
        rk = jnp.take_along_axis(
            self.resid_k.astype(dtype), src[:, None, :, None], axis=2)
        rv = jnp.take_along_axis(
            self.resid_v.astype(dtype), src[:, None, :, None], axis=2)
        sel = in_tail[:, None, :, None]
        return jnp.where(sel, rk, k), jnp.where(sel, rv, v)
