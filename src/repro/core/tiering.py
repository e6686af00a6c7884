"""Tiered KV cache: host-memory swap tier + pluggable eviction
(DESIGN.md §11).

Today a full prefix page is binary — resident in the HBM `PagePool` or
gone, and every LRU reclaim is a recompute on the next hit. This module
adds the second tier: a `HostTier` of demoted pages keyed by the SAME
chain digests the device index uses (core/paging.chain_hashes), so a
digest is a location-independent handle — demotion retargets it from a
device page id to a host record, promotion retargets it back. Three
pieces, all host-side (no jax tracing):

  * `Evictor` — the pluggable device-eviction policy. It owns the
    allocator's cached population (refcount-0, still-indexed pages) and
    picks reclaim victims; `LRUEvictor` is the historical oldest-first
    baseline, `FreqSizeEvictor` keeps hit-dense bytes resident. The
    read surface (`in` / `iter` / `len`) matches the OrderedDict it
    replaces, so pool accounting and the partition invariant are
    policy-agnostic.
  * `HostTier` — digest -> `HostPageRecord` store with its own LRU
    capacity bound (`host_pages`). Payloads are per-cache-leaf host
    numpy copies of the quantized page + its scale rows; with
    ``dtype`` set, demoted pages recompress (PackKV-style) through
    `repack_page`, trading bitwise restore for host bytes.
  * `SwapCostModel` — swap-vs-recompute arbitration in token units:
    restoring a page costs one device copy (~`copy_cost_tokens` of
    prefill work), recomputing it costs `page_size` tokens of prefill.
    Feeds demotion choice, prefetch-vs-recompute at admission, and the
    scheduler's preempt-by-swap arm (serving/scheduler.py).

The allocator side (in-flight population, prefetch begin/finish, the
demote hook) lives in `core.paging.HostPageAllocator`; the device
copies themselves are issued by the scheduler, which owns the state
pytree. DESIGN.md §11 documents the tier state machine and the
bitwise-restore caveat.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict

import jax.numpy as jnp
import numpy as np

from repro.core import quantization as Q


# ---------------------------------------------------------------------------
# Pluggable device-eviction policy (DESIGN.md §11)
# ---------------------------------------------------------------------------

class Evictor:
    """Policy owner of the allocator's cached pages — refcount 0, still in
    the content-hash index — and chooser of reclaim victims
    (DESIGN.md §11).

    Replaces the bare OrderedDict LRU inside `HostPageAllocator`: the
    allocator calls `cache` on release-to-cached, `uncache` on adoption
    (which is exactly a hit, so per-page hit counts accrue here), and
    `pop_victim` when `alloc` runs out of free pages. The dict-like read
    surface (`in`/`iter`/`len`) keeps page accounting and the partition
    invariant independent of the policy. Hit stats survive
    cache/uncache cycles of the same physical page and reset when the
    page is evicted (its content is about to change)."""

    def __init__(self):
        self._cached: OrderedDict[int, int] = OrderedDict()  # page -> bytes
        self._hits: dict[int, int] = {}

    def __contains__(self, page) -> bool:
        return page in self._cached

    def __len__(self) -> int:
        return len(self._cached)

    def __iter__(self):
        return iter(self._cached)

    def cache(self, page: int, nbytes: int = 1) -> None:
        """Admit a refcount-0 indexed page to the evictable set (MRU)."""
        self._cached[page] = nbytes
        self._hits.setdefault(page, 0)

    def uncache(self, page: int) -> None:
        """Remove an adopted page (a hit) from the evictable set; its hit
        count persists for when it returns."""
        del self._cached[page]
        self._hits[page] = self._hits.get(page, 0) + 1

    def pop_victim(self) -> int:
        """Evict and return the policy's chosen victim; its stats reset
        (the physical page is about to hold different content)."""
        page = self._select()
        del self._cached[page]
        self._hits.pop(page, None)
        return page

    def hits_of(self, page: int) -> int:
        """Accrued adoption count of a cached page (policy telemetry)."""
        return self._hits.get(page, 0)

    def _select(self) -> int:
        raise NotImplementedError


class LRUEvictor(Evictor):
    """Oldest-cached-first eviction — the historical baseline policy
    (DESIGN.md §11): identical victim order to the pre-tiering
    OrderedDict LRU, so `evictor="lru"` engines are behavior-preserving."""

    def _select(self) -> int:
        return next(iter(self._cached))


class FreqSizeEvictor(Evictor):
    """Hit-frequency / size-aware eviction (DESIGN.md §11): the victim is
    the cached page with the lowest hit density (adoptions per byte), ties
    broken oldest-first — a system prompt adopted by every request stays
    resident under pressure that would roll a pure LRU over it. Within one
    uniform pool all pages cost the same bytes, so density degenerates to
    plain hit frequency; mixed per-layer pools (§10) weigh cheap int4
    pages as cheaper to keep."""

    def _select(self) -> int:
        return min(
            ((self._hits.get(p, 0) / max(nb, 1), k, p)
             for k, (p, nb) in enumerate(self._cached.items())),
        )[2]


EVICTORS = {"lru": LRUEvictor, "freq": FreqSizeEvictor}


def make_evictor(name: str) -> Evictor:
    """Build a registered `Evictor` policy by name (DESIGN.md §11) —
    ``lru`` (baseline) or ``freq`` (hit-density aware). The registry is
    what `EngineConfig.evictor` / `serve.py --evictor` validate against."""
    if name not in EVICTORS:
        raise ValueError(f"unknown evictor {name!r}; "
                         f"expected one of {sorted(EVICTORS)}")
    return EVICTORS[name]()


# ---------------------------------------------------------------------------
# Host tier (DESIGN.md §11)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class HostPageRecord:
    """One demoted page on the host tier (DESIGN.md §11): per-cache-leaf
    numpy payloads ``(k_q, k_s, v_q, v_s)`` in scheduler traversal order,
    the storage dtype of each leaf's payload (the pool's dtype, or the
    tier's recompression dtype), and byte accounting."""
    digest: bytes
    payloads: list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]
    dtypes: list[str]
    nbytes: int
    hits: int = 0


class HostTier:
    """Host-RAM page store keyed by chain digest (DESIGN.md §11).

    The second tier of the KV cache: `HostPageAllocator`'s reclaim path
    demotes cold indexed pages here (device -> host copy of the quantized
    page + scale rows) instead of dropping them, and admission promotes
    them back ahead of prefill. Capacity is ``capacity`` pages with its
    own LRU — the tier models plentiful-but-finite host RAM, so the
    coldest *host* record is dropped when a demotion overflows it.

    With ``dtype`` set (one of `Q.KV_DTYPES`), demoted payloads are
    recompressed to that storage format via `repack_page`
    (PackKV-style: int8 on-device, int4 at rest). Recompression is
    lossy, so it trades the swap-restore bitwise guarantee for ~2x host
    capacity — the §11 caveat; ``dtype=None`` stores the device bytes
    verbatim and restores are bitwise."""

    def __init__(self, capacity: int, *, dtype: str | None = None):
        if capacity < 1:
            raise ValueError(f"host tier needs capacity >= 1 pages "
                             f"(got {capacity})")
        if dtype is not None:
            Q.kv_storage_dtype(dtype)       # validates the name
        self.capacity = capacity
        self.dtype = dtype
        self.pages: OrderedDict[bytes, HostPageRecord] = OrderedDict()
        # counters surfaced via ContinuousBatcher.pool_report
        self.demotions = 0          # device pages copied in
        self.promotions = 0         # host pages copied back out
        self.host_evictions = 0     # records dropped by the capacity LRU
        self.lost = 0               # records dropped by injected swap faults

    def __contains__(self, digest: bytes) -> bool:
        return digest in self.pages

    def __len__(self) -> int:
        return len(self.pages)

    @property
    def nbytes(self) -> int:
        """Total host bytes held — the tier's side of the split-tier byte
        accounting (`kv_cache_memory_report`, DESIGN.md §11)."""
        return sum(r.nbytes for r in self.pages.values())

    def put(self, digest: bytes, payloads, dtypes) -> bool:
        """Demote one page: store its per-leaf payloads under ``digest``
        (MRU). A digest already resident refreshes recency and is NOT
        re-copied (preempt-by-swap can race reclaim-demotion; first copy
        wins — registered pages are immutable, so both copies are equal).
        Overflow drops the coldest host record. Returns True iff a new
        record was stored."""
        if digest in self.pages:
            self.pages.move_to_end(digest)
            return False
        while len(self.pages) >= self.capacity:
            self.pages.popitem(last=False)
            self.host_evictions += 1
        nbytes = sum(int(a.nbytes) for p in payloads for a in p)
        self.pages[digest] = HostPageRecord(digest, list(payloads),
                                            list(dtypes), nbytes)
        self.demotions += 1
        return True

    def get(self, digest: bytes) -> HostPageRecord:
        """Promotion read: the record for ``digest``, refreshed to MRU.
        The record stays resident — the host copy remains valid after a
        promotion (a re-demotion of the same content skips the copy)."""
        rec = self.pages[digest]
        self.pages.move_to_end(digest)
        rec.hits += 1
        self.promotions += 1
        return rec

    def drop(self, digest: bytes) -> None:
        """Discard a record (injected swap fault): the digest stops
        matching, so the requester falls back to recompute instead of
        stalling on a copy that will never land (DESIGN.md §11)."""
        if self.pages.pop(digest, None) is not None:
            self.lost += 1

    def run_length(self, chain, start: int = 0) -> int:
        """Length of the consecutive digest run ``chain[start:]`` resident
        on this tier — the host extension of the device index's
        `HostPageAllocator.match` (pure lookup, no recency change)."""
        n = 0
        for h in chain[start:]:
            if h not in self.pages:
                break
            n += 1
        return n


# ---------------------------------------------------------------------------
# Swap-vs-recompute cost model (DESIGN.md §11)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SwapCostModel:
    """Swap-vs-recompute arbitration in prefill-token units
    (DESIGN.md §11). Restoring one page from the host tier costs a single
    device copy — ``copy_cost_tokens`` equivalent prefill tokens (default
    1: a PCIe page copy is far cheaper than recomputing a page of
    attention) — while recomputing it costs ``page_size`` real prefill
    tokens. The scheduler consults `prefer_swap` at every choice point:
    demotion (is the copy worth less than the recompute it may save?),
    admission (wait for an in-flight prefetch or re-prefill?), and
    preemption (`_preempt_row`'s preempt-by-swap arm vs plain
    drop-to-recompute). Raising ``copy_cost_tokens`` past ``page_size``
    flips every decision to recompute, which is how tests pin both arms."""

    page_size: int
    copy_cost_tokens: float = 1.0

    def swap_cost(self, n_pages: int) -> float:
        """Token-equivalent cost of copying ``n_pages`` across the
        host/device boundary."""
        return n_pages * self.copy_cost_tokens

    def recompute_cost(self, n_pages: int) -> float:
        """Token cost of re-prefilling ``n_pages`` worth of stream."""
        return float(n_pages * self.page_size)

    def prefer_swap(self, n_pages: int = 1) -> bool:
        """True when swapping ``n_pages`` beats recomputing them."""
        return self.swap_cost(n_pages) < self.recompute_cost(n_pages)


# ---------------------------------------------------------------------------
# Host recompression (PackKV-style, DESIGN.md §11)
# ---------------------------------------------------------------------------

def repack_page(q, s, src_dtype: str, dst_dtype: str):
    """Requantize ONE page's values+scales between storage dtypes
    (DESIGN.md §11): pool layout in, pool layout out — values
    ``(..., H, tokens_packed, D)`` with their per-page-channel f32 scales
    ``(..., H, 1, D)``. ``src == dst`` is the verbatim fast path (bitwise).
    Otherwise the page is dequantized and requantized through
    `Q.quantize_page_matrix`, so a demote+promote round trip through a
    cheaper host dtype costs at most the sum of both dtypes' analytic
    per-channel bounds (§9) — covered by the BENCH_accuracy-style bound
    test in tests/test_tiering.py. Returns host numpy ``(q, s)``."""
    if src_dtype == dst_dtype:
        return np.asarray(q), np.asarray(s)
    # heads-major pages are already the quantizers' (..., T, D) layout
    x = Q.dequantize_pages(jnp.asarray(q), jnp.asarray(s), src_dtype)
    q2, s2 = Q.quantize_page_matrix(x, dst_dtype)      # (..., H, tp2, D)
    return np.asarray(q2), np.asarray(s2)[..., None, :]
