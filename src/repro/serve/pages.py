"""Paged KV-cache plumbing: a shared page pool behind the slot protocol.

The paper's Split-Brain protocol (§IV-B) makes the host CPU the sole owner
of dynamic KV state; this module is the host's memory manager.  Instead of
pinning a full ``(max_slots, ..., max_len, ...)`` cache per slot, every
sequence-growing cache leaf is re-laid-out as a *page pool*

    dense leaf  (..., B, ..., S, ...)          S = max_len
    pool  leaf  (num_pages, page_size, *rest)  rest = shape minus B and S

plus one per-slot *page table* ``(max_slots, max_len // page_size)`` of
physical page ids, owned host-side by :class:`PagePool` (plain numpy — no
device sync on the allocation path).  Pages are allocated on demand as a
sequence grows and returned to the free list when its request finishes, so
resident KV bytes track actual token occupancy.

The pool layout is KERNEL-FRIENDLY: the ``(num_pages, page_size)`` axes sit
exactly where the batch axis sat in the dense leaf (``page_axis``), so
leading non-sequence axes — the stacked layer axis of
``(L, B, Hkv, S, hd)`` caches, the ``(n_groups, gs)`` group axes of the lm
family — stay leading, and the trailing ``(Hkv, hd)`` pair is stored
LANE-DENSE as ``(Hkv/f, f·hd)`` (``layers.kv_fold``: ``f = 128 // hd``
adjacent heads share a 128-lane row when that divides; ``f = 1``
otherwise), so the TPU keeps the row-major layout it is written in.  The
paged kernel reads ``layer·N + table[b, p]`` page blocks of the whole
stacked pool as stored, with no dense-view transient (DESIGN.md §6).

Every program that touches a pool leaf — the decode step, the insert, the
prefix seed, the CoW copy — scatters or gathers pages at the page axis
where it sits, on the leaf with its leading axes merged (a bitcast): only
the B=1 request cache or the page blocks of one request are ever
re-laid-out, never the pool.

Physical page 0 is reserved as a *scratch* page: table entries beyond a
slot's allocated pages point at it, so every jitted program can write a
fixed number of pages (traced indices, fixed shapes — zero steady-state
recompiles) and the excess lands in garbage that no gather ever reads
(attention masks positions >= ``len``).

Leaves that do NOT scale with ``max_len`` — rwkv WKV state, hymba SSM
state, sliding-window ring buffers, ``len`` itself — keep their dense
``(max_slots, ...)`` layout and pass through untouched: the recurrent
families effectively run a no-op page table.  Discovery is by shape
diffing (:func:`seq_axes`), the same trick ``serve/slots.py::batch_axes``
uses for the batch dimension.

The traced helpers (:func:`gather_tree` / :func:`scatter_token_tree` /
:func:`insert_tree`) are the paged variant of the dense cache plumbing:
``gather_tree`` reconstructs the exact dense-view pytree the family
``decode_step`` already understands (so paged decode reuses the verified
attention math bit-for-bit), and ``scatter_token_tree`` writes back only
the one new token per active slot — O(B × token bytes) pool traffic per
step.

Shared-prefix KV reuse (DESIGN.md §7): the pool is REF-COUNTED with
copy-on-write semantics and carries a radix-style token-block-hash prefix
index ``H(parent_key, page_tokens) -> page``.  With ``prefix_cache="on"``
admission matches a prompt against the index, maps the shared full pages
into the slot's table (refcount++, zero prefill work) and prefills only the
unmatched tail — seeded from a gathered B=1 prefix view so the
absolute-position chunk path continues from the cached position; completed
full pages are published back.  Decode always appends to a private
(refcount==1) tail page, with a CoW copy (or an unpublish, for a sole
owner) when a whole-prompt match put the append position inside a shared
page.  Freed published pages stay resident and matchable until evicted
under pressure.  Reuse engages only when every dynamic cache leaf pages —
ring/recurrent families run a no-op index, token-identical either way.

Scope of the memory claim: paging shrinks the PERSISTENT cache state — the
pool allocation and the peak pages-in-use that admission and the
serve_bench gate reason about.  The default decode discipline
(``paged_attn="inplace"``) additionally computes attention directly
through the page table (``ops.paged_decode_attention``), so the per-step
gathered dense-view TRANSIENT of the fallback/oracle discipline
(``paged_attn="gather"``, which reconstructs the dense view and reuses the
verified family ``decode_step``) is gone too — zero transient bytes, HBM
reads O(live tokens) per slot.  In-flight chunked prefills each hold a
dense B=1 request cache until insertion, bounded by the scheduler's
``max_prefill_jobs`` cap.  DESIGN.md §5–6 spell out all three pieces.
"""
from __future__ import annotations

import hashlib
import math
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import (KV_DTYPES, QuantizedLeaf, SCRATCH_PAGE,
                                 fake_quant_pages, kv_fold, kv_pow2_scale,
                                 kv_quantize, page_offsets,
                                 quant_page_append, unfold_heads)
from repro.serve import telemetry
from repro.serve.errors import PageLifecycleError, ReservationError

__all__ = [
    "PagePool",
    "HostPager",
    "PagedEngineMixin",
    "QuantizedLeaf",
    "check_chunk_width",
    "check_kv_dtype",
    "round_len",
    "seq_axes",
    "page_axis",
    "pool_shape",
    "make_pool",
    "gather_view",
    "gather_tree",
    "scatter_token_tree",
    "insert_tree",
    "copy_pages",
    "make_insert",
    "make_seed",
    "fake_quant_tree",
    "pool_bytes",
    "page_token_bytes",
    "kv_token_bytes",
    "kv_token_bytes_quant",
    "SCRATCH_PAGE",
]


def check_kv_dtype(kv_dtype: str, page_size) -> str:
    """Validate the engines' ``kv_dtype`` knob: quantized pools exist only
    in the paged layout (per-PAGE scales need pages), so anything but the
    identity "bf16" requires ``page_size``."""
    if kv_dtype not in ("bf16",) + tuple(KV_DTYPES):
        raise ValueError(
            f"kv_dtype must be one of 'bf16', "
            f"{', '.join(repr(k) for k in KV_DTYPES)}, got {kv_dtype!r}")
    if kv_dtype != "bf16" and page_size is None:
        raise ValueError(
            f"kv_dtype={kv_dtype!r} quantizes the PAGE pool (per-page "
            f"scales) — pass page_size to enable the paged layout")
    return kv_dtype


def check_chunk_width(width: int, max_len: int) -> None:
    """Chunk writes must never spill past the cache end: W | max_len plus
    the full-width feeding order (transformer.prefill_chunk precondition)
    guarantee every chunk lands inside the buffer.  Shared by both engines'
    ``prefill_chunk_slot``."""
    if max_len % width != 0:
        raise ValueError(
            f"chunk width {width} must divide max_len ({max_len}) so "
            f"chunk writes never spill past the cache end")


def round_len(n: int, *quanta: Optional[int]) -> int:
    """Round a cache length up so every given quantum (page size, prefill
    chunk width) tiles it exactly — a COMMON multiple, not each quantum in
    turn (sequential rounding can un-align the earlier one)."""
    q = math.lcm(*(int(x) for x in quanta if x))
    return -(-int(n) // q) * q


# ----------------------------------------------------------------------------
# Host-side allocator (numpy only — the host owns the dynamic state)
# ----------------------------------------------------------------------------
class PagePool:
    """Ref-counted free-list page allocator with copy-on-write semantics and
    a radix-style token-block-hash prefix index.

    Lifecycle (DESIGN.md §7): ``try_admit(slot, n_tokens, matched)`` claims
    the worst-case count of NEW pages for a request at admission time and
    maps any ``matched`` prefix pages into the slot's table (refcount++,
    zero prefill work for them); ``ensure(slot, n_tokens)`` then draws
    private pages lazily as the sequence actually grows, which therefore
    never fails — under pressure a draw evicts the least-recently-released
    refcount-0 index page instead of failing.  ``free_slot`` decrements
    every mapped page's refcount; pages that hit zero return to the free
    list, unless they are published in the prefix index, in which case they
    stay resident (and matchable) until evicted.

    The prefix index is a chained block hash
    ``key = H(parent_key, page_token_ids)`` -> physical page, which is a
    flat encoding of a radix tree over token blocks: matching walks the
    chain page by page from the root and stops at the first miss, so a
    lookup is O(matched pages) regardless of how many prefixes are stored.

    Sharing invariant: a page with ``refcount > 1``, or one still published
    in the index, is IMMUTABLE.  Writers (the decode append landing inside
    a fully-matched last page) must call :meth:`cow_page` first, which
    either hands back a private copy target (refcount>1 → the caller copies
    the device bytes src→dst) or retires the index entry when the writer is
    the sole owner (write-in-place, no copy).

    Admission safety: with ``pinned`` = distinct pages referenced by >= 1
    slot, ``R`` = outstanding worst-case new-page reservations and ``D`` =
    pages already drawn under them, admission maintains
    ``pinned + (R - D) <= capacity`` — so free + evictable pages always
    cover every future draw and ``ensure`` cannot fail mid-decode.

    ``double_free`` selects the free-after-free policy: ``"raise"``
    (default) raises ValueError, ``"ignore"`` makes it a no-op.
    Reserve-after-free of the same slot is the normal lifecycle and always
    works; reserve-after-reserve (without a free between) raises.
    """

    _ROOT_KEY = b"radix-root"

    def __init__(self, num_pages: int, page_size: int, n_slots: int,
                 slot_pages: int, double_free: str = "raise"):
        if num_pages < 2:
            raise ValueError(f"num_pages must be >= 2 (page {SCRATCH_PAGE} "
                             f"is the reserved scratch page), got {num_pages}")
        if double_free not in ("raise", "ignore"):
            raise ValueError(f"double_free must be 'raise' or 'ignore', "
                             f"got {double_free!r}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.slot_pages = int(slot_pages)
        self.double_free = double_free
        # logical->physical map; unallocated entries hit the scratch page
        self.table = np.full((n_slots, slot_pages), SCRATCH_PAGE, np.int32)
        self._free = list(range(num_pages - 1, SCRATCH_PAGE, -1))
        self._n_alloc = np.zeros(n_slots, np.int64)
        self._matched = np.zeros(n_slots, np.int64)  # leading SHARED pages
        self._reserved = np.zeros(n_slots, np.int64)  # worst-case NEW pages
        self._drawn = np.zeros(n_slots, np.int64)     # new pages drawn so far
        self._live = np.zeros(n_slots, bool)
        self.refcount = np.zeros(num_pages, np.int32)
        self._index: Dict[bytes, int] = {}            # block-hash -> page
        self._published: Dict[int, bytes] = {}        # page -> its index key
        # refcount-0 published pages, oldest-released first (eviction order)
        self._evictable: "OrderedDict[int, None]" = OrderedDict()
        self.total_reserved = 0
        self.total_drawn = 0
        self.pages_in_use = 0         # pinned pages (refcount >= 1), distinct
        self.peak_pages_in_use = 0
        self.pages_allocated = 0      # cumulative private draws (KV stored)
        self.evictions = 0
        self.cow_copies = 0

    @property
    def capacity(self) -> int:
        """Allocatable pages (scratch excluded)."""
        return self.num_pages - 1

    @property
    def cached_pages(self) -> int:
        """Refcount-0 pages kept resident by the prefix index (evictable)."""
        return len(self._evictable)

    @property
    def index_pages(self) -> int:
        """Pages currently published in the prefix index (any refcount)."""
        return len(self._index)

    def pages_for(self, n_tokens: int) -> int:
        return -(-max(int(n_tokens), 0) // self.page_size)

    # ------------------------------------------------------ radix prefix index
    def page_key(self, parent: bytes, tokens: np.ndarray) -> bytes:
        """Chained block hash: one radix-tree edge per full token page."""
        h = hashlib.blake2b(parent, digest_size=16)
        h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
        return h.digest()

    def match_prefix(self, tokens: np.ndarray) -> List[int]:
        """Longest-prefix match of ``tokens`` against the index, in FULL
        pages: walk the hash chain from the root, stop at the first miss.
        Returns the matched physical pages (possibly empty)."""
        ps = self.page_size
        tokens = np.asarray(tokens, np.int32)
        pages: List[int] = []
        key = self._ROOT_KEY
        for p in range(len(tokens) // ps):
            nxt = self.page_key(key, tokens[p * ps:(p + 1) * ps])
            page = self._index.get(nxt)
            if page is None:
                break
            pages.append(page)
            key = nxt
        return pages

    def publish(self, slot: int, tokens: np.ndarray, n_tokens: int) -> int:
        """Publish the slot's completed full pages into the prefix index.

        ``tokens`` are the slot's prompt tokens, ``n_tokens`` how many the
        slot actually holds (its prefilled body).  Only pages FULLY covered
        by ``n_tokens`` are publishable — decode never writes below that
        boundary, so published content is final.  Existing entries win (a
        concurrent identical prefill keeps its pages private).  Returns the
        number of new index entries."""
        ps = self.page_size
        tokens = np.asarray(tokens, np.int32)
        nfull = min(int(n_tokens) // ps, int(self._n_alloc[slot]),
                    len(tokens) // ps)
        key = self._ROOT_KEY
        added = 0
        for p in range(nfull):
            key = self.page_key(key, tokens[p * ps:(p + 1) * ps])
            page = int(self.table[slot, p])
            if key in self._index or page in self._published:
                continue
            self._index[key] = page
            self._published[page] = key
            added += 1
        return added

    def _unpublish(self, page: int) -> None:
        key = self._published.pop(page)
        del self._index[key]
        self._evictable.pop(page, None)

    # --------------------------------------------------------------- admission
    def try_admit(self, slot: int, n_tokens: int,
                  matched: Sequence[int] = (), extra_new: int = 0) -> bool:
        """Admission: map ``matched`` prefix pages into the slot's table
        (refcount++) and claim worst-case NEW pages for the rest.  False if
        the pool cannot take the request right now.  ``extra_new`` reserves
        additional headroom (the CoW copy target when the match covers the
        decode append position)."""
        if self._live[slot]:
            raise PageLifecycleError(
                f"slot {slot} already reserved — reserve/admit must be "
                f"paired with free_slot")
        need_total = self.pages_for(n_tokens)
        matched = list(matched)[:need_total]
        need_new = need_total - len(matched) + int(extra_new)
        if need_total > self.slot_pages:
            return False              # longer than one slot's page table
        newly = sum(1 for p in matched if self.refcount[p] == 0)
        if (self.pages_in_use + newly + self.total_reserved + need_new
                - self.total_drawn > self.capacity):
            return False
        for i, p in enumerate(matched):
            if self.refcount[p] == 0:
                self.pages_in_use += 1
                self._evictable.pop(p, None)
            self.refcount[p] += 1
            self.table[slot, i] = p
        self._n_alloc[slot] = len(matched)
        self._matched[slot] = len(matched)
        self._reserved[slot] = need_new
        self._drawn[slot] = 0
        self._live[slot] = True
        self.total_reserved += need_new
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pages_in_use)
        return True

    def try_reserve(self, slot: int, n_tokens: int) -> bool:
        """Claim worst-case pages for a request; False if the pool is full.
        (The no-sharing admission path: ``try_admit`` with no matches.)"""
        return self.try_admit(slot, n_tokens)

    def _take_page(self) -> int:
        """Draw a free page; under pressure, evict the oldest-released
        refcount-0 index page (its content is recomputable by definition —
        it was published from a prompt prefix)."""
        if self._free:
            return self._free.pop()
        page, _ = self._evictable.popitem(last=False)
        self._unpublish(page)
        self.evictions += 1
        return page

    def ensure(self, slot: int, n_tokens: int) -> None:
        """Allocate private pages so the slot can hold ``n_tokens``."""
        need = self.pages_for(n_tokens)
        while self._n_alloc[slot] < need:
            if self._drawn[slot] >= self._reserved[slot]:
                raise ReservationError(
                    f"slot {slot} drew {self._drawn[slot]} of "
                    f"{self._reserved[slot]} reserved pages but needs more "
                    f"— reservation bug")
            page = self._take_page()  # cannot fail: admission invariant
            self.refcount[page] = 1
            self.table[slot, self._n_alloc[slot]] = page
            self._n_alloc[slot] += 1
            self._drawn[slot] += 1
            self.total_drawn += 1
            self.pages_in_use += 1
            self.pages_allocated += 1
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pages_in_use)

    def cow_page(self, slot: int, logical: int) -> Optional[Tuple[int, int]]:
        """Make the slot's ``logical`` page writable (the CoW rule).

        refcount > 1 → draw a private target under the slot's reservation
        and return ``(src, dst)``: the caller must copy the device page
        bytes before writing.  Sole owner but still published → retire the
        index entry and write in place (no copy).  Private and unpublished
        → None, nothing to do.
        """
        src = int(self.table[slot, logical])
        if self.refcount[src] > 1:
            if self._drawn[slot] >= self._reserved[slot]:
                raise ReservationError(
                    f"slot {slot} has no reserved page left for the CoW "
                    f"copy of logical page {logical} — admission bug")
            dst = self._take_page()
            self.refcount[dst] = 1
            self.refcount[src] -= 1
            self.table[slot, logical] = dst
            self._drawn[slot] += 1
            self.total_drawn += 1
            self.pages_in_use += 1
            self.pages_allocated += 1
            self.peak_pages_in_use = max(self.peak_pages_in_use,
                                         self.pages_in_use)
            self.cow_copies += 1
            return (src, dst)
        if src in self._published:
            self._unpublish(src)
        return None

    def free_slot(self, slot: int) -> None:
        """Release the slot: decrement every mapped page's refcount and
        return the reservation.  Pages hitting refcount 0 go back to the
        free list unless published — those stay resident in the prefix
        index (evictable under pressure) so later requests can share them.
        """
        if not self._live[slot]:
            if self.double_free == "ignore":
                return
            raise PageLifecycleError(
                f"double free: slot {slot} is not reserved (free_slot "
                f"without a matching try_reserve/try_admit)")
        for i in range(int(self._n_alloc[slot])):
            p = int(self.table[slot, i])
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self.pages_in_use -= 1
                if p in self._published:
                    self._evictable[p] = None   # resident, matchable, LRU
                else:
                    self._free.append(p)
        self.table[slot, :] = SCRATCH_PAGE
        self._n_alloc[slot] = 0
        self._matched[slot] = 0
        self.total_reserved -= int(self._reserved[slot])
        self.total_drawn -= int(self._drawn[slot])
        self._reserved[slot] = 0
        self._drawn[slot] = 0
        self._live[slot] = False


class HostPager:
    """The host-side paging companion both engines own when ``page_size``
    is set: PagePool lifecycle, the per-slot length mirror (so the decode
    loop never syncs ``len`` off the device), admission queries (now
    prefix-matching against the pool's radix index), CoW scheduling, and
    byte accounting.  The jitted gather/scatter/seed programs stay with
    each engine (they bind its own decode step); every host-side decision
    lives here exactly once.
    """

    def __init__(self, page_size: int, num_pages: Optional[int],
                 max_len: int):
        if max_len % page_size != 0:
            raise ValueError(
                f"max_len ({max_len}) must be a multiple of page_size "
                f"({page_size}) so the page table tiles the cache exactly")
        self.page_size = int(page_size)
        self.max_len = int(max_len)
        self.slot_pages = max_len // page_size
        self._num_pages_opt = num_pages
        self.pool: Optional[PagePool] = None
        self.host_len = None
        self._table_dev = None     # device copy, invalidated on table writes
        # prefix sharing: armed by the engine's init_slot_cache when the
        # knob is on AND every dynamic cache leaf actually pages
        self.prefix_on = False
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0

    def reset(self, n_slots: int) -> PagePool:
        """Fresh pool (and prefix index) + length mirror for a new slot
        cache."""
        num_pages = (self._num_pages_opt if self._num_pages_opt is not None
                     else n_slots * self.slot_pages + 1)   # +1: scratch
        self.pool = PagePool(num_pages, self.page_size, n_slots,
                             self.slot_pages)
        self.host_len = np.zeros((n_slots,), np.int64)
        self._table_dev = None
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        return self.pool

    def _tokens_for(self, prompt_len: int, max_new: int) -> int:
        return prompt_len - 1 + max_new

    def try_reserve(self, slot: int, prompt_len: int, max_new: int) -> bool:
        return self.pool.try_reserve(slot,
                                     self._tokens_for(prompt_len, max_new))

    @telemetry.traced("engine.pager.admit")
    def admit(self, slot: int, prompt: np.ndarray, max_new: int,
              chunk: Optional[int] = None) -> Optional[int]:
        """Admission with radix prefix matching.

        Matches the prompt against the index in full pages, maps the
        matched pages into the slot's table (refcount++) and reserves
        worst-case NEW pages for the rest.  Returns the number of CACHED
        tokens (0 = no reuse), or None when the pool cannot take the
        request right now (the scheduler waits for frees).

        Match capping rules (DESIGN.md §7):
          * a match covering the whole prompt body skips prefill entirely
            (``cached = body``); when it overshoots the body — the full
            prompt including the decode-input token is indexed — the last
            matched page contains the decode append position, so one extra
            page is reserved for its CoW copy;
          * a partial match is rounded DOWN to a multiple of
            ``lcm(page_size, chunk)`` so the tail chunk stream starts
            chunk-aligned (the lm block chunk path writes full fixed-width
            chunks); without chunked prefill (``chunk=None``) only
            whole-body matches are usable, partial ones are dropped.
        """
        prompt = np.asarray(prompt, np.int32)
        body = len(prompt) - 1
        total = self._tokens_for(len(prompt), max_new)
        if not self.prefix_on or body < 1:
            return 0 if self.pool.try_admit(slot, total) else None
        pages = self.pool.match_prefix(prompt)
        m_tok = len(pages) * self.page_size
        cow = 0
        if pages and m_tok >= body:
            cached = body
            cow = 1 if m_tok > body else 0
        elif pages and chunk:
            quantum = math.lcm(self.page_size, int(chunk))
            m_tok = (m_tok // quantum) * quantum
            pages = pages[:m_tok // self.page_size]
            cached = m_tok
        else:
            pages, cached = [], 0
        if not self.pool.try_admit(slot, total, matched=pages,
                                   extra_new=cow):
            return None
        if cached:
            self.prefix_hits += 1
            self.prefix_hit_tokens += cached
            self._table_dev = None
        return cached

    def can_ever_admit(self, prompt_len: int, max_new: int) -> bool:
        """Static capacity check: could this request be admitted into an
        IDLE pool?  False means waiting for frees can never help — the
        scheduler rejects immediately instead of head-of-line blocking.
        (Deliberately prefix-blind: a hit could shrink the new-page need,
        but index contents are transient, so admission stays worst-case.)"""
        need = self.pool.pages_for(self._tokens_for(prompt_len, max_new))
        return need <= min(self.pool.slot_pages, self.pool.capacity)

    def free(self, slot: int) -> None:
        self.pool.free_slot(slot)
        self.host_len[slot] = 0
        self._table_dev = None

    def _ensure(self, slot: int, n_tokens: int) -> None:
        before = self.pool.pages_in_use
        self.pool.ensure(slot, n_tokens)
        if self.pool.pages_in_use != before:
            self._table_dev = None

    def note_insert(self, slot: int, n_tokens: int) -> None:
        """Allocate the admitted prompt's pages, mirror its length."""
        self._ensure(slot, n_tokens)
        self.host_len[slot] = n_tokens

    @telemetry.traced("engine.pager.publish")
    def publish(self, slot: int, prompt: np.ndarray) -> int:
        """Publish the slot's completed full prefill pages (positions below
        its prefilled body) into the prefix index.  No-op when prefix
        sharing is off."""
        if not self.prefix_on:
            return 0
        prompt = np.asarray(prompt, np.int32)
        return self.pool.publish(slot, prompt, int(self.host_len[slot]))

    def pre_decode(self, active: np.ndarray) -> List[Tuple[int, int]]:
        """Make every active slot's append position writable and allocated.

        Each active slot writes at position ``len``: if that position falls
        inside a SHARED or published page (a whole-prompt prefix hit), the
        CoW rule fires first — returns the ``(src, dst)`` physical page
        pairs whose device bytes the engine must copy before dispatching
        the step.  Then allocates any fresh page the step grows into."""
        copies: List[Tuple[int, int]] = []
        for s in np.flatnonzero(active):
            pos = int(self.host_len[s])
            pi = pos // self.page_size
            if pi < int(self.pool._n_alloc[s]):
                op = self.pool.cow_page(int(s), pi)
                if op is not None:
                    copies.append(op)
                    self._table_dev = None
            self._ensure(s, pos + 1)
        return copies

    def post_decode(self, active: np.ndarray) -> None:
        self.host_len[active] += 1

    def table(self) -> jnp.ndarray:
        """Device copy of the page table, re-uploaded only when a table
        entry actually changed (steady-state decode reuses it)."""
        if self._table_dev is None:
            with telemetry.span("engine.pager.table_upload"):
                self._table_dev = jnp.asarray(self.pool.table)
        return self._table_dev

    def row(self, slot: int) -> jnp.ndarray:
        return jnp.asarray(self.pool.table[slot])

    def insert_row(self, slot: int) -> jnp.ndarray:
        """Table row for the slot's INSERT program: matched prefix entries
        are redirected to the scratch page, so the B=1 request cache's
        blocks land only on the slot's private tail pages — the shared
        prefix pages are never written (they already hold the content the
        seed gathered from them)."""
        row = self.pool.table[slot].copy()
        row[:int(self.pool._matched[slot])] = SCRATCH_PAGE
        return jnp.asarray(row)

    def stats(self, cache: Any, sa: Any) -> Dict[str, int]:
        """Resident-cache accounting for the paged-vs-dense benchmark."""
        total = sum(int(a.nbytes) for a in jax.tree.leaves(cache))
        page_bytes = page_token_bytes(cache, sa, self.pool.num_pages,
                                      self.page_size) * self.page_size
        dense_leaves = total - pool_bytes(cache, sa)
        return {
            "cache_bytes": total,
            "page_size": self.page_size,
            "num_pages": self.pool.num_pages,
            # dtype-aware: pool_bytes/page_bytes come from the leaves'
            # actual nbytes (quantized codes + scales included), not page
            # counts x a dense assumption
            "pool_bytes": pool_bytes(cache, sa),
            "page_bytes": page_bytes,
            "pages_in_use": self.pool.pages_in_use,
            "peak_pages_in_use": self.pool.peak_pages_in_use,
            "pages_allocated": self.pool.pages_allocated,
            "peak_kv_bytes_in_use":
                dense_leaves + self.pool.peak_pages_in_use * page_bytes,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "index_pages": self.pool.index_pages,
            "cached_index_pages": self.pool.cached_pages,
            "evictions": self.pool.evictions,
            "cow_copies": self.pool.cow_copies,
        }


def _path_entry_key(entry) -> Any:
    """The dict key / attr name / sequence index of one KeyPath entry."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return getattr(entry, attr)
    return None


def _is_len_path(path) -> bool:
    """True for the cache's ``len`` leaf (the per-slot length vector)."""
    return bool(path) and _path_entry_key(path[-1]) == "len"


class PagedEngineMixin:
    """The slot-protocol paging hooks both serving engines share verbatim.

    An engine mixes this in and maintains two attributes: ``_pager`` (a
    :class:`HostPager`, or None when constructed dense) and
    ``_paging_active`` (set by its ``init_slot_cache`` — False when the
    family has no paging leaves and fell back to the dense layout), plus a
    ``_stats_seq_axes()`` hook returning its per-leaf sequence-axis tree.

    ``paged_attn`` selects the paged decode discipline: ``"inplace"`` (the
    default) computes attention directly through the page table
    (``ops.paged_decode_attention`` — no dense-view transient, O(live
    tokens) KV reads per slot); ``"gather"`` keeps the PR-3 reference path
    (gather dense view -> family ``decode_step`` -> scatter one token) as
    the fallback/oracle the parity suite checks the kernel against.

    ``prefix_cache`` arms shared-prefix KV reuse (DESIGN.md §7): admission
    radix-matches the prompt against the pool's block-hash index, maps the
    matched full pages into the slot's table (refcount++, zero prefill
    work) and only the unmatched tail is prefilled — seeded from a
    gathered B=1 prefix view so the absolute-position chunk attention
    continues from the cached position.  It engages only when EVERY
    dynamic cache leaf pages (``len`` aside): recurrent state and
    sliding-window ring buffers are slot-private dense leaves that a
    shared page cannot restore, so those families run a no-op index and
    fall back to full prefill — token-identical either way.
    """

    _pager: Optional[HostPager] = None
    _paging_active: bool = False
    _paged_insert_jit = None
    _paged_attn: str = "inplace"
    _prefix_cache_on: bool = False
    _prefix_shareable: bool = False
    _seed_jit = None
    _cow_jit = None
    _kv_tok_bytes: int = 0       # per-token-per-slot seq-scaling cache bytes
    _kv_quant_tok_bytes: Optional[float] = None  # quantized-pool figure
    _kv_dtype: str = "bf16"      # pool storage format (engines override)
    _kv_shards: int = 1          # TP head cut of the pool (1 = replicated)
    _slot_count: int = 0
    # TP serving mesh placements (None = single-device / unspecified): the
    # engine's ``init_slot_cache`` fills these with NamedSharding pytrees so
    # every mixin jit pins its pool/request-cache layout explicitly — the
    # sharded jit caches stay stable (zero steady-state recompiles).
    _pool_sh = None              # paged slot-cache placement pytree
    _b1_sh = None                # B=1 request-cache placement pytree

    def _stats_seq_axes(self):
        raise NotImplementedError

    def will_page(self) -> bool:
        """Whether ``init_slot_cache`` will engage the page pool — THE
        paging-leaf discovery rule (a ``page_size`` plus at least one
        sequence-scaling leaf), shared by the engines' fallback decision,
        the in-place/shard_map refusal, and serve_bench's discipline
        selection."""
        if getattr(self, "page_size", None) is None:
            return False
        return any(ax >= 0 for ax in jax.tree.leaves(self._stats_seq_axes()))

    @staticmethod
    def check_paged_attn(paged_attn: str) -> str:
        if paged_attn not in ("inplace", "gather"):
            raise ValueError(
                f"paged_attn must be 'inplace' or 'gather', got {paged_attn!r}")
        return paged_attn

    @staticmethod
    def check_prefix_cache(prefix_cache: str) -> bool:
        if prefix_cache not in ("on", "off"):
            raise ValueError(
                f"prefix_cache must be 'on' or 'off', got {prefix_cache!r}")
        return prefix_cache == "on"

    def _note_slot_cache(self, n_slots: int, cache_shape: Any, ba: Any,
                         sa: Any, kv_shards: int = 1) -> None:
        """Record the slot-cache geometry the KV-read accounting needs
        (called by both engines' ``init_slot_cache``, every layout), and
        decide prefix shareability: reuse is sound only when every dynamic
        cache leaf pages — a leaf that batch-indexes but does NOT page
        (ring K/V, recurrent state) is slot-private state a shared page
        cannot restore, so its presence demotes the prefix index to a
        no-op (``len`` is exempt: the seed program sets it directly).

        ``kv_shards`` is the TP head cut of the KV state (DESIGN.md §11):
        the aggregate read model is unchanged (``_kv_tok_bytes`` stays the
        full-model figure so every gate and exactness assertion holds
        verbatim), but per-shard accounting —
        ``kv_token_bytes(..., kv_shards)`` × shards == full — is exposed
        through :meth:`cache_stats`."""
        self._slot_count = int(n_slots)
        self._kv_tok_bytes = kv_token_bytes(cache_shape, ba, sa)
        self._kv_shards = int(kv_shards)
        if self._kv_shards > 1:     # validates exact divisibility
            kv_token_bytes(cache_shape, ba, sa, self._kv_shards)
        leaves = jax.tree_util.tree_flatten_with_path(sa)[0]
        self._prefix_shareable = all(
            ax >= 0 or _is_len_path(path) for path, ax in leaves)

    # ------------------------------------------------ host KV-read accounting
    def _kv_bytes(self, tokens) -> int:
        """KV bytes ``tokens`` token-positions occupy in the slot cache's
        STORAGE format: the quantized per-token figure (1-byte codes plus
        page-amortized scales — ``kv_token_bytes_quant``) when the pool is
        quantized, the dense figure otherwise.  Every host_read channel
        that reads or copies POOL bytes routes through here, so quantizing
        the pool shrinks the measured KV traffic accordingly."""
        if self._kv_quant_tok_bytes is not None:
            return int(round(tokens * self._kv_quant_tok_bytes))
        return int(tokens * self._kv_tok_bytes)

    def _dense_view_read_bytes(self) -> int:
        """Bytes one masked decode step reads through a dense (or gathered)
        ``(max_slots, ..., max_len, ...)`` KV view: every slot's full
        allocation, live or not.  Deliberately the DENSE figure even under
        a quantized pool — the gather discipline materializes and reads the
        dequantized dense-view transient."""
        return self._slot_count * self.max_len * self._kv_tok_bytes

    def kv_read_bytes_step(self, active: np.ndarray) -> int:
        """KV-cache bytes ONE decode step reads under the engine's current
        discipline's read MODEL (replayed host-side like every meter entry,
        not a hardware counter).  The in-place paged discipline touches only
        the LIVE pages — ``ceil((len + is_active)/page_size)`` per occupied
        slot, since the kernel's grid walks EVERY slot's table but fetches
        real pages only up to its length (free slots hold length 0 and
        all-scratch tables; the dead tail lands on the one hot scratch
        page).  Eq. 7-10's intent: traffic proportional to live tokens.
        The gather and dense disciplines materialize/read the full
        ``max_slots x max_len`` view regardless of occupancy."""
        if self._paging_active and self._paged_attn == "inplace":
            ps = self._pager.page_size
            lens = self._pager.host_len + np.asarray(active, bool)
            pages_touched = int(-((lens[lens > 0]) // -ps).sum())
            return self._kv_bytes(pages_touched * ps)
        return self._dense_view_read_bytes()

    @telemetry.traced("engine.meter")
    def _meter_kv_read(self, active: np.ndarray) -> None:
        n = self.kv_read_bytes_step(active)
        if n:
            self.meter.host_read("kv_cache_read", n)

    def gather_transient_bytes_per_step(self) -> int:
        """Dense-view TRANSIENT bytes one paged decode step materializes:
        the gather discipline copies every live slot's full dense view per
        dispatch; the in-place discipline (and the dense layout, whose
        cache IS the view) materializes none.  The serve_bench regression
        gate for the eliminated copy."""
        if self._paging_active and self._paged_attn == "gather":
            return self._dense_view_read_bytes()
        return 0

    def paged_insert(self, batched_cache, single_cache, slot: int,
                     ba: Any, sa: Any, n_tokens: int):
        """Admit one prefilled B=1 dense cache into the pool: allocate the
        slot's pages, then scatter its page blocks through the (traced)
        table row — one compiled program for every slot and assignment.
        Matched prefix entries of the row are redirected to scratch
        (``HostPager.insert_row``): the shared pages already hold the
        prefix content and must never be written.  Callers wrap this in
        their mesh context where needed."""
        self._pager.note_insert(slot, n_tokens)
        if self._paged_insert_jit is None:
            insert = make_insert(ba, sa)
            kw = {}
            if self._pool_sh is not None:
                kw = dict(in_shardings=(self._pool_sh, self._b1_sh,
                                        None, None, None),
                          out_shardings=self._pool_sh)
            self._paged_insert_jit = jax.jit(insert, donate_argnums=(0,),
                                             **kw)
        return self._paged_insert_jit(batched_cache, single_cache,
                                      self._pager.insert_row(slot),
                                      jnp.int32(slot), jnp.int32(n_tokens))

    # ------------------------------------------------- shared-prefix KV reuse
    def prefix_cache_armed(self) -> bool:
        """Whether the engine was CONSTRUCTED with the prefix cache on (a
        pre-``init_slot_cache`` predicate — shareability is not known yet).
        The scheduler's warmup keys its prefix warm trace on this."""
        return (self._prefix_cache_on
                and getattr(self, "page_size", None) is not None)

    def prefix_sharing_active(self) -> bool:
        """Whether admission actually radix-matches: the knob is on, the
        slot cache pages, and every dynamic leaf is poolable."""
        return (self._paging_active and self._prefix_cache_on
                and self._prefix_shareable)

    def admit_slot(self, slot: int, prompt: np.ndarray, max_new: int,
                   chunk: Optional[int] = None) -> Optional[int]:
        """Admission control with prefix reuse: returns the CACHED token
        count (0 = admitted with no reuse; dense engines always 0), or
        None when the paged pool cannot take the request right now and the
        scheduler should wait for running requests to free pages.
        ``chunk`` is the scheduler's prefill chunk width (alignment quantum
        for partial matches)."""
        if not self._paging_active:
            return 0
        cached = self._pager.admit(
            slot, prompt, max_new,
            chunk if self.prefix_sharing_active() else None)
        if cached:
            # host-local accounting channel (excluded from eq. 7-10): the
            # prefill KV bytes the prefix hit did NOT recompute/store —
            # measured in the pool's STORAGE format (quantized pools save
            # quantized bytes)
            self.meter.host_read("prefix_prefill_saved",
                                 self._kv_bytes(cached))
        return cached

    def publish_prefix(self, slot: int, prompt: np.ndarray) -> None:
        """Publish the slot's completed full prefill pages into the prefix
        index (post-insert hook; no-op when sharing is inactive)."""
        if self._paging_active:
            self._pager.publish(slot, prompt)

    def paged_seed(self, batched_cache, slot: int, cached_len: int,
                   ba: Any, sa: Any, b1_shape: Any):
        """The prefix-aware prefill entry: gather the slot's matched prefix
        pages into a fresh B=1 request cache with ``len = cached_len``.
        The tail chunk stream (``prefill_chunk_slot``) continues from that
        position — the absolute-position chunk attention path needs no
        change.  ``b1_shape`` is the engine's B=1 request-cache eval_shape
        (same pytree as the slot cache).  One compiled program covers
        every slot, match length and page assignment (row/len traced)."""
        if self._seed_jit is None:
            seed = make_seed(ba, sa, b1_shape)
            kw = {}
            if self._pool_sh is not None:
                kw = dict(in_shardings=(self._pool_sh, None, None),
                          out_shardings=self._b1_sh)
            self._seed_jit = jax.jit(seed, **kw)
        return self._seed_jit(batched_cache, self._pager.row(slot),
                              jnp.int32(cached_len))

    def apply_cow_copies(self, cache, copies, ba: Any, sa: Any):
        """Copy the device bytes of each CoW'd page (src -> dst) in every
        pool leaf.  Compiles once (traced page ids); runs only on CoW
        events — a whole-prompt prefix hit's first decode step — never in
        the steady state."""
        if not copies:
            return cache
        if self._cow_jit is None:
            def copy(pcache, src, dst):
                return copy_pages(pcache, src, dst, ba, sa)

            kw = {}
            if self._pool_sh is not None:
                kw = dict(in_shardings=(self._pool_sh, None, None),
                          out_shardings=self._pool_sh)
            self._cow_jit = jax.jit(copy, donate_argnums=(0,), **kw)
        telemetry.count("cow_copies", len(copies))
        page_bytes = self._kv_bytes(self._pager.page_size)
        for src, dst in copies:
            cache = self._cow_jit(cache, jnp.int32(src), jnp.int32(dst))
            self.meter.host_read("page_cow_copy", page_bytes)
        return cache

    def paged_pre_step(self, cache, active: np.ndarray, ba: Any, sa: Any):
        """Host work before one paged decode step: CoW-protect and allocate
        every active slot's append position, apply any required page
        copies, and meter the step's KV reads.  Returns the (possibly
        copied-into) cache."""
        with telemetry.span("engine.pager.pre_decode"):
            copies = self._pager.pre_decode(active)
            cache = self.apply_cow_copies(cache, copies, ba, sa)
        self._meter_kv_read(active)
        return cache

    def reserve_slot(self, slot: int, prompt_len: int, max_new: int) -> bool:
        """Admission control: claim worst-case pages for a request.  Dense
        slot caches always admit; a paged pool may ask the scheduler to
        wait until running requests free pages.  (The prefix-aware entry
        point is :meth:`admit_slot`; this stays as the plain-reservation
        protocol hook.)"""
        if not self._paging_active:
            return True
        return self._pager.try_reserve(slot, prompt_len, max_new)

    def can_ever_admit(self, prompt_len: int, max_new: int) -> bool:
        """False when the request exceeds the pool's STATIC capacity: no
        amount of waiting for frees can help, so the scheduler rejects it
        immediately instead of head-of-line blocking the queue."""
        if not self._paging_active:
            return True
        return self._pager.can_ever_admit(prompt_len, max_new)

    def free_slot(self, slot: int) -> None:
        """Release a finished request's pages (no-op for the dense layout)."""
        if self._paging_active:
            self._pager.free(slot)

    def cache_stats(self, cache: Any) -> Dict[str, int]:
        """Resident-cache accounting for the paged-vs-dense benchmark.

        ``cache_bytes`` is the allocation backing the slot cache;
        ``peak_kv_bytes_in_use`` is what the pages actually held at peak
        (== cache_bytes for the dense layout, where every slot pins
        ``max_len`` positions whether it uses them or not).  NOTE these
        measure the PERSISTENT cache state; the per-dispatch dense-view
        transient on top of it is ``gather_transient_bytes_per_step()`` —
        nonzero only under the ``paged_attn="gather"`` fallback, zero for
        the default in-place discipline (module docstring, DESIGN.md §6).
        """
        if not self._paging_active:
            total = sum(int(a.nbytes) for a in jax.tree.leaves(cache))
            return {"cache_bytes": total, "peak_kv_bytes_in_use": total}
        stats = self._pager.stats(cache, self._stats_seq_axes())
        stats["kv_shards"] = self._kv_shards
        stats["kv_token_bytes_per_shard"] = (
            self._kv_tok_bytes // self._kv_shards)
        # dtype-aware capacity accounting (DESIGN.md §13): pool bytes and
        # the per-token STORAGE cost in the pool's actual format, so the
        # serve_bench resident-token gate is checkable from the artifact
        stats["kv_dtype"] = self._kv_dtype
        stats["kv_token_bytes_stored"] = (
            self._kv_quant_tok_bytes if self._kv_quant_tok_bytes is not None
            else self._kv_tok_bytes)
        return stats


# ----------------------------------------------------------------------------
# Layout discovery (shape diffing, like slots.batch_axes)
# ----------------------------------------------------------------------------
def seq_axes(cache_a: Any, cache_b: Any, delta: int) -> Any:
    """Per-leaf sequence-axis pytree; -1 where the leaf does not page.

    ``cache_a``/``cache_b`` are the same family cache built with two
    ``max_len`` values ``delta`` apart (ShapeDtypeStructs are fine).  A leaf
    pages only if exactly one axis grew by exactly ``delta`` — ring buffers
    capped at a window, recurrent state and ``len`` all stay dense (-1),
    which is the recurrent families' no-op page table.
    """
    def axis(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                 if x != y]
        if len(diffs) == 1 and b.shape[diffs[0]] - a.shape[diffs[0]] == delta:
            return diffs[0]
        return -1

    return jax.tree.map(axis, cache_a, cache_b)


def page_axis(b_ax: int, s_ax: int) -> int:
    """Leading axis of the ``(num_pages, page_size)`` pair in a pool leaf.

    The kernel-friendly layout keeps every non-(B, S) axis in its dense
    order and drops the page axes exactly where the batch axis sat, so
    layer-leading caches stay ``lax.scan``-sweepable and per-layer pool
    slices land in the ``(num_pages, page_size, *tail)`` operand layout
    ``kernels/paged_attention.py`` expects.
    """
    return b_ax - (1 if 0 <= s_ax < b_ax else 0)


def _layers(a: jnp.ndarray, pax: int) -> jnp.ndarray:
    """A pool leaf (or its scales) with the ``pax`` axes before the page
    axis merged into one leading layer axis: ``(Lf, num_pages, ...)``.
    Those are the leaf's major axes, so on the row-major stored pool this
    is a bitcast — the page axis is indexed where it sits, and nothing of
    the pool moves (module docstring)."""
    return a.reshape((math.prod(a.shape[:pax]),) + a.shape[pax:])


def pool_shape(cache_shape: Any, ba: Any, sa: Any, num_pages: int,
               page_size: int, kv_dtype: str = "bf16", tp: int = 1) -> Any:
    """ShapeDtypeStruct pytree of the paged slot cache (``make_pool``
    without the allocation) — what the sharding rules and eval_shape-based
    plumbing consume.  The trailing ``(Hkv, hd)`` pair is stored folded,
    ``(Hkv/f, f·hd)`` with ``f = layers.kv_fold(Hkv, hd, tp)``, ``tp`` the
    degree of the head cut the pool will be placed with.  With
    ``kv_dtype`` other than "bf16" every pool leaf becomes a
    :class:`QuantizedLeaf`: codes in the pool layout at the quantized dtype
    plus per-page × per-kv-head float32 scales ``(.., num_pages, Hkv)``
    (the dense leaf less its batch, sequence and head_dim axes, plus the
    page axis) — one scale per head whether or not its head shares a lane
    row."""
    def leaf(a, b_ax, s_ax):
        if s_ax < 0:
            return jax.ShapeDtypeStruct(a.shape, a.dtype)
        rest = tuple(d for i, d in enumerate(a.shape) if i not in (b_ax, s_ax))
        pax = page_axis(b_ax, s_ax)
        lead, tail = rest[:pax], rest[pax:]
        if len(tail) >= 2:
            f = kv_fold(tail[-2], tail[-1], tp)
            tail = tail[:-2] + (tail[-2] // f, tail[-1] * f)
        shape = lead + (num_pages, page_size) + tail
        if kv_dtype == "bf16":
            return jax.ShapeDtypeStruct(shape, a.dtype)
        sc_shape = lead + (num_pages,) + rest[pax:-1]
        return QuantizedLeaf(
            jax.ShapeDtypeStruct(shape, KV_DTYPES[kv_dtype]),
            jax.ShapeDtypeStruct(sc_shape, jnp.float32),
            kv_dtype, jnp.dtype(a.dtype).name)

    return jax.tree.map(leaf, cache_shape, ba, sa)


def make_pool(cache_shape: Any, ba: Any, sa: Any, num_pages: int,
              page_size: int, shardings: Any = None,
              kv_dtype: str = "bf16", tp: int = 1) -> Any:
    """Allocate the paged slot cache: pool layout for paging leaves, dense
    ``(max_slots, ...)`` zeros for everything else.  Same pytree structure
    as the dense cache, so engines keep one cache object either way
    (quantized pools substitute a :class:`QuantizedLeaf` per pool leaf —
    a registered pytree node, so the structure contract still holds).

    ``shardings`` (optional) is a matching pytree of ``jax.sharding``
    placements — the TP serving mesh allocates each pool leaf directly in
    its head-cut layout (``tp`` ways, which the fold respects), so no full
    replica ever materializes."""
    shapes = pool_shape(cache_shape, ba, sa, num_pages, page_size, kv_dtype,
                        tp)
    if shardings is None:
        return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), shapes)
    return jax.tree.map(lambda a, sh: jnp.zeros(a.shape, a.dtype, device=sh),
                        shapes, shardings)


def pool_bytes(pcache: Any, sa: Any) -> int:
    """Resident bytes of the pool leaves (the paged share of the cache) —
    dtype-aware: a quantized leaf counts its codes AND scale arrays."""
    sizes = jax.tree.map(lambda s_ax, a: int(a.nbytes) if s_ax >= 0 else 0,
                         sa, pcache)
    return sum(jax.tree.leaves(sizes))


def page_token_bytes(pcache: Any, sa: Any, num_pages: int,
                     page_size: int) -> int:
    """KV bytes per token summed over the paged leaves (pool bytes spread
    over the pool's ``num_pages * page_size`` token positions)."""
    return pool_bytes(pcache, sa) // (int(num_pages) * int(page_size))


def kv_token_bytes(cache_shape: Any, ba: Any, sa: Any,
                   kv_shards: int = 1) -> int:
    """Per-token-per-slot bytes of the sequence-scaling cache leaves, from
    the DENSE cache shapes (paged or not: the same KV bytes per token).
    The denominator of the live-page read accounting (TrafficMeter
    ``host_read``) and of the gather-transient metric in serve_bench.

    ``kv_shards`` > 1 returns the PER-SHARD bytes of the head-cut TP pool
    (DESIGN.md §11): each model shard owns ``Hkv/kv_shards`` of every KV
    leaf, so per-shard bytes are exactly ``full/kv_shards`` — summed over
    the shards they reproduce the single-device accounting to the byte.
    The shard count must divide the total (callers pass 1 when any leaf's
    head dim is indivisible — the replication fallback)."""
    def per_tok(a, b_ax, s_ax):
        if s_ax < 0:
            return 0
        n = int(math.prod(a.shape)) // (a.shape[b_ax] * a.shape[s_ax])
        return n * jnp.dtype(a.dtype).itemsize

    sizes = jax.tree.map(per_tok, cache_shape, ba, sa)
    total = sum(jax.tree.leaves(sizes))
    kv_shards = int(kv_shards)
    if kv_shards > 1:
        if total % kv_shards != 0:
            raise ValueError(
                f"kv_token_bytes ({total}) not divisible by kv_shards "
                f"({kv_shards}) — per-shard accounting would not sum "
                f"exactly; use kv_shards=1 (replicated fallback)")
        return total // kv_shards
    return total


def kv_token_bytes_quant(cache_shape: Any, ba: Any, sa: Any,
                         page_size: int, kv_dtype: str) -> float:
    """Per-token bytes of the QUANTIZED pool leaves (DESIGN.md §13): the
    1-byte codes plus the per-page × per-kv-head float32 scales amortized
    over ``page_size`` token positions.  From the DENSE cache shapes, like
    :func:`kv_token_bytes` — may be fractional (the scale amortization),
    so callers round at the meter boundary (``PagedEngineMixin._kv_bytes``).
    """
    itemsize = jnp.dtype(KV_DTYPES[kv_dtype]).itemsize

    def per_tok(a, b_ax, s_ax):
        if s_ax < 0:
            return 0.0
        n = int(math.prod(a.shape)) // (a.shape[b_ax] * a.shape[s_ax])
        return n * itemsize + (n // a.shape[-1]) * 4.0 / int(page_size)

    sizes = jax.tree.map(per_tok, cache_shape, ba, sa)
    return float(sum(jax.tree.leaves(sizes)))


# ----------------------------------------------------------------------------
# Traced page-table ops (fixed shapes, traced indices — compile once)
# ----------------------------------------------------------------------------
def gather_view(pool, table: jnp.ndarray, b_ax: int, s_ax: int,
                head_dim: int) -> jnp.ndarray:
    """Reassemble one paged leaf into its dense ``(..., B, ..., S, ...)``
    view through the page table ``(B, P)``.  This materializes the
    O(B x max_len) transient the in-place paged attention path exists to
    avoid — fallback/oracle and prefix-seed only (DESIGN.md §6).  The pages
    are gathered where the page axis sits; only the gathered blocks are
    unfolded and re-laid-out.  ``head_dim`` is the dense leaf's last axis,
    which the stored, possibly lane-folded row (``layers.kv_fold``) is
    unfolded to (a leaf with no head axis after its pages has no fold).

    A :class:`QuantizedLeaf` gathers codes and scales together and
    DEQUANTIZES: power-of-two scales make the product exact even in a
    bfloat16 ``out_dtype`` (layers.kv_dequantize), so the dense view is
    bit-stable — the prefix seed path depends on that."""
    B, P = table.shape
    pax = page_axis(b_ax, s_ax)
    quant = isinstance(pool, QuantizedLeaf)
    codes = pool.codes if quant else pool
    g = _layers(codes, pax)[:, table]                    # (Lf, B, P, ps, ..)
    if codes.ndim - pax >= 4:           # a (rows, lanes) tail: unfold heads
        g = g.reshape(g.shape[:-2] + (-1, head_dim))
    if quant:
        sc = _layers(pool.scales, pax)[:, table]           # (Lf, B, P, .., Hkv)
        g = (g.astype(jnp.float32) * jnp.expand_dims(sc, (3, sc.ndim + 1))
             ).astype(jnp.dtype(pool.out_dtype))
    d = g.reshape(codes.shape[:pax] + (B, P * g.shape[3]) + g.shape[4:])
    return jnp.moveaxis(d, (pax, pax + 1), (b_ax, s_ax))


def gather_tree(pcache: Any, table: jnp.ndarray, ba: Any, sa: Any,
                head_dim: int) -> Any:
    """Dense-view pytree: paged leaves gathered, dense leaves passed through.
    The result is exactly the cache pytree the family decode_step expects.
    (``ba`` leads the tree.map so quantized pool subtrees arrive whole.)"""
    return jax.tree.map(
        lambda b_ax, s_ax, p: p if s_ax < 0
        else gather_view(p, table, b_ax, s_ax, head_dim),
        ba, sa, pcache)


def _take_token(leaf: jnp.ndarray, pos: jnp.ndarray, b_ax: int,
                s_ax: int) -> jnp.ndarray:
    """Slice per-slot position ``pos[b]`` along the seq axis -> (B, *rest)."""
    B = pos.shape[0]
    idx_shape = [1] * leaf.ndim
    idx_shape[b_ax] = B
    idx = pos.reshape(idx_shape).astype(jnp.int32)
    tok = jnp.take_along_axis(leaf, idx, axis=s_ax)
    tok = jnp.squeeze(tok, axis=s_ax)
    return jnp.moveaxis(tok, b_ax - (1 if s_ax < b_ax else 0), 0)


def scatter_token(pool, table: jnp.ndarray,
                  new_leaf: jnp.ndarray, pos: jnp.ndarray,
                  write: jnp.ndarray, b_ax: int, s_ax: int):
    """Write each active slot's token at ``pos[b]`` from the updated dense
    view back into its page; inactive slots land on the scratch page.
    Quantized pools route through the shared quantize-on-write append core
    (``layers.quant_page_append``) so the gather discipline's writeback and
    the in-place append encode pages identically."""
    pax = page_axis(b_ax, s_ax)
    quant = isinstance(pool, QuantizedLeaf)
    codes = _layers(pool.codes if quant else pool, pax)   # (Lf, N, ps, ..)
    B = pos.shape[0]
    tok = jnp.moveaxis(_take_token(new_leaf, pos, b_ax, s_ax), 0, pax)
    tok = tok.reshape((codes.shape[0], B) + codes.shape[3:])  # stored layout
    page, off = page_offsets(table, pos, write, codes.shape[2])
    if quant:
        # one write per (layer, slot): the layer axis joins the slot axis
        Lf = codes.shape[0]
        cl, sl = quant_page_append(
            codes, _layers(pool.scales, pax),
            tok.reshape((Lf * B,) + tok.shape[2:]),
            jnp.repeat(jnp.arange(Lf), B), jnp.tile(page, Lf),
            jnp.tile(off, Lf), pool.kv_dtype)
        return QuantizedLeaf(cl.reshape(pool.codes.shape),
                             sl.reshape(pool.scales.shape),
                             pool.kv_dtype, pool.out_dtype)
    return codes.at[:, page, off].set(tok.astype(pool.dtype)).reshape(
        pool.shape)


def scatter_token_tree(pcache: Any, new_view: Any, table: jnp.ndarray,
                       pos: jnp.ndarray, write: jnp.ndarray, ba: Any,
                       sa: Any) -> Any:
    """Per-leaf post-step writeback: paged leaves get the one new token at
    ``pos`` scattered into their page, dense leaves take the (already
    slot-masked) updated view wholesale."""
    return jax.tree.map(
        lambda b_ax, s_ax, n, p: n if s_ax < 0
        else scatter_token(p, table, n, pos, write, b_ax, s_ax),
        ba, sa, new_view, pcache)


def _dense_to_pages(leaf: jnp.ndarray, b_ax: int, s_ax: int, ps: int,
                    stored: Tuple[int, ...]) -> jnp.ndarray:
    """B=1 dense leaf -> (Lf, P, ps, *stored tail) page blocks in the
    pool's stored (folded) layout — the one re-layout an admission pays,
    of the request's own cache."""
    pax = page_axis(b_ax, s_ax)
    x = jnp.moveaxis(leaf, (b_ax, s_ax), (pax, pax + 1))   # (.., 1, S, ..)
    S = x.shape[pax + 1]
    return x.reshape((math.prod(x.shape[:pax]), S // ps, ps)
                     + tuple(stored[pax + 2:]))


def insert_tree(pcache: Any, single: Any, table_row: jnp.ndarray,
                slot: jnp.ndarray, ba: Any, sa: Any,
                n_tokens: Optional[jnp.ndarray] = None) -> Any:
    """Admit one prefilled B=1 dense cache: paged leaves scatter their page
    blocks to the slot's physical pages (excess logical pages hit scratch),
    dense leaves do the ordinary slot insert.  ``table_row``/``slot`` are
    traced — ONE compiled program covers every slot and page assignment.
    The pool is written in place at its page axis (``_layers``).

    ``n_tokens`` (traced; required for quantized pools) is the prefilled
    length: positions at or past it are GARBAGE the prefill bucketing wrote
    past the prompt, and the quantizer zeroes them before computing the
    per-page scale — otherwise a junk amax in the tail page would coarsen
    the scale of real content (and break the scale agreement with
    ``layers.fake_quant_pages``, which sees only valid positions)."""
    def leaf(b_ax, s_ax, s, p):
        if s_ax < 0:
            return jax.lax.dynamic_update_slice_in_dim(
                p, s.astype(p.dtype), slot, axis=b_ax)
        pax = page_axis(b_ax, s_ax)
        quant = isinstance(p, QuantizedLeaf)
        codes = p.codes if quant else p
        ps = codes.shape[pax + 1]
        blocks = _dense_to_pages(s, b_ax, s_ax, ps, codes.shape)
        if not quant:
            return _layers(p, pax).at[:, table_row].set(
                blocks.astype(p.dtype)).reshape(p.shape)
        hkv = p.scales.shape[-1]
        P = blocks.shape[1]
        blocks = unfold_heads(blocks, hkv)             # (Lf, P, ps, .., Hkv, D)
        pos = (jnp.arange(P) * ps)[:, None] + jnp.arange(ps)[None, :]
        valid = pos < jnp.asarray(n_tokens, jnp.int32)
        blocks = jnp.where(
            valid.reshape((1, P, ps) + (1,) * (blocks.ndim - 3)),
            blocks.astype(jnp.float32), 0.0)
        amax = jnp.max(jnp.abs(blocks), axis=(2, blocks.ndim - 1))
        sc = kv_pow2_scale(amax, p.kv_dtype)           # (Lf, P, .., Hkv)
        q = kv_quantize(blocks, jnp.expand_dims(sc, (2, blocks.ndim - 1)),
                        p.kv_dtype)
        cl = _layers(p.codes, pax)
        cl = cl.at[:, table_row].set(q.reshape((cl.shape[0], P)
                                               + cl.shape[2:]))
        sl = _layers(p.scales, pax).at[:, table_row].set(sc)
        return QuantizedLeaf(cl.reshape(p.codes.shape),
                             sl.reshape(p.scales.shape),
                             p.kv_dtype, p.out_dtype)

    return jax.tree.map(leaf, ba, sa, single, pcache)


def copy_pages(pcache: Any, src, dst, ba: Any, sa: Any) -> Any:
    """Copy physical page ``src`` onto ``dst`` in every pool leaf, in place
    (the CoW copy).  Scales travel with their page: a CoW'd page keeps
    encoding the same values in its private copy."""
    def leaf(b_ax, s_ax, p):
        if s_ax < 0:
            return p
        pax = page_axis(b_ax, s_ax)

        def cp(a):
            f = _layers(a, pax)
            return f.at[:, dst].set(f[:, src]).reshape(a.shape)
        if isinstance(p, QuantizedLeaf):
            return QuantizedLeaf(cp(p.codes), cp(p.scales), p.kv_dtype,
                                 p.out_dtype)
        return cp(p)

    return jax.tree.map(leaf, ba, sa, pcache)


def make_insert(ba: Any, sa: Any):
    """The admission program the engines jit (a profile calls it
    ``insert``): ``(pool, B=1 cache, table row, slot, n_tokens) -> pool``."""
    def insert(pcache, single, row, s, n):
        return insert_tree(pcache, single, row, s, ba, sa, n_tokens=n)
    return insert


def make_seed(ba: Any, sa: Any, b1_shape: Any):
    """The prefix-seed program the engines jit (a profile calls it
    ``seed``): ``(pool, table row, cached length m) -> B=1 cache`` holding
    the row's pages (the matched prefix) gathered from the pool, with
    ``len = m``; ``b1_shape`` is the B=1 request cache's eval_shape (same
    pytree as the slot cache)."""
    def seed(pcache, row, m):
        def leaf(path, sh, b_ax, s_ax, pl):
            if s_ax >= 0:
                return gather_view(pl, row[None, :], b_ax, s_ax,
                                   sh.shape[-1])
            if _is_len_path(path):
                return jnp.full(sh.shape, m, sh.dtype)
            # unreachable when prefix sharing is active (the shareability
            # rule excludes other dense leaves), but keep the seed total
            return jnp.zeros(sh.shape, sh.dtype)

        return jax.tree_util.tree_map_with_path(leaf, b1_shape, ba, sa,
                                                pcache)
    return seed


def fake_quant_tree(cache: Any, n_tokens, sa: Any, page_size: int,
                    kv_dtype: str) -> Any:
    """Round-trip the completed pages of a dense B=1 request cache through
    the page quantizer (``layers.fake_quant_pages`` per paging leaf; dense
    leaves untouched).  Both engines apply this after every prefill /
    prefill chunk when the pool is quantized, so the chunk stream attends
    to exactly the values insertion will store — the prefix on/off token
    identity survives quantization (DESIGN.md §13)."""
    return jax.tree.map(
        lambda s_ax, x: x if s_ax < 0
        else fake_quant_pages(x, s_ax, n_tokens, page_size, kv_dtype),
        sa, cache)
