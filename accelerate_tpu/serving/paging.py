"""Paged KV memory for the serving engine: block pool, page tables, COW
prefix sharing.

A slab a slot would reserve ``max_len`` tokens of HBM per slot whether a
request uses them or not. The paged layout is one fixed pool of
``page_size``-token blocks —
``[L, num_pages, page_size, KV, D]`` — and a **fixed-shape** int32 page table
per slot (``[num_slots, pages_per_slot]``) mapping logical token positions to
physical pages. The table rides into the jitted decode step as a small host
array exactly like ``lengths``/``active``, so the program's shapes never
depend on which pages any request holds: the zero-steady-state-recompile
invariant survives paging by construction (the GSPMD argument, arXiv
2105.04663 — the sharded program is shape-polymorphic in *nothing*).

Three pieces, all pure host bookkeeping (device programs live in
``serving/engine.py``):

- :class:`PageAllocator` — LIFO free list + per-page reference counts. Page 0
  is the **null page**: unused page-table entries point at it, inactive
  decode lanes write (sanitized zeros) to it, and it is never allocated —
  so a gather through any table row is always defined and always finite.
- :class:`PrefixCache` — copy-on-write prefix sharing, keyed by a *chained*
  per-page hash of the prompt tokens (hash of page ``j`` folds in the hash of
  page ``j-1``, so a hit on page ``j`` certifies the whole aligned prefix).
  A registered page holds one registry reference; concurrent requests fork
  it (``incref``) instead of re-prefilling — a fleet-wide system prompt is
  prefilled once and referenced by every request that carries it. Entries
  evict LRU under page pressure, and every hit is verified against the
  stored tokens (a hash collision must degrade to a re-prefill, never to
  wrong attention).
- :class:`PagedKVCache` — the per-engine facade: pools + tables + lengths/
  active mirrors + lane (slot) allocator
  (:class:`~.kv_cache.SlotAllocator`), and whatever else a lane carries.

Not every cached layer has the pool's shape. A model with sliding-window
layers beside full ones (``models/exaone_moe.py``) gives the manager two kinds:
the FULL layers live in the page pool above, ``[Lf, num_pages, ...]``, every
token kept, pages growing with the context; the WINDOW layers live in one ring
a slot, ``wk``/``wv`` a tuple of one array a window layer, ``[num_slots, KV,
window, D]`` (position ``p`` at entry ``p % window``), allocated once and the
same size whatever the length: a window layer never keeps or reads more than
its window. One array a layer, lanes first: a layer's ring is then no slice of
a stack (which XLA is free to copy out before a product reads it), a lane's
ring is one contiguous run of bytes, and a decode step's new entry goes in
through its own tile while nothing else of the ring moves
(``ops/ring_write.py``). A ring belongs to
its lane, not to pages, so nothing of it can be shared, parked or handed off:
the engine refuses those for such a model, by name. What a lane carries
beside its pages is the cache's to say, as ``extras``: the pytree the engine's
decode and prefill programs take after the pool and hand back. It is empty
for a model of one kind, so that its programs have no such argument; for this
one it holds ``wk``, ``wv`` and ``counts``, the rings and the int32 counters
the programs pass along with them.

A model with state-space layers (``models/jamba.py``) gives the manager the
third kind: the RECURRENT layers keep, a lane, a convolution tail and a float32
state, ``conv`` ``[num_slots, Lm, (K - 1) * C]`` and ``ssm`` ``[num_slots, Lm,
N, C]`` — a fixed cost a LANE whatever its context, so that slots, not tokens,
fill the memory. Like a ring it belongs to its lane: nothing of it lies in a
page, so prefix sharing, parking and handoff are refused for such a model too,
and a speculative window could not be rolled back out of it. ``extras`` is one
named structure, :class:`LaneExtras`: each of rings (``wk``, ``wv``), counters
(``counts``) and state (``conv``, ``ssm``) is there or is ``None``, and what is
``None`` is no argument of a compiled program.

Copy-on-write: sharing is page-aligned (full pages only — the unaligned tail
of a shared prefix is recomputed, never half-shared), so in steady state a
slot's write position always lands in a private page. ``prepare_write`` is
the backstop that keeps that invariant local: if the page holding the next
write position is shared (refcount > 1), it allocates a replacement and asks
the engine for an on-device copy of **that page only** — the write then goes
to the private copy and every other holder keeps the original.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Any, NamedTuple, Optional, Sequence

import numpy as np

from .kv_cache import SlotAllocator


def pages_for(tokens: int, page_size: int) -> int:
    """Pages needed to hold ``tokens`` positions."""
    return -(-tokens // page_size)


def paged_buckets(buckets: Sequence[int], page_size: int, capacity: int) -> tuple[int, ...]:
    """Round prefill buckets up to page multiples (a prefill span scatters
    whole pages), capped at the pool-backed capacity."""
    rounded = sorted(
        {min(pages_for(b, page_size) * page_size, capacity) for b in buckets if b > 0}
    )
    if not rounded:
        raise ValueError(f"no usable prefill buckets in {tuple(buckets)}")
    return tuple(rounded)


class LaneExtras(NamedTuple):
    """What a lane carries beside its pages, by name; a member that the model
    has no use for is ``None`` (no leaf of the pytree, so no argument of a
    program). The order is the programs' argument order."""

    wk: Any = None  # the window layers' rings: a tuple, one [num_slots, KV, window, D] a layer
    wv: Any = None
    counts: Any = None  # int32 counters the programs pass along (routed experts)
    conv: Any = None  # the recurrent layers' convolution tails [num_slots, Lm, (K - 1) * C]
    ssm: Any = None  # and their float32 states [num_slots, Lm, N, C]

    @property
    def by_lane(self) -> dict:
        """The members that hold something a LANE: an array, or a tuple of
        them, with the lanes on axis 0."""
        held = {"wk": self.wk, "wv": self.wv, "conv": self.conv, "ssm": self.ssm}
        return {name: member for name, member in held.items() if member is not None}


class PageAllocator:
    """Free-list + refcount bookkeeping over ``num_pages`` physical pages.

    Page 0 is reserved as the null page (see module docstring): it is born
    with a pinned reference and never enters the free list. LIFO reuse keeps
    a freshly freed page's cache lines hot, mirroring
    :class:`~.kv_cache.SlotAllocator`.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"num_pages must be >= 2 (null page + one real), got {num_pages}")
        self.num_pages = num_pages
        self.refcounts = np.zeros((num_pages,), np.int32)
        self.refcounts[0] = 1  # the null page: pinned, never allocated or freed
        self._free = list(range(num_pages - 1, 0, -1))  # pop() yields page 1 first

    def alloc(self) -> Optional[int]:
        """Claim one free page (refcount 1), or None when the pool is dry."""
        if not self._free:
            return None
        page = self._free.pop()
        self.refcounts[page] = 1
        return page

    def alloc_many(self, n: int) -> Optional[list[int]]:
        """All-or-nothing allocation of ``n`` pages."""
        if n < 0:
            raise ValueError(f"n must be >= 0, got {n}")
        if len(self._free) < n:
            return None
        return [self.alloc() for _ in range(n)]

    def incref(self, page: int) -> None:
        """A new holder (forked page table, or a prefix-cache entry)."""
        if page == 0:
            return  # the null page is reference-free by construction
        if self.refcounts[page] <= 0:
            raise ValueError(f"page {page} is free — cannot share it")
        self.refcounts[page] += 1

    def decref(self, page: int) -> bool:
        """Drop one holder; returns True when the page just became free."""
        if page == 0:
            return False
        if self.refcounts[page] <= 0:
            raise ValueError(f"page {page} is already free")
        self.refcounts[page] -= 1
        if self.refcounts[page] == 0:
            self._free.append(page)
            return True
        return False

    def fork(self, pages: Sequence[int]) -> None:
        """Copy-on-write fork: a second page table now references ``pages``.
        No device copy happens here — a copy is paid only if and when a
        holder needs to *write* one of them (:meth:`PagedKVCache.prepare_write`)."""
        for page in pages:
            self.incref(page)

    def is_shared(self, page: int) -> bool:
        return page != 0 and self.refcounts[page] > 1

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        """Pages holding live data (the null page is not counted)."""
        return self.num_pages - 1 - len(self._free)

    @property
    def occupancy(self) -> float:
        capacity = self.num_pages - 1
        return self.used_count / capacity if capacity else 0.0


class PrefixCache:
    """Page-granular prefix registry: chained token hash → physical page.

    ``register_chain`` files each full page of a finished prefill under the
    chained digest of every token up to and including that page; ``lookup``
    walks a new prompt's pages through the same chain and returns the longest
    verified run of cached pages. The registry holds one reference per
    registered page, so a retired request's prefix pages survive for the next
    hit; ``evict_for_pressure`` drops least-recently-used entries when the
    allocator runs dry — page pressure reclaims cache before it sheds
    requests.
    """

    def __init__(self, allocator: PageAllocator, page_size: int, max_entries: int = 256):
        self.allocator = allocator
        self.page_size = page_size
        self.max_entries = max_entries
        # digest -> (page, block_tokens) in LRU order (last = most recent)
        self._entries: "OrderedDict[bytes, tuple[int, np.ndarray]]" = OrderedDict()
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    @staticmethod
    def _chain(parent: bytes, block: np.ndarray) -> bytes:
        return hashlib.sha256(parent + np.ascontiguousarray(block, np.int32).tobytes()).digest()

    def lookup(self, tokens: np.ndarray) -> tuple[int, list[int]]:
        """Longest page-aligned cached prefix of ``tokens``. Returns
        ``(hit_tokens, pages)`` — ``hit_tokens`` is a multiple of
        ``page_size`` and ``pages`` the physical pages holding it (NOT yet
        referenced: the caller forks them on admission). Every hit page's
        stored tokens are compared exactly — a digest collision degrades to
        a shorter hit, never to wrong K/V."""
        ps = self.page_size
        tokens = np.asarray(tokens, np.int32)
        pages: list[int] = []
        digest = b""
        for j in range(tokens.size // ps):
            block = tokens[j * ps : (j + 1) * ps]
            digest = self._chain(digest, block)
            entry = self._entries.get(digest)
            if entry is None or not np.array_equal(entry[1], block):
                break
            self._entries.move_to_end(digest)  # LRU touch
            pages.append(entry[0])
        return len(pages) * ps, pages

    def register_chain(self, tokens: np.ndarray, pages: Sequence[int]) -> int:
        """File each full page of a completed prefill. ``tokens`` must be
        page-aligned and ``pages[j]`` hold its ``j``-th block. Pages already
        registered under the same chain keep their existing entry (the
        content is identical by construction — same tokens, same positions,
        same params). Returns how many new entries were created."""
        ps = self.page_size
        tokens = np.asarray(tokens, np.int32)
        if tokens.size % ps:
            raise ValueError(f"prefix length {tokens.size} is not page-aligned (page_size={ps})")
        digest, created = b"", 0
        for j, page in enumerate(pages):
            block = tokens[j * ps : (j + 1) * ps]
            digest = self._chain(digest, block)
            if digest in self._entries:
                self._entries.move_to_end(digest)
                continue
            self.allocator.incref(page)
            self._entries[digest] = (page, block.copy())
            created += 1
            while len(self._entries) > self.max_entries:
                self._evict_one()
        return created

    def _evict_one(self) -> bool:
        """Drop the least-recently-used entry; returns True if its page
        became free (no live request still holds it)."""
        if not self._entries:
            return False
        _, (page, _) = self._entries.popitem(last=False)
        self.evictions += 1
        return self.allocator.decref(page)

    def evict_for_pressure(self, needed: int) -> None:
        """Evict LRU entries until ``needed`` pages are free or the registry
        is empty. Entries whose pages are still held by live requests free
        nothing immediately, but their reference drops so the page frees the
        moment the last request retires."""
        while self.allocator.free_count < needed and self._entries:
            self._evict_one()

    def invalidate_pages(self, pages: Sequence[int]) -> int:
        """Drop every entry referencing ``pages`` (their content is suspect —
        the quarantine path). Returns the number of entries dropped."""
        doomed = set(int(p) for p in pages)
        victims = [d for d, (page, _) in self._entries.items() if page in doomed]
        for digest in victims:
            page, _ = self._entries.pop(digest)
            self.allocator.decref(page)
        return len(victims)


class PagedKVCache:
    """Pools + page tables + host mirrors, behind the engine.

    ``k``/``v`` come from the model's own ``init_cache(num_pages, page_size)``
    — pages ride the protocol's batch axis, so any decode-protocol model
    pages without changes. ``tables``/``lengths``/``active`` are HOST arrays
    shipped into the jitted programs per step; all device shapes are fixed at
    construction. ``init_window(num_slots)`` (a model with window layers)
    adds the second kind of cached layer: ``wk``/``wv``, one ring a slot, and
    ``counters`` int32 counters beside them; ``init_state(num_slots)`` (a
    model with recurrent layers) the third, ``conv``/``ssm``; together
    ``extras`` (:class:`LaneExtras`, see the module docstring). ``windowed``
    and ``stateful`` say which there are."""

    def __init__(
        self,
        init_cache,
        num_slots: int,
        max_len: int,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        dtype=None,
        prefix_entries: int = 256,
        init_window=None,
        counters: int = 0,
        init_state=None,
    ):
        import jax.numpy as jnp

        if max_len < 2:
            raise ValueError(f"max_len must be >= 2 (prompt + one token), got {max_len}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.page_size = page_size
        self.pages_per_slot = pages_for(max_len, page_size)
        # the gathered per-slot view is whole pages: capacity rounds UP
        self.view_len = self.pages_per_slot * page_size
        if num_pages is None:
            num_pages = num_slots * self.pages_per_slot + 1
        dtype = dtype if dtype is not None else jnp.bfloat16
        cache = init_cache(num_pages, page_size, dtype=dtype)
        self.k, self.v = cache["k"], cache["v"]
        self.extras = LaneExtras()
        if init_window is not None:
            rings = init_window(num_slots, dtype=dtype)
            self.extras = self.extras._replace(wk=rings["wk"], wv=rings["wv"], counts=jnp.zeros((counters,), jnp.int32))
        if init_state is not None:
            state = init_state(num_slots, dtype=dtype)
            self.extras = self.extras._replace(conv=state["conv"], ssm=state["ssm"])
        self.num_pages = num_pages
        self.num_slots = num_slots
        self.max_len = max_len
        self.dtype = dtype
        self.lengths = np.zeros((num_slots,), np.int32)
        self.active = np.zeros((num_slots,), bool)
        self.tables = np.zeros((num_slots, self.pages_per_slot), np.int32)  # 0 = null page
        self.held = np.zeros((num_slots,), np.int32)  # valid leading entries per row
        self.lanes = SlotAllocator(num_slots)
        self.pages = PageAllocator(num_pages)
        self.prefix = PrefixCache(self.pages, page_size, max_entries=prefix_entries)

    # -- capacity ------------------------------------------------------------

    def put(self, k, v, extras: LaneExtras) -> None:
        """Take back what a decode or prefill program hands back: the pools
        and whatever else a lane carries."""
        self.k, self.v, self.extras = k, v, extras

    @property
    def windowed(self) -> bool:
        return self.extras.wk is not None

    @property
    def stateful(self) -> bool:
        """Whether a lane carries recurrent state (a model with state-space layers)."""
        return self.extras.ssm is not None

    @property
    def wk(self):
        return self.extras.wk

    @property
    def wv(self):
        return self.extras.wv

    def _lane_bytes(self, *names) -> int:
        import jax

        held = [member for name, member in self.extras.by_lane.items() if name in names]
        return sum(int(array.nbytes) for array in jax.tree.leaves(held)) // self.num_slots

    @property
    def lane_bytes(self) -> int:
        """Device bytes a LANE holds whatever its context: its rings and its
        recurrent state (0 for a model of pages alone)."""
        return self._lane_bytes("wk", "wv", "conv", "ssm")

    @property
    def state_bytes_per_slot(self) -> int:
        """Of ``lane_bytes``, the recurrent layers' convolution tail and state."""
        return self._lane_bytes("conv", "ssm")

    @property
    def nbytes(self) -> int:
        return int(self.k.nbytes + self.v.nbytes) + self.lane_bytes * self.num_slots

    @property
    def page_bytes(self) -> int:
        """Device bytes of one (k + v) page."""
        return int(self.k.nbytes + self.v.nbytes) // self.num_pages

    @property
    def window_tokens_per_slot(self) -> int:
        """Tokens a slot's window layers keep, each: the ring's length, whatever
        the context (0 without window layers)."""
        return int(self.wk[0].shape[2]) if self.wk else 0

    @property
    def pages_in_use(self) -> int:
        return self.pages.used_count

    @property
    def page_occupancy(self) -> float:
        return self.pages.occupancy

    @property
    def occupancy(self) -> float:
        return self.lanes.occupancy

    @property
    def quarantined(self) -> frozenset:
        return self.lanes.quarantined

    def pages_of(self, slot: int) -> list[int]:
        """The physical pages slot currently references, in position order."""
        return [int(p) for p in self.tables[slot, : int(self.held[slot])]]

    def fits(self, total_tokens: int) -> bool:
        """Whether a request spanning ``total_tokens`` positions can EVER be
        served by this pool (admission-time feasibility, so an impossible
        request sheds with ValueError instead of deadlocking the queue)."""
        return pages_for(total_tokens, self.page_size) <= self.num_pages - 1

    # -- admission / release ---------------------------------------------------

    def _alloc(self, n: int) -> Optional[list[int]]:
        """Allocate ``n`` pages, reclaiming LRU prefix-cache entries under
        pressure before giving up."""
        if self.pages.free_count < n:
            self.prefix.evict_for_pressure(n)
        return self.pages.alloc_many(n)

    def admit(self, shared_pages: Sequence[int], new_pages: int) -> Optional[int]:
        """Claim a lane + pages for one request: ``shared_pages`` are forked
        (COW — refcount, no copy), ``new_pages`` freshly allocated for the
        private suffix. Returns the slot, or None when lanes or pages are
        exhausted (admission is gated on PAGES, not just lanes — the caller's
        request waits in queue either way)."""
        slot = self.lanes.admit()
        if slot is None:
            return None
        # fork BEFORE allocating: ``_alloc`` may evict prefix-cache entries
        # under pressure, and a hit page whose only reference was the
        # registry's would be freed mid-admission and handed back out as a
        # "fresh" suffix page — the same physical page twice in one table row
        self.pages.fork(shared_pages)
        fresh = self._alloc(new_pages)
        if fresh is None:
            for page in shared_pages:  # roll back: pages are the scarce resource
                self.pages.decref(page)
            self.lanes.retire(slot)
            return None
        row = list(shared_pages) + fresh
        self.tables[slot, : len(row)] = row
        self.tables[slot, len(row):] = 0
        self.held[slot] = len(row)
        self.lengths[slot] = 0
        self.active[slot] = False  # decode-visible only once prefill completes
        return slot

    def grow(self, slot: int, n: int) -> bool:
        """Append ``n`` fresh pages to a slot's table (prefill chunks, decode
        crossing a page boundary). False = page pressure (caller preempts or
        stalls)."""
        if n <= 0:
            return True
        fresh = self._alloc(n)
        if fresh is None:
            return False
        held = int(self.held[slot])
        self.tables[slot, held : held + n] = fresh
        self.held[slot] = held + n
        return True

    def prepare_write(self, slot: int) -> tuple[str, int, int]:
        """Make position ``lengths[slot]`` writable before the next decode.

        Returns ``("ok", 0, 0)`` when the target page exists and is private;
        ``("grow", 0, 0)`` after allocating a fresh page for a just-crossed
        boundary; ``("cow", src, dst)`` when the target page was SHARED — a
        replacement is allocated and swapped into the table, and the caller
        must copy ``src → dst`` on device before decoding (the write-triggered
        copy of exactly one page); ``("pressure", 0, 0)`` when the pool is
        dry (caller preempts)."""
        idx = int(self.lengths[slot]) // self.page_size
        if idx >= int(self.held[slot]):
            if not self.grow(slot, idx - int(self.held[slot]) + 1):
                return ("pressure", 0, 0)
            return ("grow", 0, 0)
        page = int(self.tables[slot, idx])
        if not self.pages.is_shared(page):
            return ("ok", 0, 0)
        replacement = self._alloc(1)
        if replacement is None:
            return ("pressure", 0, 0)
        dst = replacement[0]
        self.tables[slot, idx] = dst
        self.pages.decref(page)
        return ("cow", page, dst)

    def trim_to_length(self, slot: int) -> list[int]:
        """Speculative rollback: drop trailing pages beyond what
        ``lengths[slot]`` committed positions need. Before a verify step the
        engine grows the slot far enough to hold the whole candidate window;
        after acceptance lands short, the surplus pages are released here —
        refcounts drop (a forked tree branch's surplus simply un-shares;
        the last holder frees the page back to the pool) and the table's
        tail re-points at the null page. Returns the pages that became free
        (candidates for scrubbing only if they ever held non-finite data —
        speculative windows are ordinary finite K/V, so no scrub here)."""
        keep = pages_for(int(self.lengths[slot]), self.page_size)
        held = int(self.held[slot])
        if keep >= held:
            return []
        freed = []
        for idx in range(keep, held):
            page = int(self.tables[slot, idx])
            if page and self.pages.decref(page):
                freed.append(page)
            self.tables[slot, idx] = 0
        self.held[slot] = keep
        return freed

    def _release_pages(self, slot: int) -> list[int]:
        """Drop the slot's references; returns pages that became free."""
        freed = [p for p in self.pages_of(slot) if self.pages.decref(p)]
        self.tables[slot, :] = 0
        self.held[slot] = 0
        self.lengths[slot] = 0
        self.active[slot] = False
        return freed

    def park(self, slot: int) -> list[int]:
        """Detach a slot's pages WITHOUT dropping their references: the lane
        frees (it can admit the next prefill immediately) but every page keeps
        the refcount this slot held, so the allocator cannot recycle them.
        This is the source half of a live-KV handoff (docs/serving.md): the
        parked pages stay readable — and exactly as shared/registered as they
        were — until the destination acknowledges adoption (the caller then
        decrefs each parked page, mirroring :meth:`retire`) or the handoff
        falls back (same release; re-prefill regenerates the content).
        Returns the parked pages in position order."""
        pages = self.pages_of(slot)
        self.lanes.retire(slot)
        self.tables[slot, :] = 0
        self.held[slot] = 0
        self.lengths[slot] = 0
        self.active[slot] = False
        return pages

    def seat(self, pages: Sequence[int], length: int) -> Optional[int]:
        """Claim a lane for pages the caller already owns (freshly allocated
        by ``adopt_kv``, or a parked row being resumed in place) and make it
        decode-visible at ``length``. Returns the slot, or None when no lane
        is free — the caller keeps its page references and retries later."""
        slot = self.lanes.admit()
        if slot is None:
            return None
        self.tables[slot, : len(pages)] = list(pages)
        self.tables[slot, len(pages):] = 0
        self.held[slot] = len(pages)
        self.lengths[slot] = length
        self.active[slot] = True
        return slot

    def retire(self, slot: int) -> None:
        """Free the lane and the slot's page references. Registered prefix
        pages survive through the registry's own reference; everything else
        returns to the pool. No device work: a freed page's stale K/V is
        unreachable (gathers mask positions >= length, and a new holder's
        prefill overwrites whole pages before they become visible)."""
        self.lanes.retire(slot)
        self._release_pages(slot)

    def quarantine(self, slot: int) -> list[int]:
        """Poisoned lane: pull it from circulation and release its pages.
        Returns the pages that must be SCRUBBED on device before reuse —
        non-finite K/V in a recycled page would poison its next holder
        through the attention matmul (a masked position's softmax weight is
        exactly 0.0, but 0 × NaN is still NaN). Prefix entries referencing
        the slot's pages are invalidated first: their content is suspect, and
        an entry that survived would hand poisoned pages to new requests."""
        pages = self.pages_of(slot)
        self.lanes.quarantine(slot)
        self.prefix.invalidate_pages(pages)
        freed = self._release_pages(slot)
        # pages still shared by other live slots stay (those requests have
        # been decoding through them finitely); only fully-freed pages scrub
        return freed

    def release_quarantined(self, slot: int) -> None:
        """Probe passed: the lane may serve requests again."""
        self.lanes.release(slot)
        self.lengths[slot] = 0
        self.active[slot] = False
