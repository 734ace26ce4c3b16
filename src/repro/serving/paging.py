"""Host-side page allocator + prefix index for the paged KV-cache.

The device holds one page arena per layer (``[num_pages + 1, KV,
page_size, hd]``); this module owns the *ids*. Physical page 0 is reserved as the
trash page: page-table entries beyond a slot's allocation point at it, so
fixed-shape scatters can always write a full table row and fixed-shape
gathers can always read one — writes land in trash, reads are masked by the
per-row valid length.

Two classes cooperate:

* :class:`PageAllocator` — refcounted free-list over physical page ids.
  ``alloc`` hands out pages at refcount 1; ``ref`` lets several slots map
  the SAME physical page (prefix sharing); ``free`` decrements and only a
  decrement-to-zero releases the page. A page that the prefix index still
  wants (``retain``) parks in an LRU side pool instead of the free list: its
  KV bytes stay valid on device and a later request can revive it for free,
  but the allocator reclaims LRU pages (oldest first, notifying
  ``evict_cb``) the moment real demand needs them — cached pages are
  capacity, not leaks.

* :class:`PrefixCache` — vLLM/SGLang-style block-hash index. The prompt is
  cut into page-sized token blocks and each block keyed by a *chain* hash
  (parent hash + this block's tokens, verified token-exact on lookup, so a
  Python hash collision can only cause a miss, never false sharing).
  ``match`` walks the chain for the longest page-aligned shared prefix and
  then tries the *partial tail* entries under the last matched hash — a
  cached page whose first ``k`` tokens agree can be copy-on-write'd by the
  engine (device page copy) so even a non-page-aligned retrieval context is
  shared up to the last token.

Everything here is plain numpy/python — consulted at admission/retirement
only (host side, off the jit path), never per decode step.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

TRASH_PAGE = 0


class PagingError(RuntimeError):
    """Page bookkeeping violation (double free, trash-page free, foreign id,
    pool exhaustion). A real exception — unlike an ``assert`` — survives
    ``python -O``, where a silently corrupted free list would hand the same
    physical page to two slots and let their device scatters race."""


def pages_needed(tokens: int, page_size: int) -> int:
    """Pages to reserve for a request that will occupy ``tokens`` cache
    positions (prompt + decode budget)."""
    return max(1, -(-tokens // page_size))


class PageAllocator:
    """Refcounted free-list over physical page ids ``1..num_pages`` (0 is
    trash). Page states: FREE (on the free list), ACTIVE (refcount >= 1,
    mapped by one or more slots), CACHED (refcount 0 but retained in the LRU
    pool for prefix reuse; reclaimed on demand)."""

    def __init__(self, num_pages: int):
        if num_pages <= 0:
            raise PagingError(f"need at least one page, got {num_pages}")
        self.num_pages = num_pages
        # LIFO: recently freed pages are reused first (warm in cache)
        self._free: List[int] = list(range(num_pages, 0, -1))
        self._free_set = set(self._free)    # O(1) membership/double-free check
        self._refs = np.zeros(num_pages + 1, np.int32)
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self.evict_cb: Optional[Callable[[int], None]] = None
        self.generation = 0       # bumped on every state change (plan memos)

    # ---- introspection ------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def cached_pages(self) -> int:
        """Refcount-0 pages retained for prefix reuse (reclaimable)."""
        return len(self._lru)

    @property
    def available_pages(self) -> int:
        """Pages an ``alloc`` could obtain right now (free + evictable)."""
        return len(self._free) + len(self._lru)

    @property
    def used_pages(self) -> int:
        return self.num_pages - self.available_pages

    def refcount(self, pid: int) -> int:
        return int(self._refs[int(pid)])

    def audit(self, mapped: Optional[Dict[int, int]] = None
              ) -> Dict[str, int]:
        """Full page-accounting audit — the DST page oracle, also run by the
        bench ``--check`` quiescence sweeps.

        Verifies that every physical page is in exactly one of the three
        states (FREE on the free list, CACHED in the LRU pool, ACTIVE with
        refcount >= 1) and that the three populations sum to ``num_pages``
        (zero leaks, zero aliasing). When ``mapped`` is given — ``{page id:
        number of slot mappings}`` gathered from the engine's resident page
        tables — additionally verifies that each page's refcount equals its
        mapping count (a skipped decrement or double ref shows up here).
        Raises :class:`PagingError` on any breach; returns the population
        counts ``{"num_pages", "free", "cached", "active"}`` otherwise."""
        free_list = [int(p) for p in self._free]
        free = set(free_list)
        if len(free) != len(free_list):
            dup = sorted(p for p in free if free_list.count(p) > 1)
            raise PagingError(f"free list contains duplicates: {dup}")
        if free != self._free_set:
            raise PagingError(
                f"free list/set disagree: list {sorted(free)} vs "
                f"set {sorted(self._free_set)}")
        cached = {int(p) for p in self._lru}
        for name, grp in (("free list", free), ("LRU pool", cached)):
            if TRASH_PAGE in grp:
                raise PagingError(f"trash page 0 found in the {name}")
            bad = sorted(p for p in grp if not 1 <= p <= self.num_pages)
            if bad:
                raise PagingError(f"foreign page ids in the {name}: {bad}")
        both = free & cached
        if both:
            raise PagingError(
                f"pages simultaneously free and cached: {sorted(both)}")
        neg = [p for p in range(1, self.num_pages + 1) if self._refs[p] < 0]
        if neg:
            raise PagingError(f"negative refcounts on pages {neg}")
        active = {p for p in range(1, self.num_pages + 1)
                  if self._refs[p] > 0}
        ghost = (free | cached) & active
        if ghost:
            raise PagingError(
                f"pages on the free list/LRU pool with refcount > 0: "
                f"{sorted(ghost)}")
        if len(free) + len(cached) + len(active) != self.num_pages:
            lost = sorted(set(range(1, self.num_pages + 1))
                          - free - cached - active)
            raise PagingError(
                f"page leak: free {len(free)} + cached {len(cached)} + "
                f"active {len(active)} != num_pages {self.num_pages}; "
                f"unaccounted pages {lost}")
        if mapped is not None:
            bad = sorted(p for p in mapped
                         if not 1 <= int(p) <= self.num_pages)
            if bad:
                raise PagingError(f"slots map foreign page ids: {bad}")
            for p in range(1, self.num_pages + 1):
                want = int(mapped.get(p, 0))
                have = int(self._refs[p])
                if want != have:
                    raise PagingError(
                        f"refcount mismatch on page {p}: refcount {have} "
                        f"but {want} resident slot mapping(s)")
        return {"num_pages": self.num_pages, "free": len(free),
                "cached": len(cached), "active": len(active)}

    def bump_generation(self) -> None:
        """Force plan-memo invalidation without a page state change (e.g.
        the prefix index was cleared, so cached admission matches are
        stale even though no page moved)."""
        self.generation += 1

    def is_cached(self, pid: int) -> bool:
        return int(pid) in self._lru

    def can_reserve(self, n_fresh: int, reuse_ids: Sequence[int] = ()) -> bool:
        """Could a request mapping ``reuse_ids`` (shared/CoW-source pages)
        still allocate ``n_fresh`` pages? Reviving a CACHED reused page
        removes it from the evictable pool, so it is not double-counted."""
        revive = sum(1 for p in reuse_ids if int(p) in self._lru)
        return n_fresh <= len(self._free) + len(self._lru) - revive

    # ---- validation helpers -------------------------------------------
    def _check_id(self, pid: int) -> int:
        pid = int(pid)
        if pid == TRASH_PAGE:
            raise PagingError("page 0 is the trash page and is never owned")
        if not 1 <= pid <= self.num_pages:
            raise PagingError(
                f"page id {pid} outside pool 1..{self.num_pages}")
        return pid

    # ---- lifecycle ----------------------------------------------------
    def alloc(self, n: int) -> np.ndarray:
        """Pop ``n`` distinct physical page ids at refcount 1, evicting LRU
        cached pages (oldest first, via ``evict_cb``) if the free list runs
        short. Raises :class:`PagingError` if even eviction cannot cover the
        request — callers gate on :meth:`can_reserve` first."""
        if n > self.available_pages:
            raise PagingError(
                f"page pool exhausted: need {n}, have {len(self._free)} free "
                f"+ {len(self._lru)} cached of {self.num_pages}")
        while len(self._free) < n:
            self._evict_one()
        ids = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(ids)
        self._refs[ids] = 1
        self.generation += 1
        return np.asarray(ids, np.int32)

    def _evict_one(self) -> None:
        pid, _ = self._lru.popitem(last=False)        # oldest first
        if self.evict_cb is not None:
            self.evict_cb(pid)
        self._free.append(pid)
        self._free_set.add(pid)

    def ref(self, ids: Sequence[int]) -> None:
        """Take one extra reference on each page (a slot mapping a shared
        prefix page). Reviving a CACHED page removes it from the LRU pool."""
        for pid in ids:
            pid = self._check_id(pid)
            if pid in self._free_set:
                raise PagingError(f"ref of free page {pid}")
            if self._refs[pid] == 0:
                if pid not in self._lru:
                    raise PagingError(
                        f"page {pid} has refcount 0 but is not cached")
                del self._lru[pid]
            self._refs[pid] += 1
        self.generation += 1

    def free(self, ids: Sequence[int],
             retain: Optional[Callable[[int], bool]] = None) -> None:
        """Drop one reference per page. On decrement-to-zero the page either
        returns to the free list or — when ``retain(pid)`` says the prefix
        index still values its contents — parks in the LRU pool, where its
        KV stays valid until the allocator actually needs the capacity."""
        for pid in ids:
            pid = self._check_id(pid)
            if pid in self._free_set:
                raise PagingError(f"double free of page {pid}")
            if self._refs[pid] <= 0:
                raise PagingError(
                    f"free of page {pid} with refcount {int(self._refs[pid])}")
            self._refs[pid] -= 1
            if self._refs[pid] == 0:
                if retain is not None and retain(pid):
                    self._lru[pid] = None
                    self._lru.move_to_end(pid)        # most-recently used
                else:
                    self._free.append(pid)
                    self._free_set.add(pid)
        self.generation += 1


class PrefixCache:
    """Block-hash index: chain hashes of page-sized token blocks -> the
    physical page holding that block's KV, plus partial-tail entries for the
    copy-on-write path. Pure host-side bookkeeping; the engine owns when to
    ref/copy pages."""

    _ROOT = 0xE0C0

    def __init__(self, page_size: int):
        self.page_size = page_size
        # chain hash -> (page id, block tokens) — tokens kept to verify the
        # match exactly (hash collisions degrade to misses, never aliasing)
        self._blocks: Dict[int, Tuple[int, Tuple[int, ...]]] = {}
        # parent chain hash -> {tail tokens -> page id} (partially filled
        # last prompt page, CoW source)
        self._tails: Dict[int, Dict[Tuple[int, ...], int]] = {}
        # page id -> index keys referencing it (for O(keys) eviction)
        self._page_keys: Dict[int, List[tuple]] = {}

    @staticmethod
    def _chain(parent: int, block: Tuple[int, ...]) -> int:
        return hash((parent, block))

    # ---- introspection ------------------------------------------------
    def __len__(self) -> int:
        return len(self._blocks) + sum(len(b) for b in self._tails.values())

    def owns(self, pid: int) -> bool:
        """Does the index reference this page (i.e. retain it on free)?"""
        return int(pid) in self._page_keys

    # ---- lookup -------------------------------------------------------
    def match(self, tokens: Sequence[int]
              ) -> Tuple[List[int], Optional[Tuple[int, int]]]:
        """Longest page-aligned shared prefix of ``tokens``.

        Returns ``(full_page_ids, tail)`` where ``full_page_ids`` are the
        physical pages of consecutively matched full blocks and ``tail`` is
        ``(page_id, n_tokens)`` for the best partial-tail continuation (a
        cached page whose first ``n_tokens`` agree with what follows the
        full match) — the engine copies that page (CoW) rather than mapping
        it, because the new request will keep writing into it. Callers cap
        ``tokens`` (e.g. at prompt length - 1) so a suffix always remains to
        prefill for first-token logits."""
        ps = self.page_size
        tokens = tuple(int(t) for t in tokens)
        h = self._ROOT
        pages: List[int] = []
        i = 0
        while i + ps <= len(tokens):
            block = tokens[i:i + ps]
            nxt = self._chain(h, block)
            hit = self._blocks.get(nxt)
            if hit is None or hit[1] != block:
                break
            pages.append(hit[0])
            h = nxt
            i += ps
        tail: Optional[Tuple[int, int]] = None
        rest = tokens[i:]
        if rest:
            best = 0
            for ttoks, pid in self._tails.get(h, {}).items():
                k = 0
                for a, b in zip(rest, ttoks):
                    if a != b:
                        break
                    k += 1
                if k > best:
                    best, tail = k, (pid, k)
        return pages, tail

    # ---- registration -------------------------------------------------
    def insert(self, tokens: Sequence[int], page_row: Sequence[int]) -> None:
        """Index a freshly prefilled prompt: every full block (and the
        partial tail, if any) of ``tokens`` maps to the page at the same
        logical index in ``page_row``. Already-indexed blocks keep their
        canonical page (first writer wins)."""
        ps = self.page_size
        tokens = tuple(int(t) for t in tokens)
        h = self._ROOT
        n_full = len(tokens) // ps
        for j in range(n_full):
            block = tokens[j * ps:(j + 1) * ps]
            h = self._chain(h, block)
            hit = self._blocks.get(h)
            if hit is None:
                pid = int(page_row[j])
                self._blocks[h] = (pid, block)
                self._page_keys.setdefault(pid, []).append(("b", h))
            elif hit[1] != block:
                # hash collision with a different block: registering our
                # descendants under this chain would let a later walker
                # token-verify them against the WRONG prefix — stop here so
                # a collision stays a miss, never false sharing
                return
        tail = tokens[n_full * ps:]
        if tail:
            bucket = self._tails.setdefault(h, {})
            if tail not in bucket:
                pid = int(page_row[n_full])
                bucket[tail] = pid
                self._page_keys.setdefault(pid, []).append(("t", h, tail))

    def forget(self, pid: int) -> None:
        """Drop every index entry referencing ``pid`` (allocator evicted the
        page). Orphaned descendants of a dropped chain link simply become
        unreachable and age out of the LRU pool on their own."""
        for key in self._page_keys.pop(int(pid), []):
            if key[0] == "b":
                self._blocks.pop(key[1], None)
            else:
                bucket = self._tails.get(key[1])
                if bucket is not None:
                    bucket.pop(key[2], None)
                    if not bucket:
                        del self._tails[key[1]]

    def clear(self) -> int:
        """Drop EVERY index entry (knowledge rotation made the cached
        retrieved-context prefixes stale). Page refcounts are untouched:
        resident slots keep their mappings, and refcount-0 pages parked in
        the allocator's LRU pool simply stop being revivable — ``owns``
        now answers False, so they return to the free list on their next
        release or are reclaimed on demand. Returns the number of entries
        dropped."""
        n = len(self)
        self._blocks.clear()
        self._tails.clear()
        self._page_keys.clear()
        return n


__all__ = ["PageAllocator", "PrefixCache", "PagingError", "pages_needed",
           "TRASH_PAGE"]
