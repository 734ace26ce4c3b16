"""Paged KV-cache engine: refcounted allocator + prefix-cache lifecycle,
page-gated admission, copy-on-write tail sharing, token-identity with the
contiguous layout (and with the prefix cache off), and compile stability."""
import numpy as np
import pytest

from repro.serving.engine import Request, make_edge_engine
from repro.serving.paging import (
    PageAllocator, PagingError, PrefixCache, pages_needed,
)
from repro.serving.scheduler import TierScheduler


# ---------------------------------------------------------------------------
# Allocator: refcounts, guards, LRU retention
# ---------------------------------------------------------------------------

def test_allocator_distinct_ids_and_recycling():
    a = PageAllocator(8)
    x = a.alloc(3)
    y = a.alloc(5)
    ids = np.concatenate([x, y])
    assert len(set(ids.tolist())) == 8 and 0 not in ids    # distinct, no trash
    assert a.free_pages == 0
    with pytest.raises(PagingError):
        a.alloc(1)
    a.free(x)
    assert a.free_pages == 3
    z = a.alloc(3)
    assert sorted(z.tolist()) == sorted(x.tolist())        # recycled

def test_allocator_guards_raise_real_exceptions():
    """Bookkeeping violations raise PagingError (a RuntimeError), not bare
    asserts that vanish under ``python -O``."""
    a = PageAllocator(4)
    ids = a.alloc(2)
    with pytest.raises(PagingError):
        a.free([int(ids[0]), int(ids[0])])                 # double free
    with pytest.raises(PagingError):
        a.free([0])                                        # trash page
    with pytest.raises(PagingError):
        a.free([99])                                       # foreign id
    with pytest.raises(PagingError):
        a.ref([int(a._free[-1])])                          # ref of free page
    assert issubclass(PagingError, RuntimeError)

def test_pages_needed_rounding():
    assert pages_needed(1, 16) == 1
    assert pages_needed(16, 16) == 1
    assert pages_needed(17, 16) == 2
    assert pages_needed(0, 16) == 1

def test_refcount_share_fork_free_lifecycle():
    """A page mapped by several slots is released only by the LAST free."""
    a = PageAllocator(4)
    (pid,) = a.alloc(1).tolist()
    a.ref([pid])                      # second slot maps the same page
    a.ref([pid])                      # third
    assert a.refcount(pid) == 3
    a.free([pid])
    a.free([pid])
    assert a.refcount(pid) == 1 and a.free_pages == 3      # still mapped
    with pytest.raises(PagingError):
        a.alloc(4)                    # page is not reclaimable while mapped
    a.free([pid])
    assert a.free_pages == 4          # decrement-to-zero released it

def test_lru_retention_and_demand_eviction():
    """retain=True parks refcount-0 pages in the LRU pool: available but not
    free; ``ref`` revives them; alloc evicts oldest-first via evict_cb."""
    a = PageAllocator(4)
    evicted = []
    a.evict_cb = evicted.append
    keep = {1, 2, 3, 4}
    p1 = a.alloc(2)          # say pages [4, 3]
    p2 = a.alloc(2)
    a.free(p1, retain=keep.__contains__)
    assert a.free_pages == 0 and a.cached_pages == 2 and a.available_pages == 2
    # revival: ref pulls a cached page back to refcount 1 with no device work
    a.ref([int(p1[0])])
    assert a.cached_pages == 1 and a.refcount(int(p1[0])) == 1
    a.free([int(p1[0])], retain=keep.__contains__)
    # demand eviction: alloc(2) must evict both cached pages, oldest first
    got = a.alloc(2)
    assert sorted(got.tolist()) == sorted(p1.tolist())
    assert a.cached_pages == 0 and sorted(evicted) == sorted(p1.tolist())
    a.free(got)
    a.free(p2)

def test_can_reserve_counts_revived_pages_once():
    a = PageAllocator(3)
    ids = a.alloc(3)
    a.free(ids, retain=lambda p: True)        # all cached
    assert a.available_pages == 3
    reuse = [int(ids[0])]
    assert a.can_reserve(2, reuse)            # revive 1, evict 2 -> fits
    assert not a.can_reserve(3, reuse)        # 3 fresh + 1 revived > pool


# ---------------------------------------------------------------------------
# PrefixCache: chain hashes, tails, eviction
# ---------------------------------------------------------------------------

def test_prefix_cache_match_insert_roundtrip():
    pc = PrefixCache(4)
    toks = list(range(11))                    # 2 full blocks + 3-token tail
    pc.insert(toks, [10, 11, 12])
    pages, tail = pc.match(toks[:10])         # capped at L-1
    assert pages == [10, 11]
    assert tail == (12, 2)                    # 2 of the 3 tail tokens usable
    # diverging second block breaks the chain after block 0
    pages, tail = pc.match([0, 1, 2, 3, 9, 9, 9, 9])
    assert pages == [10] and tail is None
    # partial tail match: first token of the tail agrees
    pages, tail = pc.match(toks[:8] + [8, 77])
    assert pages == [10, 11] and tail == (12, 1)

def test_prefix_cache_forget_drops_all_keys():
    pc = PrefixCache(4)
    pc.insert(list(range(6)), [5, 6])
    assert pc.owns(5) and pc.owns(6)
    pc.forget(5)
    assert not pc.owns(5)
    pages, tail = pc.match(list(range(5)))
    assert pages == [] and tail is None       # chain root gone -> full miss
    pc.forget(6)
    assert len(pc) == 0

def test_prefix_cache_first_writer_wins():
    pc = PrefixCache(2)
    pc.insert([1, 2, 3, 4], [7, 8])
    pc.insert([1, 2, 3, 4], [9, 9])           # same blocks, other pages
    pages, _ = pc.match([1, 2, 3])
    assert pages == [7]                       # canonical page kept


# ---------------------------------------------------------------------------
# Engine: paged layout end-to-end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def paged():
    eng = make_edge_engine(max_seq=96, max_batch=3, seed=0)   # auto -> paged
    assert eng.kv_layout == "paged" and eng._prefix is not None
    return eng

@pytest.fixture(scope="module")
def contiguous():
    return make_edge_engine(max_seq=96, max_batch=3, seed=0,
                            kv_layout="contiguous")


REQS = [Request("What is the capital of France?", max_new_tokens=6),
        Request("Hello", max_new_tokens=9),
        Request("a" * 60, max_new_tokens=30),
        Request("tiered rag serving", max_new_tokens=4),
        Request("edge node", max_new_tokens=12),
        Request("q" * 30, max_new_tokens=7)]


def test_paged_greedy_token_identical_to_contiguous(paged, contiguous):
    """The tentpole acceptance: greedy decode through the page arena —
    prefix sharing, CoW tails and suffix prefill included — emits exactly
    the tokens the contiguous per-slot lanes emit."""
    out_p, _ = paged.generate(REQS)
    out_c, _ = contiguous.generate(REQS)
    assert out_p == out_c
    # and the static path through the paged engine agrees with itself
    static, _ = paged.generate_static(REQS[:3])
    assert static == out_p[:3]

def test_prefix_sharing_on_vs_off_token_identical():
    """Greedy outputs must not depend on whether prompts were prefilled
    from scratch or assembled from shared pages + CoW tail + suffix."""
    ctx = "shared retrieved context: the Eiffel Tower is in Paris. "
    reqs = [Request(ctx + q, max_new_tokens=8)
            for q in ("who?", "where?", "when?", "why?")]
    on = make_edge_engine(max_seq=128, max_batch=4, seed=0)
    off = make_edge_engine(max_seq=128, max_batch=4, seed=0,
                           prefix_cache=False)
    out_on, st_on = on.generate(reqs)
    out_off, st_off = off.generate(reqs)
    assert out_on == out_off
    assert st_on.prefix_hits == 3 and st_on.prefix_misses == 1
    assert st_on.prefix_tokens_shared >= 3 * (len(ctx) // on.page_size
                                              * on.page_size)
    assert st_off.prefix_hits == 0 and st_off.prefix_tokens_shared == 0

def test_shared_pages_counted_once(paged):
    """Two residents sharing a prefix hold the shared pages at refcount 2
    and together consume fewer pages than two independent requests."""
    drain(paged)
    base = paged.available_pages
    r1 = Request("z" * 40, max_new_tokens=4)
    r2 = Request("z" * 40, max_new_tokens=4)
    need = pages_needed(41 + 4, paged.page_size)
    paged.admit(r1)
    used1 = base - paged.available_pages
    assert used1 == need
    paged.admit(r2)
    used2 = base - paged.available_pages
    # second request allocates fresh pages only for CoW tail + budget
    assert used2 < 2 * need
    shared = paged._page_tables[0][: 41 // paged.page_size]
    for pid in shared:
        assert paged._allocator.refcount(int(pid)) == 2
    drain(paged)
    assert paged.available_pages == base

def drain(eng):
    while eng.has_active:
        eng.step()

def test_pages_recycled_after_drain(paged):
    drain(paged)
    assert paged.available_pages == paged.num_pages
    paged.generate(REQS)
    assert paged.available_pages == paged.num_pages
    assert not paged.has_active
    assert (paged._page_tables == 0).all()
    # retained prefix pages are CACHED (reclaimable), not leaked or free
    assert paged.cached_pages > 0
    assert paged.free_pages + paged.cached_pages == paged.num_pages

def test_page_reservation_matches_prompt_plus_budget():
    """While a request is resident it holds exactly
    ceil((prompt + budget) / page_size) pages (prefix cache off: every page
    is private)."""
    eng = make_edge_engine(max_seq=96, max_batch=3, seed=0,
                           prefix_cache=False)
    r = Request("hello world", max_new_tokens=10)
    L = len(eng.tok.encode(r.prompt))
    need = pages_needed(L + 10, eng.page_size)
    eng.admit(r)
    assert eng.free_pages == eng.num_pages - need
    drain(eng)
    assert eng.free_pages == eng.num_pages

def test_decode_never_retraces_across_mixed_stream(paged):
    before = paged.trace_counts["decode"]
    reqs = [Request("x" * (3 + 7 * i), max_new_tokens=1 + i % 5)
            for i in range(8)]
    paged.generate(reqs)
    assert paged.trace_counts["decode"] == before
    # the paged path writes prefill straight into pages: no insert ever
    assert paged.trace_counts["insert"] == 0
    assert paged.trace_counts["copy"] <= 1

def test_lru_eviction_under_page_pressure():
    """A pool far smaller than the distinct-prompt working set must keep
    admitting (evicting stale cached prefixes) and never corrupt outputs."""
    eng = make_edge_engine(max_seq=64, max_batch=2, seed=0,
                           num_pages=2 * (64 // 16))
    ref = make_edge_engine(max_seq=64, max_batch=2, seed=0,
                           prefix_cache=False,
                           num_pages=2 * (64 // 16))
    reqs = [Request(f"distinct prompt number {i} padded out", max_new_tokens=3)
            for i in range(6)]
    out, _ = eng.generate(reqs)
    out_ref, _ = ref.generate(reqs)
    assert out == out_ref
    assert eng.available_pages == eng.num_pages
    # the tiny pool cannot retain every prompt: evictions must have fired
    assert eng.cached_pages <= eng.num_pages

def test_cached_prefix_survives_completion_and_rehits():
    """LRU retention: a prompt admitted AFTER its twin completed still hits
    — the refcount-0 pages kept their KV."""
    eng = make_edge_engine(max_seq=128, max_batch=2, seed=0)
    r = Request("the quick brown fox jumps over the lazy dog",
                max_new_tokens=4)
    eng.generate([r])
    assert eng.prefix_hits == 0
    out2, st = eng.generate([Request(r.prompt, max_new_tokens=4)])
    assert st.prefix_hits == 1
    assert st.prefix_tokens_shared == len(eng.tok.encode(r.prompt)) - 1

def test_admission_blocks_on_pages_not_slots():
    """With a page pool far smaller than the slot pool, residency is bounded
    by pages; queued work still drains to completion."""
    eng = make_edge_engine(max_seq=64, max_batch=6, seed=0,
                           num_pages=64 // 16)     # exactly one worst case
    assert eng.kv_layout == "paged"
    big = Request("z" * 40, max_new_tokens=20)     # needs the whole pool
    assert eng.can_admit(big)
    eng.admit(big)
    small = Request("hi", max_new_tokens=2)
    assert eng.free_slots > 0 and not eng.can_admit(small)
    with pytest.raises(PagingError):
        eng.admit(small)
    drain(eng)
    assert eng.can_admit(small)
    sched = TierScheduler({"edge": eng})
    for i in range(6):                    # 6 free slots, but only 4 pages
        sched.submit(Request(f"q{i}", max_new_tokens=2), "edge")
    done = sched.drain()
    assert len(done) == 6
    assert eng.available_pages == eng.num_pages
    # each small request needs 1 page: with 6 slots free the scheduler still
    # only reaches 4 residents — pages, not slots, were the binding limit
    assert eng.peak_active == 4

def test_more_residents_than_equal_memory_contiguous():
    """At equal KV token capacity, short requests pack >2x more resident
    work into the paged pool than the contiguous layout's max_batch."""
    base_batch, max_seq, ps = 2, 128, 16
    eng = make_edge_engine(max_seq=max_seq, max_batch=4 * base_batch, seed=0,
                           page_size=ps,
                           num_pages=base_batch * (max_seq // ps))
    assert eng.kv_cache_tokens == base_batch * max_seq
    reqs = [Request("ab", max_new_tokens=8) for _ in range(8)]
    eng.generate(reqs)
    assert eng.peak_active >= 2 * base_batch

def test_contiguous_layout_still_available():
    eng = make_edge_engine(max_seq=64, max_batch=2, kv_layout="contiguous")
    assert eng.kv_layout == "contiguous"
    assert eng.free_pages is None
    assert eng.can_admit(Request("x"))
    texts, _ = eng.generate([Request("hello", max_new_tokens=3)])
    assert len(texts) == 1

def test_kv_head_major_arena_at_two_kv_heads():
    """The arena is [layers, pages, KV, page_size, hd] and the page axis is
    found from its names, so the CoW page copy and the token scatters stay
    right with KV > 1 (the reduced configs all have KV == 1): paged greedy
    output, prefix hits and CoW tails included, equals contiguous lanes."""
    import dataclasses
    from repro.configs import get_config
    from repro.serving.engine import ServingEngine
    cfg = dataclasses.replace(get_config("qwen2-0.5b", reduced=True),
                              n_heads=4, n_kv_heads=2)
    paged = ServingEngine(cfg, max_seq=96, max_batch=3, seed=0)
    contiguous = ServingEngine(cfg, max_seq=96, max_batch=3, seed=0,
                               kv_layout="contiguous")
    hd = cfg.resolved_head_dim
    for leaf in (paged._cache["blocks"]["k"], paged._cache["blocks"]["v"]):
        assert leaf.shape == (cfg.n_layers, paged.num_pages + 1, 2,
                              paged.page_size, hd)
    assert paged._page_ax["blocks"]["k"] == 1
    ctx = "shared retrieved context: the Eiffel Tower is in Paris. "
    reqs = REQS + [Request(ctx + q, max_new_tokens=5)
                   for q in ("who?", "where?")]
    out_p, st = paged.generate(reqs)
    out_c, _ = contiguous.generate(reqs)
    assert out_p == out_c
    assert st.prefix_hits >= 1 and paged.trace_counts["copy"] == 1


def test_paged_rejected_for_unpageable_model():
    from repro.configs import get_config
    from repro.serving.engine import ServingEngine
    cfg = get_config("gemma3-4b", reduced=True)    # sliding-window ring
    with pytest.raises(ValueError):
        ServingEngine(cfg, max_seq=64, max_batch=1, kv_layout="paged")
    eng = ServingEngine(cfg, max_seq=64, max_batch=1)     # auto falls back
    assert eng.kv_layout == "contiguous"
