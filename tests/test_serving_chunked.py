"""Fused chunked-prefill + decode (token-budget serving).

Covers the PR's acceptance surface: greedy token-identity of chunked vs
whole-suffix admission (including prefix-cache hits landing mid-chunk and
CoW tails), the budget policy (interactive-first chunk selection, the
starvation guard), preempt/resume and crash/requeue of half-prefilled
residents, stats surfacing (mixed_steps / prefill_chunks /
budget_utilization / ttft_s), and trace discipline (a single chunk pad
bucket: one prefill + one fused trace, zero decode retraces)."""
import numpy as np
import pytest

from repro.core.clock import VirtualClock
from repro.kernels.decode_attention.kernel import append_walk
from repro.serving.engine import EngineError, Request, make_edge_engine
from repro.serving.scheduler import TierScheduler

LONG = "retrieval augmented generation at the edge with adaptive update "
MIX = [
    LONG,                       # multi-chunk prompt
    "short q",                  # single-chunk prompt
    LONG + "and a longer unique tail for the second document",
    "x",                        # degenerate 2-token prompt
]


def budget_engine(**kw):
    kw.setdefault("max_seq", 128)
    kw.setdefault("max_batch", 4)
    kw.setdefault("step_token_budget", 12)
    kw.setdefault("prefill_chunk", 16)
    return make_edge_engine(seed=0, **kw)


def whole_engine(**kw):
    kw.setdefault("max_seq", 128)
    kw.setdefault("max_batch", 4)
    return make_edge_engine(seed=0, **kw)


def drain_virtual(sched, clock, step=0.05, max_steps=10_000):
    done = []
    for _ in range(max_steps):
        if not (sched.pending() or sched.in_flight()):
            return done
        done.extend(sched.pump(now=clock.now()))
        clock.advance(step)
    raise AssertionError("virtual drain did not converge")


# ---------------------------------------------------------------------------
# greedy token identity
# ---------------------------------------------------------------------------

def test_chunked_greedy_identical_to_whole_suffix():
    reqs = lambda: [Request(p, max_new_tokens=8) for p in MIX]   # noqa: E731
    ref, _ = whole_engine().generate(reqs())
    eng = budget_engine()
    out, stats = eng.generate(reqs())
    assert out == ref
    assert eng.prefill_chunks > 0
    assert eng.mixed_steps > 0           # decode really overlapped a chunk
    assert stats.prefill_chunks == eng.prefill_chunks
    assert 0.0 < stats.budget_utilization <= 1.0
    eng.assert_quiescent()


def test_prefix_hit_mid_chunk_identity():
    """A prefix-cache hit leaves prefill_done mid-prompt (shared pages +
    CoW tail, generally NOT chunk- or page-aligned): chunking must resume
    from there and stay token-identical to whole-suffix admission."""
    ctx = "c o m m o n r e t r i e v e d c o n t e x t " * 2
    batch1 = [Request(ctx + "alpha?", max_new_tokens=6)]
    batch2 = [Request(ctx + "beta!!", max_new_tokens=6)]
    we = whole_engine()
    ref = we.generate(batch1)[0] + we.generate(batch2)[0]
    eng = budget_engine()
    out = eng.generate(batch1)[0]
    out += eng.generate(batch2)[0]
    assert out == ref
    assert eng.prefix_hits >= 1
    assert eng.prefix_tokens_shared > 0
    eng.assert_quiescent()


# ---------------------------------------------------------------------------
# budget policy
# ---------------------------------------------------------------------------

def test_pick_chunk_interactive_first_and_starvation_guard():
    eng = budget_engine()
    rid_b = eng.admit(Request(LONG, max_new_tokens=4, slo="batch"))
    rid_i = eng.admit(Request(LONG + "??", max_new_tokens=4,
                              slo="interactive"))
    # interactive wins despite the batch request's earlier admission
    ci, cs, clen = eng._pick_chunk(0)
    assert cs.req_id == rid_i
    assert clen == eng.prefill_chunk
    # budget partially consumed by decode rows: chunk gets the leftover
    ci, cs, clen = eng._pick_chunk(eng.step_token_budget - 5)
    assert cs.req_id == rid_i and clen == 5
    # budget fully consumed: the interactive head still gets a small
    # chunk (starvation guard — first tokens are the interactive SLO)
    ci, cs, clen = eng._pick_chunk(eng.step_token_budget)
    assert cs.req_id == rid_i and 0 < clen <= 8
    # ...but a batch head does not
    eng.preempt(rid_i)
    assert eng._pick_chunk(eng.step_token_budget) is None
    ci, cs, clen = eng._pick_chunk(0)
    assert cs.req_id == rid_b and clen == eng.prefill_chunk
    eng.preempt(rid_b)
    eng.assert_quiescent()


def test_admission_is_async_and_first_token_deferred():
    eng = budget_engine(max_batch=2)
    p0 = eng.prefill_tokens
    rid = eng.admit(Request(LONG, max_new_tokens=4))
    assert eng.prefill_tokens == p0        # no model compute at admit
    assert eng.prefilling_slots == 1
    assert eng.harvest() == []             # nothing to emit mid-prefill
    steps = 0
    while eng.prefilling_slots and steps < 50:
        eng.step()
        steps += 1
    s = next(s for s in eng._slots if s is not None and s.req_id == rid)
    assert s.pending is not None           # first token sampled...
    assert s.first_token_at is not None    # ...and stamped, at final chunk
    assert eng.prefill_tokens - p0 == s.prompt_tokens
    eng.preempt(rid)
    eng.assert_quiescent()


def test_append_walk_counters_rise_by_one_chunk():
    """Each chunk step adds the append kernel's walk for that chunk
    (:func:`append_walk`) to the engine's two counters: host arithmetic
    the CPU path counts as the chip path would."""
    eng = budget_engine(max_batch=2)
    rid = eng.admit(Request(LONG, max_new_tokens=4))
    s = next(s for s in eng._slots if s is not None and s.req_id == rid)
    for _ in range(2):
        lo = s.prefill_done
        clen = min(eng.prefill_chunk, s.prompt_tokens - lo)
        want = append_walk(lo, lo + clen, eng._chunk_pad, eng.pages_per_slot,
                           eng.page_size)
        w0, g0 = eng.append_blocks_walked, eng.append_blocks_grid
        eng.dispatch()
        eng.collect()
        assert (eng.append_blocks_walked - w0,
                eng.append_blocks_grid - g0) == want
        assert 0 < want[0] <= want[1]
    eng.preempt(rid)
    eng.assert_quiescent()


# ---------------------------------------------------------------------------
# preempt / crash of half-prefilled residents
# ---------------------------------------------------------------------------

def test_preempt_half_prefilled_resident_resumes_identical():
    clock = VirtualClock()
    eng = budget_engine(max_batch=1, clock=clock)
    batch = Request(LONG, max_new_tokens=6, slo="batch")
    ref, _ = eng.generate([Request(LONG, max_new_tokens=6)])
    eng.invalidate_prefix_cache()

    sched = TierScheduler({"edge": eng}, clock=clock)
    sched.submit(batch, "edge", now=clock.now())
    sched.pump(now=clock.now())            # batch parks mid-prefill
    assert eng.prefilling_slots == 1
    inter = Request("hi there", max_new_tokens=4, slo="interactive")
    sched.submit(inter, "edge", now=clock.now())
    done = {id(c.request): c for c in drain_virtual(sched, clock)}
    assert sched.counters["preempted"] >= 1
    assert sched.counters["resumed"] >= 1
    assert done[id(batch)].preemptions >= 1
    assert done[id(batch)].text == ref[0]  # half-prefilled resume, greedy
    eng.assert_quiescent()


def test_crash_requeues_half_prefilled_residents():
    clock = VirtualClock()
    eng = budget_engine(max_batch=2, clock=clock)
    reqs = [Request(p, max_new_tokens=6) for p in (LONG, LONG + "more")]
    ref, _ = eng.generate([Request(p, max_new_tokens=6)
                           for p in (LONG, LONG + "more")])
    eng.invalidate_prefix_cache()

    sched = TierScheduler({"edge": eng}, clock=clock, requeue_lost=True)
    for r in reqs:
        sched.submit(r, "edge", now=clock.now())
    sched.pump(now=clock.now())
    assert eng.prefilling_slots >= 1       # half-prefilled work is resident
    lost = eng.crash()                     # every device-side byte is gone
    assert len(lost) == 2
    eng.restart()
    done = {id(c.request): c for c in drain_virtual(sched, clock)}
    assert sched.counters["requeued_lost"] == 2
    assert [done[id(r)].text for r in reqs] == ref
    eng.assert_quiescent()


# ---------------------------------------------------------------------------
# stats, TTFT, trace discipline
# ---------------------------------------------------------------------------

def test_scheduler_surfaces_fused_stats_and_ttft():
    clock = VirtualClock()
    eng = budget_engine(clock=clock)
    sched = TierScheduler({"edge": eng}, clock=clock)
    reqs = [Request(p, max_new_tokens=6,
                    slo="interactive" if i % 2 else "batch")
            for i, p in enumerate(MIX)]
    for r in reqs:
        sched.submit(r, "edge", now=clock.now())
    done = drain_virtual(sched, clock)
    assert len(done) == len(reqs)
    for c in done:
        # 0.0 is legal for a single-chunk prompt admitted and finished
        # within one pump (the virtual clock only moves between pumps)
        assert c.ttft_s >= 0.0
        assert c.ttft_s <= c.queue_wait_s + c.time_in_engine_s + 1e-9
    long_ttfts = [c.ttft_s for c in done
                  if c.request.prompt.startswith(LONG)]
    assert long_ttfts and all(t > 0.0 for t in long_ttfts)
    #      ^ multi-chunk prompts span pumps, so their first token is late
    e = sched.debug_state_dict()["tiers"]["edge"]["engines"][0]
    for key in ("prefilling", "mixed_steps", "prefill_chunks",
                "budget_utilization"):
        assert key in e
    assert e["mixed_steps"] == eng.mixed_steps > 0
    eng.assert_quiescent()


def test_single_chunk_bucket_and_zero_retraces():
    eng = budget_engine()
    # budget mode prefills ONLY fixed-size chunks: warmup collapses to the
    # single chunk bucket no matter how long the prompts are
    eng.warmup(len(eng.tok.encode(p)) for p in MIX)
    assert list(eng.pad_buckets) == [eng._chunk_pad]
    t0 = dict(eng.trace_counts)
    assert t0["prefill"] == 1 and t0["fused"] == 1
    eng.generate([Request(p, max_new_tokens=8) for p in MIX])
    for kind in ("prefill", "fused", "decode"):
        assert eng.trace_counts[kind] == t0[kind], kind
    eng.assert_quiescent()


def test_budget_mode_guards():
    with pytest.raises(EngineError):
        budget_engine(kv_layout="contiguous")
    with pytest.raises(EngineError):
        budget_engine(step_token_budget=0)
