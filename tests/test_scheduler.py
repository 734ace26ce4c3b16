"""Serving scheduler + continuous-batching engine behaviour."""
import pytest

from repro.core.clock import VirtualClock
from repro.serving.engine import Request, make_edge_engine
from repro.serving.scheduler import TierScheduler


@pytest.fixture(scope="module")
def engine():
    return make_edge_engine(max_seq=96, max_batch=3, seed=0)


@pytest.fixture()
def sched(engine):
    assert not engine.has_active
    return TierScheduler({"edge": engine})


# ---------------------------------------------------------------------------
# Admission / slot reuse
# ---------------------------------------------------------------------------

def test_slot_reuse_streams_past_max_batch(engine, sched):
    """11 requests stream through 3 slots; occupancy never exceeds the pool
    and every request completes exactly once."""
    for i in range(11):
        sched.submit(Request(f"query number {i}", max_new_tokens=2 + i % 5),
                     "edge")
    assert sched.pending() == 11
    peak, done = 0, []
    while sched.pending() or sched.in_flight():
        done.extend(sched.pump())
        peak = max(peak, engine.active_slots)
    assert peak == engine.max_batch == 3
    assert engine.free_slots == 3
    assert len(done) == 11
    assert sorted(c.request.prompt for c in done) == \
        sorted(f"query number {i}" for i in range(11))


def test_admission_is_incremental(engine, sched):
    """A freed slot is refilled mid-stream: with budgets (1, 8) and a queued
    third request, the third is admitted long before the 8-token request
    finishes."""
    sched.submit(Request("aaaa", max_new_tokens=1), "edge")
    sched.submit(Request("bbbb", max_new_tokens=8), "edge")
    sched.submit(Request("cccc", max_new_tokens=8), "edge")
    sched.submit(Request("dddd", max_new_tokens=1), "edge")
    done = sched.pump()               # admits first 3 (pool of 3), one step
    assert sched.pending() == 1
    while sched.in_flight() or sched.pending():
        done.extend(sched.pump())
    # the 1-token requests finish first; "dddd" was admitted into a freed
    # slot while bbbb/cccc were still decoding
    assert [c.request.prompt for c in done][:2] == ["aaaa", "dddd"]
    assert len(done) == 4


# ---------------------------------------------------------------------------
# Deadline ordering across tiers
# ---------------------------------------------------------------------------

def test_deadline_priority_within_tier(engine, sched):
    sched.submit(Request("late", max_new_tokens=2), "edge", deadline_s=10.0)
    sched.submit(Request("urgent", max_new_tokens=2), "edge", deadline_s=1.0)
    done = sched.drain()
    assert done[0].request.prompt == "urgent"


def test_deadline_ordering_across_tiers():
    """Each tier serves its own deadline heap; completions carry the tier."""
    edge = make_edge_engine(max_seq=64, max_batch=1, seed=0)
    cloud = make_edge_engine(max_seq=64, max_batch=1, seed=1)
    sched = TierScheduler({"edge": edge, "cloud": cloud})
    for tier in ("edge", "cloud"):
        sched.submit(Request(f"{tier}-late", max_new_tokens=2), tier,
                     deadline_s=50.0)
        sched.submit(Request(f"{tier}-urgent", max_new_tokens=2), tier,
                     deadline_s=1.0)
    done = sched.drain()
    assert len(done) == 4
    for tier in ("edge", "cloud"):
        order = [c.request.prompt for c in done if c.tier == tier]
        assert order == [f"{tier}-urgent", f"{tier}-late"]


def test_unknown_tier_rejected(sched):
    with pytest.raises(KeyError):
        sched.submit(Request("x"), "nonexistent")


# ---------------------------------------------------------------------------
# Per-request completion accounting
# ---------------------------------------------------------------------------

def test_completion_accounting(engine, sched):
    reqs = [Request("what is rag", max_new_tokens=3),
            Request("hello there serving engine", max_new_tokens=5)]
    for r in reqs:
        sched.submit(r, "edge")
    done = sched.drain()
    assert len(done) == 2
    by_prompt = {c.request.prompt: c for c in done}
    for r in reqs:
        c = by_prompt[r.prompt]
        assert c.tier == "edge"
        assert c.queue_wait_s >= 0.0
        assert c.time_in_engine_s > 0.0
        assert c.prompt_tokens == len(engine.tok.encode(r.prompt))
        assert 0 < c.new_tokens <= r.max_new_tokens
        assert len(engine.tok.encode(c.text, bos=False)) == c.new_tokens
        assert len(c.token_ids) == c.new_tokens
        assert engine.tok.decode(c.token_ids) == c.text


# ---------------------------------------------------------------------------
# Logical-clock timing (the old wall/logical clock-mixing bug: submit took a
# logical now= but pump always subtracted it from time.perf_counter)
# ---------------------------------------------------------------------------

def test_queue_wait_exact_under_injected_clock(engine):
    """Queue waits are EXACT logical-time differences when a virtual clock
    drives the scheduler — no wall-clock leakage anywhere."""
    clock = VirtualClock()
    sched = TierScheduler({"edge": engine}, clock=clock)
    sched.submit(Request("hello", max_new_tokens=2), "edge")  # enqueue @ 0.0
    clock.advance(3.5)
    done = list(sched.pump(now=clock.now()))       # admitted @ exactly 3.5
    rounds = 1
    while not done:
        clock.advance(0.25)
        done = sched.pump(now=clock.now())
        rounds += 1
    c = done[0]
    assert c.queue_wait_s == 3.5                   # exact, not approximate
    assert c.time_in_engine_s == 0.25 * (rounds - 1)


def test_pump_now_overrides_per_round(engine):
    """submit(now=...) + pump(now=...) pin every timing to caller time even
    while the scheduler's own clock default would disagree."""
    sched = TierScheduler({"edge": engine})        # default wall clock
    sched.submit(Request("hi", max_new_tokens=1), "edge", now=100.0)
    t, done = 107.0, []
    while not done:
        done = sched.pump(now=t)
        t += 1.0
    assert done[0].queue_wait_s == 7.0


def test_scheduler_clock_is_used_without_now(engine):
    """With an injected clock, calls WITHOUT now= read that clock — never
    the wall clock."""
    clock = VirtualClock(start=50.0)
    sched = TierScheduler({"edge": engine}, clock=clock)
    sched.submit(Request("yo", max_new_tokens=1), "edge")
    clock.advance(2.0)
    done = []
    while not done:
        done = sched.pump()
    assert done[0].queue_wait_s == 2.0


# ---------------------------------------------------------------------------
# Engine pools behind one tier
# ---------------------------------------------------------------------------

def test_tier_pool_spreads_load():
    """A tier backed by a pool of engines admits the queue head into ANY
    member with capacity: two max_batch=1 engines serve two requests in the
    same round."""
    pool = [make_edge_engine(max_seq=64, max_batch=1, seed=i)
            for i in range(2)]
    sched = TierScheduler({"edge": pool})
    for i in range(4):
        sched.submit(Request(f"req {i}", max_new_tokens=2), "edge")
    first = sched.pump()
    assert sched.in_flight("edge") + len(first) == 2   # both members busy
    done = list(first) + sched.drain()
    assert len(done) == 4
    assert {c.engine_index for c in done} == {0, 1}
    assert all(c.tier == "edge" for c in done)


# ---------------------------------------------------------------------------
# Per-slot decode budgets (the old static-batch clamp bug)
# ---------------------------------------------------------------------------

def test_budgets_are_per_slot(engine):
    """A short prompt sharing a batch with a near-max_seq prompt keeps its
    full max_new_tokens; only the long prompt is clamped by max_seq. (The
    seed engine clamped every request by the LONGEST prompt in the batch.)"""
    long_req = Request("a" * 60, max_new_tokens=40)    # 61 toks -> budget 35
    short_req = Request("Hello", max_new_tokens=40)    # 6 toks -> budget 40
    texts, stats = engine.generate([long_req, short_req])
    n_long = len(engine.tok.encode(texts[0], bos=False))
    n_short = len(engine.tok.encode(texts[1], bos=False))
    assert n_long <= 96 - 61 == 35
    # greedy on the seed-0 random model never emits EOS for these prompts,
    # so the short request must run to its own full budget
    assert n_short == 40


# ---------------------------------------------------------------------------
# Continuous path == static path (greedy, token-identical)
# ---------------------------------------------------------------------------

def test_continuous_matches_static_greedy(engine):
    reqs = [Request("What is the capital of France?", max_new_tokens=6),
            Request("Hello", max_new_tokens=9),
            Request("a" * 60, max_new_tokens=40),
            Request("tiered rag serving", max_new_tokens=4),
            Request("edge node", max_new_tokens=12),
            Request("q" * 30, max_new_tokens=7),
            Request("adaptive knowledge update", max_new_tokens=11)]
    continuous, _ = engine.generate(reqs)
    static = []
    for i in range(0, len(reqs), engine.max_batch):
        ts, _ = engine.generate_static(reqs[i:i + engine.max_batch])
        static.extend(ts)
    assert continuous == static
    # and the continuous path is itself deterministic
    again, _ = engine.generate(reqs)
    assert again == continuous
