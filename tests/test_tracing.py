"""Host spans of the serving path (``repro.core.tracing``): nothing is
recorded while no profiler runs; under the profiler, spans carry their
attributes and parents, in the record and in the trace; and a budget-mode
engine under ``TierScheduler`` spans every step the same way."""
import glob
import os

import jax
import pytest

from repro.core import tracing
from repro.core.tracing import span
from repro.serving.engine import Request, make_edge_engine
from repro.serving.scheduler import TierScheduler

LONG = "retrieval augmented generation at the edge with adaptive update "


@pytest.fixture(autouse=True)
def empty_record():
    tracing.clear()
    yield
    tracing.clear()


def _host_events(trace_dir):
    """{name: [stats dict, ...]} of the host plane's events."""
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(dict(e.stats))
    return out


def test_no_records_while_no_profiler_runs():
    with span("outer", a=1) as s:
        s.set(b=2)
        with span("inner"):
            pass
    assert tracing.records() == []
    assert span("x") is span("y")          # one shared null context


def test_spans_under_the_profiler_carry_attributes_and_parents(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with span("outer", a=1) as s:
            with span("inner", kind="fused"):
                pass
            with span("inner2"):
                pass
            s.set(late=7)
        with span("second"):
            pass
    recs = tracing.records()
    assert [r[0] for r in recs] == ["outer", "inner", "inner2", "second"]
    assert [r[3] for r in recs] == [-1, 0, 0, -1]
    assert recs[0][4] == {"a": 1, "late": 7}
    assert recs[1][4] == {"kind": "fused"}
    for name, start, end, parent, _ in recs:
        assert start <= end
        if parent >= 0:
            assert recs[parent][1] <= start and end <= recs[parent][2]
    host = _host_events(str(tmp_path))
    assert host["outer"] == [{"a": 1, "late": 7}]
    assert host["inner"] == [{"kind": "fused"}]


def test_the_record_is_bounded(tmp_path, monkeypatch):
    monkeypatch.setattr(tracing, "MAX_RECORDS", 2)
    with jax.profiler.trace(str(tmp_path)):
        with span("a"):
            with span("b"):
                with span("c"):          # past the bound: profiler only
                    pass
        with span("d"):
            pass
    assert [r[0] for r in tracing.records()] == ["a", "b"]
    assert "c" in _host_events(str(tmp_path))


def _children(recs, i):
    return [r[0] for r in recs if r[3] == i]


def test_engine_spans_every_step_under_the_scheduler(tmp_path):
    eng = make_edge_engine(seed=0, max_seq=128, max_batch=4,
                           step_token_budget=12, prefill_chunk=16)
    eng.warmup()
    sched = TierScheduler({"edge": eng})
    prompts = [LONG, "short q", LONG + "and a unique tail"]
    for p in prompts:
        sched.submit(Request(p, max_new_tokens=3), "edge")
    steps0 = eng.budget_steps
    with jax.profiler.trace(str(tmp_path)):
        done = []
        while sched.pending() or sched.in_flight():
            done += sched.pump()
    assert len(done) == len(prompts)
    recs = tracing.records()
    by = {}
    for i, r in enumerate(recs):
        by.setdefault(r[0], []).append(i)

    # every span sits under one scheduling round
    for i, r in enumerate(recs):
        while recs[i][3] >= 0:
            i = recs[i][3]
        assert recs[i][0] == "sched.pump"

    admits = {recs[i][4]["rid"]: recs[i] for i in by["engine.admit"]}
    assert len(admits) == len(prompts)
    for a in admits.values():
        assert a[4]["prompt_tokens"] > a[4]["prefix_tokens"] >= 0

    # a dispatch with nothing to launch (its rows all just finished) only
    # prepares; every other one launches a step that a collect waits on
    idle = [i for i in by["engine.dispatch"] if not recs[i][4]]
    assert all(_children(recs, i) == ["engine.prepare"] for i in idle)
    dispatches = [i for i in by["engine.dispatch"] if i not in idle]
    collects = by["engine.collect"]
    assert len(dispatches) == len(collects) == eng.budget_steps - steps0
    first, final = {}, {}
    for d, c in zip(dispatches, collects):
        att = recs[d][4]
        assert _children(recs, d) == ["engine.prepare", "engine.launch",
                                      "engine.sample"]
        prep = next(i for i in range(d, len(recs)) if recs[i][3] == d)
        assert _children(recs, prep) == ["engine.prepare.upload"]
        assert _children(recs, c) == ["engine.collect.wait",
                                      "engine.collect.apply"]
        assert recs[c][4]["step"] == att["step"]
        assert recs[d][2] <= recs[c][1]
        assert att["kind"] in ("decode", "fused", "prefill")
        if att["kind"] != "decode":
            rid = att["chunk_rid"]
            assert rid in admits and att["chunk_tokens"] > 0
            assert 0 < att["append_blocks_walked"] <= att["append_blocks_grid"]
            if att["first_chunk"]:
                first[rid] = recs[d]
            if att["final_chunk"]:
                final[rid] = recs[d]
    assert set(first) == set(final) == set(admits)
    chunks = [recs[d][4] for d in dispatches if recs[d][4]["kind"] != "decode"]
    assert sum(a["append_blocks_walked"] for a in chunks) == \
        eng.append_blocks_walked
    for rid, d in first.items():
        assert admits[rid][2] <= d[1]         # admitted before it launched
    landed = [recs[i][4]["first_token_rid"]
              for i in by["engine.collect.apply"]
              if "first_token_rid" in recs[i][4]]
    assert sorted(landed) == sorted(admits)
    # the engine was warm: no step traced anew
    assert [recs[i][4]["compiled"] for i in by["engine.launch"]] == \
        [0] * len(dispatches)
    assert all(recs[i][4]["finished"] >= 0 for i in by["engine.harvest"])

    host = _host_events(str(tmp_path))
    for name in ("sched.pump", "engine.admit", "engine.harvest",
                 "engine.dispatch", "engine.prepare", "engine.launch",
                 "engine.sample", "engine.collect", "engine.collect.wait",
                 "engine.collect.apply"):
        assert name in host, name
    assert {"rid", "prompt_tokens", "prefix_tokens"} <= set(
        host["engine.admit"][0])
    assert all("compiled" in s for s in host["engine.launch"])
