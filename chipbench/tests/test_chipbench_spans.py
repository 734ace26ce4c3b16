"""The readers of the program's own spans on hand-made records and device
events: idle inside and outside ``sched.pump``, a request whose first
prefill chunk waits two steps, and nothing to read without a trace, without
records, or in a program that records no spans; and a traced tiny run on
the CPU, where the spans are read and the device trace is not."""
import sys
from types import SimpleNamespace

import pytest

from chipbench import run as R
from chipbench import trace
from chipbench.run import reader
from chipbench.tests._tiny import tiny_cell
from repro.core import tracing

MS = 1e6                          # ns
T0 = 10.0                         # host-clock second the window opened


def ns(t):
    """Host-clock seconds to ``perf_counter_ns`` nanoseconds."""
    return t * 1e9


def _trace():
    # window 0-1000 ms; busy 0-100, 200-600, 700-1000: idle 100-200 and
    # 600-700 ms
    return trace.Reduction({
        "device": {"/device:TPU:0": [["fusion.1", 0.0, 100 * MS],
                                    ["fusion.2", 200 * MS, 400 * MS],
                                    ["fusion.3", 700 * MS, 300 * MS]]},
        "host": [["window", 0.0, 1000 * MS]]})


def _rec(rid, first=None):
    return SimpleNamespace(rid=rid, first=first, due=T0)


def _run(recs=(), traced=True, steps=3):
    return SimpleNamespace(
        trace=_trace() if traced else None, window_t=(T0, T0 + 1.0),
        give_up=T0 + 2.0, delta={"steps": steps}, recs=list(recs))


def _span(name, a, b, **attrs):
    return [name, ns(a), ns(b), -1, attrs]


@pytest.fixture()
def records(monkeypatch):
    held = []
    monkeypatch.setattr(tracing, "records", lambda: held)
    return held


def test_step_idle_counts_idle_inside_the_pump(records):
    records += [
        _span("sched.pump", T0 - 0.5, T0 - 0.01),   # before the window
        _span("sched.pump", T0 + 0.05, T0 + 0.65),  # 100-200 and 600-650
        _span("engine.collect.wait", T0 + 0.1, T0 + 0.2),
        _span("generator", T0 + 0.65, T0 + 0.7)]    # 650-700: outside
    got = reader("step_idle_ms.tpot")(_run(), "step_idle_ms.tpot")
    assert got == pytest.approx(150.0 / 3)
    assert reader("step_idle_ms.tput")(_run(steps=0), "x") is None


def test_prefill_wait_counts_the_steps_before_the_first_chunk(records):
    records += [
        _span("engine.admit", T0 + 0.08, T0 + 0.09, rid=1),
        _span("engine.admit", T0 + 0.095, T0 + 0.1, rid=0),
        # rid 1's two chunks run first; rid 0 launches in the third step
        _span("engine.dispatch", T0 + 0.1, T0 + 0.11, chunk_rid=1,
              first_chunk=1),
        _span("engine.dispatch", T0 + 0.3, T0 + 0.31, chunk_rid=1,
              first_chunk=0),
        _span("engine.dispatch", T0 + 0.5, T0 + 0.51, chunk_rid=0,
              first_chunk=1),
        _span("engine.admit", T0 + 0.8, T0 + 0.9, rid=2)]
    run = _run([_rec(0, first=T0 + 0.9), _rec(1, first=T0 + 0.5),
                _rec(2), _rec(None)])
    # waits: 0.4 (two steps), 0.01, and rid 2's 1.1 to the give-up; the
    # request never admitted (rid None) has no prefill wait
    got = reader("prefill_wait_p50_s")(run, "prefill_wait_p50_s")
    assert got == pytest.approx(0.4)
    # served, but its first chunk launched after the spans stopped: at
    # least until the window's end
    run.recs = [_rec(2, first=T0 + 1.5)]
    assert reader("prefill_wait_p50_s")(run, "p") == pytest.approx(0.1)


@pytest.mark.parametrize("name", ["step_idle_ms.tpot", "prefill_wait_p50_s"])
def test_span_readers_need_a_trace_and_records(name, records, monkeypatch):
    records += [_span("sched.pump", T0, T0 + 1.0),
                _span("engine.admit", T0, T0 + 0.1, rid=0)]
    recs = [_rec(0)]
    assert reader(name)(_run(recs, traced=False), name) is None
    assert reader(name)(_run(recs), name) is not None
    records.clear()
    assert reader(name)(_run(recs), name) is None
    # a program without repro.core.tracing
    import repro.core
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert reader(name)(_run(recs), name) is None


def test_traced_cpu_run_reads_the_spans(tmp_path):
    res = R.run(tiny_cell(), 2 ** 31 + 2025, 1.5, True, out_dir=tmp_path)
    assert res["correct"] is True
    # the CPU has no device trace: the idle per step finds nothing
    assert "step_idle_ms.tpot" not in res["metrics"]
    # the program's spans time the host: the prefill wait is read
    assert res["metrics"]["prefill_wait_p50_s"]["value"] >= 0.0
