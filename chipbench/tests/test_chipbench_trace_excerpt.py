"""The trace reduction on an excerpt of a real chip trace: the first
operations of a traced ``edge-rag-shared`` window on one TPU v5e, with the
benchmark's host spans (``chipbench/testdata``)."""
import json
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parents[1] / "testdata"


@pytest.fixture(scope="module")
def red():
    events = json.loads((DATA / "trace_excerpt_edge.json").read_text())
    return trace.Reduction(events)


def test_excerpt_is_a_tpu_window(red):
    assert red.used_planes == ["/device:TPU:0"]
    assert 0 < red.busy_s() <= red.window_s


def test_both_paged_kernels_are_found(red):
    dec = red.kernel_s(r"paged_decode_attention")
    app = red.kernel_s(r"paged_append_attention")
    assert dec > 0 and app > 0
    assert dec + app <= red.busy_s()


def test_self_times_add_up_to_busy(red):
    # operations nest (the layer loop holds its body) but never overlap
    # partially, so self times sum to the union of the intervals
    total = sum(red.self_times("/device:TPU:0").values())
    assert total == pytest.approx(red.busy_s(), rel=1e-6)
    assert red.top_ops(10)[0][1] >= red.top_ops(10)[-1][1]


def test_idle_gaps_are_labelled_with_benchmark_spans(red):
    gaps = red.idle_gaps(10)
    assert gaps
    assert {g[0] for g in gaps} <= set(trace.SPANS) | {"outside_spans"}
    assert sum(g[1] for g in red.idle_gaps(10 ** 6)) == pytest.approx(
        red.window_s - red.busy_s(), rel=1e-6)
