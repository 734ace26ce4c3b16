"""The yardstick's arithmetic: operation and byte counts against hand
counts, the trace reduction on a hand-made trace, and the readers'
percentiles and token accounting."""
from types import SimpleNamespace

import pytest

from chipbench import flops, trace
from chipbench.run import percentile, reader

DIMS = {"layers": 2, "d": 64, "heads": 4, "kv_heads": 2, "head_dim": 8,
        "ffn": 96, "vocab": 1000, "tied": True}


def test_decode_attention_hand_count():
    # 3 rows, 100 live tokens in all, 2 layers, 4 heads of 8
    f, b = flops.decode_attention(DIMS, rows=3, live_tokens=100)
    assert f == 4 * 4 * 8 * 100 * 2                 # q.k and p.v, 2 each
    kv = 100 * (2 * 2 * 8 * 2) * 2                  # K+V, 2 heads, bf16
    qo = 3 * 4 * 8 * 2 * 2 * 2                      # q in, out, per layer
    assert b == kv + qo


def test_append_attention_hand_count():
    # 10 computed tokens at positions 5..14 attend 6..15 keys: 105 in all
    attended = sum(p + 1 for p in range(5, 15))
    f, b = flops.append_attention(DIMS, computed=10, attended=attended,
                                  context_tokens=15)
    assert attended == 105
    assert f == 4 * 4 * 8 * 105 * 2
    assert b == 15 * (2 * 2 * 8 * 2) * 2 + 10 * 4 * 8 * 2 * 2 * 2


def test_model_step_hand_count():
    per_layer = 64 * (4 + 2 * 2) * 8 + 4 * 8 * 64 + 3 * 64 * 96
    assert flops.layer_matmul_params(DIMS) == per_layer
    got = flops.model_step(DIMS, tokens=7, logit_rows=2, attention_flops=11)
    assert got == 2 * per_layer * 2 * 7 + 2 * 64 * 1000 * 2 + 11


def test_qwen2_05b_counts():
    d = {"layers": 24, "d": 896, "heads": 14, "kv_heads": 2, "head_dim": 64,
         "ffn": 4864, "vocab": 151936, "tied": True}
    # 12 KiB of K and V per token; ~0.36 B matmul weights in the layers
    assert flops.kv_bytes_per_token(d) == 12 * 1024
    assert flops.layer_matmul_params(d) * 24 == 357_826_560


def _events():
    # window 0..100 ns; a loop 10-40 holding two operations, one at 60-70
    return {
        "device": {"/device:TPU:0": [
            ["while.2", 10.0, 30.0],
            ["fusion.1", 10.0, 15.0],
            ["paged_decode_attention_pallas.3", 25.0, 15.0],
            ["fusion.1", 60.0, 10.0],
            ["fusion.9", 150.0, 10.0]]},          # after the window
        "host": [["window", 0.0, 100.0], ["pump", 5.0, 80.0],
                 ["pump.collect", 40.0, 30.0], ["generator", 85.0, 10.0]],
    }


def test_reduction_busy_kernel_and_gaps():
    red = trace.Reduction(_events())
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s() == pytest.approx(40e-9)     # 10-40 and 60-70
    assert red.kernel_s(r"paged_decode_attention") == pytest.approx(15e-9)
    gaps = red.idle_gaps()
    # gaps: 0-10 (pump), 40-60 (pump.collect), 70-100 (generator at 85)
    assert gaps[0] == ["generator", pytest.approx(30e-9)]
    assert gaps[1] == ["pump.collect", pytest.approx(20e-9)]
    assert gaps[2] == ["pump", pytest.approx(10e-9)]
    # self time: the loop's own time excludes its body
    assert red.top_ops(3) == [["fusion.1", pytest.approx(25e-9)],
                              ["paged_decode_attention_pallas.3",
                               pytest.approx(15e-9)],
                              ["while.2", pytest.approx(0.0)]]


def test_excerpt_keeps_the_window_shape():
    ex = trace.excerpt(_events(), max_device=3)
    red = trace.Reduction(ex)
    assert len(ex["device"]["/device:TPU:0"]) == 3
    assert red.busy_s() == pytest.approx(30e-9)


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert percentile(vals, 0.9) == 90
    assert percentile([5.0], 0.9) == 5.0
    assert percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], 0.9) == 10


def _run(**kw):
    rec = lambda **r: SimpleNamespace(**{
        "due": 0.0, "first": None, "done": None, "tokens": [],
        "max_new": 4, "prompt": [1] * 10, "admitted": 0.0, "shared": 0,
        "queue_wait": None, **r})
    base = dict(window_s=2.0, give_up=10.0, window_t=(0.0, 2.0),
                delta={"produced": 50, "steps": 10, "decode_rows": 40,
                       "prefill_tokens": 20, "prefix_tokens_shared": 60,
                       "prefill_chunks": 2},
                cell={"config": {"engine": {"max_batch": 8}}},
                recs=[rec(first=0.5, done=1.0, tokens=[1, 2, 3]),
                      rec(first=1.5, done=1.9, tokens=[1]),
                      rec()],                        # never served
                dims=DIMS, peaks={}, trace=None)
    base.update(kw)
    base["all_recs"] = base["recs"]
    return SimpleNamespace(**base)


def test_readers_count_misses_and_tokens():
    run = _run()
    # ttft: 0.5, 1.5 and the unserved one at give-up (10): median 1.5
    assert reader("ttft_p50_s")(run, "ttft_p50_s") == 1.5
    # tpot: (1.0 - 0.5) / 2 = 0.25 s; unserved: (10 - 0) / 3 from due
    assert reader("tpot_p50_ms")(run, "tpot_p50_ms") == pytest.approx(250)
    run.recs[0].first = None
    # a first token never stamped counts from the due time: (1.0 - 0) / 2
    assert reader("tpot_p50_ms")(run, "t") == pytest.approx(500)
    assert reader("output_tokens_per_s")(run, "x") == 25.0
    assert reader("batch_occupancy.tput")(run, "batch_occupancy.tput") == 50.0
    assert reader("prefix_hit_token_share")(run, "p") == 75.0
    assert reader("engine_step_ms.tpot")(run, "e") == 200.0


def test_trace_readers_return_nothing_without_a_trace():
    run = _run()
    for name in ("paged_decode_roofline.tpot", "paged_append_roofline.ttft",
                 "device_idle_share.tput", "step_mfu.tput"):
        assert reader(name)(run, name) is None


def _picks(shared):
    return [SimpleNamespace(shared=s) for s in shared]


def test_readings_keep_a_hit_path_fault_undiluted():
    import numpy as np
    from chipbench import check
    # eleven sound misses of 27 tokens, one prefix hit 2 logits off
    gaps = [np.full(27, 0.05)] * 11 + [np.full(27, 2.0)]
    read = check.readings(gaps, _picks([0] * 11 + [3584]))
    assert read["served_tokens"] == 12 * 27
    assert read["mean_logit_gap"] == pytest.approx((11 * 0.05 + 2.0) / 12)
    assert read["worst_path_mean_gap"] == pytest.approx(2.0)
    assert read["worst_request_mean_gap"] == pytest.approx(2.0)
    assert read["max_logit_gap"] == pytest.approx(2.0)


@pytest.mark.parametrize("value,unfinished,correct", [
    (0.1, 0, True), (0.3, 0, False), (0.1, 1, False), (0.1, None, True)])
def test_judge_holds_each_number_to_its_limit(value, unfinished, correct):
    from chipbench import check
    read = {"worst_path_mean_gap": value, "served_tokens": 5}
    ok, compared = check.judge(read, {"worst_path_mean_gap": 0.2},
                               unfinished)
    assert ok is correct
    assert compared["worst_path_mean_gap"] == {"value": value,
                                               "limit": 0.2}
    assert ("unfinished_requests" in compared) is (unfinished is not None)


def test_judge_wants_a_served_token():
    from chipbench import check
    read = check.readings([], [])
    assert read["served_tokens"] == 0
    assert check.judge(read, {"worst_path_mean_gap": 0.2})[0] is False
