"""The harness on the CPU at toy widths, with the timed path broken.

Each test skips the harness's look for a chip and drives the rest of a
run (weights from the seed, engine, warm-up, lead-in, open-loop window,
drain, reference comparison) through ``chipbench.run.run``. A sound run is
correct; a run whose engine alters the tokens it samples, whose steps hand
back the KV arena unwritten, or whose prefix-cache hits map another
prompt's pages, is not."""
import pytest

from chipbench import run as R
from chipbench.faults import alter_tokens, state_unchanged, wrong_prefix_pages
from chipbench.tests._tiny import tiny_cell, tiny_llm_only_cell

SEED = 2 ** 31 + 2024


@pytest.mark.parametrize("fault,correct", [
    (None, True),
    (alter_tokens, False),
    (state_unchanged, False),
    (wrong_prefix_pages, False),
])
def test_correct_sees_the_fault(fault, correct, tmp_path):
    hooks = {"after_engine": fault} if fault else {}
    res = R.run(tiny_cell(), SEED, 1.5, False, out_dir=tmp_path,
                hooks=hooks)
    assert res["correct"] is correct, res["compared"]
    assert list(res)[-1] == "compared"
    assert res["attempted"] == 12 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p50_s", "tpot_p50_ms", "setup_s"}


def test_traced_run_reports_per_layer_metrics(tmp_path):
    res = R.run(tiny_cell(), SEED + 1, 1.5, True, out_dir=tmp_path)
    assert res["correct"] is True
    # the CPU has no device trace: the trace readers find nothing
    assert "paged_decode_roofline.tpot" not in res["metrics"]
    assert {"queue_wait_p50_s", "prefix_hit_token_share",
            "engine_step_ms.tpot"} <= set(res["metrics"])
    assert res["device"]["busy_s"] == 0.0


def test_saturated_mix_compares_resident_tokens(tmp_path):
    cell = tiny_cell()
    mix = cell["traffic"]
    mix.update(rate_per_s=40.0, drain_s=0)
    mix["lead_in"] = {"burst": 6, "fill": True}
    mix["output"] = {"dist": "const", "value": 200}
    cell["config"]["engine"]["max_seq"] = 512
    cell["config"]["engine"]["num_pages"] = 128
    res = R.run(cell, SEED + 2, 1.0, False, out_dir=tmp_path)
    # nothing finishes: the residents' tokens so far are compared
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0
    assert "unfinished_requests" not in res["compared"]
    assert res["compared"]["served_tokens_compared"]["value"] > 0


@pytest.mark.parametrize("fault,correct", [
    (None, True),
    (alter_tokens, False),
    (state_unchanged, False),
])
def test_llm_only_sees_the_fault(fault, correct, tmp_path):
    """The saturated LLM-only mix: no request drains, the residents' tokens
    so far are compared, and a fault in the timed path still shows."""
    hooks = {"after_engine": fault} if fault else {}
    res = R.run(tiny_llm_only_cell(), SEED + 3, 1.0, False,
                out_dir=tmp_path, hooks=hooks)
    assert res["correct"] is correct, res["compared"]
    assert res["attempted"] == 40 and res["failed"] == 0
    assert res["compared"]["served_tokens_compared"]["value"] >= 100
    assert "setup_s" in res["metrics"]
