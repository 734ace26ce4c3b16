"""A tiny cell for the CPU tests: the edge configuration's layout at toy
widths, and the shared naive-RAG mix at toy lengths. Never a benchmark
cell: the benchmark's cells run the published widths on the chip."""
from __future__ import annotations

import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "edge-rag-shared"


def tiny_cell(max_logit_gap: float = 1.0) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads(
        (ROOT / "chipbench/configs/qwen2-0.5b-edge.json").read_text())
    cfg["model"].update(num_hidden_layers=2, hidden_size=64,
                        num_attention_heads=4, num_key_value_heads=2,
                        intermediate_size=128, vocab_size=512)
    cfg["engine"].update(max_seq=256, max_batch=4, step_token_budget=64,
                         prefill_chunk=64, num_pages=64)
    cfg["check"] = {"max_logit_gap": max_logit_gap}
    mix = json.loads(
        (ROOT / "chipbench/traffic/naive-rag-shared.json").read_text())
    mix.update(rate_per_s=8.0, drain_s=30)
    mix["prompt"][0].update(pool=8, length={"dist": "const", "value": 96})
    mix["prompt"][1]["length"] = {"dist": "uniform", "min": 4, "max": 8}
    mix["output"] = {"dist": "normal", "mean": 4, "std": 2, "min": 1,
                     "max": 8}
    mix["lead_in"] = {"warm_contexts": 2, "seconds": 0.3}
    mix["check"] = {"requests": 4}

    def mine(ms):
        return [copy.deepcopy(m) for m in ms
                if "workloads" not in m or CELL in m["workloads"]]

    return {"name": CELL, "chips": 1, "config": cfg, "traffic": mix,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}
