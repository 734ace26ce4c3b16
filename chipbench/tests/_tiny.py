"""Tiny cells for the CPU tests: the edge configuration's layout at toy
widths, under the shared naive-RAG mix at toy lengths or the LLM-only mix
at its own lengths. Never a benchmark cell: the benchmark's cells run the
published widths on the chip."""
from __future__ import annotations

import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _read(path: str) -> dict:
    return json.loads((ROOT / path).read_text())


def _cell(name: str, config: dict, mix: dict) -> dict:
    """A cell as ``chipbench.run.load_cell`` gives it, with the metrics
    ``BENCHMARK.json`` gives the cell ``name``."""
    bench = _read("BENCHMARK.json")

    def mine(ms):
        return [copy.deepcopy(m) for m in ms
                if "workloads" not in m or name in m["workloads"]]

    return {"name": name, "chips": 1, "config": config, "traffic": mix,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def tiny_cell(max_logit_gap: float = 1.0) -> dict:
    cfg = _read("chipbench/configs/qwen2-0.5b-edge.json")
    cfg["model"].update(num_hidden_layers=2, hidden_size=64,
                        num_attention_heads=4, num_key_value_heads=2,
                        intermediate_size=128, vocab_size=512)
    cfg["engine"].update(max_seq=256, max_batch=4, step_token_budget=64,
                         prefill_chunk=64, num_pages=64)
    cfg["check"] = {"max_logit_gap": max_logit_gap}
    mix = _read("chipbench/traffic/naive-rag-shared.json")
    mix.update(rate_per_s=8.0, drain_s=30)
    mix["prompt"][0].update(pool=8, length={"dist": "const", "value": 96})
    mix["prompt"][1]["length"] = {"dist": "uniform", "min": 4, "max": 8}
    mix["output"] = {"dist": "normal", "mean": 4, "std": 2, "min": 1,
                     "max": 8}
    mix["lead_in"] = {"warm_contexts": 2, "seconds": 0.3}
    mix["check"] = {"requests": 4}
    return _cell("edge-rag-shared", cfg, mix)


def tiny_llm_only_cell() -> dict:
    """``edge-llm-only`` at toy widths: the mix's own prompt and output
    lengths, saturated (a burst of more requests than rows, then arrivals
    faster than four rows serve them)."""
    mix = _read("chipbench/traffic/llm-only.json")
    mix.update(rate_per_s=40.0, lead_in={"burst": 6, "fill": True})
    return _cell("edge-llm-only", tiny_cell()["config"], mix)
