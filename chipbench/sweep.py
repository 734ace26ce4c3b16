#!/usr/bin/env python3
"""Find a cell's knee once: the highest offered rate without a growing
backlog. Not part of a benchmark run.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1,2,4

One process builds the cell's engine once, runs its warm contexts (and, for
a saturated mix, its lead-in until every row decodes), then for each rate
(in the order given) offers the cell's mix at that rate for ``--seconds``
and prints one JSON line: arrivals, completions, backlog (queued +
resident) at the start and end of the window, output tokens/s, and TTFT /
TPOT p90 over the window's finished requests. Between rates a paced mix
waits until the engine is idle; a saturated one goes straight on, so its
backlog carries over and its change over each window shows whether the
rate is above what the engine completes.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from chipbench import run as R  # noqa: E402


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    import jax
    from chipbench.generator import Generator
    from repro.serving.scheduler import TierScheduler

    cell = R.load_cell(args.workload)
    R.require_chips(cell["chips"])
    R.enable_compile_cache()
    config, mix = cell["config"], cell["traffic"]
    cfg, dims = R.model_config(config)
    params = R.arch(config).program_params(args.seed, dims, config["init"])
    eng = R.build_engine(config, cfg, params)
    eng.warmup()
    drv = R.Driver(TierScheduler({R.TIER: eng}), eng, spans=False)
    plan = Generator(mix, args.seed, dims["vocab"]).schedule(1.0)
    t = time.perf_counter()
    for ctx in plan.warm:
        drv.submit(ctx, 1, t, window=False)
    drv.run([], t, t + 600, False, until=lambda: not drv.busy())
    saturated = float(mix.get("drain_s", 0.0)) <= 0
    if mix.get("lead_in", {}).get("fill"):
        t = time.perf_counter()
        drv.run(plan.lead_in, t, t + 600, False,
                until=lambda: R.full(eng, drv.sched))
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        sched = Generator(m, args.seed + k + 1, dims["vocab"]).schedule(
            args.seconds)
        for r in drv.recs.values():
            r.window = False
        b0 = drv.sched.pending() + drv.sched.in_flight()
        c0 = R.counters(eng)
        t0 = time.perf_counter()
        drv.run(sched.window, t0, t0 + args.seconds, True)
        t1 = time.perf_counter()
        c1 = R.counters(eng)
        b1 = drv.sched.pending() + drv.sched.in_flight()
        win = [r for r in drv.recs.values() if r.window]
        done = [r for r in win if r.done is not None]
        completed = sum(r.done is not None and t0 <= r.done < t1
                        for r in drv.recs.values())
        ttft = [r.first - r.due for r in done]
        tpot = [(r.done - r.first) / (len(r.tokens) - 1) for r in done
                if len(r.tokens) > 1]
        print(json.dumps({
            "rate": rate, "seconds": t1 - t0, "arrivals": len(win),
            "finished_in_window": len(done), "completed": completed,
            "backlog_start": b0,
            "backlog_end": b1, "steps": c1["steps"] - c0["steps"],
            "output_tokens_per_s": (c1["produced"] - c0["produced"])
            / (t1 - t0),
            "ttft_p90_s": R.percentile(ttft, 0.9) if ttft else None,
            "tpot_p90_ms": 1e3 * R.percentile(tpot, 0.9) if tpot else None,
        }), flush=True)
        if not saturated:
            t2 = time.perf_counter()
            drv.run([], t2, t2 + 300, False, until=lambda: not drv.busy())
    jax.block_until_ready(params)
    return 0


if __name__ == "__main__":
    sys.exit(main())
