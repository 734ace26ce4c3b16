#!/usr/bin/env python3
"""Readings that set a cell's limits: sound runs of the program on many
seeds, the control on some of them, and planted faults. Not part of a
benchmark run.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,3,... [--control-seeds 1,2,3] [--fault <name>]

Runs the cell's timed path in this one process once per seed, at the
cell's own sizes and load for ``--seconds`` (a short window that still
finishes the mix's longest requests), and prints one JSON line per seed:
the run's own verdict and readings (``check.readings``). For a control
seed it also puts the int8-weight reference in the program's place and
prints the verdict of the harness's own comparison (``check.judge`` with
the configuration's limits) on the tokens the control ranks first. With
``--fault`` every run has that fault (``chipbench/faults.py``) planted in
the timed path. Exits 1 if a control or a faulty run comes out correct.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from chipbench import check, run as R  # noqa: E402
from chipbench.faults import FAULTS  # noqa: E402


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args()
    cell = R.load_cell(args.workload)
    devices, peaks = R.require_chips(cell["chips"])
    R.enable_compile_cache()
    control = {int(s) for s in args.control_seeds.split(",") if s}
    config = cell["config"]
    escaped = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        seen = {}

        def after_check(picks, gaps, seed=seed):
            seen["picks"] = len(picks)
            seen["hits"] = sum(r.shared > 0 for r in picks)
            seen["readings"] = check.readings(gaps, picks)
            seen["request_means"] = [float(g.mean()) for g in gaps if g.size]
            if seed in control and picks:
                _, ctrl = check.control_gaps(
                    R.arch(config), seed, config["model"], config["init"],
                    picks)
                read = check.readings(ctrl, picks)
                ok, _ = check.judge(read, config["check"])
                seen["control_readings"] = read
                seen["control_correct"] = ok
                seen["control_request_means"] = [float(g.mean())
                                                 for g in ctrl if g.size]

        hooks = {"after_check": after_check}
        if args.fault:
            hooks["after_engine"] = FAULTS[args.fault]
        res = R.run(cell, seed, args.seconds, False, device=devices[0],
                    peaks=peaks, hooks=hooks)
        escaped += bool(seen.get("control_correct"))
        escaped += bool(args.fault and res["correct"])
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": res["correct"], **seen,
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}), flush=True)
    return 1 if escaped else 0


if __name__ == "__main__":
    sys.exit(main())
