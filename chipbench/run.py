#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the repository root. The cell is an entry of ``workloads`` in
``BENCHMARK.json``; its configuration file, its traffic mix
(``chipbench/traffic/<traffic>.json``) and the reader of each of its metrics
(``chipbench/metrics/<metric>.py``, or ``<prefix>.py`` for a metric named
``<prefix>.<suffix>``) and its configuration's architecture
(``chipbench/archs/<class>.py``, the class the configuration's
``model.architectures`` names) are found by name, so a new cell, metric or
model is new files and new entries only.

A run: refuse anything but a TPU with published peaks (``peaks.py``) and
enough chips; turn on JAX's persistent compilation cache in the checkout;
make the weights on the device from the seed; build one ``ServingEngine``
(paged KV, prefix cache, token-budget chunked prefill) under one
``TierScheduler``; warm the cell's programs; run the mix's lead-in; then
drive the open-loop arrivals for ``--seconds`` (every request timed from its
due time). Paced mixes then wait up to ``drain_s`` for the window's
requests. With ``--trace 1`` the window is traced and the per-layer metrics
are read; otherwise the end-to-end ones. Then the program's state is freed
and a sample of served requests is held to the float32 reference
(``check.py``). The last line of standard output is one JSON object.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

OUT_DIR = ROOT / "chipbench_out"
CACHE_DIR = ROOT / ".jax_cache"
TIER = "serve"


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown device, bad cell)."""


# ---------------------------------------------------------------------------
# Cells
# ---------------------------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> dict:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "chipbench" / "traffic"
                        / f"{w['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    return {"name": name, "chips": w["chips"], "config": config,
            "traffic": traffic, "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def reader(metric: str):
    """The ``read(run, name)`` function of a metric, from its own file."""
    d = HERE / "metrics"
    if str(d) not in sys.path:
        sys.path.insert(0, str(d))      # readers share metrics/_common.py
    for stem in (metric, metric.split(".")[0]):
        path = d / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"chipbench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise BenchError(f"no reader for metric {metric!r} under {d}")


def arch(config: dict):
    """The architecture module of a configuration, from its own file:
    ``chipbench/archs/<class>.py`` for the published class name in
    ``config["model"]["architectures"][0]``. It gives

    - ``dims(model)``: the sizes this chip holds, from the ``model`` group;
    - ``program_config(config, dims)``: the program's ``ModelConfig``;
    - ``program_params(seed, dims, init)``: every weight, in the program's
      layout and served type, from one jitted call on the device;
    - ``final_states(seed, model, init, rows, quant=None)``: the plain
      float32 reference's final normed states of each token row and its top
      weights, with the head under ``"head"``; ``quant="int8"`` gives the
      control;
    - the yardstick: ``layer_matmul_params``, ``kv_bytes_per_token``,
      ``decode_attention``, ``append_attention``, ``model_step``."""
    name = config["model"]["architectures"][0]
    path = HERE / "archs" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no architecture module for {name!r}: {path} "
                         f"does not exist")
    spec = importlib.util.spec_from_file_location(f"chipbench_arch_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------

def require_chips(chips: int):
    import jax
    from chipbench.peaks import peaks_for
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devs[0].platform if devs else None!r}")
    if len(devs) < chips:
        raise BenchError(f"cell needs {chips} chips, JAX has {len(devs)}")
    return devs[:chips], peaks_for(devs[0].device_kind)


def enable_compile_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

def model_config(config: dict):
    """The program's ``ModelConfig`` and the sizes this chip holds, from the
    configuration's architecture module."""
    a = arch(config)
    dims = a.dims(config["model"])
    return a.program_config(config, dims), dims


def build_engine(config: dict, cfg, params):
    from repro.serving.engine import ServingEngine
    e = config["engine"]
    return ServingEngine(
        cfg, max_seq=e["max_seq"], max_batch=e["max_batch"], seed=0,
        params=params, kv_layout="paged", page_size=e["page_size"],
        num_pages=e["num_pages"], prefix_cache=True,
        step_token_budget=e["step_token_budget"],
        prefill_chunk=e["prefill_chunk"])


@dataclass
class Rec:
    """One request, as the benchmark sees it from outside the program."""
    due: float                   # absolute host-clock due time
    prompt: List[int]
    max_new: int
    window: bool
    admitted: Optional[float] = None
    shared: int = 0              # prompt tokens the prefix cache served
    done: Optional[float] = None
    first: Optional[float] = None
    queue_wait: Optional[float] = None
    tokens: List[int] = field(default_factory=list)
    request: object = None       # held so that its id() stays unique
    rid: Optional[int] = None    # the engine's id for it


def produced(eng) -> int:
    """Tokens the engine has sampled: one per decode row dispatched, plus
    one first token per admission whose prefill finished."""
    return ((eng.budget_tokens_used - eng.prefill_tokens)
            + eng.prefix_hits + eng.prefix_misses - eng.prefilling_slots)


def full(eng, sched) -> bool:
    """Every row decoding (none mid-prefill), or nothing left to admit."""
    return eng.prefilling_slots == 0 and (
        eng.active_slots == eng.max_batch or not sched.pending())


def counters(eng) -> Dict[str, float]:
    return {
        "steps": eng.budget_steps,
        "decode_rows": eng.budget_tokens_used - eng.prefill_tokens,
        "prefill_tokens": eng.prefill_tokens,
        "prefill_chunks": eng.prefill_chunks,
        "prefix_tokens_shared": eng.prefix_tokens_shared,
        "produced": produced(eng),
    }


class Driver:
    """Open-loop arrivals into one scheduler over one engine."""

    def __init__(self, sched, eng, spans: bool):
        self.sched, self.eng = sched, eng
        self.recs: Dict[int, Rec] = {}
        self.lateness: List[float] = []
        self.spans = spans
        orig_admit = eng.admit

        def admit(request):
            before = eng.prefix_tokens_shared
            with self.span("pump.admit"):
                rid = orig_admit(request)
            rec = self.recs.get(id(request))
            if rec is not None:
                rec.admitted = time.perf_counter()
                rec.shared = eng.prefix_tokens_shared - before
                rec.rid = rid
            return rid

        eng.admit = admit
        if spans:
            for name in ("harvest", "dispatch", "collect"):
                orig = getattr(eng, name)
                setattr(eng, name, self._spanned(f"pump.{name}", orig))

    def _spanned(self, label, fn):
        def f(*a, **k):
            with self.span(label):
                return fn(*a, **k)
        return f

    def span(self, name):
        if not self.spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def submit(self, prompt, max_new, due, window):
        from repro.serving.engine import Request
        req = Request(prompt="", max_new_tokens=int(max_new),
                      prompt_ids=list(prompt))
        rec = Rec(due, list(prompt), int(max_new), window, request=req)
        self.recs[id(req)] = rec
        self.lateness.append(time.perf_counter() - due)
        self.sched.submit(req, TIER, deadline_s=due, now=due)
        return req

    def pump(self):
        with self.span("pump"):
            comps = self.sched.pump()
        for c in comps:
            rec = self.recs[id(c.request)]
            rec.queue_wait = c.queue_wait_s
            rec.first = rec.due + c.ttft_s
            rec.done = rec.due + c.queue_wait_s + c.time_in_engine_s
            rec.tokens = list(c.token_ids)
        return comps

    def busy(self) -> bool:
        return bool(self.sched.pending() or self.sched.in_flight())

    def run(self, arrivals, t_zero, t_stop, window, until=None):
        """Submit ``arrivals`` (due offsets from ``t_zero``) as they fall
        due and pump, until ``t_stop``, or earlier once ``until()``."""
        i = 0
        while True:
            now = time.perf_counter()
            with self.span("generator"):
                while (i < len(arrivals)
                       and t_zero + arrivals[i].due <= min(now, t_stop)):
                    a = arrivals[i]
                    self.submit(a.prompt, a.max_new, t_zero + a.due, window)
                    i += 1
            if now >= t_stop or (until is not None and until()):
                # whatever fell due before the stop is due in this phase
                while i < len(arrivals) and t_zero + arrivals[i].due < t_stop:
                    a = arrivals[i]
                    self.submit(a.prompt, a.max_new, t_zero + a.due, window)
                    i += 1
                return
            if self.busy():
                self.pump()
            elif i < len(arrivals):
                time.sleep(max(0.0, min(t_zero + arrivals[i].due, t_stop)
                               - time.perf_counter()))
            elif until is not None:
                return
            else:
                time.sleep(max(0.0, t_stop - time.perf_counter()))


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclass
class RunData:
    """What the metric readers read."""
    cell: dict
    dims: dict
    arch: object                 # the configuration's module (``arch``)
    peaks: dict
    window_s: float
    delta: Dict[str, float]
    recs: List[Rec]              # requests due in the window
    all_recs: List[Rec]
    window_t: tuple              # (start, end) on the host clock
    give_up: float = 0.0         # when the run stopped waiting
    trace: object = None         # trace.Reduction, traced runs only


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (the value with q of the sample at or
    below it)."""
    v = sorted(values)
    if not v:
        return float("nan")
    return v[max(0, math.ceil(q * len(v)) - 1)]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(cell: dict, seed: int, seconds: float, trace: bool, *,
        device=None, peaks: Optional[dict] = None,
        t_process: float = T_PROCESS, out_dir: Path = OUT_DIR,
        hooks: Optional[dict] = None) -> dict:
    """Run ``cell`` once and return the result object. ``hooks`` lets a
    test break the timed path (``after_engine(eng)``) and the control read
    the compared requests (``after_check(picks, gaps)``)."""
    import jax
    from chipbench import check
    from chipbench.generator import Generator
    from repro.serving.engine import EngineError
    from repro.serving.scheduler import TierScheduler

    config, mix = cell["config"], cell["traffic"]
    cfg, dims = model_config(config)
    model_arch = arch(config)
    device = device if device is not None else jax.devices()[0]
    hooks = hooks or {}

    # ---- set-up: weights, engine, warm programs, lead-in ---------------
    params = model_arch.program_params(seed, dims, config["init"])
    jax.block_until_ready(params)
    eng = build_engine(config, cfg, params)
    eng.warmup()
    if "after_engine" in hooks:
        hooks["after_engine"](eng)
    sched = TierScheduler({TIER: eng})
    drv = Driver(sched, eng, spans=trace)
    sched_plan = Generator(mix, seed, dims["vocab"]).schedule(seconds)
    t_lead = time.perf_counter()
    for ctx in sched_plan.warm:
        drv.submit(ctx, 1, t_lead, window=False)
    drv.run([], t_lead, t_lead + 600, False, until=lambda: not drv.busy())
    lead = mix.get("lead_in", {})
    lead_s = float(lead.get("seconds", 0.0))
    t_zero = time.perf_counter() + lead_s
    if lead.get("fill"):
        # saturated mixes: every row decoding before the window opens
        drv.run(sched_plan.lead_in, t_zero, t_zero + 600, False,
                until=lambda: full(eng, sched))
    else:
        drv.run(sched_plan.lead_in, t_zero, t_zero, False)

    # ---- the measured window ----------------------------------------------
    trace_dir = out_dir / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(trace_dir))
    c0 = counters(eng)
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    drift = t0 - t_zero
    with drv.span("window"):
        drv.run(sched_plan.window, t0, t0 + seconds, True)
    t1 = time.perf_counter()
    c1 = counters(eng)
    if trace:
        jax.profiler.stop_trace()
    window_s = t1 - t0
    log(f"window: {window_s:.3f} s, lead-in overran by {drift:.3f} s, "
        f"{len(sched_plan.window)} arrivals, {c1['steps'] - c0['steps']} "
        f"engine steps")

    # ---- paced mixes: wait for the window's requests --------------------
    win = [r for r in drv.recs.values() if r.window]
    drain_s = float(mix.get("drain_s", 0.0))
    if drain_s > 0:
        drv.run([], t1, t1 + drain_s, False,
                until=lambda: all(r.done is not None for r in win))
    late = sorted(drv.lateness[-len(win):]) if win else [0.0]
    log(f"generator lateness over the window's arrivals: median "
        f"{percentile(late, 0.5):.6f} s, p99 {percentile(late, 0.99):.6f} s,"
        f" max {late[-1]:.6f} s")
    stats = device.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    data = RunData(cell=cell, dims=dims, arch=model_arch,
                   peaks=peaks or {}, window_s=window_s,
                   delta={k: c1[k] - c0[k] for k in c0}, recs=win,
                   all_recs=list(drv.recs.values()), window_t=(t0, t1))
    data.give_up = time.perf_counter()
    unfinished = sum(r.done is None for r in win)

    if win:
        ttfts = [(r.first if r.first is not None else data.give_up) - r.due
                 for r in win]
        log("ttft over the window's requests (s): " + json.dumps(
            {f"p{int(q * 100)}": percentile(ttfts, q)
             for q in (0.5, 0.75, 0.9)}))

    # ---- metrics ------------------------------------------------------
    result_metrics: Dict[str, dict] = {}
    device_info = {"platform": device.platform, "kind": device.device_kind,
                   "count": cell["chips"], "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        from chipbench import trace as tr
        events = tr.events_from_xplane(tr.find_xplane(str(trace_dir)))
        tr.save_json(tr.excerpt(events), str(out_dir / "trace_excerpt.json"))
        data.trace = tr.Reduction(events)
        device_info["busy_s"] = data.trace.busy_s()
        device_info["window_s"] = data.trace.window_s
        breakdown = {"device_ops": data.trace.top_ops(10),
                     "idle_gaps": data.trace.idle_gaps(10)}
        log("idle by host span: " + json.dumps(data.trace.idle_by_span()))
        metrics = cell["per_layer"]
    else:
        metrics = cell["end_to_end"]
    for m in metrics:
        if m["name"] == "setup_s":
            val = setup_s
        else:
            val = reader(m["name"])(data, m["name"])
        if val is not None:
            result_metrics[m["name"]] = {"value": val, "unit": m["unit"]}

    # ---- correctness: free the program, then the reference -----------
    if drain_s <= 0:
        # saturated mixes never drain: read what the residents were served
        # so far (``preempt`` hands back the tokens they emitted)
        for r in drv.recs.values():
            if r.rid is not None and r.done is None:
                try:
                    r.tokens = list(eng.preempt(r.rid).emitted_ids)
                except EngineError:        # no longer resident
                    pass
    done = [r for r in drv.recs.values() if r.tokens and (
        r.done is None or r.done >= t0)]
    chk = mix["check"]
    picks = check.select(done, seed, chk["requests"],
                         config["engine"]["prefill_chunk"])
    del sched, drv, eng, params
    gc.collect()
    t_ref = time.perf_counter()
    gaps = (check.served_gaps(model_arch, seed, config["model"],
                              config["init"], picks) if picks else [])
    ref_s = time.perf_counter() - t_ref
    if "after_check" in hooks:
        hooks["after_check"](picks, gaps)
    readings = check.readings(gaps, picks)
    if drain_s <= 0:
        unfinished = 0       # a saturated queue is never expected to drain
    correct, compared = check.judge(
        readings, config["check"], unfinished if drain_s > 0 else None)
    log(f"reference: {len(picks)} requests, {readings['served_tokens']} "
        f"served tokens, {ref_s:.3f} s; readings " + json.dumps(readings))
    for k, v in compared.items():
        log(f"compared {k}: {v['value']} (limit {v['limit']})")

    result = {"correct": bool(correct), "attempted": len(win),
              "failed": unfinished, "metrics": result_metrics,
              "device": device_info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        devices, peaks = require_chips(cell["chips"])
    except BenchError as e:
        log(f"chipbench: {e}")
        return 2
    enable_compile_cache()
    result = run(cell, args.seed, args.seconds, bool(args.trace),
                 device=devices[0], peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
