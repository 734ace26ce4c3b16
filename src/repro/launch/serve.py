"""Serving launcher: continuous-batching generation on a (reduced) arch, or
the full tiered EACO cluster demo (examples/serve_cluster.py drives the
latter).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
      --prompts "hello world" "what is rag"

The arch runs as its reduced smoke variant unless ``--published`` asks for
its published widths (qwen2-0.5b: 24 layers, d 896, vocab 151,936 — a
chip-sized run). The persistent compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` at the
repository root.

The engine streams any number of prompts through a fixed pool of
``--max-batch`` slots backed by a block-granular paged KV-cache (page
arena + per-slot page tables) wherever the arch supports it, with a
prefix cache on top: prompts sharing a page-aligned prefix (RAG context
reuse at an edge node) map the same physical pages and only their unique
suffix is prefilled. Pass ``--no-prefix-cache`` to disable the sharing,
``--kv-layout contiguous`` for the worst-case per-slot lanes,
``--page-size`` / ``--num-pages`` to shape the page pool, and ``--static``
to run the blocking static-batch baseline (one padded batch at a time).

The continuous path runs through the SLO-aware :class:`TierScheduler`:
``--slo-class`` tags every prompt (interactive sorts ahead of batch and
may preempt resident batch work when slots run out), ``--no-preemption``
disables resident reclaim, and ``--overload-watermark`` sheds batch-class
submissions (typed, reported per prompt) once queued + resident work
reaches that multiple of slot capacity.

Crash-tolerance knobs (the health layer, all optional):
``--breaker-threshold N`` arms a per-engine circuit breaker — N
consecutive losses (crash reaps, stuck-resident timeouts) quarantine the
engine until a timed half-open probe; ``--hedge-ms M`` spawns a second
"cloud" engine and fires a backup submission for any interactive prompt
still waiting after M milliseconds (first completion wins, the loser is
cancelled); ``--chaos`` hard-crashes the edge engine mid-run — all
device state is lost, the engine restarts cold, and the scheduler
re-enqueues the dead engine's residents (banked tokens resume via the
prefix cache), demonstrating that no prompt is lost.
"""
from __future__ import annotations

import argparse
import time

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import Request, ServingEngine
from repro.serving.scheduler import TierScheduler


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--published", action="store_true",
                    help="serve the arch at its published widths instead "
                         "of the reduced smoke variant")
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--static", action="store_true",
                    help="static-batch baseline instead of continuous")
    ap.add_argument("--kv-layout", default="auto",
                    choices=["auto", "paged", "contiguous"],
                    help="KV-cache layout (auto: paged where supported)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged layout)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-pool size (default: worst case, "
                         "max_batch * max_seq / page_size)")
    ap.add_argument("--prefix-cache", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="share KV pages across common prompt prefixes "
                         "(paged layout only; --no-prefix-cache disables)")
    ap.add_argument("--step-token-budget", type=int, default=None,
                    help="fused chunked-prefill + decode: per-step token "
                         "budget mixing every resident decode row with one "
                         "bounded prefill chunk (paged layout only; "
                         "default: whole-suffix admission)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="max prompt tokens prefilled per fused step "
                         "(with --step-token-budget)")
    ap.add_argument("--slo-class", default="interactive",
                    choices=["interactive", "batch"],
                    help="SLO class tagged on every prompt (interactive "
                         "sorts ahead of batch and may preempt it)")
    ap.add_argument("--preemption", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="let the scheduler reclaim strictly-lower-"
                         "priority residents when slots run out")
    ap.add_argument("--overload-watermark", type=float, default=None,
                    help="shed batch-class submissions (typed) once "
                         "(queued + resident) / slot capacity reaches "
                         "this value")
    ap.add_argument("--breaker-threshold", type=int, default=None,
                    help="per-engine circuit breaker: quarantine an "
                         "engine after this many consecutive losses "
                         "(crash reaps / stuck-resident timeouts) until "
                         "a timed half-open probe succeeds")
    ap.add_argument("--hedge-ms", type=float, default=None,
                    help="fire a backup submission on a second 'cloud' "
                         "engine for interactive prompts still waiting "
                         "after this many ms; first completion wins and "
                         "the loser is cancelled")
    ap.add_argument("--chaos", action="store_true",
                    help="hard-crash the edge engine mid-run (all device "
                         "state lost) and restart it cold; the scheduler "
                         "re-enqueues the lost residents — demonstrates "
                         "zero-loss crash recovery")
    ap.add_argument("--prompts", nargs="+",
                    default=["What is the capital of France?"])
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=not args.published)
    if cfg.vocab < 300:
        raise SystemExit("arch vocab too small for byte tokenizer")
    if args.static and (args.chaos or args.hedge_ms is not None
                        or args.breaker_threshold is not None):
        raise SystemExit("--chaos/--hedge-ms/--breaker-threshold need the "
                         "scheduler: drop --static")
    if args.static and args.step_token_budget is not None:
        raise SystemExit("--step-token-budget is a continuous-serving "
                         "feature: drop --static")
    eng = ServingEngine(cfg, max_seq=args.max_seq, max_batch=args.max_batch,
                        kv_layout=args.kv_layout, page_size=args.page_size,
                        num_pages=args.num_pages,
                        prefix_cache=args.prefix_cache,
                        step_token_budget=args.step_token_budget,
                        prefill_chunk=args.prefill_chunk)
    kv = (f"paged KV: {eng.num_pages} x {eng.page_size}-token pages, "
          f"prefix cache {'on' if eng.prefix_cache_enabled else 'off'}"
          if eng.kv_layout == "paged" else "contiguous KV lanes")
    width = "published" if args.published else "reduced"
    print(f"serving {cfg.arch_id} ({width}, {eng.model.n_params():,} params, "
          f"{kv}; random weights — output is noise; the engine is real)")
    reqs = [Request(p, max_new_tokens=args.max_new,
                    temperature=args.temperature, slo=args.slo_class)
            for p in args.prompts]
    if args.static:
        from repro.serving.engine import GenStats
        texts, chunks = [], []
        for i in range(0, len(reqs), eng.max_batch):
            ts, st = eng.generate_static(reqs[i:i + eng.max_batch])
            texts.extend(ts)
            chunks.append(st)
        stats = GenStats(sum(s.prompt_tokens for s in chunks),
                         sum(s.new_tokens for s in chunks),
                         sum(s.prefill_s for s in chunks),
                         sum(s.decode_s for s in chunks),
                         prefill_traces=sum(s.prefill_traces for s in chunks),
                         prefix_hits=sum(s.prefix_hits for s in chunks),
                         prefix_misses=sum(s.prefix_misses for s in chunks),
                         prefix_tokens_shared=sum(s.prefix_tokens_shared
                                                  for s in chunks))
        for p, t in zip(args.prompts, texts):
            print(f"> {p!r}\n  -> {t!r}")
        print(f"[static] prefill {stats.prefill_s*1e3:.0f}ms, "
              f"{stats.new_tokens} tokens at {stats.tokens_per_s:.1f} "
              f"tok/s; traces: {eng.trace_counts}")
    else:
        pools = {"edge": eng}
        hedge_s = None
        if args.hedge_ms is not None:
            # hedging needs somewhere to hedge TO: a second engine
            # standing in for the cloud tier (same reduced arch)
            pools["cloud"] = ServingEngine(
                cfg, max_seq=args.max_seq, max_batch=args.max_batch,
                seed=1, kv_layout=args.kv_layout,
                page_size=args.page_size, num_pages=args.num_pages,
                prefix_cache=args.prefix_cache,
                step_token_budget=args.step_token_budget,
                prefill_chunk=args.prefill_chunk)
            hedge_s = args.hedge_ms / 1e3
        sched = TierScheduler(pools, preempt=args.preemption,
                              overload_watermark=args.overload_watermark,
                              breaker_threshold=args.breaker_threshold,
                              hedge_s=hedge_s, hedge_from="edge",
                              hedge_to="cloud")
        t0 = time.perf_counter()
        for r in reqs:
            sched.submit(r, "edge")
        comps = {}
        if args.chaos:
            # let work land, then kill the engine under it: every
            # device-side byte is gone; the reap + requeue path must
            # re-serve the lost residents after the cold restart
            for _ in range(3):
                comps.update({id(c.request): c for c in sched.pump()})
            lost = eng.crash()
            eng.restart()
            print(f"[chaos] edge engine crashed with {len(lost)} "
                  f"resident(s); restarted cold (generation "
                  f"{eng.engine_generation})")
        comps.update({id(c.request): c for c in sched.drain()})
        wall = time.perf_counter() - t0
        sheds = {id(s.request): s for s in sched.pop_sheds()}
        for p, r in zip(args.prompts, reqs):
            if id(r) in comps:
                c = comps[id(r)]
                tag = (f"  [preempted x{c.preemptions}, resumed]"
                       if c.preemptions else "")
                if c.hedged:
                    tag += f"  [hedged -> {c.tier}]"
                print(f"> {p!r}\n  -> {c.text!r}{tag}")
            else:
                s = sheds[id(r)]
                print(f"> {p!r}\n  -> SHED({s.reason}) after "
                      f"{s.queue_wait_s:.2f}s queued")
        tokens = sum(c.new_tokens for c in comps.values())
        sc = sched.counters
        print(f"[continuous] {len(comps)}/{len(reqs)} served, {tokens} "
              f"tokens at {tokens / max(wall, 1e-9):.1f} tok/s; "
              f"preempted {sc['preempted']}, resumed {sc['resumed']}, "
              f"shed {sched.shed_total}; traces: {eng.trace_counts}")
        if eng.budget_mode:
            ttfts = sorted(c.ttft_s for c in comps.values())
            p95 = ttfts[min(len(ttfts) - 1,
                            int(0.95 * len(ttfts)))] if ttfts else 0.0
            print(f"[fused-step] budget {eng.step_token_budget} tok/step, "
                  f"chunk {eng.prefill_chunk}: {eng.mixed_steps} mixed "
                  f"steps, {eng.prefill_chunks} chunks, budget utilization "
                  f"{eng.budget_utilization:.0%}, p95 TTFT "
                  f"{p95 * 1e3:.0f}ms")
        if args.chaos or args.breaker_threshold is not None or hedge_s:
            from repro.serving.health import breaker_states
            br = (breaker_states(sched.breakers, sched.clock())
                  if sched.breakers else {})
            print(f"[health] crashes {eng.crashes}, lost-to-crash "
                  f"{sc['engine_lost'] + sc['requeued_lost']}, requeued "
                  f"{sc['requeued_lost']}, hedged {sc['hedged']}, "
                  f"cancelled {sc['cancelled']}"
                  + (f"; breakers {br}" if br else ""))
    if eng.kv_layout == "paged" and eng.prefix_cache_enabled:
        print(f"[prefix-cache] {eng.prefix_hits} hits / "
              f"{eng.prefix_misses} misses, "
              f"{eng.prefix_tokens_shared} prompt tokens served from "
              f"shared pages")


if __name__ == "__main__":
    main()
