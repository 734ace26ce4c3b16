#!/usr/bin/env python3
"""Smoke run of the serving path on one TPU chip, at qwen2-0.5b's published
width (24 layers, d 896, 14/2 heads, vocab 151,936; random weights from
``--seed``).

Run from the repository root on a machine with a TPU:

    python3 chip_smoke.py [--seed 0]

Every phase runs in this one process (a chip belongs to one process at a
time), and any failure raises, so the exit code is non-zero:

1. device      — refuse to run unless JAX's first device is a TPU.
2. kernels     — the paged decode and paged append Pallas kernels, compiled,
                 against their jnp references at qwen2-0.5b serving shapes.
3. engine      — one TierScheduler over a full-width qwen2-0.5b engine (paged
                 KV, prefix cache), once with whole-suffix admission and once
                 with a per-step token budget: every request completes, the
                 request counts balance, the page arena audits clean, decode
                 and fused steps compile once, and each request's first-token
                 and first-decode-step logits match ``Model.prefill`` (the
                 contiguous jnp path).
4. kernel use  — the compiled decode, fused and prefill steps contain the
                 Pallas kernels (``tpu_custom_call``), so no reference has
                 silently replaced one.
5. closed loop — a few steps of ``EACOCluster(backend="engines")`` with its
                 default pools: gate, retrieval, scheduler and engines.

The timings printed are one run's smoke timings, not benchmark metrics. The
last line of standard output is a JSON object naming the device, printed
only when every phase passed.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import importlib.metadata
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Paged attention kernels vs their jnp references. Both read the same bf16
# arenas and accumulate in f32; the kernel rounds its output to bf16 (half
# an ulp is 2^-9 relative, <= 0.008 at the |out| <= 4 a softmax average of
# N(0, 1) values reaches) and sums in another order. Sound kernels read
# <= 0.0039 on a v5e. A mask that admits one key too many reads 4.4 (decode,
# ragged batch) and 0.60 (append, 53-token prefix) in interpret mode; over
# the 3,589-token prefix the same append fault reads only 0.0088.
KERNEL_TOL = 2e-2
# Logits, paged engine vs Model.prefill, as max |diff| over the standard
# deviation of the reference logits. Both paths keep bf16 weights,
# activations and KV, but round in different places: 0.08-0.11 at 24 layers
# on a v5e, 0.06-0.08 at 12 layers on the CPU. Keys written one slot off
# in the page read 0.66-2.46 at 12 layers on the CPU (every prompt, prefill
# scatter) and 0.87-0.89 (one-page prompt, decode scatter).
LOGIT_TOL = 0.25

PAGE_SIZE = 16
MAX_SEQ = 4096
MAX_BATCH = 8
CONTEXT_TOKENS = 3584        # shared retrieved context: 224 pages; with the
#                              question ~3.6k tokens, the paper's naive-RAG
#                              prompt (cost_model.TABLE1_TOKENS)
STEP_TOKEN_BUDGET = 512      # budget mode: decode rows + one prefill chunk
PREFILL_CHUNK = 256
CLOSED_LOOP_STEPS = 4

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Sums JAX's tracing, lowering and compile durations."""

    def __init__(self):
        self.total_s = 0.0

    def __call__(self, event: str, duration_s: float, **_):
        if event in _COMPILE_EVENTS:
            self.total_s += duration_s


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, device):
    c0, t0 = clock.total_s, time.perf_counter()
    print(f"== {name}", flush=True)
    try:
        yield
    except BaseException:
        print(f"== {name}: FAILED", flush=True)
        raise
    wall = time.perf_counter() - t0
    peak = device.memory_stats()["peak_bytes_in_use"]
    print(f"[smoke timing, not a benchmark metric] {name}: wall {wall} s, "
          f"of which tracing and compiling {clock.total_s - c0} s; "
          f"peak_bytes_in_use {peak}", flush=True)


def device_phase():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this smoke runs only on the chip",
              file=sys.stderr)
        sys.exit(2)
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    print(f"device: {dev.device_kind}, {len(jax.devices())} device(s); "
          f"jax {jax.__version__}, libtpu {libtpu}")
    return dev


def kernels_phase(seed: int):
    """Paged decode over a ragged batch and paged append over a multi-page
    prefix with a padded suffix, at qwen2-0.5b's 14/2 heads, head dim 64."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention.kernel import (
        paged_append_attention_pallas, paged_decode_attention_pallas)
    from repro.kernels.decode_attention.ref import (
        paged_append_attention_ref, paged_decode_attention_ref)

    H, KV, hd = 14, 2, 64
    n_pages = MAX_SEQ // PAGE_SIZE
    P = MAX_BATCH * n_pages + 1                  # + trash page 0
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    k_arena = jax.random.normal(keys[0], (P, KV, PAGE_SIZE, hd),
                                jnp.bfloat16)
    v_arena = jax.random.normal(keys[1], (P, KV, PAGE_SIZE, hd),
                                jnp.bfloat16)
    perm = rng.permutation(np.arange(1, P)).astype(np.int32)

    lengths = np.array([CONTEXT_TOKENS + 16, 1, 17, MAX_SEQ - 1, MAX_SEQ,
                        MAX_SEQ // 4 + 3, 31, MAX_SEQ // 2], np.int32)
    table = np.zeros((MAX_BATCH, n_pages), np.int32)   # trash past each row
    used = 0
    for b, n in enumerate(lengths):
        k = -(-int(n) // PAGE_SIZE)
        table[b, :k] = perm[used:used + k]
        used += k
    q = jax.random.normal(keys[2], (MAX_BATCH, H, hd), jnp.bfloat16)
    got = paged_decode_attention_pallas(q, k_arena, v_arena, table, lengths,
                                        interpret=False)
    ref = paged_decode_attention_ref(q, k_arena, v_arena, jnp.asarray(table),
                                     jnp.asarray(lengths))
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    print(f"paged decode, lengths {lengths.tolist()}: max abs err {err} "
          f"(tolerance {KERNEL_TOL})")
    check(err <= KERNEL_TOL, f"paged decode kernel err {err} > {KERNEL_TOL}")

    # a naive-RAG-length prefix, and one of three pages and a bit, over which
    # a mask that admits one key too many still moves the output
    suffix, padded = 100, 128
    q = jax.random.normal(keys[3], (padded, H, hd), jnp.bfloat16)
    for prefix in (CONTEXT_TOKENS + 5, 3 * PAGE_SIZE + 5):
        total = prefix + suffix
        row = np.zeros(n_pages, np.int32)
        k = -(-total // PAGE_SIZE)
        row[:k] = perm[used:used + k]
        got = paged_append_attention_pallas(
            q, k_arena, v_arena, row, np.array([prefix, total], np.int32),
            interpret=False)
        ref = paged_append_attention_ref(q, k_arena, v_arena,
                                         jnp.asarray(row), prefix, total)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        print(f"paged append, prefix {prefix} tokens + suffix {suffix} padded "
              f"to {padded}: max abs err {err} (tolerance {KERNEL_TOL})")
        check(err <= KERNEL_TOL,
              f"paged append kernel err {err} > {KERNEL_TOL}")


def make_requests(vocab: int, seed: int):
    """Two requests share a naive-RAG-length context (the second takes a
    prefix hit), four are short and unshared; token ids, not text. The
    shortest fits in one page: a key written to the wrong place is one of
    eight there, where in a long prompt its effect on the logits sinks
    into bf16 rounding."""
    import numpy as np
    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    ids = lambda n: rng.integers(0, vocab, n).tolist()   # noqa: E731
    ctx = ids(CONTEXT_TOKENS)
    prompts = [ctx + ids(40), ctx + ids(27), ids(48), ids(7), ids(150),
               ids(200)]
    max_new = [27, 16, 32, 20, 24, 32]
    return [Request("", max_new_tokens=m, prompt_ids=p)
            for p, m in zip(prompts, max_new)]


def reference_prefill(model, params):
    """``Model.prefill`` (contiguous, jnp attention) as a function of a
    token-id list: the logits that follow its last token, prompts
    right-padded to one of two bucket shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fn = jax.jit(lambda p, t, n: model.prefill(p, t, None, n)[0])

    @functools.cache
    def logits(ids: tuple):
        pad = MAX_SEQ if len(ids) > 256 else 256
        toks = np.zeros((1, pad), np.int32)
        toks[0, :len(ids)] = ids
        return np.asarray(fn(params, jnp.asarray(toks),
                             jnp.asarray([len(ids)], jnp.int32))[0])
    return lambda ids: logits(tuple(ids))


def logit_err(got, ref) -> float:
    """max |got - ref| over the vocabulary, in standard deviations of ref."""
    import numpy as np
    return float(np.abs(np.asarray(got) - ref).max() / ref.std())


def serve(eng, requests, ref_prefill):
    """Serve the workload through a TierScheduler; return token ids per
    request. The shared-context request goes first and finishes before the
    rest are submitted, so the second one finds its pages indexed.

    Two logit rows per request are held to ``ref_prefill``: the first
    token's (paged prefill) and the first decode step's (the first token
    scattered into its page and attended by the decode kernel)."""
    from repro.serving.scheduler import TierScheduler

    first_logits = []                 # [1, V] logits sampled for a first
    step_logits = {}                  # token, in admission order; id(request)
    sample = eng._sample              # -> (fed token, logits) of its first
    #                                   decode step

    def recording_sample(logits, temps, key):
        if logits.shape[0] == 1:
            first_logits.append(logits)
        else:
            for i, s in enumerate(eng._slots):
                if (s is not None and s.pending is not None
                        and eng._positions[i] == s.prompt_tokens):
                    step_logits[id(s.request)] = (s.pending, logits[i])
        return sample(logits, temps, key)

    eng._sample = recording_sample
    try:
        sched = TierScheduler({"edge": eng})
        sched.submit(requests[0], "edge")
        comps = sched.drain()
        for r in requests[1:]:
            sched.submit(r, "edge")
        comps += sched.drain()
    finally:
        eng._sample = sample

    by_req = {id(c.request): c for c in comps}
    check(len(comps) == len(requests) and len(by_req) == len(requests),
          f"{len(comps)} of {len(requests)} requests completed")
    check(sched.shed_total == 0, f"{sched.shed_total} requests shed")
    check(sched.conservation_ok(), f"request counts do not balance: "
          f"{sched.counters}")
    eng.assert_quiescent()
    check(eng.trace_counts["decode"] == 1,
          f"decode traced {eng.trace_counts['decode']} times")
    if eng.budget_mode:
        check(eng.trace_counts["fused"] == 1,
              f"fused step traced {eng.trace_counts['fused']} times")
    check(eng.prefix_hits >= 1, "the shared context took no prefix hit")
    check(len(first_logits) == len(requests),
          f"{len(first_logits)} first-token samples for {len(requests)} "
          "requests")
    check(len(step_logits) == len(requests),
          f"{len(step_logits)} first decode steps for {len(requests)} "
          "requests")
    tokens = []
    for r, got in zip(requests, first_logits):
        c = by_req[id(r)]
        got = got[0]
        check(int(got.argmax()) == c.token_ids[0],
              "first-token logits recorded out of admission order")
        fed, step = step_logits[id(r)]
        check(fed == c.token_ids[0], "the first decode step was not fed "
              "the first token")
        err = logit_err(got, ref_prefill(r.prompt_ids))
        step_err = logit_err(step, ref_prefill(r.prompt_ids + [fed]))
        print(f"  prompt {len(r.prompt_ids)} tokens: {c.new_tokens} new, "
              f"logits max|diff|/std(ref): first token {err}, first decode "
              f"step {step_err} (tolerance {LOGIT_TOL})")
        for what, e in (("first-token", err), ("first decode step", step_err)):
            check(e <= LOGIT_TOL, f"{what} logits err {e} > {LOGIT_TOL} "
                  f"for a {len(r.prompt_ids)}-token prompt")
        tokens.append(c.token_ids)
    print(f"  prefix cache: {eng.prefix_hits} hits, "
          f"{eng.prefix_tokens_shared} prompt tokens from shared pages; "
          f"traces {eng.trace_counts}")
    return tokens


def kernel_use_phase(whole, budget):
    """The compiled engine steps call the Pallas kernels."""
    import jax.numpy as jnp
    from repro.serving.paging import TRASH_PAGE

    def compiled_text(jitted, *args):
        return jitted.lower(*args).compile().as_text()

    trash_row = jnp.full((whole.pages_per_slot,), TRASH_PAGE, jnp.int32)
    steps = {}
    for tag, eng in (("whole-suffix", whole), ("budget", budget)):
        steps[f"{tag} decode"] = compiled_text(
            eng._decode, eng.params, eng._cache,
            jnp.asarray(eng._tokens)[:, None], jnp.asarray(eng._positions),
            jnp.asarray(eng._page_tables))
    steps["whole-suffix prefill"] = compiled_text(
        whole._prefill_paged, whole.params, whole._cache,
        jnp.zeros((1, 64), jnp.int32), jnp.int32(1), jnp.int32(0),
        trash_row)
    steps["budget fused"] = compiled_text(
        budget._fused, budget.params, budget._cache,
        jnp.asarray(budget._tokens)[:, None],
        jnp.asarray(budget._positions), jnp.asarray(budget._page_tables),
        jnp.zeros((1, budget._chunk_pad), jnp.int32), jnp.int32(1),
        jnp.int32(0), trash_row)
    for name, text in steps.items():
        n = text.count("tpu_custom_call")
        print(f"  {name}: {n} tpu_custom_call sites")
        check(n > 0, f"{name} step has no Pallas kernel")


def closed_loop_phase(seed: int):
    from repro.cluster.simulator import EACOCluster, SimConfig
    from repro.data.corpus import wiki_like

    sim = EACOCluster(wiki_like(seed=seed), SimConfig(seed=seed),
                      backend="engines")
    logs = sim.run(CLOSED_LOOP_STEPS)
    check(len(logs) > 0, "the closed loop served nothing")
    check(sim.conservation_ok(), f"cluster counts do not balance: "
          f"{sim.counters}")
    for tier, pool in sim.sched.pools.items():
        for eng in pool:
            eng.assert_quiescent()
            check(eng.decode_traces <= 1,
                  f"{tier} engine decode traced {eng.decode_traces} times")
    served = sum(l.outcome == "ok" for l in logs)
    tiers = sorted({l.tier for l in logs})
    print(f"  {len(logs)} queries, {served} served, tiers {tiers}, arms "
          f"{sorted({l.arm_name for l in logs})}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, data and traffic")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run the "
              "script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    dev = device_phase()
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    with phase("kernels", clock, dev):
        kernels_phase(args.seed)

    from repro.configs import get_config
    from repro.serving.engine import ServingEngine
    cfg = get_config("qwen2-0.5b")
    requests = make_requests(cfg.vocab, args.seed)
    with phase("engine build + reference logits", clock, dev):
        whole = ServingEngine(cfg, max_seq=MAX_SEQ, max_batch=MAX_BATCH,
                              page_size=PAGE_SIZE, seed=args.seed)
        print(f"  {cfg.arch_id}: {whole.model.n_params():,} params, "
              f"{whole.num_pages} x {PAGE_SIZE}-token pages "
              f"({whole.kv_cache_bytes:,} arena bytes)")
        ref = reference_prefill(whole.model, whole.params)
        for r in requests:                   # compiles both pad shapes
            ref(r.prompt_ids)
    with phase("engine, whole-suffix admission", clock, dev):
        whole_tokens = serve(whole, requests, ref)
    with phase("engine, token-budget admission", clock, dev):
        budget = ServingEngine(cfg, max_seq=MAX_SEQ, max_batch=MAX_BATCH,
                               page_size=PAGE_SIZE, params=whole.params,
                               step_token_budget=STEP_TOKEN_BUDGET,
                               prefill_chunk=PREFILL_CHUNK)
        budget_tokens = serve(budget, make_requests(cfg.vocab, args.seed),
                              ref)
    same = sum(a == b for a, b in zip(whole_tokens, budget_tokens))
    agree = sum(x == y for a, b in zip(whole_tokens, budget_tokens)
                for x, y in zip(a, b))
    print(f"greedy agreement between admission modes (not gated: bf16 "
          f"near-ties over {cfg.vocab} logits can flip): {same}/"
          f"{len(requests)} requests identical, {agree}/"
          f"{sum(map(len, whole_tokens))} tokens")
    with phase("kernel use in compiled steps", clock, dev):
        kernel_use_phase(whole, budget)
    del whole, budget
    with phase("closed loop", clock, dev):
        closed_loop_phase(args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
