"""Continuous-batching serving engine with a prefix-cached paged KV-cache.

This is the engine that runs at edge nodes (reduced SLM) and — in pod
deployment — behind the cloud tier. Requests stream through a fixed pool of
``max_batch`` slots; the KV-cache behind those slots comes in two layouts:

* ``paged`` (default where the model supports it) — one global page arena
  per layer, ``[num_pages + 1, KV, page_size, hd]``, plus a host-side
  per-slot page table ``[max_batch, max_seq // page_size]`` of physical page
  ids. A slot reserves only ``ceil((prompt + decode_budget) / page_size)``
  pages at admission, so short requests no longer strand a worst-case
  ``max_seq`` lane and the number of *resident* requests is bounded by
  actual token demand, not by ``max_batch x max_seq`` worst-case memory.
  Physical page 0 is the trash page: table entries past a slot's allocation
  point at it, keeping every scatter/gather fixed-shape.

  On top of the arena sits a **prefix cache** (on by default,
  ``prefix_cache=False`` to disable) — the EACO-RAG edge tier answers many
  queries grounded in the same retrieved context, so requests sharing a
  prompt prefix should share its KV instead of recomputing it:

  - *hash chains*: prompts are cut into page-sized token blocks and indexed
    by chain hash (parent hash + block tokens, token-verified on lookup) in
    :class:`~repro.serving.paging.PrefixCache`. ``admit`` walks the chain
    for the longest page-aligned shared prefix and maps those physical
    pages into the new slot's page table read-only.
  - *CoW tail*: the partially-filled last prompt page of a cached prompt is
    indexed too; when its leading tokens agree with the new request, the
    page is copied on-device (copy-on-write — the new slot will keep
    writing into that logical page) so even a non-page-aligned retrieval
    context is shared up to its last token. The match is always capped at
    ``prompt_len - 1`` so at least one suffix token remains to produce
    first-token logits.
  - *refcount lifecycle*: shared pages carry one reference per mapping slot
    (:meth:`PageAllocator.ref`); retirement decrements and only
    decrement-to-zero releases a page. Pages the index still values park in
    an LRU pool — KV bytes stay valid for future hits — and are reclaimed
    (oldest first) only when the allocator actually needs the capacity, so
    cached prefixes cost nothing under low pressure and nothing *extra*
    under high pressure.
  - *suffix-only prefill*: after the match, only the unique suffix runs
    through the model (``Model.prefill_paged`` -> per-layer ``fwd_append``
    -> the chunked paged append-attention kernel), scattering its KV
    straight into freshly allocated pages — there is no intermediate
    contiguous lane and no lane->arena copy anywhere in the paged path.

  Remaining invariants from the plain paged design: the allocator hands
  each slot's *private* pages to exactly one slot (shared pages are only
  ever read after their writer finishes with them — block pages are
  write-once at prefill, CoW sources are copied, and decode always writes
  at positions >= prompt_len, which land in private pages); pages are
  reserved for prompt + full decode budget at admission, so a resident
  request always runs to completion; page tables ride into the jitted
  decode as fixed-shape ``[max_batch, pages_per_slot]`` int32 arguments —
  remapping or sharing slots never re-traces.

* ``contiguous`` — the PR-1 layout, one persistent ``[max_batch, max_seq,
  ...]`` lane per slot. Kept as the numerical/throughput baseline (see
  ``benchmarks/serving_bench.py``) and as the fallback for models whose
  decoder state cannot be paged (sliding-window rings, int8 caches, SSM /
  RWKV state, cross-attention memories).

Admission via :meth:`admit` requires :meth:`can_admit` — a free slot AND, in
paged mode, enough allocatable pages (free + LRU-evictable) for the
request's *unshared* pages. ``step()`` runs ONE fused decode for all slots
at ``[max_batch, 1]``.

**Fused chunked-prefill + decode (the token-budget state machine).**
Passing ``step_token_budget`` (paged layout only) replaces stop-the-world
admission with a Sarathi-style fused step. The machinery:

- *Async admission*: :meth:`admit` becomes host-only — it plans, maps
  shared prefix pages, CoW-copies a matched tail and reserves fresh pages,
  but runs NO model compute. The slot parks **mid-prefill**
  (``prefill_done < prompt_tokens``, ``pending is None``) with its decode
  row masked: page-table row all trash, token ``pad``, position 0 — so the
  fixed-shape decode can carry it inertly (a 1-token attention over the
  trash page is finite and its result is never read).
- *Budgeted steps*: each :meth:`dispatch` packs every resident decode row
  (one token each) plus ONE bounded prefill chunk of the highest-priority
  mid-prefill resident into ``step_token_budget`` tokens. The chunk runs
  through the same ``fwd_append`` path (and chunked append-attention
  kernel) as whole-suffix prefill, fused with the decode in a single jit
  (:meth:`Model.fused_step` -> ``run_segments_fused``) that compiles
  exactly once — zero decode retraces, and chunk tokens are always padded
  to one fixed ``_pad_bucket(prefill_chunk)`` bucket. When decodes alone
  meet the budget, a chunk still rides along only for an *interactive*
  head (a small starvation guard); with no decodes resident the chunk runs
  through the ordinary suffix-prefill jit at the same fixed bucket.
- *Deferred first token*: chunk logits are computed every chunk at a fixed
  shape but only the FINAL chunk's are first-token logits — that step
  samples the pending token, unmasks the decode row (real page table,
  position ``prompt_tokens``), stamps ``first_token_at`` (engine
  :attr:`EngineCompletion.ttft_s`) and — only now — inserts the prompt
  into the prefix index (indexing pages before their KV is written would
  let a later admission map garbage read-only).
- *Async dispatch hazards*: :meth:`step` is ``harvest -> dispatch ->
  collect``, but a scheduler may dispatch EVERY engine and collect at the
  end of its round, overlapping host-side planning with device compute
  (JAX async dispatch — nothing blocks until ``collect`` fetches the
  sampled tokens). Between dispatch and collect the slot table may change
  under the in-flight step (preempt, cancel, crash): ``collect`` applies a
  result only if the slot still holds the same ``req_id`` in the same
  phase, and a stale in-flight write to a since-freed page is harmless —
  a reader only gathers positions below its own length, and every such
  position in a re-allocated private page is rewritten by its new owner
  before that owner's length covers it (shared pages are only ever
  indexed after being fully written).
- *Preempt / crash of a half-prefilled resident*: nothing special —
  ``preempt`` snapshots zero emitted tokens and the full budget (prefill
  compute already spent on chunks is the only loss; greedy resume is
  token-identical), ``crash`` drops the slot with everything else.
- *Accounting*: a step's cost is additive — ``decode_rounds`` counts steps
  with >= 1 live decode row, ``prefill_tokens`` counts chunk tokens — so
  the virtual-clock delta formula ``modeled_prefill_s(Δtokens) + Δrounds *
  modeled_decode_round_s`` (and its per-step form
  :func:`~repro.core.cost_model.modeled_mixed_step_s`) stays exact under
  chunking. ``mixed_steps`` / ``prefill_chunks`` / ``budget_utilization``
  expose the mix.

**Feasibility is explicit, never silent.** A prompt longer than
``max_seq - 1`` tokens can never leave room for a single generated token;
admitting it truncated would silently drop the prompt *tail* — which in a
context-first RAG prompt is the question itself. Such requests are
*infeasible*: :meth:`fits` answers False, :meth:`can_admit` permanently
refuses (so schedulers reject at submit instead of wedging their
deadline-ordered queue behind an inadmissible head), and :meth:`admit` /
:meth:`generate` raise :class:`EngineError`.

**Preemption (the overload state machine, engine side).** A resident
request can be reclaimed mid-decode with :meth:`preempt`: the slot is
freed immediately and every page reference is dropped exactly as on normal
retirement — private suffix pages return to the allocator while shared
prefix pages the index values survive in the LRU pool. The caller receives
a :class:`PreemptedRequest` snapshot (encoded prompt + tokens emitted so
far + remaining budget). Resuming is just a new admission of ``prompt_ids
= enc + emitted`` (token ids, via :attr:`Request.prompt_ids`, because
generated ids need not round-trip through text): the prefix cache matches
the original prompt's blocks — still indexed from the first admission —
so only the generated suffix is recomputed, and greedy decode emits the
exact tokens the victim would have produced uninterrupted (the sampled-
but-unemitted ``pending`` token is deliberately NOT part of the snapshot;
greedy resume re-derives it from identical logits). The scheduler layers
shed/timeout/failover on top (:mod:`repro.serving.scheduler`,
:mod:`repro.cluster.simulator`).

**Crash and recovery (the hard-failure state machine, engine side).**
Unlike a stall (engine frozen, state intact) or a preemption (one resident
reclaimed, shared pages survive), :meth:`crash` models a process/device
loss: EVERY slot, the whole page arena, the allocator and the prefix index
are gone at once. ``crash()`` marks the engine ``dead`` (admit/step/preempt
raise :class:`EngineError`; ``can_admit`` answers False) and returns the
request ids of the residents that died with it — the scheduler reaps those
as typed ``engine_lost`` outcomes and re-serves them from their original
prompts (tokens still in engine memory are lost; tokens a scheduler banked
from an earlier preemption survive, because they live in the control
plane). :meth:`restart` rebuilds a COLD engine — zeroed arena, fresh
allocator and prefix index, empty slots — and bumps
:attr:`engine_generation` so stale references (a scheduler's resident keys,
memoized admission plans) are detectably invalid. The jitted functions are
kept: shapes and dtypes are unchanged, so a restarted engine re-serves
without re-tracing, and greedy output is token-identical to a never-crashed
engine.

**Clocks.** Engine-level request timestamps (admission time, completion
``time_in_engine_s``) read the injectable ``clock`` (any zero-arg callable
returning seconds; default ``time.perf_counter``) — a simulator injecting a
:class:`~repro.core.clock.VirtualClock` gets logical residency times that
compose with its queue waits instead of mixing wall and event time. The
compute timers are explicitly wall-clock and NAMED so:
``prefill_wall_s``/``decode_wall_s`` measure real jit compute for
``engine_time="wall"``, while the *logical* counters — ``prefill_tokens``,
``decode_rounds``, ``prefill_chunks``, ``mixed_steps`` — are pure
functions of the request stream, so DST replays that compare engine
progress stay byte-identical regardless of host speed.

All jitted functions run at fixed shapes — decode, sampling, page-copy and
(contiguous) insert compile exactly once per engine config; prefill
compiles once per power-of-two pad bucket (heavy-tailed prompt mixes
therefore retrace at most ``log2(max_seq)`` times, and :meth:`warmup`
precompiles every bucket up front). ``trace_counts`` exposes per-function
trace counters so tests and benchmarks can assert compile stability.
Decode budgets stay per-slot: each request may emit up to
``min(max_new_tokens, max_seq - prompt_len)`` tokens.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import (
    Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.tracing import span
from repro.data.tokenizer import ByteTokenizer
from repro.kernels.decode_attention.kernel import append_walk
from repro.models.api import Model, build_model
from repro.models.pdefs import is_pdef
from repro.serving.paging import (
    TRASH_PAGE, PageAllocator, PrefixCache, pages_needed,
)


class EngineError(RuntimeError):
    """Caller-facing serving-engine invariant violation (vocab coverage,
    page-size divisibility, batch bounds, busy pool). A real exception —
    unlike a bare ``assert`` — survives ``python -O``, where a silently
    admitted bad config would corrupt KV state long after the cause
    (mirrors :class:`~repro.serving.paging.PagingError`)."""


@dataclass
class GenStats:
    prompt_tokens: int
    new_tokens: int
    prefill_s: float
    decode_s: float
    prefill_traces: int = 0        # _prefill traces during this generate
    prefix_hits: int = 0           # admissions that shared >= 1 prefix token
    prefix_misses: int = 0         # paged admissions with nothing shared
    prefix_tokens_shared: int = 0  # prompt tokens served from cached pages
    mixed_steps: int = 0           # fused steps carrying a chunk AND decodes
    prefill_chunks: int = 0        # bounded prefill chunks run (budget mode)
    budget_utilization: float = 0.0  # tokens used / step budget, mean

    @property
    def tokens_per_s(self) -> float:
        return self.new_tokens / self.decode_s if self.decode_s > 0 else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        n = self.prefix_hits + self.prefix_misses
        return self.prefix_hits / n if n else 0.0


@dataclass
class Request:
    prompt: str
    max_new_tokens: int = 32
    temperature: float = 0.0     # 0 = greedy
    slo: str = "batch"           # SLO class: "interactive" | "batch"
    # pre-encoded prompt override (resume path): generated token ids need
    # not round-trip through text, so a preemption resume carries raw ids
    prompt_ids: Optional[List[int]] = None


@dataclass
class EngineCompletion:
    """Per-request result carried out of the slot pool."""
    req_id: int
    request: Request
    text: str
    token_ids: List[int]
    prompt_tokens: int
    new_tokens: int
    time_in_engine_s: float      # admit -> finish (prefill + resident decode)
    ttft_s: float = 0.0          # admit -> first token (engine clock; 0 in
    #                              whole-suffix mode, where admit blocks
    #                              through the first sample)


@dataclass
class PreemptedRequest:
    """Resumable snapshot returned by :meth:`ServingEngine.preempt`.

    ``prompt_ids + emitted_ids`` is the exact token state to re-admit
    (as :attr:`Request.prompt_ids`); the sampled-but-unemitted pending
    token is intentionally absent — greedy resume recomputes it from
    identical logits, keeping resumed output token-identical."""
    req_id: int
    request: Request
    prompt_ids: List[int]        # the prompt as admitted (encoded)
    emitted_ids: List[int]       # tokens generated before preemption
    prompt_tokens: int
    budget_left: int             # decode budget remaining at preemption


@dataclass
class _Slot:
    req_id: int
    request: Request
    budget: int                  # per-slot decode budget
    prompt_tokens: int
    pending: Optional[int]       # sampled, not yet emitted/fed token; None
    #                              while the slot is still mid-prefill
    admitted_at: float
    page_ids: Optional[np.ndarray] = None   # pages referenced (shared+own)
    out_ids: List[int] = field(default_factory=list)
    enc: List[int] = field(default_factory=list)   # encoded prompt
    # ---- budget-mode partial-prefill state ----------------------------
    prefill_done: int = 0        # prompt tokens already in the arena
    prefix_tokens: int = 0       # of which the prefix cache served
    page_row: Optional[np.ndarray] = None   # full page-table row, applied
    #                              to the decode table at prefill finish
    first_token_at: Optional[float] = None  # engine clock at first sample


@dataclass
class _Plan:
    """Host-side admission plan (memoized per request + page-state
    generation: matches go stale whenever pages move)."""
    enc: List[int]
    budget: int
    feasible: bool = True        # prompt fits max_seq - 1 (never truncated)
    total_pages: int = 0
    shared_ids: List[int] = field(default_factory=list)   # full-block pages
    tail: Optional[Tuple[int, int]] = None   # (CoW source page, tokens)
    need_fresh: int = 0

    @property
    def reuse_ids(self) -> List[int]:
        """Pages the admission reads from the cache: shared full-block maps
        plus the CoW source — all must be protected from eviction."""
        return self.shared_ids + ([self.tail[0]] if self.tail else [])


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees, is_leaf=is_pdef)


class ServingEngine:
    """One model instance serving a continuously-batched slot pool."""

    def __init__(self, cfg: ModelConfig, *, max_seq: int = 512,
                 max_batch: int = 8, seed: int = 0, params=None,
                 kv_layout: str = "auto", page_size: int = 16,
                 num_pages: Optional[int] = None, prefix_cache: bool = True,
                 clock: Optional[Callable[[], float]] = None,
                 step_token_budget: Optional[int] = None,
                 prefill_chunk: int = 32):
        self.cfg = cfg
        self.max_seq = max_seq
        self.max_batch = max_batch
        self._clock: Callable[[], float] = (time.perf_counter
                                            if clock is None else clock)
        self.tok = ByteTokenizer()
        if cfg.vocab < self.tok.vocab_size:
            raise EngineError(
                f"vocab {cfg.vocab} cannot cover the byte tokenizer's "
                f"{self.tok.vocab_size} ids")
        self.model = build_model(cfg, max_seq=max_seq)
        self.params = params if params is not None else self.model.init(
            jax.random.PRNGKey(seed))
        self._key = jax.random.PRNGKey(seed + 1)

        if kv_layout not in ("auto", "paged", "contiguous"):
            raise EngineError(f"unknown kv_layout {kv_layout!r}")
        if kv_layout == "auto":
            kv_layout = ("paged" if self.model.supports_paged_cache
                         else "contiguous")
        if kv_layout == "paged" and not self.model.supports_paged_cache:
            raise ValueError(
                f"{cfg.arch_id}: decoder cache cannot be paged "
                "(window/int8/SSM/cross state); use kv_layout='contiguous'")
        self.kv_layout = kv_layout

        # ---- fused chunked-prefill + decode (token-budget) config ---------
        self.budget_mode = step_token_budget is not None
        if self.budget_mode:
            if kv_layout != "paged":
                raise EngineError(
                    "step_token_budget requires the paged KV layout "
                    "(chunked prefill appends straight into arena pages)")
            if step_token_budget < 1 or prefill_chunk < 1:
                raise EngineError(
                    f"step_token_budget {step_token_budget} and "
                    f"prefill_chunk {prefill_chunk} must be >= 1")
        self.step_token_budget = step_token_budget
        self.prefill_chunk = min(prefill_chunk, max_seq)

        if kv_layout == "paged":
            if page_size % 8 != 0:
                raise EngineError(
                    f"page_size {page_size} must keep the 8-row layout")
            if max_seq % page_size != 0:
                raise EngineError(
                    f"max_seq {max_seq} not divisible by page_size "
                    f"{page_size}")
            self.page_size = page_size
            self.pages_per_slot = max_seq // page_size
            self.num_pages = (max_batch * self.pages_per_slot
                              if num_pages is None else num_pages)
            if self.num_pages < self.pages_per_slot:
                raise EngineError(
                    f"page pool of {self.num_pages} cannot fit one "
                    f"worst-case request ({self.pages_per_slot} pages)")
            # ---- page arena (+1: trash page 0) + host page state ----------
            arena_defs = self.model.paged_cache_defs(self.num_pages + 1,
                                                     page_size)
            self._cache = _tmap(lambda d: jnp.zeros(d.shape, d.dtype),
                                arena_defs)
            self._page_ax = _tmap(lambda d: d.axes.index("pages"), arena_defs)
            self._allocator = PageAllocator(self.num_pages)
            self._prefix = PrefixCache(page_size) if prefix_cache else None
            if self._prefix is not None:
                self._allocator.evict_cb = self._prefix.forget
            self._page_tables = np.full(
                (max_batch, self.pages_per_slot), TRASH_PAGE, np.int32)
        else:
            self.page_size = None
            self.pages_per_slot = None
            self.num_pages = None
            self._allocator = None
            self._prefix = None
            self._page_tables = None
            # ---- persistent KV-cache pool: one lane per slot --------------
            pool_defs = self.model.cache_defs(max_batch)
            self._batch_ax = _tmap(lambda d: d.axes.index("batch"), pool_defs)
            self._cache = _tmap(lambda d: jnp.zeros(d.shape, d.dtype),
                                pool_defs)

        # ---- host-side slot state -----------------------------------------
        self._slots: List[Optional[_Slot]] = [None] * max_batch
        self._tokens = np.full(max_batch, self.tok.pad_id, np.int32)
        self._positions = np.zeros(max_batch, np.int32)
        self._temps = np.zeros(max_batch, np.float32)
        self._next_req_id = 0
        self._plan_cache = None   # one-entry (request, generation, plan) memo
        self.peak_active = 0      # high-water mark of resident requests
        # wall-clock compute timers (real jit time; see module docstring —
        # logical progress lives in the token/round counters below)
        self.prefill_wall_s = 0.0
        self.decode_wall_s = 0.0
        self.prefill_tokens = 0   # suffix tokens actually prefilled
        self.decode_rounds = 0    # fused decode steps run with active slots
        self.mixed_steps = 0      # fused steps with a chunk AND >=1 decode
        self.prefill_chunks = 0   # bounded prefill chunks run (budget mode)
        self.budget_steps = 0     # budget-mode steps dispatched
        self.budget_tokens_used = 0  # decode rows + chunk tokens dispatched
        self._outstanding = None  # in-flight dispatch awaiting collect()
        self.prefix_hits = 0      # engine-lifetime prefix-cache counters
        self.prefix_misses = 0
        self.prefix_tokens_shared = 0
        # (query tile, KV block) pairs of one KV head in one layer that the
        # paged append kernel computes, and the pairs in its static grid
        self.append_blocks_walked = 0
        self.append_blocks_grid = 0
        self.preemptions = 0      # residents reclaimed via preempt()
        self.dead = False         # crashed and not yet restarted
        self.engine_generation = 0  # bumped on every restart()
        self.crashes = 0          # crash() calls over the engine's lifetime

        # ---- fixed-shape jitted functions with trace instrumentation ------
        # the counters increment only when JAX (re)traces a function, so a
        # stable engine shows exactly one decode/sample/insert/copy trace no
        # matter how many streams of differing batch mix it serves; prefill
        # traces once per power-of-two pad bucket.
        self.trace_counts: Dict[str, int] = {
            "prefill": 0, "decode": 0, "sample": 0, "insert": 0, "copy": 0,
            "fused": 0}

        def _prefill_fn(params, tokens, lengths):
            self.trace_counts["prefill"] += 1
            return self.model.prefill(params, tokens, None, lengths)

        def _prefill_paged_fn(params, cache, tokens, suffix_len, prefix_len,
                              page_row):
            self.trace_counts["prefill"] += 1
            return self.model.prefill_paged(
                params, cache, tokens, suffix_len, prefix_len, page_row,
                page_size=self.page_size)

        def _decode_fn(params, cache, tokens1, positions):
            self.trace_counts["decode"] += 1
            return self.model.decode_step(params, cache, tokens1, positions)

        def _decode_paged_fn(params, cache, tokens1, positions, page_tables):
            self.trace_counts["decode"] += 1
            return self.model.decode_step_paged(
                params, cache, tokens1, positions, page_tables,
                page_size=self.page_size)

        def _fused_fn(params, cache, tokens1, positions, page_tables,
                      chunk_tokens, chunk_suffix_len, chunk_prefix_len,
                      chunk_page_row):
            self.trace_counts["fused"] += 1
            return self.model.fused_step(
                params, cache, tokens1, positions, page_tables,
                chunk_tokens, chunk_suffix_len, chunk_prefix_len,
                chunk_page_row, page_size=self.page_size)

        def _sample_fn(logits, temps, key):
            self.trace_counts["sample"] += 1
            greedy = jnp.argmax(logits, -1).astype(jnp.int32)
            t = jnp.maximum(temps, 1e-4)[:, None]
            sampled = jax.random.categorical(key, logits / t, axis=-1)
            return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)

        def _insert_fn(pool, one, slot):
            self.trace_counts["insert"] += 1

            def put(big, small, ax):
                big_m = jnp.moveaxis(big, ax, 0)
                row = jnp.moveaxis(small, ax, 0)[0].astype(big_m.dtype)
                big_m = jax.lax.dynamic_update_index_in_dim(
                    big_m, row, slot, 0)
                return jnp.moveaxis(big_m, 0, ax)

            return jax.tree_util.tree_map(put, pool, one, self._batch_ax)

        def _copy_page_fn(arena, src, dst):
            """Device copy of one physical page across every layer's arena —
            the copy-on-write step for a matched partial tail page."""
            self.trace_counts["copy"] += 1

            def cp(big, ax):
                big_m = jnp.moveaxis(big, ax, 0)
                row = jax.lax.dynamic_index_in_dim(big_m, src, 0,
                                                   keepdims=False)
                big_m = jax.lax.dynamic_update_index_in_dim(
                    big_m, row, dst, 0)
                return jnp.moveaxis(big_m, 0, ax)

            return jax.tree_util.tree_map(cp, arena, self._page_ax)

        # donate the cache pool/arena through decode/insert/prefill so XLA
        # updates it in place instead of copying the whole pool per call
        # (CPU doesn't implement donation and would warn)
        donate = jax.default_backend() != "cpu"
        self._sample = jax.jit(_sample_fn)
        if kv_layout == "paged":
            self._prefill_paged = jax.jit(
                _prefill_paged_fn, donate_argnums=(1,) if donate else ())
            self._copy_page = jax.jit(
                _copy_page_fn, donate_argnums=(0,) if donate else ())
            self._decode = jax.jit(_decode_paged_fn,
                                   donate_argnums=(1,) if donate else ())
            if self.budget_mode:
                self._fused = jax.jit(
                    _fused_fn, donate_argnums=(1,) if donate else ())
                # chunk tokens always pad to ONE fixed bucket, so the fused
                # step and the chunk-only prefill each compile exactly once
                self._chunk_pad = self._pad_bucket(self.prefill_chunk)
        else:
            self._prefill = jax.jit(_prefill_fn)
            self._decode = jax.jit(_decode_fn,
                                   donate_argnums=(1,) if donate else ())
            self._insert = jax.jit(_insert_fn,
                                   donate_argnums=(0,) if donate else ())

    # ------------------------------------------------------------------
    # Slot-pool / page-pool introspection
    # ------------------------------------------------------------------
    @property
    def free_slots(self) -> int:
        return sum(s is None for s in self._slots)

    @property
    def active_slots(self) -> int:
        return self.max_batch - self.free_slots

    @property
    def has_active(self) -> bool:
        return any(s is not None for s in self._slots)

    @property
    def decode_traces(self) -> int:
        return self.trace_counts["decode"]

    @property
    def prefilling_slots(self) -> int:
        """Residents still mid-prefill (budget mode; no first token yet)."""
        return sum(1 for s in self._slots
                   if s is not None and s.pending is None)

    @property
    def budget_utilization(self) -> float:
        """Mean fraction of ``step_token_budget`` actually dispatched per
        budget-mode step (decode rows + chunk tokens)."""
        if not self.budget_mode or self.budget_steps == 0:
            return 0.0
        return self.budget_tokens_used / (
            self.budget_steps * self.step_token_budget)

    @property
    def free_pages(self) -> Optional[int]:
        return self._allocator.free_pages if self._allocator else None

    @property
    def cached_pages(self) -> Optional[int]:
        """Refcount-0 pages retained by the prefix cache (reclaimable)."""
        return self._allocator.cached_pages if self._allocator else None

    @property
    def available_pages(self) -> Optional[int]:
        """Pages an admission could obtain (free + LRU-evictable)."""
        return self._allocator.available_pages if self._allocator else None

    @property
    def kv_cache_tokens(self) -> int:
        """Token capacity of the KV memory (paged: usable pages; contiguous:
        the full slot pool)."""
        if self.kv_layout == "paged":
            return self.num_pages * self.page_size
        return self.max_batch * self.max_seq

    @property
    def kv_cache_bytes(self) -> int:
        return int(sum(x.nbytes for x in jax.tree_util.tree_leaves(
            self._cache)))

    @property
    def prefix_cache_enabled(self) -> bool:
        return self._prefix is not None

    @property
    def pad_buckets(self) -> List[int]:
        """Every prefill pad bucket this engine can compile — the bound on
        lifetime prefill traces; also what :meth:`warmup` iterates. In
        budget mode all prefill runs as fixed-size chunks, so exactly ONE
        bucket (``_pad_bucket(prefill_chunk)``) is reachable and the
        power-of-two sweep collapses; otherwise 8, 16, ..., ``max_seq``."""
        if self.budget_mode:
            return [self._chunk_pad]
        out, b = [], self._pad_bucket(1)
        while b < self.max_seq:
            out.append(b)
            b = self._pad_bucket(b + 1)
        out.append(self._pad_bucket(self.max_seq))
        return out

    # ------------------------------------------------------------------
    # Continuous-batching API: can_admit / admit / step
    # ------------------------------------------------------------------
    def _pad_bucket(self, n: int) -> int:
        """Prefill pad length for ``n`` tokens: next power of two (>= 8,
        capped at ``max_seq``). Heavy-tailed workloads therefore retrace
        prefill at most ``log2(max_seq)`` times instead of once per
        ``q_chunk`` multiple; :meth:`warmup` precompiles every bucket."""
        p = max(8, 1 << (max(n, 1) - 1).bit_length())
        qc = max(self.cfg.q_chunk, 1)
        if p > qc and p % qc:
            p = -(-p // qc) * qc          # blockwise prefill needs qc chunks
        return min(p, self.max_seq)

    def _encode(self, request: Request) -> List[int]:
        """Token ids for a request's prompt: the pre-encoded override when
        present (preemption resume carries generated ids that need not
        round-trip through text), otherwise the tokenizer."""
        if request.prompt_ids is not None:
            return [int(t) for t in request.prompt_ids]
        return self.tok.encode(request.prompt)

    def fits(self, request: Request) -> bool:
        """Could this request EVER be admitted here (i.e. on an idle
        engine)? False when the encoded prompt is empty or cannot leave
        room for one generated token — admission would have to silently
        truncate the prompt tail (the question, in a context-first RAG
        prompt), so such requests are rejected up front instead
        (:class:`SchedulerError <repro.serving.scheduler.SchedulerError>`
        at submit; :class:`EngineError` at admit)."""
        return 1 <= len(self._encode(request)) <= self.max_seq - 1

    def _plan(self, request: Request) -> _Plan:
        """Admission plan: encoded prompt, decode budget and — in paged
        mode — the prefix-cache match (shared full-block pages + CoW tail)
        and the fresh-page demand it leaves. Memoized for the last request
        seen at the current page-state generation: a queue head blocked on
        pages is re-planned by ``can_admit`` every decode step, and
        ``admit`` re-plans right after the ``can_admit`` that green-lit it
        — but any alloc/free/evict in between invalidates the match.
        Prompts that cannot fit are marked infeasible, never truncated."""
        gen = self._allocator.generation if self._allocator else 0
        cached = self._plan_cache
        if cached is not None and cached[0] is request and cached[1] == gen:
            return cached[2]
        enc = self._encode(request)
        L = len(enc)
        if not 1 <= L <= self.max_seq - 1:
            plan = _Plan(enc, 0, feasible=False)
            self._plan_cache = (request, gen, plan)
            return plan
        budget = max(0, min(request.max_new_tokens, self.max_seq - L))
        plan = _Plan(enc, budget)
        if self.kv_layout == "paged":
            plan.total_pages = pages_needed(L + budget, self.page_size)
            if self._prefix is not None:
                # cap the match at L-1 tokens: at least one suffix token
                # must remain to prefill for first-token logits
                plan.shared_ids, plan.tail = self._prefix.match(enc[:L - 1])
            plan.need_fresh = plan.total_pages - len(plan.shared_ids)
        self._plan_cache = (request, gen, plan)
        return plan

    def can_admit(self, request: Request) -> bool:
        """A free slot AND (paged) enough allocatable pages for the
        request's unshared demand. Because pages are reserved through a
        request's whole budget, an engine draining its residents always
        becomes admissible again. A crashed engine admits nothing until
        :meth:`restart`."""
        if self.dead or self.free_slots == 0:
            return False
        plan = self._plan(request)
        if not plan.feasible:
            return False
        if self.kv_layout != "paged":
            return True
        return self._allocator.can_reserve(plan.need_fresh, plan.reuse_ids)

    def admit(self, request: Request) -> int:
        """Prefill one request into a free slot. In paged mode this is the
        prefix-cache hot path: map matched shared pages, CoW-copy a matched
        partial tail page, then prefill ONLY the unique suffix straight
        into freshly allocated pages. Returns the engine-local request id
        used in :class:`EngineCompletion`. Callers gate on
        :meth:`can_admit`."""
        with span("engine.admit") as sp:
            return self._admit(request, sp)

    def _admit(self, request: Request, sp) -> int:
        if self.dead:
            raise EngineError("admit: engine crashed; restart() first")
        slot = next((i for i, s in enumerate(self._slots) if s is None), None)
        if slot is None:
            raise RuntimeError("no free slot; check can_admit before admit")
        plan = self._plan(request)
        if not plan.feasible:
            raise EngineError(
                f"prompt of {len(plan.enc)} tokens cannot fit max_seq "
                f"{self.max_seq} with >=1 generated token; refusing to "
                "truncate silently")
        enc, budget = plan.enc, plan.budget
        L = len(enc)

        t0 = time.perf_counter()
        if self.kv_layout == "paged":
            ps = self.page_size
            # protect every reused page (shared maps AND the CoW source)
            # from the eviction that alloc may trigger
            self._allocator.ref(plan.reuse_ids)
            try:
                fresh = self._allocator.alloc(plan.need_fresh)
            except Exception:
                # callers that skipped can_admit must not leak references
                self._allocator.free(
                    plan.reuse_ids,
                    retain=self._prefix.owns if self._prefix else None)
                raise
            n_shared = len(plan.shared_ids)
            row = np.full(self.pages_per_slot, TRASH_PAGE, np.int32)
            row[:n_shared] = plan.shared_ids
            row[n_shared:plan.total_pages] = fresh
            prefix_len = n_shared * ps
            if plan.tail is not None:
                src, t_match = plan.tail
                self._cache = self._copy_page(
                    self._cache, jnp.int32(src), jnp.int32(int(row[n_shared])))
                prefix_len += t_match
                # drop the temporary protection ref on the CoW source (its
                # contents now live in the slot's private copy)
                self._allocator.free(
                    [src], retain=self._prefix.owns if self._prefix else None)
            if self.budget_mode:
                # ---- async admission: NO model compute here ----------
                # Pages are mapped and reserved, but every prefill token
                # runs later as budgeted chunks in dispatch(). The slot
                # parks mid-prefill with a masked decode row (trash page
                # table, pad token, position 0); the prefix-cache insert
                # waits for the final chunk — indexing pages before their
                # KV exists would let a later admission map garbage.
                page_ids = row[:plan.total_pages].copy()
                if self._prefix is not None:
                    if prefix_len:
                        self.prefix_hits += 1
                    else:
                        self.prefix_misses += 1
                    self.prefix_tokens_shared += prefix_len
                self.prefill_wall_s += time.perf_counter() - t0
                rid = self._next_req_id
                self._next_req_id += 1
                self._slots[slot] = _Slot(
                    rid, request, budget, L, None,
                    admitted_at=self._clock(), page_ids=page_ids, enc=enc,
                    prefill_done=prefix_len, prefix_tokens=prefix_len,
                    page_row=row)
                self.peak_active = max(self.peak_active, self.active_slots)
                sp.set(rid=rid, prompt_tokens=L, prefix_tokens=prefix_len)
                return rid
            suffix = enc[prefix_len:]
            pad_len = self._pad_bucket(len(suffix))
            tokens, _ = self.tok.pad_batch([suffix], pad_len)
            walked, grid = self._count_append(prefix_len, L, pad_len)
            sp.set(append_blocks_walked=walked, append_blocks_grid=grid)
            logits, self._cache = self._prefill_paged(
                self.params, self._cache, jnp.asarray(tokens),
                jnp.int32(len(suffix)), jnp.int32(prefix_len),
                jnp.asarray(row))
            self._page_tables[slot] = row
            self.prefill_tokens += len(suffix)
            page_ids = row[:plan.total_pages].copy()
            if self._prefix is not None:
                self._prefix.insert(enc, row)
                if prefix_len:
                    self.prefix_hits += 1
                else:
                    self.prefix_misses += 1
                self.prefix_tokens_shared += prefix_len
        else:
            page_ids = None
            prefix_len = 0
            pad_len = self._pad_bucket(L)
            tokens, lengths = self.tok.pad_batch([enc], pad_len)
            logits, lane = self._prefill(self.params, jnp.asarray(tokens),
                                         jnp.asarray(lengths))
            self._cache = self._insert(self._cache, lane, np.int32(slot))
            self.prefill_tokens += L
        self._key, sub = jax.random.split(self._key)
        first = self._sample(logits,
                             jnp.asarray([request.temperature], jnp.float32),
                             sub)
        pending = int(jax.block_until_ready(first)[0])
        self.prefill_wall_s += time.perf_counter() - t0

        rid = self._next_req_id
        self._next_req_id += 1
        self._slots[slot] = _Slot(rid, request, budget, L, pending,
                                  admitted_at=self._clock(),
                                  page_ids=page_ids, enc=enc)
        self._tokens[slot] = pending
        self._positions[slot] = L
        self._temps[slot] = request.temperature
        self.peak_active = max(self.peak_active, self.active_slots)
        sp.set(rid=rid, prompt_tokens=L, prefix_tokens=prefix_len)
        return rid

    def step(self) -> List[EngineCompletion]:
        """One pump of the pool: harvest pending tokens (retiring finished
        sequences, freeing their slot and page references), then dispatch
        and immediately collect ONE fixed-shape device step — a fused
        decode, or in budget mode a fused chunked-prefill + decode — for
        whatever remains active. Schedulers wanting async overlap call
        :meth:`harvest` / :meth:`dispatch` per engine and :meth:`collect`
        at the end of the round instead."""
        done = self.harvest()
        self.dispatch()
        self.collect()
        return done

    def harvest(self) -> List[EngineCompletion]:
        """Emit pending tokens and retire finished sequences (freeing their
        slot and page references). Mid-prefill residents (budget mode,
        ``pending is None``) have nothing to emit and are skipped."""
        if self.dead:
            raise EngineError("step: engine crashed; restart() first")
        with span("engine.harvest") as sp:
            done: List[EngineCompletion] = []
            now = self._clock()
            for i, s in enumerate(self._slots):
                if s is None or s.pending is None:
                    continue
                finished = (s.pending == self.tok.eos_id
                            or len(s.out_ids) >= s.budget)
                if not finished:
                    s.out_ids.append(s.pending)
                    finished = len(s.out_ids) >= s.budget
                if finished:
                    ft = (s.first_token_at if s.first_token_at is not None
                          else s.admitted_at)
                    done.append(EngineCompletion(
                        s.req_id, s.request, self.tok.decode(s.out_ids),
                        s.out_ids, s.prompt_tokens, len(s.out_ids),
                        time_in_engine_s=max(now - s.admitted_at, 0.0),
                        ttft_s=max(ft - s.admitted_at, 0.0)))
                    self._free(i)
            sp.set(finished=len(done))
        return done

    def _count_append(self, prefix_len: int, total_len: int, pad: int):
        """Add one paged prefill's append-kernel walk (:func:`append_walk`
        over a ``pad``-token suffix) to the engine's counters; returns
        ``(walked, grid)`` for the caller's span. Host arithmetic only."""
        walked, grid = append_walk(prefix_len, total_len, pad,
                                   self.pages_per_slot, self.page_size)
        self.append_blocks_walked += walked
        self.append_blocks_grid += grid
        return walked, grid

    def _pick_chunk(self, n_decode: int):
        """Budget policy: which mid-prefill resident advances this step,
        and by how many tokens. Highest priority first (interactive SLO
        before batch, then admission order). Decode rows spend one budget
        token each; the chunk gets what is left, capped at
        ``prefill_chunk``. A fully decode-consumed budget still yields a
        small chunk for an *interactive* head (starvation guard — first
        tokens are what the interactive SLO is about); with no decodes
        resident the chunk takes the whole ``prefill_chunk``."""
        cands = [(0 if s.request.slo == "interactive" else 1, s.req_id, i, s)
                 for i, s in enumerate(self._slots)
                 if s is not None and s.pending is None]
        if not cands:
            return None
        _, _, ci, cs = min(cands)
        remaining = cs.prompt_tokens - cs.prefill_done
        leftover = self.step_token_budget - n_decode
        if n_decode == 0:
            clen = min(self.prefill_chunk, remaining)
        elif leftover > 0:
            clen = min(self.prefill_chunk, remaining, leftover)
        elif cs.request.slo == "interactive":
            clen = min(8, self.prefill_chunk, remaining)
        else:
            return None
        return (ci, cs, clen)

    def dispatch(self) -> None:
        """Launch the next device step WITHOUT blocking (JAX async
        dispatch): the fixed-shape decode for every live decode row, fused
        — in budget mode — with one bounded prefill chunk chosen by
        :meth:`_pick_chunk`. Results are fetched by :meth:`collect`; a
        second dispatch before that is an error. No-op when nothing is
        resident (or, budget mode, nothing fits the policy this step)."""
        if self.dead:
            raise EngineError("dispatch: engine crashed; restart() first")
        if self._outstanding is not None:
            raise EngineError(
                "dispatch: a step is already in flight; collect() first")
        with span("engine.dispatch") as sp:
            with span("engine.prepare"):
                dec = [(i, s.req_id) for i, s in enumerate(self._slots)
                       if s is not None and s.pending is not None]
                chunk = (self._pick_chunk(len(dec)) if self.budget_mode
                         else None)
                if not dec and chunk is None:
                    return
                t0 = time.perf_counter()
                step = (self.budget_steps if self.budget_mode
                        else self.decode_rounds)
                out = {"t0": t0, "step": step, "dec": dec, "dec_tokens": None,
                       "chunk": None}
                if self.budget_mode:
                    self.budget_steps += 1
                    self.budget_tokens_used += len(dec) + (
                        chunk[2] if chunk else 0)
                if chunk is not None:
                    ci, cs, clen = chunk
                    lo = cs.prefill_done
                    ctoks, _ = self.tok.pad_batch([cs.enc[lo:lo + clen]],
                                                  self._chunk_pad)
                    finishing = lo + clen >= cs.prompt_tokens
                    walked, grid = self._count_append(lo, lo + clen,
                                                      self._chunk_pad)
                    sp.set(step=step, kind="fused" if dec else "prefill",
                           decode_rows=len(dec), chunk_rid=cs.req_id,
                           chunk_tokens=clen,
                           first_chunk=int(lo == cs.prefix_tokens),
                           final_chunk=int(finishing),
                           append_blocks_walked=walked,
                           append_blocks_grid=grid)
                else:
                    sp.set(step=step, kind="decode", decode_rows=len(dec))
                # the host-to-device copies in a span of their own, so that
                # device idle inside prepare tells them from the host's work
                with span("engine.prepare.upload"):
                    dec_args = ()
                    if dec:
                        dec_args = (jnp.asarray(self._tokens)[:, None],
                                    jnp.asarray(self._positions))
                        if self.kv_layout == "paged":
                            dec_args += (jnp.asarray(self._page_tables),)
                    if chunk is not None:
                        chunk_args = (jnp.asarray(ctoks), jnp.int32(clen),
                                      jnp.int32(lo),
                                      jnp.asarray(cs.page_row))
            with span("engine.launch") as lp:
                traces = sum(self.trace_counts.values())
                if chunk is None:
                    dec_logits, self._cache = self._decode(
                        self.params, self._cache, *dec_args)
                elif dec:
                    dec_logits, chunk_logits, self._cache = self._fused(
                        self.params, self._cache, *dec_args, *chunk_args)
                else:
                    chunk_logits, self._cache = self._prefill_paged(
                        self.params, self._cache, *chunk_args)
                lp.set(compiled=sum(self.trace_counts.values()) - traces)
            with span("engine.sample"):
                if chunk is not None:
                    ctok = None
                    if finishing:
                        # only the FINAL chunk's logits are first-token ones
                        self._key, sub = jax.random.split(self._key)
                        ctok = self._sample(
                            chunk_logits,
                            jnp.asarray([cs.request.temperature], jnp.float32),
                            sub)
                    out["chunk"] = (ci, cs.req_id, clen, finishing, ctok)
                if dec:
                    self.decode_rounds += 1
                    if chunk is not None:
                        self.mixed_steps += 1
                    self._key, sub = jax.random.split(self._key)
                    out["dec_tokens"] = self._sample(
                        dec_logits, jnp.asarray(self._temps), sub)
            self._outstanding = out

    def collect(self) -> None:
        """Block on the in-flight step (if any) and apply its results
        host-side: feed sampled decode tokens back as the next pending
        token, advance the chunk owner's ``prefill_done``, and — on the
        final chunk — unmask its decode row, stamp ``first_token_at`` and
        insert the now-complete prompt into the prefix index. Results are
        applied only to slots still holding the same request in the same
        phase, so a preempt/cancel/crash that raced the in-flight step is
        simply dropped (see the module docstring's hazard notes)."""
        if self._outstanding is None:
            return
        out, self._outstanding = self._outstanding, None
        with span("engine.collect", step=out["step"]):
            with span("engine.collect.wait"):
                nxt = None
                if out["dec_tokens"] is not None:
                    nxt = np.asarray(jax.block_until_ready(out["dec_tokens"]))
                ch = out["chunk"]
                ctok_val = None
                if ch is not None and ch[4] is not None:
                    ctok_val = int(jax.block_until_ready(ch[4])[0])
            with span("engine.collect.apply") as sp:
                wall = time.perf_counter() - out["t0"]
                if out["dec"]:
                    self.decode_wall_s += wall
                else:
                    self.prefill_wall_s += wall
                for i, rid in out["dec"]:
                    s = self._slots[i]
                    if s is None or s.req_id != rid or s.pending is None:
                        continue      # retired/preempted while in flight
                    s.pending = int(nxt[i])
                    self._tokens[i] = s.pending
                    self._positions[i] += 1
                if ch is not None:
                    ci, rid, clen, finishing, _ = ch
                    s = self._slots[ci]
                    if s is not None and s.req_id == rid and s.pending is None:
                        s.prefill_done += clen
                        self.prefill_tokens += clen
                        self.prefill_chunks += 1
                        if finishing:
                            s.pending = ctok_val
                            self._tokens[ci] = ctok_val
                            self._positions[ci] = s.prompt_tokens
                            self._page_tables[ci] = s.page_row
                            s.first_token_at = self._clock()
                            if self._prefix is not None:
                                self._prefix.insert(s.enc, s.page_row)
                            sp.set(first_token_rid=rid)

    def _free(self, slot: int) -> None:
        s = self._slots[slot]
        if s is not None and s.page_ids is not None:
            # drop one reference per page; decrement-to-zero pages the
            # prefix index values are retained (LRU) instead of freed
            self._allocator.free(
                s.page_ids,
                retain=self._prefix.owns if self._prefix else None)
            self._page_tables[slot] = TRASH_PAGE
        self._slots[slot] = None
        self._tokens[slot] = self.tok.pad_id
        self._positions[slot] = 0     # inactive lanes park at position 0
        self._temps[slot] = 0.0

    def preempt(self, req_id: int) -> PreemptedRequest:
        """Reclaim a resident request mid-decode and return a resumable
        snapshot. The slot and every page reference are released exactly as
        on normal retirement (private suffix pages go back to the
        allocator; shared prefix pages the index values park in the LRU
        pool), so page accounting balances to the admission-time state.

        The snapshot excludes the sampled-but-unemitted pending token:
        resuming re-admits ``prompt_ids = enc + emitted_ids`` (through
        :attr:`Request.prompt_ids`), the prefix cache serves the original
        prompt's pages, only the generated suffix is recomputed, and greedy
        decode re-derives the pending token from identical logits — so a
        preempted-then-resumed greedy request is token-identical to an
        uninterrupted run. Raises :class:`EngineError` for unknown ids."""
        if self.dead:
            raise EngineError(
                "preempt: engine crashed — nothing survives a crash; the "
                "scheduler reaps lost residents instead of preempting them")
        slot = next((i for i, s in enumerate(self._slots)
                     if s is not None and s.req_id == req_id), None)
        if slot is None:
            raise EngineError(f"preempt: request {req_id} is not resident")
        s = self._slots[slot]
        snap = PreemptedRequest(
            req_id=s.req_id, request=s.request, prompt_ids=list(s.enc),
            emitted_ids=list(s.out_ids), prompt_tokens=s.prompt_tokens,
            budget_left=s.budget - len(s.out_ids))
        self._free(slot)
        self.preemptions += 1
        return snap

    def audit(self) -> Dict[str, int]:
        """Page-accounting audit: cross-check the allocator's free list, LRU
        pool and refcounts against the resident slots' page mappings (every
        page exactly one of FREE/CACHED/ACTIVE, populations summing to
        ``num_pages``, refcount == number of slots mapping the page).
        This is the DST page-arena oracle, also called at the end of every
        bench ``--check``. Raises :class:`PagingError` on any breach.
        Contiguous engines have no allocator and dead engines' device
        bookkeeping is declared lost until :meth:`restart` — both return a
        trivial report instead of being checked."""
        if self._allocator is None or self.dead:
            return {"num_pages": 0, "free": 0, "cached": 0, "active": 0,
                    "skipped": 1}
        mapped: Dict[int, int] = {}
        for s in self._slots:
            if s is not None and s.page_ids is not None:
                for pid in s.page_ids:
                    pid = int(pid)
                    mapped[pid] = mapped.get(pid, 0) + 1
        return self._allocator.audit(mapped)

    def assert_quiescent(self) -> Dict[str, int]:
        """Audit an engine that should be fully drained: no resident slots,
        and every page either free or parked in the LRU pool (ACTIVE count
        zero — anything else is a leaked reference). Raises
        :class:`EngineError` / :class:`PagingError` on violation; returns
        the audit report. Dead engines are skipped (restart rebuilds cold)."""
        if self.dead:
            return {"num_pages": 0, "free": 0, "cached": 0, "active": 0,
                    "skipped": 1}
        if self.has_active:
            raise EngineError(
                f"assert_quiescent: {self.active_slots} slot(s) still "
                f"resident")
        rep = self.audit()
        if rep.get("active", 0):
            raise EngineError(
                f"assert_quiescent: page leak — {rep['active']} page(s) "
                f"still referenced with no resident slots")
        return rep

    def invalidate_prefix_cache(self) -> int:
        """Drop every prefix-cache entry (knowledge rotation made cached
        retrieved-context prefixes stale). Bumps the allocator generation
        so memoized admission plans re-match, and leaves refcount-0 pages
        in the LRU pool unowned — reclaimed on demand, never served again.
        Returns the number of index entries dropped (0 when the prefix
        cache is disabled or the layout is contiguous)."""
        if self._prefix is None:
            return 0
        n = self._prefix.clear()
        self._allocator.bump_generation()
        self._plan_cache = None
        return n

    # ------------------------------------------------------------------
    # Hard failure: crash / restart
    # ------------------------------------------------------------------
    def crash(self) -> List[int]:
        """Hard failure: the engine process/device is gone. Every resident
        request dies with it (their generated-so-far tokens included —
        unlike :meth:`preempt`, nothing is snapshotted), the page arena,
        allocator and prefix index are lost, and the engine refuses all
        work (``dead``) until :meth:`restart`. Returns the engine-local
        request ids of the residents that were lost, so a scheduler can
        reap its bookkeeping for them."""
        if self.dead:
            raise EngineError("crash: engine is already dead")
        lost = [s.req_id for s in self._slots if s is not None]
        self.dead = True
        self.crashes += 1
        # host-side slot state is wiped immediately; the device arena and
        # page bookkeeping are rebuilt cold by restart()
        self._slots = [None] * self.max_batch
        self._tokens[:] = self.tok.pad_id
        self._positions[:] = 0
        self._temps[:] = 0.0
        self._plan_cache = None
        self._outstanding = None     # in-flight device step died with it
        return lost

    def restart(self) -> None:
        """Rebuild a COLD engine after :meth:`crash`: zeroed KV arena,
        fresh allocator and prefix index, empty slot pool, and a bumped
        :attr:`engine_generation` (so any stale external reference —
        scheduler resident keys, memoized plans — is detectably invalid).
        The jitted functions are kept: shapes and dtypes are unchanged,
        so a restarted engine serves without re-tracing. Request ids keep
        counting up across restarts — a pre-crash id can never collide
        with a post-restart admission."""
        if not self.dead:
            raise EngineError("restart: engine has not crashed")
        if self.kv_layout == "paged":
            arena_defs = self.model.paged_cache_defs(self.num_pages + 1,
                                                     self.page_size)
            self._cache = _tmap(lambda d: jnp.zeros(d.shape, d.dtype),
                                arena_defs)
            self._allocator = PageAllocator(self.num_pages)
            if self._prefix is not None:
                self._prefix = PrefixCache(self.page_size)
                self._allocator.evict_cb = self._prefix.forget
            self._page_tables = np.full(
                (self.max_batch, self.pages_per_slot), TRASH_PAGE, np.int32)
        else:
            pool_defs = self.model.cache_defs(self.max_batch)
            self._cache = _tmap(lambda d: jnp.zeros(d.shape, d.dtype),
                                pool_defs)
        self._plan_cache = None
        self.engine_generation += 1
        self.dead = False

    # ------------------------------------------------------------------
    # Batch conveniences on top of the pool
    # ------------------------------------------------------------------
    def generate(self, requests: Sequence[Request]
                 ) -> Tuple[List[str], GenStats]:
        """Continuously-batched generation: requests are admitted as slots
        (and pages) free up, so any number of requests stream through
        ``max_batch`` lanes. Output order matches input order."""
        return self._pump_all(requests, continuous=True)

    def generate_static(self, requests: Sequence[Request]
                        ) -> Tuple[List[str], GenStats]:
        """Static-batch baseline: admit one batch (<= max_batch), then block
        until EVERY sequence finishes — no mid-decode admission. Kept for
        benchmarking and equivalence testing against the continuous path.
        With a deliberately small page pool the batch may not fit at once;
        size ``num_pages`` for the worst case when using this path."""
        if not 0 < len(requests) <= self.max_batch:
            raise EngineError(
                f"static batch of {len(requests)} requests exceeds the "
                f"bounds (1..{self.max_batch})")
        return self._pump_all(requests, continuous=False)

    def _pump_all(self, requests: Sequence[Request], *, continuous: bool
                  ) -> Tuple[List[str], GenStats]:
        if self.dead:
            raise EngineError("engine crashed; restart() first")
        if self.has_active:
            raise EngineError("engine already has resident requests")
        bad = next((r for r in requests if not self.fits(r)), None)
        if bad is not None:
            raise EngineError(
                f"request with {len(self._encode(bad))} prompt tokens can "
                f"never fit max_seq {self.max_seq}; the pump loop would "
                "spin on it forever")
        p0, d0 = self.prefill_wall_s, self.decode_wall_s
        t0 = self.trace_counts["prefill"]
        h0, m0, s0 = (self.prefix_hits, self.prefix_misses,
                      self.prefix_tokens_shared)
        ms0, pc0 = self.mixed_steps, self.prefill_chunks
        queue = list(requests)
        rid_to_idx: Dict[int, int] = {}
        comps: Dict[int, EngineCompletion] = {}
        if not continuous:                      # one up-front batch, no more
            for i, r in enumerate(queue):
                rid_to_idx[self.admit(r)] = i
            queue = []
        while queue or self.has_active:
            while continuous and queue and self.can_admit(queue[0]):
                req = queue.pop(0)
                rid_to_idx[self.admit(req)] = len(requests) - len(queue) - 1
            for ec in self.step():
                comps[rid_to_idx[ec.req_id]] = ec
        ordered = [comps[i] for i in range(len(requests))]
        stats = GenStats(
            prompt_tokens=sum(c.prompt_tokens for c in ordered),
            new_tokens=sum(c.new_tokens for c in ordered),
            prefill_s=self.prefill_wall_s - p0,
            decode_s=self.decode_wall_s - d0,
            prefill_traces=self.trace_counts["prefill"] - t0,
            prefix_hits=self.prefix_hits - h0,
            prefix_misses=self.prefix_misses - m0,
            prefix_tokens_shared=self.prefix_tokens_shared - s0,
            mixed_steps=self.mixed_steps - ms0,
            prefill_chunks=self.prefill_chunks - pc0,
            budget_utilization=self.budget_utilization)
        return [c.text for c in ordered], stats

    # ------------------------------------------------------------------
    def warmup(self, prompt_lens: Iterable[int] = (1,)) -> None:
        """Pre-compile every fixed-shape function (decode, sample, page
        copy / insert) and EVERY reachable prefill bucket up to the
        largest implied by ``prompt_lens``, leaving the pool idle. Smaller
        buckets are compiled too because prefix-cache hits shrink the
        prefilled suffix below the prompt length. In budget mode the
        power-of-two sweep collapses to the single chunk bucket (the only
        prefill shape :meth:`dispatch` can ever issue) plus the fused
        step — ``prompt_lens`` no longer matters, and warmup compiles
        O(1) functions instead of ``log2(max_seq)`` unused ones. Lets
        benchmarks separate compile from serve time."""
        if self.dead:
            raise EngineError("cannot warm up a crashed engine")
        if self.has_active:
            raise EngineError("cannot warm up a busy engine")
        if self.budget_mode:
            buckets = list(self.pad_buckets)     # just the chunk bucket
        else:
            cap = max((self._pad_bucket(max(n, 1)) for n in prompt_lens),
                      default=8)
            buckets = [b for b in self.pad_buckets if b <= cap]
        key = jax.random.PRNGKey(0)
        paged = self.kv_layout == "paged"
        # rebind the pool at every call: the cache argument is donated, so
        # the old buffer is dead after each decode/prefill/copy (pool is
        # idle — a paged warmup scribbles only on the trash page, a
        # contiguous one on lane 0, which is rewritten on admission)
        for pad_len in buckets:
            toks = jnp.zeros((1, pad_len), jnp.int32)
            if paged:
                trash_row = jnp.full((self.pages_per_slot,), TRASH_PAGE,
                                     jnp.int32)
                logits, self._cache = self._prefill_paged(
                    self.params, self._cache, toks, jnp.int32(1),
                    jnp.int32(0), trash_row)
            else:
                logits, lane = self._prefill(
                    self.params, toks, jnp.asarray([pad_len], jnp.int32))
                self._cache = self._insert(self._cache, lane, np.int32(0))
            self._sample(logits, jnp.asarray([0.0], jnp.float32), key)
        if paged:
            self._cache = self._copy_page(self._cache, jnp.int32(TRASH_PAGE),
                                          jnp.int32(TRASH_PAGE))
        if self.budget_mode:
            # warm the fused step: all-trash rows, 1-token chunk — writes
            # land only on the trash page, results are discarded
            trash_row = jnp.full((self.pages_per_slot,), TRASH_PAGE,
                                 jnp.int32)
            _, cl, self._cache = self._fused(
                self.params, self._cache,
                jnp.asarray(self._tokens)[:, None],
                jnp.asarray(self._positions),
                jnp.asarray(self._page_tables),
                jnp.zeros((1, self._chunk_pad), jnp.int32),
                jnp.int32(1), jnp.int32(0), trash_row)
            self._sample(cl, jnp.asarray([0.0], jnp.float32), key)
        args = (self.params, self._cache,
                jnp.asarray(self._tokens)[:, None],
                jnp.asarray(self._positions))
        if paged:
            args += (jnp.asarray(self._page_tables),)
        _, self._cache = self._decode(*args)
        self._sample(jnp.zeros((self.max_batch, self.cfg.vocab), jnp.float32),
                     jnp.asarray(self._temps), key)


def make_edge_engine(*, max_seq: int = 512, max_batch: int = 8,
                     seed: int = 0, **kw) -> ServingEngine:
    """Default edge SLM: reduced qwen2-0.5b (byte vocab capable). Extra
    keyword args (kv_layout, page_size, num_pages, prefix_cache, ...) pass
    through."""
    from repro.configs import get_config
    cfg = get_config("qwen2-0.5b", reduced=True)
    return ServingEngine(cfg, max_seq=max_seq, max_batch=max_batch, seed=seed,
                         **kw)


def make_cloud_engine(*, max_seq: int = 512, max_batch: int = 8,
                      seed: int = 0, **kw) -> ServingEngine:
    """Cloud-tier engine: reduced qwen2-72b family (the paper's large-LLM
    arm), byte-vocab capable. Extra keyword args pass through."""
    from repro.configs import get_config
    cfg = get_config("qwen2-72b", reduced=True)
    return ServingEngine(cfg, max_seq=max_seq, max_batch=max_batch, seed=seed,
                         **kw)


__all__ = ["ServingEngine", "Request", "GenStats", "EngineCompletion",
           "EngineError", "PreemptedRequest", "make_edge_engine",
           "make_cloud_engine"]
