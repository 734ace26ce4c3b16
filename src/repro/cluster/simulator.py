"""The EACO-RAG tiered serving simulator: real retrieval + gating + adaptive
knowledge updates over an edge-cloud topology.

Two backends:

* ``backend="oracle"`` (default) — the calibrated accuracy oracle
  (DESIGN.md §5) plus the paper's cost model score each gate decision
  analytically; token counts are drawn from Table 1 distributions. This is
  the fast path used by the Table 4/5/6 benchmarks.

* ``backend="engines"`` — the closed loop. Every gate ``Decision`` builds
  the real prompt (query + retrieved context from the edge stores /
  GraphRAG) and submits it through a :class:`TierScheduler` to per-tier
  :class:`ServingEngine` pools: edge SLM engines (reduced qwen2-0.5b,
  paged KV + prefix cache on) and one larger cloud-tier engine (reduced
  qwen2-72b family). Arrivals are bursty multi-user
  (``WorkloadGenerator.bursts``), and everything — arrival stamps, queue
  waits, engine service time, network transit — composes on ONE
  :class:`VirtualClock`: per scheduling round the clock advances by the
  engines' service time, either ``engine_time="modeled"`` (the tier spec's
  prefill/decode rates applied to the REAL token counts the engines
  processed — deterministic under a fixed seed) or ``"wall"`` (the
  measured jit compute time). Completions flow back as measured delay
  (queue wait + time in engine + network transit) and real token counts
  feeding the cost model and the gate's SafeOBO update — replacing the
  drawn ``OUT_TOKENS``.

Policies: "eaco" (collaborative gate) or "fixed:<arm_idx>" baselines —
fixed:0 = SLM-only, fixed:1 = naive edge RAG, fixed:2 = 3B+GraphRAG,
fixed:3 = 72B+GraphRAG (the paper's Table 4 rows).

**Overload robustness (engines backend).** The failover/escalation state
machine sits above the scheduler's preempt/shed/timeout machinery
(:mod:`repro.serving.scheduler`):

* *watermark escalation* — an edge-bound query arriving while the edge
  pool's saturation is at/above ``overload_watermark`` is routed straight
  to the cloud tier (``failed_over`` counter, ``StepLog.rerouted``), and
  ``_finalize`` prices it with the CLOUD tier spec + cloud transit, so the
  cost model and the SafeOBO update see the TRUE cost/delay of the
  re-route, not the arm's nominal tier.
* *retry with bounded exponential backoff* — a scheduler ``Shed``
  (deadline / timeout / overload) or a completion dropped in transit
  (:class:`~repro.cluster.faults.FaultInjector`) re-submits the query —
  edge failures escalate to cloud — after ``failover_backoff_s * 2**n``
  (capped), with a fresh deadline. After ``failover_max_retries``
  resubmissions the query is terminal: ``outcome="shed"`` (gave up on a
  scheduler shed) or ``"failed"`` (lost completion), logged with zero
  cost and ``correct=False``, never silently dropped.
* *conservation* — ``submitted == completed + shed + failed`` over the
  counters, with nothing left pending; :meth:`EACOCluster.conservation_ok`
  checks it and ``benchmarks/cluster_bench.py --check`` gates on it.
* the gate learns only from SERVED completions; terminal drops surface in
  counters/metrics instead of feeding SafeOBO a synthetic reward.

**Hard-failure model (engines backend).** Crashes, partitions, and the
health machinery that keeps the loop serving through them:

* *engine crashes* — ``FaultInjector.crashed`` windows call
  :meth:`ServingEngine.crash` on entry (ALL device state lost: slots,
  arena, prefix index) and :meth:`restart` on exit (cold engine, bumped
  ``engine_generation``). The scheduler is built with
  ``requeue_lost=False`` here, so reaped residents surface as typed
  ``Shed("engine_lost")`` outcomes and flow through the SAME failover
  path as any other shed — bounded backoff, edge->cloud escalation,
  typed terminal outcomes — preserving request conservation. Only
  schedule-driven crashes are schedule-restarted; an engine a test
  crashed by hand stays down.
* *circuit breakers* — two layers. Per-ENGINE breakers inside the
  scheduler (``engine_breaker_threshold``) stop admission onto a flaky
  pool member. Per-TIER breakers here (``breaker_threshold``) gate
  routing: a query bound for a tier whose breaker is open is rerouted to
  the other tier (``breaker_reroutes``), tier failures/successes feed
  the breaker from ``_handle_failure``/``_finalize``.
* *hedging* (``hedge_s``) — the scheduler fires an edge->cloud backup
  for interactive requests past the latency threshold; first completion
  wins. A hedged completion served by the cloud pays cloud transit on
  top of its route (``_finalize``), and hedges are gated off while the
  link is partitioned.
* *partitions* — while ``FaultInjector.partitioned`` holds: the gate's
  arm-availability mask excludes cloud-dependent arms (cloud generation
  AND GraphRAG retrieval), failover retries stay on the edge instead of
  escalating, hedges don't fire, and knowledge updates DEFER (epoch
  advances, nothing ships). Edges keep serving from their last-synced
  chunk set; edge-RAG completions from a store behind the newest epoch
  are flagged ``stale_epoch`` — degraded, never silent. On heal,
  anti-entropy (:meth:`AdaptiveKnowledgeUpdater.sync`) replays deferred
  refreshes and invalidates edge prefix caches. In-flight cloud work
  completes across a partition onset (the link model covers the
  control-plane update path, not queued generations), and fixed:<arm>
  baseline policies ignore the mask — they are the paper's
  non-adaptive comparison points.

**Fault model and deterministic simulation testing.** The full fault
vocabulary above is represented as explicit event timelines
(:class:`~repro.cluster.faults.FaultEvent`): every fault is a record
``(t, kind, duration, victim, magnitude)`` with
``kind in {"stall", "crash", "partition", "net_spike", "drop"}``, active
on the half-open virtual-time window ``[t, t + duration)``. The periodic
``FaultConfig`` formulas used by the hand-authored chaos cases lazily
expand into the same records, so a hand schedule and a fuzzer schedule
are the same object — replayable, serializable, shrinkable.

:mod:`repro.cluster.dst` builds FoundationDB-style deterministic
simulation testing on top: a seeded generator composes overlapping fault
+ workload timelines (arrival bursts, knowledge-update bursts, SLO-mix
shifts on top of the five fault kinds), a harness drives real engine
pools + scheduler + knowledge updater through them on one virtual clock,
and after EVERY pump re-checks the invariant oracles — request
conservation, generation-fence legality, breaker state-machine legality,
monotone knowledge epochs with no unflagged ``stale_epoch`` completion,
page-arena audit (free + cached + active == num_pages; refcount == slot
mappings; zero leaks at quiescence), and greedy token identity for
resumed/hedged work. Failures record a JSON trace that replays
byte-identically and ddmin-shrinks to a minimal event schedule
(``make fuzz`` / ``benchmarks/dst_bench.py``).

All knobs default off (no shedding, no timeout, no watermark, no faults,
no breakers, no hedging), which reproduces the pre-overload closed loop
exactly.
"""
from __future__ import annotations

import heapq
import itertools
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.clock import VirtualClock
from repro.core.cost_model import (
    PAPER_CLOUD, PAPER_EDGE, RETRIEVAL_DELAY_S, CostWeights, TierSpec,
    generation_delay, inference_tflops, modeled_decode_round_s,
    modeled_prefill_s, time_cost_tflops, total_cost,
)
from repro.core.edge_assist import edge_assisted_search, query_keywords, select_edge
from repro.core.gating import (
    PAPER_ARMS, Arm, CollaborativeGate, Decision, QueryContext,
)
from repro.core.knowledge import AdaptiveKnowledgeUpdater, KnowledgeUpdateConfig
from repro.cluster.faults import FaultConfig, FaultInjector
from repro.cluster.network import NetworkConfig, NetworkModel
from repro.cluster.oracle import AccuracyOracle
from repro.cluster.workload import QueryEvent, WorkloadConfig, WorkloadGenerator
from repro.data.corpus import Corpus
from repro.retrieval.graph_rag import KnowledgeGraph
from repro.retrieval.store import VectorStore
from repro.serving.engine import (
    Request, ServingEngine, make_cloud_engine, make_edge_engine,
)
from repro.serving.health import CircuitBreaker
from repro.serving.scheduler import Completion, TierScheduler

# calibration: the paper uses ~500-token chunks; our synthetic chunks are
# ~95 tokens, so prompt sizes are scaled to match Table 1 token statistics.
# The cloud LLM receives a summarized GraphRAG context (the paper's 72B
# prompt is ~4.8k tokens by its cost arithmetic, vs ~9k for the 3B path).
PROMPT_SCALE = {("none", "local"): 1.0, ("edge", "local"): 7.0,
                ("graph", "local"): 8.0, ("graph", "cloud"): 4.4}
OUT_TOKENS = {  # Table 1 output-token distributions (mean, std)
    ("none", "local"): (27.21, 14.83),
    ("edge", "local"): (26.59, 19.81),
    ("graph", "local"): (142.7, 91.58),
    ("graph", "cloud"): (142.7, 91.58),
}


def _count_tokens(text: str) -> float:
    return len(text.split()) * 1.3


@dataclass
class StepLog:
    t: float
    edge_id: str
    arm: int
    arm_name: str
    correct: bool
    delay: float
    cost: float
    u_r: float
    u_d: float
    hit: bool
    overlap: float
    multihop: bool
    in_tokens: float
    out_tokens: float
    phase: str = ""
    retrieved: List[str] = field(default_factory=list)
    tier: str = ""                  # engines backend: serving tier name
    queue_wait_s: float = 0.0       # engines backend: submit -> admission
    engine_s: float = 0.0           # engines backend: admission -> finish
    outcome: str = "ok"             # "ok" | "shed" | "failed" (terminal)
    slo: str = "interactive"        # SLO class the query was served under
    rerouted: bool = False          # escalated off its nominal tier
    attempts: int = 0               # failover resubmissions before terminal
    hedged: bool = False            # served by the backup hedge submission
    epoch: int = 0                  # serving edge's knowledge epoch
    stale_epoch: bool = False       # edge-RAG answer from a stale epoch


@dataclass
class SimConfig:
    n_edges: int = 6
    edge_capacity: int = 1000
    retrieval_k: int = 5
    graph_retrieval_k: int = 10
    qos_min_acc: float = 0.9
    qos_max_delay: float = 5.0
    warmup_steps: int = 300
    beta: float = 2.0
    delta1: float = 1.0
    delta2: float = 1.0
    update_trigger: int = 20
    max_chunks_per_update: int = 500
    initial_fill: float = 0.4       # fraction of capacity pre-seeded
    drift_period: float = 250.0
    edge_assist_enabled: bool = True   # False = local-store-only (Fig. 4)
    seed: int = 0
    # ---- engines backend (backend="engines") --------------------------
    n_edge_engines: int = 2         # pool size behind the "edge" tier
    edge_max_seq: int = 192
    edge_max_batch: int = 4
    cloud_max_seq: int = 256
    cloud_max_batch: int = 4
    engine_page_size: int = 16
    # fused chunked-prefill + decode (None = whole-suffix admission). The
    # virtual-clock pricing below needs no change: decode_rounds / prefill
    # token deltas stay additive under chunking (modeled_mixed_step_s)
    engine_step_token_budget: Optional[int] = None
    engine_prefill_chunk: int = 32
    max_new_slm: int = 16           # decode budget, non-graph arms
    max_new_graph: int = 48         # decode budget, GraphRAG arms
    arrival_period_s: float = 1.0   # virtual seconds between arrival steps
    engine_time: str = "modeled"    # "modeled" (deterministic) | "wall"
    mean_arrivals: float = 1.5      # Poisson mean queries per arrival step
    max_arrivals: int = 6           # burst cap per step
    hot_topic_boost: float = 0.0    # extra interest mass on the hot topic
    # ---- overload robustness (all off by default = pre-overload loop) --
    preemption: bool = True         # scheduler may reclaim residents (only
    #                                 fires across SLO classes, see scheduler)
    shed_overdue: bool = False      # shed queued work past its deadline
    request_timeout_s: Optional[float] = None   # stuck-resident timeout
    overload_watermark: Optional[float] = None  # edge saturation -> cloud
    failover_max_retries: int = 2   # resubmissions before terminal drop
    failover_backoff_s: float = 0.25            # base of 2**n backoff
    failover_backoff_cap_s: float = 2.0
    drain_timeout_s: float = 300.0  # virtual-s wedge guard while draining
    stall_tick_s: float = 0.05      # idle clock step when faults stall all
    # ---- hard failures / health (all off by default) -------------------
    engine_breaker_threshold: Optional[int] = None  # scheduler per-engine
    breaker_threshold: Optional[int] = None         # cluster per-tier
    breaker_reset_s: float = 5.0    # open -> half-open probe delay
    hedge_s: Optional[float] = None  # edge->cloud hedge after this wait


@dataclass
class _Pending:
    """Host-side record of a submitted query, joined to its Completion (or
    carried through failover resubmissions until a terminal outcome)."""
    ev: QueryEvent
    qc: QueryContext
    arm: Arm
    hit: bool
    texts: List[str]
    net_delay_s: float
    phase: str
    request: Request
    tier_name: str = "edge"         # tier currently serving the query
    attempts: int = 0               # resubmissions so far
    rerouted: bool = False          # ever escalated off the nominal tier
    last_reason: str = ""           # last failure reason ("" = none)


class EACOCluster:
    def __init__(self, corpus: Corpus, cfg: Optional[SimConfig] = None,
                 policy: str = "eaco",
                 edge_tier: TierSpec = PAPER_EDGE,
                 cloud_tier: TierSpec = PAPER_CLOUD,
                 oracle: Optional[AccuracyOracle] = None,
                 backend: str = "oracle",
                 engines: Optional[Dict[str, Union[
                     ServingEngine, Sequence[ServingEngine]]]] = None,
                 clock: Optional[VirtualClock] = None,
                 faults: Optional[FaultInjector] = None):
        self.corpus = corpus
        # default built per instance — a shared default SimConfig would let
        # one caller's mutation leak into every later default construction
        self.cfg = cfg = SimConfig() if cfg is None else cfg
        self.policy = policy
        self.edge_tier = edge_tier
        self.cloud_tier = cloud_tier
        if backend not in ("oracle", "engines"):
            raise ValueError(f"unknown backend {backend!r}")
        if cfg.engine_time not in ("modeled", "wall"):
            raise ValueError(f"unknown engine_time {cfg.engine_time!r}")
        self.backend = backend
        self.weights = CostWeights(cfg.delta1, cfg.delta2)
        self.rng = np.random.default_rng(cfg.seed)
        self.oracle = oracle or AccuracyOracle(seed=cfg.seed + 1)
        self.net = NetworkModel(seed=cfg.seed + 2)
        self.workload = WorkloadGenerator(
            corpus, WorkloadConfig(n_edges=cfg.n_edges,
                                   drift_period=cfg.drift_period,
                                   mean_arrivals=cfg.mean_arrivals,
                                   max_arrivals=cfg.max_arrivals,
                                   hot_topic_boost=cfg.hot_topic_boost),
            seed=cfg.seed + 3)
        # cloud knowledge graph over the full corpus
        self.graph = KnowledgeGraph(seed=cfg.seed).build(corpus.chunks)
        self.updater = AdaptiveKnowledgeUpdater(
            self.graph, KnowledgeUpdateConfig(
                update_trigger=cfg.update_trigger,
                max_chunks_per_update=cfg.max_chunks_per_update))
        # edge stores seeded with their initially-popular topics
        self.stores: Dict[str, VectorStore] = {}
        for eid in self.workload.edge_ids:
            store = VectorStore(capacity=cfg.edge_capacity)
            budget = int(cfg.edge_capacity * cfg.initial_fill)
            got: List = []
            for topic in self.workload.popular_topics(eid, k=3):
                got.extend(corpus.chunks_for_topic(topic))
            store.add(got[:budget])
            self.stores[eid] = store
        self.gate = CollaborativeGate(
            qos_min_acc=cfg.qos_min_acc, qos_max_delay=cfg.qos_max_delay,
            warmup_steps=cfg.warmup_steps, beta=cfg.beta, seed=cfg.seed,
            n_edges=cfg.n_edges)
        self.logs: List[StepLog] = []
        # ---- engines backend: one virtual clock, real engine pools -----
        self.clock = VirtualClock() if clock is None else clock
        self.sched: Optional[TierScheduler] = None
        self.faults = faults
        self._pending: Dict[int, _Pending] = {}
        # failover retry queue: (ready_at, seq, pending) — resubmitted once
        # the virtual clock passes ready_at (bounded exponential backoff)
        self._retries: List[Tuple[float, int, _Pending]] = []
        self._retry_seq = itertools.count()
        # request-conservation ledger: submitted == completed + shed +
        # failed once nothing is outstanding (see conservation_ok)
        self.counters: Dict[str, int] = {
            "submitted": 0, "completed": 0, "shed": 0, "failed": 0,
            "failed_over": 0, "retries": 0, "dropped_completions": 0,
            "prefix_invalidations": 0, "engine_crashes": 0,
            "engine_restarts": 0, "breaker_reroutes": 0,
            "anti_entropy_syncs": 0, "hedged_served": 0,
            "stale_served": 0}
        # ---- hard-failure state ----------------------------------------
        self._link_down = False           # edge<->cloud partition active
        self._fault_crashed: set = set()  # (tier, i) crashed BY the schedule
        self.tier_breakers: Dict[str, CircuitBreaker] = {}
        if backend == "engines" and cfg.breaker_threshold is not None:
            self.tier_breakers = {
                t: CircuitBreaker(cfg.breaker_threshold, cfg.breaker_reset_s)
                for t in ("edge", "cloud")}
        if backend == "engines":
            if engines is None:
                engines = self.build_engines()
            self.sched = TierScheduler(
                engines, clock=self.clock, preempt=cfg.preemption,
                shed_overdue=cfg.shed_overdue,
                request_timeout_s=cfg.request_timeout_s,
                # crashes surface as typed engine_lost sheds so the
                # cluster's failover (backoff + escalation) owns recovery
                requeue_lost=False,
                breaker_threshold=cfg.engine_breaker_threshold,
                breaker_reset_s=cfg.breaker_reset_s,
                hedge_s=cfg.hedge_s, hedge_from="edge", hedge_to="cloud",
                hedge_gate=lambda now: not self._link_down)
            if set(self.sched.pools) != {"edge", "cloud"}:
                raise ValueError(
                    f"engines backend needs 'edge' and 'cloud' tiers, got "
                    f"{sorted(self.sched.pools)}")

    # ------------------------------------------------------------------
    def build_engines(self) -> Dict[str, List[ServingEngine]]:
        """Default tier pools: ``n_edge_engines`` reduced-SLM edge engines
        plus one cloud-tier engine, paged KV + prefix cache on."""
        c = self.cfg
        fused = dict(step_token_budget=c.engine_step_token_budget,
                     prefill_chunk=c.engine_prefill_chunk)
        edge = [make_edge_engine(
            max_seq=c.edge_max_seq, max_batch=c.edge_max_batch,
            seed=c.seed + 100 + i, kv_layout="paged",
            page_size=c.engine_page_size, prefix_cache=True, **fused)
            for i in range(c.n_edge_engines)]
        cloud = [make_cloud_engine(
            max_seq=c.cloud_max_seq, max_batch=c.cloud_max_batch,
            seed=c.seed + 200, kv_layout="paged",
            page_size=c.engine_page_size, prefix_cache=True, **fused)]
        return {"edge": edge, "cloud": cloud}

    # ------------------------------------------------------------------
    def _retrieve(self, arm: Arm, ev: QueryEvent):
        """Real retrieval for the chosen source. Returns (texts, hit, sel)."""
        q = ev.qa.question
        if arm.retrieval == "none":
            return [], False, None
        if arm.retrieval == "edge":
            if self.cfg.edge_assist_enabled:
                results, sel = edge_assisted_search(
                    self.stores, q, self.cfg.retrieval_k,
                    local_edge=ev.edge_id)
            else:  # ablation: only the local edge dataset
                results = self.stores[ev.edge_id].search(
                    q, self.cfg.retrieval_k)
                sel = None
            texts = [c.text for c, _ in results]
        else:  # cloud GraphRAG
            results = self.graph.retrieve(q, self.cfg.graph_retrieval_k)
            texts = [c.text for c, _ in results]
            sel = None
        hit = any(ev.qa.answer in t for t in texts)
        return texts, hit, sel

    def _tokens(self, arm: Arm, query: str, texts: List[str]):
        in_t = _count_tokens(query)
        in_t += (sum(_count_tokens(t) for t in texts)
                 * PROMPT_SCALE[(arm.retrieval, arm.generation)])
        mu, sd = OUT_TOKENS[(arm.retrieval, arm.generation)]
        out_t = max(1.0, float(self.rng.normal(mu, sd)))
        return in_t, out_t

    def _tier_and_net(self, arm: Arm, qc: QueryContext
                      ) -> Tuple[TierSpec, float]:
        """Serving tier spec + network transit delay for an (arm, context)."""
        if arm.generation == "local":
            tier = self.edge_tier
            net_delay = qc.d_edge if arm.retrieval == "edge" else 0.005
            if arm.retrieval == "graph":
                net_delay += qc.d_cloud          # fetch context from cloud
        else:
            tier = self.cloud_tier
            net_delay = qc.d_cloud
        net_delay += RETRIEVAL_DELAY_S[(arm.retrieval, arm.generation)]
        return tier, net_delay

    def _execute(self, arm: Arm, ev: QueryEvent, qc: QueryContext,
                 texts: List[str], hit: bool) -> StepLog:
        in_t, out_t = self._tokens(arm, ev.qa.question, texts)
        tier, net_delay = self._tier_and_net(arm, qc)
        delay = generation_delay(tier, in_t, out_t, net_delay)
        u_r = inference_tflops(tier.model_params_b, in_t, out_t)
        u_d = time_cost_tflops(tier, delay)
        cost = total_cost(u_r, u_d, self.weights)
        correct = self.oracle.draw(arm.name, hit=hit, multihop=ev.qa.multihop)
        return StepLog(
            t=ev.t, edge_id=ev.edge_id, arm=arm.idx, arm_name=arm.name,
            correct=correct, delay=delay, cost=cost, u_r=u_r, u_d=u_d,
            hit=hit, overlap=qc.overlap, multihop=ev.qa.multihop,
            in_tokens=in_t, out_tokens=out_t, retrieved=texts)

    def _context(self, ev: QueryEvent) -> QueryContext:
        sel = select_edge(self.stores, ev.qa.question, local_edge=ev.edge_id)
        d_cloud = self.net.cloud(ev.t)
        d_edge = (self.net.edge_local(ev.t) if sel.edge_id == ev.edge_id
                  else self.net.inter_edge(ev.t))
        edge_index = self.workload.edge_ids.index(sel.edge_id) \
            if sel.edge_id in self.workload.edge_ids else 0
        return QueryContext.analyze(ev.qa.question, d_cloud, d_edge,
                                    sel.overlap, sel.edge_id, edge_index)

    def _arm_mask(self) -> Optional[Tuple[bool, ...]]:
        """Arm-availability mask from infrastructure health: a partition
        cuts off every cloud-dependent arm (cloud generation and GraphRAG
        retrieval both need the link), an open tier breaker cuts off the
        arms generating on that tier. ``None`` when everything is
        reachable — which keeps the gate's RNG stream bit-identical to a
        fault-free run — or when NOTHING is (no usable mask: serve on the
        nominal route and let failover handle the outcome)."""
        if self.sched is None:
            return None
        now = self.clock.now()
        edge_b = self.tier_breakers.get("edge")
        cloud_b = self.tier_breakers.get("cloud")
        edge_ok = edge_b is None or edge_b.allow(now)
        cloud_ok = cloud_b is None or cloud_b.allow(now)
        mask = []
        for arm in self.gate.arms:
            ok = True
            if self._link_down and (arm.generation == "cloud"
                                    or arm.retrieval == "graph"):
                ok = False
            if arm.generation == "cloud" and not cloud_ok:
                ok = False
            if arm.generation == "local" and not edge_ok:
                ok = False
            mask.append(ok)
        if all(mask) or not any(mask):
            return None
        return tuple(mask)

    def _decide(self, qc: QueryContext) -> Tuple[Arm, str]:
        if self.policy == "eaco":
            decision = self.gate.decide(qc, available=self._arm_mask())
            return decision.arm, decision.info.get("phase", "")
        return PAPER_ARMS[int(self.policy.split(":")[1])], "fixed"

    def step(self, ev: QueryEvent) -> StepLog:
        """Oracle backend: decide, retrieve ONCE, score analytically. The
        retrieved texts ride on ``StepLog.retrieved`` so callers (and the
        engines backend) never need to re-run retrieval."""
        if self.backend == "engines":
            raise RuntimeError(
                "step() is the oracle path; use submit_query()/run() with "
                "backend='engines'")
        qc = self._context(ev)
        arm, phase = self._decide(qc)
        texts, hit, _ = self._retrieve(arm, ev)
        log = self._execute(arm, ev, qc, texts, hit)
        log.phase = phase
        if self.policy == "eaco":
            self.gate.update(qc, arm, cost=log.cost,
                             accuracy=1.0 if log.correct else 0.0,
                             delay=log.delay)
        # adaptive knowledge update: cloud observes all served queries
        self._observe_and_invalidate(ev)
        self.counters["submitted"] += 1
        self.counters["completed"] += 1
        self.logs.append(log)
        return log

    def _observe_and_invalidate(self, ev: QueryEvent) -> None:
        """Feed the adaptive-knowledge updater; when it SHIPS an update
        (rotating the edge's knowledge chunks), every edge engine's prefix
        cache is invalidated so a stale retrieved-context prefix can never
        serve a post-update query — the next same-context prompt recomputes
        against the rotated knowledge."""
        store = self.stores[ev.edge_id]
        epoch_before = store.epoch
        self.updater.observe_query(
            ev.edge_id, ev.qa.question, store, now=ev.t,
            link_up=not self._link_down)
        # invalidate only when chunks actually SHIPPED (epoch advanced);
        # an update deferred behind a partition changes nothing edge-side
        if store.epoch != epoch_before and self.sched is not None:
            for e in self.sched.pools["edge"]:
                if not e.dead:
                    e.invalidate_prefix_cache()
            self.counters["prefix_invalidations"] += 1

    # ------------------------------------------------------------------
    # Engines backend: gate decision -> real engine -> completion -> update
    # ------------------------------------------------------------------
    def _build_prompt(self, ev: QueryEvent, texts: List[str],
                      max_chars: int) -> str:
        """Retrieved context first (shared across same-topic queries, so the
        prefix cache can share its KV pages), question last; the context is
        truncated to leave room for the question and decode budget."""
        qpart = f"Q: {ev.qa.question}\nA:"
        ctx = " ".join(texts)
        ctx_budget = max(max_chars - len(qpart) - 10, 0)
        if ctx and ctx_budget > 0:
            return f"Context: {ctx[:ctx_budget]}\n{qpart}"
        return qpart[:max_chars]

    def submit_query(self, ev: QueryEvent) -> Request:
        """One gate decision routed to a real engine: decide, retrieve,
        build the prompt, submit to the tier's pool on the virtual clock.
        The SafeOBO update happens when the completion surfaces.

        With ``overload_watermark`` set, an edge-bound query arriving while
        the edge pool's saturation is at/above the watermark escalates
        straight to the cloud tier (recorded as a ``failed_over`` re-route
        with cloud transit added, so cost/delay reflect the true route)."""
        if self.sched is None:
            raise RuntimeError("submit_query() requires backend='engines'")
        cfg = self.cfg
        qc = self._context(ev)
        arm, phase = self._decide(qc)
        texts, hit, _ = self._retrieve(arm, ev)
        tier_name = "edge" if arm.generation == "local" else "cloud"
        _, net_delay = self._tier_and_net(arm, qc)
        rerouted = False
        if (tier_name == "edge" and cfg.overload_watermark is not None
                and self.sched.saturation("edge") >= cfg.overload_watermark):
            tier_name = "cloud"
            rerouted = True
            net_delay += qc.d_cloud          # the re-route pays cloud transit
            self.counters["failed_over"] += 1
        # tier-breaker reroute: an open breaker sheds the whole tier from
        # routing; go to the other tier if ITS breaker allows (when both
        # are open, submit on the nominal tier and let failover recover)
        other = "cloud" if tier_name == "edge" else "edge"
        now_b = self.clock.now()
        b, b_other = (self.tier_breakers.get(tier_name),
                      self.tier_breakers.get(other))
        if (b is not None and not b.allow(now_b)
                and (b_other is None or b_other.allow(now_b))
                and not (other == "cloud" and self._link_down)):
            if other == "cloud":
                net_delay += qc.d_cloud
            tier_name = other
            rerouted = True
            self.counters["breaker_reroutes"] += 1
        max_new = (cfg.max_new_graph if arm.retrieval == "graph"
                   else cfg.max_new_slm)
        max_seq = min(e.max_seq for e in self.sched.pools[tier_name])
        prompt = self._build_prompt(ev, texts, max_seq - max_new - 8)
        req = Request(prompt, max_new_tokens=max_new, slo="interactive")
        now = self.clock.now()
        self.counters["submitted"] += 1
        self._pending[id(req)] = _Pending(ev, qc, arm, hit, texts,
                                          net_delay, phase, req,
                                          tier_name=tier_name,
                                          rerouted=rerouted)
        self.sched.submit(req, tier_name,
                          deadline_s=now + cfg.qos_max_delay, now=now)
        self._observe_and_invalidate(ev)
        return req

    def pump_engines(self) -> List[StepLog]:
        """One scheduling round on the virtual clock: resubmit due failover
        retries, admit + one fused decode step per engine (skipping
        fault-stalled pool members), then advance the clock by the round's
        service time — ``modeled`` (tier rates x real token counts;
        deterministic) or ``wall`` (measured jit seconds). Pools run in
        parallel, so the round costs the SLOWEST engine's time. Completions
        harvested this round close the loop (measured delay and real token
        counts feed the cost model and the gate) unless the fault layer
        drops them in transit; scheduler sheds and dropped completions go
        through the failover path."""
        if self.sched is None:
            raise RuntimeError("pump_engines() requires backend='engines'")
        now = self.clock.now()
        self._apply_fault_transitions(now)
        self._resubmit_ready(now)
        stalled = None
        if self.faults is not None:
            pools = self.sched.pools

            def stalled(t: str, i: int, _now: float = now) -> bool:
                return self.faults.stalled(t, i, _now, len(pools[t]))

        flat = [(t, e) for t, pool in self.sched.pools.items() for e in pool]
        pre = [(e.prefill_tokens, e.decode_rounds,
                e.prefill_wall_s + e.decode_wall_s) for _, e in flat]
        comps = self.sched.pump(now=now, stalled=stalled)
        dt = 0.0
        for (tier_name, e), (p0, r0, w0) in zip(flat, pre):
            if self.cfg.engine_time == "wall":
                dt_e = (e.prefill_wall_s + e.decode_wall_s) - w0
            else:
                spec = (self.edge_tier if tier_name == "edge"
                        else self.cloud_tier)
                # exact under fused chunking too: a budget-mode round is
                # one decode round + its chunk tokens, so this delta form
                # equals summing modeled_mixed_step_s per step
                dt_e = (modeled_prefill_s(spec, e.prefill_tokens - p0)
                        + (e.decode_rounds - r0)
                        * modeled_decode_round_s(spec))
            dt = max(dt, dt_e)
        if dt > 0:
            self.clock.advance(dt)
        t_done = self.clock.now()
        out: List[StepLog] = []
        for c in comps:
            if (self.faults is not None
                    and self.faults.drop_completion(t_done)):
                self.counters["dropped_completions"] += 1
                p = self._pending.pop(id(c.request))
                self._handle_failure(p, "dropped", t_done)
                continue
            out.append(self._finalize(c))
        for s in self.sched.pop_sheds():
            p = self._pending.pop(id(s.request))
            self._handle_failure(p, s.reason, t_done)
        return out

    # ---- hard-failure transitions -------------------------------------
    def _apply_fault_transitions(self, now: float) -> None:
        """Drive the deterministic crash / partition schedules onto real
        state: crash engines entering their dead window, restart them on
        exit (only engines THIS schedule crashed — a manually-crashed
        engine stays down), and on partition heal run anti-entropy so
        deferred knowledge updates ship before the next query is served."""
        if self.faults is None or self.sched is None:
            return
        for tier, pool in self.sched.pools.items():
            for i, e in enumerate(pool):
                want_dead = self.faults.crashed(tier, i, now, len(pool))
                if want_dead and not e.dead:
                    e.crash()
                    self._fault_crashed.add((tier, i))
                    self.counters["engine_crashes"] += 1
                elif (not want_dead and e.dead
                        and (tier, i) in self._fault_crashed):
                    e.restart()
                    self._fault_crashed.discard((tier, i))
                    self.counters["engine_restarts"] += 1
        down = self.faults.partitioned(now)
        if down and not self._link_down:
            self._link_down = True
        elif not down and self._link_down:
            self._link_down = False
            self._anti_entropy(now)

    def _anti_entropy(self, now: float) -> None:
        """Partition healed: replay every deferred knowledge update so the
        affected edges catch up to the newest epoch, and invalidate edge
        prefix caches (their retrieved-context prefixes may now be built
        from rotated chunk sets)."""
        synced_any = False
        for eid in sorted(self.updater.deferred):
            if self.updater.sync(eid, self.stores[eid], now=now):
                synced_any = True
            self.counters["anti_entropy_syncs"] += 1
        if synced_any and self.sched is not None:
            for e in self.sched.pools["edge"]:
                if not e.dead:
                    e.invalidate_prefix_cache()
            self.counters["prefix_invalidations"] += 1

    # ---- failover / escalation ----------------------------------------
    def _handle_failure(self, p: _Pending, reason: str, now: float) -> None:
        """A query failed on its current tier (scheduler shed or dropped
        completion). Retry with bounded exponential backoff — edge
        failures ESCALATE to the cloud tier — until ``failover_max_retries``
        resubmissions, then record the typed terminal outcome."""
        cfg = self.cfg
        p.last_reason = reason
        b = self.tier_breakers.get(p.tier_name)
        if b is not None:
            b.record_failure(now)
        if p.attempts >= cfg.failover_max_retries:
            outcome = "failed" if reason == "dropped" else "shed"
            self.counters[outcome] += 1
            self._log_terminal(p, outcome, now)
            return
        backoff = min(cfg.failover_backoff_s * (2.0 ** p.attempts),
                      cfg.failover_backoff_cap_s)
        p.attempts += 1
        # escalate to the next tier up — unless the link is partitioned,
        # in which case the retry stays on the edge (degraded but serving)
        if p.tier_name == "edge" and not self._link_down:
            p.tier_name = "cloud"
            p.rerouted = True
            p.net_delay_s += p.qc.d_cloud    # true transit of the new route
            self.counters["failed_over"] += 1
        self.counters["retries"] += 1
        heapq.heappush(self._retries,
                       (now + backoff, next(self._retry_seq), p))

    def _resubmit_ready(self, now: float) -> None:
        """Re-enter retry-queue entries whose backoff has expired: rebuild
        the prompt for the (possibly escalated) tier's geometry, register a
        fresh Request, and submit with a fresh deadline."""
        cfg = self.cfg
        while self._retries and self._retries[0][0] <= now:
            _, _, p = heapq.heappop(self._retries)
            max_new = p.request.max_new_tokens
            max_seq = min(e.max_seq for e in self.sched.pools[p.tier_name])
            prompt = self._build_prompt(p.ev, p.texts, max_seq - max_new - 8)
            req = Request(prompt, max_new_tokens=max_new,
                          slo=p.request.slo)
            p.request = req
            self._pending[id(req)] = p
            self.sched.submit(req, p.tier_name,
                              deadline_s=now + cfg.qos_max_delay, now=now)

    def _log_terminal(self, p: _Pending, outcome: str, now: float) -> None:
        """Typed terminal record for a query the cluster gave up on: zero
        cost/tokens, ``correct=False``, age as delay. The gate is NOT
        updated — SafeOBO learns from served completions only; drops
        surface through counters and the conservation gate instead."""
        self.logs.append(StepLog(
            t=p.ev.t, edge_id=p.ev.edge_id, arm=p.arm.idx,
            arm_name=p.arm.name, correct=False,
            delay=max(now - p.ev.t, 0.0), cost=0.0, u_r=0.0, u_d=0.0,
            hit=p.hit, overlap=p.qc.overlap, multihop=p.ev.qa.multihop,
            in_tokens=0.0, out_tokens=0.0, phase=p.phase,
            retrieved=p.texts, tier=p.tier_name, outcome=outcome,
            slo=p.request.slo, rerouted=p.rerouted, attempts=p.attempts))

    def _finalize(self, c: Completion) -> StepLog:
        """Join a Completion back to its query: real token counts -> cost,
        composed virtual-clock delay -> QoS, oracle -> accuracy, and (eaco)
        the SafeOBO update that closes the control loop. The tier spec is
        taken from the tier that ACTUALLY served the completion — a
        watermark or failover re-route prices at the cloud tier, so the
        cost model and the gate see the true cost/delay of the re-route."""
        p = self._pending.pop(id(c.request))
        tier = self.edge_tier if c.tier == "edge" else self.cloud_tier
        b = self.tier_breakers.get(c.tier)
        if b is not None:
            b.record_success(self.clock.now())
        in_t = float(c.prompt_tokens)
        out_t = float(max(c.new_tokens, 1))
        net_delay = p.net_delay_s
        if c.hedged and c.tier == "cloud" and p.tier_name == "edge":
            net_delay += p.qc.d_cloud    # true transit of the backup route
        if self.faults is not None:
            net_delay += self.faults.net_spike(self.clock.now())
        delay = (tier.base_delay_s + net_delay
                 + c.queue_wait_s + c.time_in_engine_s)
        u_r = inference_tflops(tier.model_params_b, in_t, out_t)
        u_d = time_cost_tflops(tier, delay)
        cost = total_cost(u_r, u_d, self.weights)
        correct = self.oracle.draw(p.arm.name, hit=p.hit,
                                   multihop=p.ev.qa.multihop)
        # knowledge-epoch provenance: edge-RAG answers are served from the
        # edge's chunk set; if that set trails the newest epoch (deferred
        # update behind a partition) the answer is flagged — never silent
        store = self.stores[p.ev.edge_id]
        stale = (p.arm.retrieval == "edge"
                 and self.updater.is_stale(store))
        log = StepLog(
            t=p.ev.t, edge_id=p.ev.edge_id, arm=p.arm.idx,
            arm_name=p.arm.name, correct=correct, delay=delay, cost=cost,
            u_r=u_r, u_d=u_d, hit=p.hit, overlap=p.qc.overlap,
            multihop=p.ev.qa.multihop, in_tokens=in_t, out_tokens=out_t,
            phase=p.phase, retrieved=p.texts, tier=c.tier,
            queue_wait_s=c.queue_wait_s, engine_s=c.time_in_engine_s,
            slo=c.slo, rerouted=p.rerouted, attempts=p.attempts,
            hedged=c.hedged, epoch=store.epoch, stale_epoch=stale)
        self.counters["completed"] += 1
        if c.hedged:
            self.counters["hedged_served"] += 1
        if stale:
            self.counters["stale_served"] += 1
        if self.policy == "eaco":
            self.gate.update(p.qc, p.arm, cost=cost,
                             accuracy=1.0 if correct else 0.0, delay=delay)
        self.logs.append(log)
        return log

    def conservation_ok(self) -> bool:
        """The request-conservation law: every submitted query reached a
        terminal state (completed, shed, or failed) and nothing is still
        outstanding. Benchmarks gate on this so future PRs can't silently
        drop work."""
        c = self.counters
        outstanding = len(self._pending) + len(self._retries)
        return (c["submitted"] == c["completed"] + c["shed"] + c["failed"]
                + outstanding)

    def drain_engines(self) -> List[StepLog]:
        """Serve until every outstanding query reaches a terminal state
        (completion, shed, or failed), riding out fault-stalled engines and
        waiting out failover backoffs by idling the virtual clock forward.
        Raises ``RuntimeError`` if no terminal progress happens within
        ``drain_timeout_s`` virtual seconds — a wedge fails loudly instead
        of spinning forever."""
        if self.sched is None:
            raise RuntimeError("drain_engines() requires backend='engines'")
        out: List[StepLog] = []

        def progress() -> tuple:
            # REAL progress only — the clock moving (including our own idle
            # advances below) must not reset the wedge guard
            return (len(self.logs), self.sched.pending(),
                    self.sched.in_flight(), len(self._retries),
                    tuple(self.sched.counters.values()))

        wedge_at = self.clock.now() + self.cfg.drain_timeout_s
        while self._pending or self._retries:
            before = progress()
            t0 = self.clock.now()
            out.extend(self.pump_engines())
            if progress() != before:
                wedge_at = self.clock.now() + self.cfg.drain_timeout_s
                continue
            if self.clock.now() >= wedge_at:
                now_w = self.clock.now()
                ready = ", ".join(f"{r[0]:.3f}" for r in
                                  sorted(self._retries)[:8])
                tb = {t: b.state(now_w)
                      for t, b in self.tier_breakers.items()}
                raise RuntimeError(
                    f"cluster wedged: {self.sched.pending()} queued, "
                    f"{self.sched.in_flight()} resident, "
                    f"{len(self._retries)} awaiting retry with no progress "
                    f"for {self.cfg.drain_timeout_s}s of virtual time\n"
                    f"now={now_w:.3f} link_down={self._link_down} "
                    f"tier_breakers={tb or None} "
                    f"retry_ready_at=[{ready}]\n"
                    f"cluster_counters={self.counters}\n"
                    f"{self.sched.debug_state(now_w)}")
            if self.clock.now() > t0:
                continue      # modeled time moved; let fault windows expire
            # nothing can move until a backoff or stall window expires —
            # idle the clock toward the next actionable instant instead of
            # spinning, bounded by the wedge guard above
            if self._retries and not (self.sched.pending()
                                      or self.sched.in_flight()):
                step = max(self._retries[0][0] - self.clock.now(),
                           self.cfg.stall_tick_s)
            else:
                step = self.cfg.stall_tick_s
            self.clock.advance(step)
        return out

    def run(self, n_steps: int) -> List[StepLog]:
        if self.backend != "engines":
            for ev in self.workload.stream(n_steps):
                self.step(ev)
            return self.logs
        period = self.cfg.arrival_period_s
        for events in self.workload.bursts(n_steps, clock=self.clock):
            for ev in events:
                self.submit_query(ev)
            # serve until the engines' virtual time reaches the next
            # arrival tick, then idle the clock forward to it
            target = self.clock.now() + period
            while ((self.sched.pending() or self.sched.in_flight()
                    or self._retries) and self.clock.now() < target):
                before = self.clock.now()
                self.pump_engines()
                if self.clock.now() <= before:
                    break
            if self.clock.now() < target:
                self.clock.advance(target - self.clock.now())
        self.drain_engines()
        return self.logs

    # ------------------------------------------------------------------
    def metrics(self, skip_warmup: bool = True) -> Dict[str, Any]:
        """Aggregates over SERVED completions (``outcome == "ok"``);
        terminal drops are reported via ``drop_rate`` and ``counters``
        instead of skewing the served-quality means with zero-cost rows."""
        logs = self.logs
        if skip_warmup and self.policy == "eaco":
            logs = [l for l in logs if l.phase != "warmup"]
        dropped = sum(l.outcome != "ok" for l in logs)
        logs = [l for l in logs if l.outcome == "ok"]
        if not logs:
            return {}
        acc = float(np.mean([l.correct for l in logs]))
        n_arms = len(self.gate.arms)
        return {
            "n": len(logs),
            "dropped": dropped,
            "drop_rate": dropped / max(len(logs) + dropped, 1),
            "rerouted": sum(l.rerouted for l in logs),
            "counters": dict(self.counters),
            "accuracy": acc,
            "delay_mean": float(np.mean([l.delay for l in logs])),
            "delay_std": float(np.std([l.delay for l in logs])),
            "cost_mean": float(np.mean([l.cost for l in logs])),
            "cost_std": float(np.std([l.cost for l in logs])),
            "u_r_mean": float(np.mean([l.u_r for l in logs])),
            "u_d_mean": float(np.mean([l.u_d for l in logs])),
            "hit_rate": float(np.mean([l.hit for l in logs])),
            "arm_fracs": [float(np.mean([l.arm == a for l in logs]))
                          for a in range(n_arms)],
            "in_tokens_mean": float(np.mean([l.in_tokens for l in logs])),
            "out_tokens_mean": float(np.mean([l.out_tokens for l in logs])),
            "queue_wait_mean": float(np.mean([l.queue_wait_s for l in logs])),
        }


__all__ = ["EACOCluster", "SimConfig", "StepLog", "FaultInjector",
           "FaultConfig"]
