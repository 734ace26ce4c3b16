"""Request scheduler: arrival queues -> continuous slot-pool admission,
with SLO-aware preemption, load shedding, and stuck-work timeouts.

Per-tier priority heaps (edge engines + cloud engine) feed the engines'
slot pools. ``pump()`` runs one scheduling round: for every tier it admits
queued requests into whatever slots just freed, harvests per-request
completions, and *dispatches* each engine's next step — one fused decode,
or (engines built with ``step_token_budget``) one fused chunked-prefill +
decode step whose budget split the engine steers by SLO rank (interactive
first-token work ahead of batch). Dispatch is asynchronous: the pump
enqueues every engine's step and only blocks at the very end of the round
(``collect``), so host-side scheduling overlaps device compute. The gate
decides the tier; the scheduler keeps the lanes full.

A tier may be backed by a POOL of engines (``{"edge": [e0, e1], "cloud":
e2}``): the tier shares one queue and the head request is admitted into the
first pool member with a free slot (and, paged, enough pages).

**Queue order** is ``(SLO rank, deadline, arrival seq)``: every
``interactive`` request sorts ahead of every ``batch`` request, and within
a class the earliest deadline wins. If the head doesn't fit on ANY pool
member, later requests wait behind it rather than jumping the queue, so a
big request can't be starved by a stream of small ones.

**The overload + hard-failure state machine** (every transition is a typed
outcome, never a silent drop)::

    submit ──fits no pool member──────────────────────> SchedulerError
    submit ──batch + saturation >= overload_watermark──> Shed("overload")
    queued ──shed_overdue and deadline <= now──────────> Shed("deadline")
    queued ──head outranks a resident, no slot anywhere─> resident PREEMPTED
                 (engine snapshot -> re-enqueued -> resumes via prefix
                  cache, greedy token-identical)
    resident ──no engine progress for request_timeout_s─> Shed("timeout")
    resident ──engine crashed / restarted under it──────> REAPED: re-enqueued
                 from its original prompt (requeue_lost=True, default) or
                 emitted as Shed("engine_lost") for the caller's failover
    resident ──finished────────────────────────────────> Completion

- *Preemption* (``preempt=True``, the default): when the head cannot be
  admitted anywhere, the WORST resident of the same tier — largest
  ``(rank, deadline)`` — is reclaimed iff it is STRICTLY lower priority
  than the head (so uniform-priority workloads never preempt and behave
  exactly as before). The engine returns a resumable snapshot; the victim
  re-enters the queue carrying its emitted tokens and resumes as a new
  admission of ``prompt_ids = enc + emitted``, hitting the prefix cache on
  its original prompt pages. Greedy resume is token-identical.
- *Shedding* (``shed_overdue=True``; off by default because wall-clock
  callers submit with sentinel deadlines): queued requests whose hard
  deadline has already passed are dropped as ``Shed("deadline")`` before
  admission — capacity goes to requests that can still meet their SLO.
- *Timeouts* (``request_timeout_s``): a resident whose engine has made no
  scheduling progress for that long (e.g. a stalled engine, see the
  ``stalled`` hook on :meth:`pump`) is preempted off the engine — freeing
  its slot and pages — and emitted as ``Shed("timeout")``; a cluster layer
  may then fail it over to another tier.
- *Admission-time overload shed* (``overload_watermark``): batch-class
  submissions are shed immediately when the tier's saturation (queued +
  resident over total slot capacity) is at/above the watermark;
  interactive submissions always enqueue.
- *Engine-loss reaping*: every resident records the ``engine_generation``
  it was admitted under. At the top of each pump, residents whose engine
  is dead — or restarted since admission (generation mismatch) — are
  reaped: their in-engine tokens died with the device state, but tokens
  banked by an EARLIER preemption (already in ``item.emitted``) survive
  in the control plane. With ``requeue_lost=True`` the reaped item
  re-enters the queue through the same resume path preemption uses (the
  restarted engine's prefix cache is cold, so the whole prompt reruns —
  still token-identical under greedy decode); with ``requeue_lost=False``
  it is emitted as ``Shed("engine_lost")`` so a cluster layer can apply
  its own failover policy (backoff, tier escalation).
- *Circuit breakers* (``breaker_threshold``): each pool member gets a
  :class:`~repro.serving.health.CircuitBreaker`. Reaped residents and
  stuck-resident timeouts count as failures against the engine they were
  on; completions count as successes. An engine whose breaker won't
  ``allow()`` is skipped at admission exactly like a stalled one — so a
  flaky node stops receiving fresh work until a half-open probe (one
  request, marked via ``begin_probe``) proves it healthy.
- *Hedging* (``hedge_s``): an interactive request still unfinished
  ``hedge_s`` after submission to ``hedge_from`` fires ONE backup
  submission of the same prompt on ``hedge_to``; first completion wins
  and the loser is cancelled (removed from its queue, or preempted off
  its engine with the snapshot discarded). The pair shares one logical
  request: the winner's :class:`Completion` always carries the PRIMARY
  ``Request`` object so callers can join on identity, and the losing leg
  retires as ``cancelled`` — never a Shed, never a second completion.
  ``hedge_gate`` (a ``now -> bool`` callable) can veto hedge firing, e.g.
  while the edge<->cloud link is partitioned.

Every terminal outcome is counted (``counters``) and hedge-aware
conservation — ``submitted + hedged == completed + shed_total + cancelled
+ queued + resident`` — is checkable at any time via
:meth:`conservation_ok`, so work can never vanish. ``drain()`` detects
wedges (no admission, step, shed, or preemption progress while work
remains) and raises :class:`SchedulerError` carrying a full
:meth:`debug_state` dump — queue depths, per-engine residents, breaker
states — instead of spinning forever.

All timings run on an injectable ``clock`` (any zero-arg callable returning
seconds; default ``time.perf_counter``). ``submit(now=...)`` and
``pump(now=...)`` override the clock per call, so a simulator driving the
scheduler with logical event time gets exact logical queue waits and
service times — never a mix of event time and wall time.
"""
from __future__ import annotations

import heapq
import itertools
import json
import time
from dataclasses import dataclass, field
from typing import (
    Callable, Dict, List, Optional, Sequence, Tuple, Union,
)

from repro.core.tracing import span
from repro.serving.engine import Request, ServingEngine
from repro.serving.health import CircuitBreaker

# lower rank = higher priority; unknown classes schedule as batch
SLO_RANK: Dict[str, int] = {"interactive": 0, "batch": 1}


class SchedulerError(RuntimeError):
    """Caller-facing scheduler invariant violation: a request that can
    never fit any pool member of its tier (rejected at ``submit`` so the
    deadline-ordered queue can't wedge behind it), or a drain that stopped
    making progress. A real exception — survives ``python -O``."""


def _rank(request: Request) -> int:
    return SLO_RANK.get(request.slo, SLO_RANK["batch"])


@dataclass(order=True)
class _Item:
    rank: int                    # SLO class rank (compare key 1)
    deadline: float              # hard deadline, scheduler clock (key 2)
    seq: int                     # arrival tiebreak (key 3)
    request: Request = field(compare=False)
    tier: str = field(compare=False, default="edge")
    enqueued_at: float = field(compare=False, default=0.0)
    admitted_at: float = field(compare=False, default=0.0)
    queue_wait_s: float = field(compare=False, default=0.0)   # accumulated
    resident_s: float = field(compare=False, default=0.0)     # accumulated
    # ---- preemption/resume state --------------------------------------
    run_request: Optional[Request] = field(compare=False, default=None)
    enc: Optional[List[int]] = field(compare=False, default=None)
    emitted: List[int] = field(compare=False, default_factory=list)
    preemptions: int = field(compare=False, default=0)
    last_progress_at: float = field(compare=False, default=0.0)
    # ---- crash-reaping / hedging state --------------------------------
    admit_gen: int = field(compare=False, default=0)   # engine_generation
    #                                                    at admission time
    submitted_at: float = field(compare=False, default=0.0)
    partner: Optional["_Item"] = field(compare=False, default=None)
    is_hedge: bool = field(compare=False, default=False)
    done: bool = field(compare=False, default=False)


@dataclass
class Completion:
    request: Request
    text: str
    tier: str
    queue_wait_s: float          # submit -> slot admission (scheduler clock)
    time_in_engine_s: float      # resident time, summed across preemptions
    prompt_tokens: int = 0
    new_tokens: int = 0
    engine_index: int = 0        # which pool member finished it
    slo: str = "batch"
    preemptions: int = 0         # times this request was preempted
    hedged: bool = False         # served by the backup (hedge) submission
    ttft_s: float = 0.0          # submit -> first token (scheduler clock):
    #                              queue wait + prior residencies + the
    #                              engine-side first-token delay of the
    #                              final admission (an upper bound for
    #                              preempted-then-resumed requests, whose
    #                              true first token came even earlier)
    token_ids: List[int] = field(default_factory=list)   # generated ids
    #                              (text keeps only the byte-range ones)


@dataclass
class Shed:
    """Typed terminal outcome for work the scheduler gave up on — the
    request was NOT served and the caller must decide (fail over to
    another tier, return an error upstream, ...). Never a silent drop:
    every Shed is counted and queued on :meth:`TierScheduler.pop_sheds`."""
    request: Request
    tier: str
    reason: str         # "deadline" | "timeout" | "overload" | "engine_lost"
    t: float                     # scheduler-clock time of the shed
    slo: str = "batch"
    queue_wait_s: float = 0.0
    emitted_tokens: int = 0      # tokens generated before a timeout shed
    preemptions: int = 0


_SHED_COUNTER = {"deadline": "shed", "timeout": "timed_out",
                 "overload": "overload_shed", "engine_lost": "engine_lost"}


class TierScheduler:
    """SLO- and deadline-ordered continuous scheduler over named
    engine-pool tiers, with preemption / shedding / timeouts (see module
    docstring for the full state machine).

    Defaults preserve pre-overload behavior exactly: ``preempt=True``
    never fires under a uniform SLO class with monotone deadlines (it
    requires STRICT priority dominance), and shedding / timeouts /
    watermarks are opt-in.
    """

    def __init__(self, engines: Dict[str, Union[ServingEngine,
                                                Sequence[ServingEngine]]],
                 clock: Optional[Callable[[], float]] = None, *,
                 preempt: bool = True,
                 shed_overdue: bool = False,
                 request_timeout_s: Optional[float] = None,
                 overload_watermark: Optional[float] = None,
                 requeue_lost: bool = True,
                 breaker_threshold: Optional[int] = None,
                 breaker_reset_s: float = 5.0,
                 hedge_s: Optional[float] = None,
                 hedge_from: str = "edge",
                 hedge_to: str = "cloud",
                 hedge_gate: Optional[Callable[[float], bool]] = None):
        self.pools: Dict[str, List[ServingEngine]] = {}
        for tier, pool in engines.items():
            members = list(pool) if isinstance(pool, (list, tuple)) else [pool]
            if not members:
                raise ValueError(f"tier {tier!r} has an empty engine pool")
            self.pools[tier] = members
        self.engines = engines
        self.clock: Callable[[], float] = (time.perf_counter
                                           if clock is None else clock)
        self.preempt = preempt
        self.shed_overdue = shed_overdue
        self.request_timeout_s = request_timeout_s
        self.overload_watermark = overload_watermark
        self.requeue_lost = requeue_lost
        self.hedge_s = hedge_s
        self.hedge_from = hedge_from
        self.hedge_to = hedge_to
        self.hedge_gate = hedge_gate
        self._queues: Dict[str, List[_Item]] = {t: [] for t in self.pools}
        self._inflight: Dict[Tuple[str, int, int], _Item] = {}
        self._seq = itertools.count()
        self.breakers: Dict[Tuple[str, int], CircuitBreaker] = {}
        if breaker_threshold is not None:
            for tier, pool in self.pools.items():
                for i in range(len(pool)):
                    self.breakers[(tier, i)] = CircuitBreaker(
                        breaker_threshold, breaker_reset_s)
        self.counters: Dict[str, int] = {
            "submitted": 0, "completed": 0, "shed": 0, "timed_out": 0,
            "overload_shed": 0, "preempted": 0, "resumed": 0,
            "engine_lost": 0, "requeued_lost": 0, "hedged": 0,
            "cancelled": 0}
        self.sheds: List[Shed] = []

    # ------------------------------------------------------------------
    # Introspection / accounting
    # ------------------------------------------------------------------
    def pending(self, tier: Optional[str] = None) -> int:
        """Queued requests not yet admitted into a slot."""
        if tier:
            return len(self._queues[tier])
        return sum(len(q) for q in self._queues.values())

    def in_flight(self, tier: Optional[str] = None) -> int:
        """Requests resident in an engine slot, still decoding."""
        if tier:
            return sum(t == tier for t, _, _ in self._inflight)
        return len(self._inflight)

    def capacity(self, tier: str) -> int:
        """Total slot capacity of a tier's pool."""
        return sum(e.max_batch for e in self.pools[tier])

    def saturation(self, tier: str) -> float:
        """Outstanding work over slot capacity: ``(queued + resident) /
        capacity``. >= 1.0 means every slot is full AND work is queued —
        the overload watermark and cluster failover key off this."""
        return (self.pending(tier) + self.in_flight(tier)) / max(
            self.capacity(tier), 1)

    @property
    def shed_total(self) -> int:
        return (self.counters["shed"] + self.counters["timed_out"]
                + self.counters["overload_shed"]
                + self.counters["engine_lost"])

    def conservation_ok(self) -> bool:
        """Every submission — original or hedge leg — is accounted for:
        completed, shed (any reason), cancelled (the losing leg of a hedge
        pair), still queued, or resident. The invariant future PRs must
        not break — work never silently vanishes."""
        return self.counters["submitted"] + self.counters["hedged"] == (
            self.counters["completed"] + self.shed_total
            + self.counters["cancelled"]
            + self.pending() + self.in_flight())

    def pop_sheds(self) -> List[Shed]:
        """Drain the typed shed outcomes accumulated since the last call
        (callers that fail work over to another tier consume these)."""
        out, self.sheds = self.sheds, []
        return out

    # ------------------------------------------------------------------
    # Submission
    # ------------------------------------------------------------------
    def submit(self, request: Request, tier: str,
               deadline_s: float = 1e9, now: Optional[float] = None) -> None:
        """Enqueue a request on a tier.

        Raises :class:`SchedulerError` when no pool member could EVER
        admit the request (prompt too long for every engine's ``max_seq``)
        — without this, the deadline-ordered queue would wedge behind an
        inadmissible head and ``drain()`` would spin forever. Batch-class
        requests are shed immediately (``Shed("overload")``) when the
        tier's saturation is at/above ``overload_watermark``."""
        if tier not in self._queues:
            raise KeyError(f"unknown tier {tier!r}")
        if not any(e.fits(request) for e in self.pools[tier]):
            raise SchedulerError(
                f"request can never be admitted on tier {tier!r}: prompt "
                f"exceeds every pool member's max_seq "
                f"({[e.max_seq for e in self.pools[tier]]})")
        now = self.clock() if now is None else now
        self.counters["submitted"] += 1
        item = _Item(_rank(request), deadline_s, next(self._seq), request,
                     tier, enqueued_at=now, last_progress_at=now,
                     submitted_at=now)
        if (self.overload_watermark is not None
                and item.rank >= SLO_RANK["batch"]
                and self.saturation(tier) >= self.overload_watermark):
            self._record_shed(item, "overload", now)
            return
        heapq.heappush(self._queues[tier], item)

    # ------------------------------------------------------------------
    # The pump
    # ------------------------------------------------------------------
    def pump(self, now: Optional[float] = None,
             stalled: Optional[Callable[[str, int], bool]] = None
             ) -> List[Completion]:
        """One scheduling round across every tier: shed overdue queued
        work, time out stuck residents, fill free slots from the priority
        heap (preempting strictly-lower-priority residents for a head that
        fits nowhere), advance each engine one decode step, and return the
        requests that finished this round.

        Admission asks the engines via ``can_admit`` — a free slot AND,
        for a paged KV-cache, enough free pages for the request's prompt +
        decode budget. Admission stays strictly priority-ordered within a
        tier (see module docstring for the queue key).

        ``now`` pins the whole round to one logical timestamp
        (simulators); without it the injected clock is read as events
        happen, so wall-mode completions still include the round's
        measured compute. ``stalled(tier, engine_index) -> bool`` marks
        pool members the fault layer has frozen: they are skipped for
        admission and stepping this round, their residents accrue no
        progress, and — with ``request_timeout_s`` — eventually time out
        and free their slots. Dead engines (crashed, not yet restarted) are
        likewise skipped, after their lost residents are reaped."""
        with span("sched.pump", queued=self.pending(),
                  resident=self.in_flight()):
            return self._pump(now, stalled)

    def _pump(self, now: Optional[float],
              stalled: Optional[Callable[[str, int], bool]]
              ) -> List[Completion]:
        t_round = self.clock() if now is None else now
        out: List[Completion] = []
        for tier, pool in self.pools.items():
            self._reap_lost(tier, pool, t_round)
        if self.hedge_s is not None:
            self._fire_hedges(t_round)
        for tier, pool in self.pools.items():
            q = self._queues[tier]

            def is_stalled(i: int, _tier: str = tier) -> bool:
                return stalled is not None and bool(stalled(_tier, i))

            if self.shed_overdue:
                self._shed_overdue_queued(q, t_round)
            if self.request_timeout_s is not None:
                self._timeout_stuck(tier, pool, t_round)
            while q:
                head = q[0]
                run_req = self._run_request(head)
                eng_i = next(
                    (i for i, e in enumerate(pool)
                     if not is_stalled(i)
                     and self._breaker_allows(tier, i, t_round)
                     and e.can_admit(run_req)), None)
                if eng_i is None:
                    if self.preempt and self._preempt_for(tier, pool, head,
                                                          t_round):
                        continue      # a slot/pages just freed; retry head
                    break
                item = heapq.heappop(q)
                item.queue_wait_s += max(t_round - item.enqueued_at, 0.0)
                item.admitted_at = t_round
                item.last_progress_at = t_round
                rid = pool[eng_i].admit(run_req)
                item.admit_gen = pool[eng_i].engine_generation
                b = self.breakers.get((tier, eng_i))
                if b is not None:
                    b.begin_probe(t_round)   # no-op unless half-open
                if item.emitted or item.preemptions:
                    self.counters["resumed"] += 1
                self._inflight[(tier, eng_i, rid)] = item
            for eng_i, eng in enumerate(pool):
                if is_stalled(eng_i) or eng.dead or not eng.has_active:
                    continue
                for ec in eng.harvest():
                    item = self._inflight.pop((tier, eng_i, ec.req_id))
                    item.done = True
                    b = self.breakers.get((tier, eng_i))
                    if b is not None:
                        b.record_success(t_round)
                    t_done = self.clock() if now is None else now
                    partner = item.partner
                    if partner is not None and not partner.done:
                        self._cancel_item(partner, t_done)
                    # the winner's Completion always carries the PRIMARY
                    # request so callers can join on object identity
                    primary = (partner if item.is_hedge
                               and partner is not None else item)
                    ids = item.emitted + ec.token_ids
                    self.counters["completed"] += 1
                    out.append(Completion(
                        request=primary.request,
                        text=eng.tok.decode(ids), tier=tier,
                        queue_wait_s=item.queue_wait_s,
                        time_in_engine_s=item.resident_s
                        + max(t_done - item.admitted_at, 0.0),
                        prompt_tokens=(len(item.enc) if item.enc is not None
                                       else ec.prompt_tokens),
                        new_tokens=len(ids),
                        engine_index=eng_i,
                        slo=primary.request.slo,
                        preemptions=item.preemptions,
                        hedged=item.is_hedge,
                        ttft_s=item.queue_wait_s + item.resident_s
                        + ec.ttft_s,
                        token_ids=ids))
                eng.dispatch()
                # residents on an engine that just stepped made progress
                for key, it in self._inflight.items():
                    if key[0] == tier and key[1] == eng_i:
                        it.last_progress_at = t_round
        # collect AFTER every engine has dispatched: host-side scheduling
        # (planning, page mapping, queue work) for engine N+1 overlapped
        # the device compute of engine N — JAX async dispatch means nothing
        # above blocked on a result; only here do we fetch sampled tokens
        for pool in self.pools.values():
            for eng in pool:
                if not eng.dead:
                    eng.collect()
        return out

    # one pump used to serve a whole batch; keep the name as an alias for
    # callers that just want "advance the scheduler"
    step = pump

    def drain(self) -> List[Completion]:
        """Pump until no work remains. Raises :class:`SchedulerError` if a
        round makes NO progress (no admission, decode step, completion,
        shed, or preemption) while work is still outstanding — a wedged
        scheduler fails loudly instead of spinning forever, and the error
        carries a :meth:`debug_state` dump so the wedge is diagnosable
        from the message alone."""
        out: List[Completion] = []
        while self.pending() or self.in_flight():
            before = self._progress_fingerprint()
            out.extend(self.pump())
            if (self._progress_fingerprint() == before
                    and (self.pending() or self.in_flight())):
                raise SchedulerError(
                    f"scheduler wedged: {self.pending()} queued, "
                    f"{self.in_flight()} resident, and a full pump made no "
                    "progress (no admission, step, completion, shed, or "
                    f"preemption)\n{self.debug_state()}")
        return out

    def debug_state_dict(self, now: Optional[float] = None) -> dict:
        """Machine-readable diagnostic snapshot — the same information
        :meth:`debug_state` renders for humans, as a JSON-serializable
        dict, so wedge dumps and DST trace artifacts share one format.
        Per-tier queue depth and head deadline, per-engine residents /
        free slots / liveness / generation / breaker snapshot, and the
        full counter map. Pure introspection — never mutates anything
        (breaker state promotion open -> half_open on read is the
        breaker's own documented clock behavior)."""
        now = self.clock() if now is None else now
        tiers = {}
        for tier, pool in self.pools.items():
            q = self._queues[tier]
            engines = []
            for i, e in enumerate(pool):
                res = sum(1 for k in self._inflight
                          if k[0] == tier and k[1] == i)
                b = self.breakers.get((tier, i))
                engines.append({
                    "residents": res, "free_slots": e.free_slots,
                    "dead": bool(e.dead),
                    "generation": e.engine_generation,
                    "breaker": b.snapshot(now) if b is not None else None,
                    # fused-step telemetry (all zero off budget mode)
                    "prefilling": e.prefilling_slots,
                    "mixed_steps": e.mixed_steps,
                    "prefill_chunks": e.prefill_chunks,
                    "budget_utilization": round(e.budget_utilization, 4),
                })
            tiers[tier] = {
                "queued": len(q),
                "head_deadline": q[0].deadline if q else None,
                "engines": engines,
            }
        return {"t": now, "tiers": tiers, "counters": dict(self.counters),
                "conservation_ok": self.conservation_ok(),
                "fences": self.resident_fences()}

    def debug_state(self, now: Optional[float] = None) -> str:
        """Multi-line diagnostic snapshot for wedge reports, rendered from
        :meth:`debug_state_dict` with the raw JSON appended on the last
        line (grep for ``json=``) so a pasted wedge dump is also machine
        readable."""
        now = self.clock() if now is None else now
        d = self.debug_state_dict(now)
        lines = []
        for tier, td in d["tiers"].items():
            head = ("-" if td["head_deadline"] is None
                    else f"{td['head_deadline']:.3f}")
            lines.append(f"tier {tier!r}: queued={td['queued']} "
                         f"head_deadline={head}")
            for i, ed in enumerate(td["engines"]):
                bs = (ed["breaker"]["state"] if ed["breaker"] is not None
                      else "none")
                lines.append(
                    f"  engine[{i}]: residents={ed['residents']} "
                    f"free_slots={ed['free_slots']} dead={ed['dead']} "
                    f"generation={ed['generation']} breaker={bs}")
        lines.append(f"counters={self.counters}")
        lines.append(f"json={json.dumps(d, sort_keys=True)}")
        return "\n".join(lines)

    def resident_fences(self) -> List[dict]:
        """Raw material for the DST generation-fence oracle: one record
        per resident ``(tier, engine index, admit-time generation,
        engine's current generation, dead flag)``. A legal scheduler
        never holds a resident whose engine is dead or whose generation
        moved past the admit fence — :meth:`pump` reaps those before
        anything else runs."""
        out: List[dict] = []
        for (tier, i, rid), it in self._inflight.items():
            e = self.pools[tier][i]
            out.append({"tier": tier, "engine": i, "req_id": rid,
                        "admit_gen": it.admit_gen,
                        "engine_gen": e.engine_generation,
                        "dead": bool(e.dead)})
        return out

    def fences_ok(self) -> bool:
        """Generation-fence legality: no resident maps to a dead engine or
        to a generation other than the one it was admitted under."""
        return all(not f["dead"] and f["admit_gen"] == f["engine_gen"]
                   for f in self.resident_fences())

    # ------------------------------------------------------------------
    # Internals
    # ------------------------------------------------------------------
    def _progress_fingerprint(self) -> tuple:
        work = sum(e.prefill_tokens + e.decode_rounds
                   for pool in self.pools.values() for e in pool)
        return (self.pending(), self.in_flight(), work,
                tuple(self.counters.values()))

    def _run_request(self, item: _Item) -> Request:
        """The request actually handed to engines: the original on first
        admission, the resume request (``prompt_ids = enc + emitted``)
        after a preemption. Kept on the item so engine plan memos stay
        effective across ``can_admit`` probes."""
        if item.run_request is None:
            item.run_request = item.request
        return item.run_request

    def _record_shed(self, item: _Item, reason: str, now: float,
                     queued: bool = True) -> None:
        item.done = True
        if item.partner is not None and not item.partner.done:
            # the other leg of the hedge pair is still live and carries
            # the request — this leg just retires as a cancelled duplicate
            self.counters["cancelled"] += 1
            return
        primary = (item.partner if item.is_hedge
                   and item.partner is not None else item)
        self.counters[_SHED_COUNTER[reason]] += 1
        wait = item.queue_wait_s
        if queued:
            wait += max(now - item.enqueued_at, 0.0)
        self.sheds.append(Shed(
            request=primary.request, tier=item.tier, reason=reason, t=now,
            slo=primary.request.slo, queue_wait_s=wait,
            emitted_tokens=len(item.emitted),
            preemptions=item.preemptions))

    def _shed_overdue_queued(self, q: List[_Item], now: float) -> None:
        """Drop queued items whose hard deadline already passed — they can
        no longer meet their SLO, so capacity goes to ones that can. Only
        QUEUED work sheds on deadline; residents hold reserved pages and
        finishing them is cheaper than wasting the work (they time out via
        ``request_timeout_s`` if truly stuck)."""
        if not any(it.deadline <= now for it in q):
            return
        keep = [it for it in q if it.deadline > now]
        dead = [it for it in q if it.deadline <= now]
        q[:] = keep
        heapq.heapify(q)
        for it in dead:
            self._record_shed(it, "deadline", now)

    def _timeout_stuck(self, tier: str, pool: List[ServingEngine],
                       now: float) -> None:
        """Reclaim residents whose engine made no progress for
        ``request_timeout_s`` (stalled engine / wedged decode): preempt
        them off the engine — host-side bookkeeping that works even when
        the engine itself is frozen — and emit ``Shed("timeout")``."""
        for key in [k for k in self._inflight if k[0] == tier]:
            it = self._inflight[key]
            if now - it.last_progress_at <= self.request_timeout_s:
                continue
            _, eng_i, rid = key
            snap = pool[eng_i].preempt(rid)
            del self._inflight[key]
            it.resident_s += max(now - it.admitted_at, 0.0)
            it.emitted.extend(snap.emitted_ids)
            self._breaker_fail(tier, eng_i, now)
            self._record_shed(it, "timeout", now, queued=False)

    def _preempt_for(self, tier: str, pool: List[ServingEngine],
                     head: _Item, now: float) -> bool:
        """Reclaim a slot for a queued head that fits nowhere: pick the
        WORST resident of the tier — largest ``(rank, deadline)`` — and
        preempt it iff it is STRICTLY lower priority than the head.
        The victim's snapshot (emitted tokens) folds into its item and it
        re-enters the queue; its next admission resumes via the prefix
        cache (original prompt pages are still indexed) and recomputes
        only the generated suffix, token-identical under greedy decode.
        Returns True when a victim was reclaimed (the caller retries
        admission), False when nobody is strictly below the head."""
        head_key = (head.rank, head.deadline)
        worst_key: Optional[Tuple[int, float]] = None
        worst: Optional[Tuple[Tuple[str, int, int], _Item]] = None
        for key, it in self._inflight.items():
            if key[0] != tier:
                continue
            k = (it.rank, it.deadline)
            if k <= head_key:
                continue
            if worst_key is None or k > worst_key:
                worst_key, worst = k, (key, it)
        if worst is None:
            return False
        (_, eng_i, rid), it = worst
        snap = pool[eng_i].preempt(rid)
        del self._inflight[(tier, eng_i, rid)]
        if it.enc is None:
            it.enc = list(snap.prompt_ids)    # original prompt encoding
        it.emitted.extend(snap.emitted_ids)
        it.preemptions += 1
        it.resident_s += max(now - it.admitted_at, 0.0)
        it.enqueued_at = now
        it.last_progress_at = now
        it.run_request = self._resume_request(it)
        heapq.heappush(self._queues[tier], it)
        self.counters["preempted"] += 1
        return True

    def _resume_request(self, it: _Item) -> Request:
        """The request for a fresh admission after the current residency
        ended early (preemption or engine loss): the original prompt plus
        whatever tokens the CONTROL PLANE has banked in ``it.emitted``.
        After a crash that is only tokens saved by an earlier preemption —
        in-engine progress died with the device state."""
        if it.enc is None or not it.emitted:
            return it.request
        return Request(
            prompt=it.request.prompt,
            prompt_ids=it.enc + it.emitted,
            max_new_tokens=it.request.max_new_tokens - len(it.emitted),
            temperature=it.request.temperature,
            slo=it.request.slo)

    # ------------------------------------------------------------------
    # Crash reaping / breakers / hedging
    # ------------------------------------------------------------------
    def _breaker_allows(self, tier: str, eng_i: int, now: float) -> bool:
        b = self.breakers.get((tier, eng_i))
        return b is None or b.allow(now)

    def _breaker_fail(self, tier: str, eng_i: int, now: float) -> None:
        b = self.breakers.get((tier, eng_i))
        if b is not None:
            b.record_failure(now)

    def _reap_lost(self, tier: str, pool: List[ServingEngine],
                   now: float) -> None:
        """Reclaim residents whose engine crashed — or crashed AND
        restarted — since they were admitted (``engine_generation``
        mismatch catches a full crash/restart cycle between pumps).
        Device-side progress is gone; each lost resident either re-enters
        the queue from its original prompt (+ any tokens banked by an
        earlier preemption) or becomes a typed ``Shed("engine_lost")``
        for the caller's failover. Every loss counts against the engine's
        breaker."""
        for key in [k for k in self._inflight if k[0] == tier]:
            _, eng_i, rid = key
            it = self._inflight[key]
            e = pool[eng_i]
            if not e.dead and e.engine_generation == it.admit_gen:
                continue
            del self._inflight[key]
            it.resident_s += max(now - it.admitted_at, 0.0)
            self._breaker_fail(tier, eng_i, now)
            if it.partner is not None and not it.partner.done:
                it.done = True
                self.counters["cancelled"] += 1
            elif self.requeue_lost:
                it.run_request = self._resume_request(it)
                it.enqueued_at = now
                it.last_progress_at = now
                heapq.heappush(self._queues[tier], it)
                self.counters["requeued_lost"] += 1
            else:
                self._record_shed(it, "engine_lost", now, queued=False)

    def _fire_hedges(self, now: float) -> None:
        """Interactive requests still unfinished ``hedge_s`` after
        submission to ``hedge_from`` get ONE backup submission of the
        same original prompt on ``hedge_to``. First completion wins; the
        loser is cancelled by the completion/shedding paths via the
        ``partner`` link."""
        if (self.hedge_to not in self.pools
                or self.hedge_from not in self.pools
                or self.hedge_to == self.hedge_from):
            return
        if self.hedge_gate is not None and not self.hedge_gate(now):
            return
        cands = list(self._queues[self.hedge_from]) + [
            it for (t, _, _), it in self._inflight.items()
            if t == self.hedge_from]
        for it in cands:
            if (it.is_hedge or it.partner is not None or it.done
                    or it.rank != SLO_RANK["interactive"]
                    or now - it.submitted_at < self.hedge_s):
                continue
            r = it.request
            hedge_req = Request(
                prompt=r.prompt, prompt_ids=r.prompt_ids,
                max_new_tokens=r.max_new_tokens,
                temperature=r.temperature, slo=r.slo)
            h = _Item(it.rank, it.deadline, next(self._seq), hedge_req,
                      self.hedge_to, enqueued_at=now, last_progress_at=now,
                      submitted_at=now, is_hedge=True, partner=it)
            it.partner = h
            heapq.heappush(self._queues[self.hedge_to], h)
            self.counters["hedged"] += 1

    def _cancel_item(self, it: _Item, now: float) -> None:
        """Retire the losing leg of a hedge pair: remove it from its
        queue, or preempt it off its engine with the snapshot discarded.
        Counted ``cancelled`` — never a Shed, never a completion — so
        hedge-aware conservation stays exact."""
        it.done = True
        q = self._queues.get(it.tier)
        if q is not None and it in q:
            q.remove(it)
            heapq.heapify(q)
            self.counters["cancelled"] += 1
            return
        key = next((k for k, v in self._inflight.items() if v is it), None)
        if key is not None:
            tier, eng_i, rid = key
            eng = self.pools[tier][eng_i]
            if not eng.dead:
                eng.preempt(rid)     # free slot + pages; progress dropped
            del self._inflight[key]
        self.counters["cancelled"] += 1


__all__ = ["TierScheduler", "Completion", "Shed", "SchedulerError",
           "SLO_RANK"]
