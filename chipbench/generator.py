"""Open-loop traffic from a mix's data file and a seed.

A mix (``chipbench/traffic/<name>.json``) gives an arrival rate, the parts
of each prompt (a context drawn from a pool by Zipf popularity, or unique
tokens), the output length, and a lead-in. This one generator reads every
mix; a new mix is a new data file.

The seed changes the order of the work, not its amount: every length,
every gap between arrivals and every context's popularity rank is a
stratified quantile of its distribution (the ``(i + 0.5) / n`` points put
through the inverse CDF), and the seed permutes them and draws the token
ids. So two seeds send the same set of sizes and ranks in another order,
the window always holds ``rate * seconds`` arrivals, and its ranks reach
as far into the Zipf tail as ``n`` stratified draws do (at n = 20 and 256
topics: 9 requests to the top 8 and 11 to distinct topics ranked 10-220).

A mix whose window holds only a few long requests may fix the order too:
with ``"order_seed": k`` the permutations of sizes, gaps and ranks are
drawn from ``k``, so every run seed sends the same sizes in the same
order, and the run's seed draws only the token ids (and the weights).

Length distributions: ``{"dist": "const", "value": v}``,
``{"dist": "uniform", "min": a, "max": b}`` (integers, both ends in), and
``{"dist": "normal", "mean": m, "std": s, "min": a, "max": b}`` (clipped).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List, Optional

import numpy as np

# ids 256-258 are the engine's pad/bos/eos; prompts never hold them
FIRST_ID = 259


@dataclass
class Arrival:
    due: float                   # seconds from the window's start
    prompt: List[int]
    max_new: int
    topic: Optional[int] = None  # context pool index, when shared


@dataclass
class Schedule:
    warm: List[List[int]] = field(default_factory=list)
    lead_in: List[Arrival] = field(default_factory=list)  # due < 0
    window: List[Arrival] = field(default_factory=list)   # 0 <= due < T


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a length distribution, as integers."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "const":
        return np.full(n, int(spec["value"]), np.int64)
    if kind == "uniform":
        lo, hi = int(spec["min"]), int(spec["max"])
        return np.floor(lo + u * (hi - lo + 1)).astype(np.int64)
    if kind == "normal":
        nd = NormalDist(spec["mean"], spec["std"])
        x = np.array([nd.inv_cdf(float(p)) for p in u])
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def zipf_ranks(pool: int, s: float, n: int) -> np.ndarray:
    """The n stratified quantiles of Zipf(s) over ``pool`` topics: topic
    ``k`` (0 the most popular) for each point ``(i + 0.5) / n`` that falls
    in its step of the CDF, in rank order."""
    p = 1.0 / np.arange(1, pool + 1) ** s
    cdf = np.cumsum(p / p.sum())
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u, side="right"), pool - 1)


def _gaps(rng, rate: float, n: int) -> np.ndarray:
    """n + 1 exponential gaps (stratified, permuted) scaled so that n
    arrivals at their partial sums span exactly n / rate seconds."""
    u = (np.arange(n + 1) + 0.5) / (n + 1)
    g = -np.log1p(-u)
    g = rng.permutation(g)
    return g * ((n / rate) / g.sum())


class Generator:
    """One mix, one seed, one vocabulary."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(int(seed) % (1 << 64))
        # sizes, gaps and ranks are permuted by this one; ids by ``rng``
        self.order = (np.random.default_rng(int(mix["order_seed"]))
                      if "order_seed" in mix else self.rng)
        self.contexts = {}       # (part index, topic) -> token list

    def _ids(self, n: int) -> List[int]:
        return self.rng.integers(FIRST_ID, self.vocab, n).tolist()

    def _context(self, part: int, topic: int, length: int) -> List[int]:
        key = (part, topic)
        if key not in self.contexts:
            self.contexts[key] = self._ids(length)
        return self.contexts[key]

    def _arrivals(self, n: int, t0: float) -> List[Arrival]:
        mix = self.mix
        if n == 0:
            return []
        times = t0 + np.cumsum(_gaps(self.order, mix["rate_per_s"], n))[:n]
        outs = self.order.permutation(quantiles(mix["output"], n))
        parts = []
        topics = None
        for j, part in enumerate(mix["prompt"]):
            lens = self.order.permutation(quantiles(part["length"], n))
            if part.get("pool"):
                topics = self.order.permutation(zipf_ranks(
                    part["pool"], part.get("zipf_s", 0.0), n))
            parts.append((j, part, lens))
        arrivals = []
        for i in range(n):
            prompt: List[int] = []
            for j, part, lens in parts:
                if part.get("pool"):
                    prompt += self._context(j, int(topics[i]), int(lens[i]))
                else:
                    prompt += self._ids(int(lens[i]))
            arrivals.append(Arrival(float(times[i]), prompt, int(outs[i]),
                                    None if topics is None
                                    else int(topics[i])))
        return arrivals

    def schedule(self, seconds: float) -> Schedule:
        mix = self.mix
        lead = mix.get("lead_in", {})
        sched = Schedule()
        for j, part in enumerate(mix["prompt"]):
            if part.get("pool") and lead.get("warm_contexts"):
                length = int(quantiles(part["length"], 1)[0])
                # Zipf ranks are the topic ids: the warm set is the top ones
                for topic in range(min(lead["warm_contexts"], part["pool"])):
                    sched.warm.append(self._context(j, topic, length))
        lead_s = float(lead.get("seconds", 0.0))
        burst = int(lead.get("burst", 0))
        n_lead = int(round(mix["rate_per_s"] * lead_s))
        sched.lead_in = self._arrivals(burst, -lead_s)
        for a in sched.lead_in:
            a.due = -lead_s
        sched.lead_in += self._arrivals(n_lead, -lead_s)
        n = max(1, int(math.floor(mix["rate_per_s"] * seconds)))
        sched.window = self._arrivals(n, 0.0)
        return sched
