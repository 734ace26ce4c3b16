"""The comparison that decides ``correct``.

After the window, a sample of the requests the timed path finished, drawn
from the seed, always holding the one with the most served tokens, a
prefix-cache hit and a prompt that took more than one prefill chunk where
the window has them, is run through the plain float32 reference of the
configuration's architecture (its module's ``final_states``) once each:
prompt plus served tokens. For every served token, the gap by which the
reference's logit of that token lies below the reference's best logit at
that position is read. Greedy decoding serves the argmax of the program's
own logits, so a sound program only loses to rounding near ties; a wrong
page, mask, position or token loses by whole logits.

``readings`` reduces the gaps to the numbers a configuration's ``check``
may name; ``judge`` holds them to its limits and is the one verdict of a
run. The cells compare ``worst_path_mean_gap``: the larger of the mean gap
over the tokens of the prefix-hit requests and that over the other
requests, so a fault confined to the hit path is not diluted by the
misses. The mean over all tokens, the worst single request's mean and the
widest gap are logged beside it; the last two swing with requests of one
or two tokens and do not separate the program from the control.

The control (``control_gaps``) puts the reference computed with int8
weights in the program's place and reads, at each of the same positions,
the gap of the token it ranks first; ``readings`` and ``judge`` then give
the control's verdict exactly as a run's.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import reference


def select(done: Sequence, seed: int, max_requests: int,
           chunk: int) -> List:
    """Pick the requests to compare: the longest served, a prefix hit, a
    multi-chunk prefill, then others in an order drawn from the seed.
    ``done`` items carry ``prompt``, ``tokens`` and ``shared``."""
    done = [r for r in done if r.tokens]
    if not done:
        return []
    rng = np.random.default_rng((int(seed) + 7) % (1 << 64))
    order = [done[i] for i in rng.permutation(len(done))]
    picks = [max(order, key=lambda r: len(r.tokens))]
    for want in (lambda r: r.shared > 0,
                 lambda r: len(r.prompt) - r.shared > chunk):
        hit = next((r for r in order if want(r) and r not in picks), None)
        if hit is not None:
            picks.append(hit)
    for r in order:
        if len(picks) >= max_requests:
            break
        if r not in picks:
            picks.append(r)
    return picks


def _rows(picks) -> Tuple[list, list]:
    rows, positions = [], []
    for r in picks:
        rows.append(list(r.prompt) + list(r.tokens[:-1]))
        L = len(r.prompt)
        positions.append(list(range(L - 1, L - 1 + len(r.tokens))))
    return rows, positions


def served_gaps(arch, seed: int, model: dict, init: dict,
                picks) -> List[np.ndarray]:
    """Per request, per served token: the reference's best logit minus its
    logit of the served token. ``arch`` is the configuration's module
    (``chipbench.run.arch``)."""
    rows, positions = _rows(picks)
    states, top = arch.final_states(seed, model, init, rows)
    (gaps,) = reference.token_gaps(states, top, positions,
                                   [r.tokens for r in picks])
    return gaps


def control_gaps(arch, seed: int, model: dict, init: dict, picks,
                 quant: str = "int8"
                 ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """Per request: the gaps of the served tokens and of the control's
    first-ranked tokens, both against the float32 reference, at the same
    positions."""
    rows, positions = _rows(picks)
    qstates, qtop = arch.final_states(seed, model, init, rows, quant=quant)
    states, top = arch.final_states(seed, model, init, rows)
    served, ctrl = reference.token_gaps(states, top, positions,
                                        [r.tokens for r in picks],
                                        control=(qstates, qtop))
    return served, ctrl


def readings(gaps: Sequence[np.ndarray], picks) -> Dict[str, float]:
    """The numbers a ``check`` may compare, from per-request gaps. With no
    served token every gap reads infinite."""
    gaps = [np.asarray(g, np.float64) for g in gaps]
    n = int(sum(g.size for g in gaps))
    if n == 0:
        inf = float("inf")
        return {"worst_path_mean_gap": inf, "mean_logit_gap": inf,
                "worst_request_mean_gap": inf, "max_logit_gap": inf,
                "served_tokens": 0}
    flat = np.concatenate(gaps)
    hit = [g for g, r in zip(gaps, picks) if getattr(r, "shared", 0) > 0]
    miss = [g for g, r in zip(gaps, picks)
            if getattr(r, "shared", 0) == 0]
    paths = [np.concatenate(p).mean() for p in (hit, miss)
             if sum(g.size for g in p)]
    return {
        "worst_path_mean_gap": float(max(paths)),
        "mean_logit_gap": float(flat.mean()),
        "worst_request_mean_gap": float(max(g.mean() for g in gaps
                                            if g.size)),
        "max_logit_gap": float(flat.max()),
        "served_tokens": n,
    }


def judge(read: Dict[str, float], limits: Dict[str, float],
          unfinished: Optional[int] = None) -> Tuple[bool, dict]:
    """The verdict: every number the configuration's ``check`` names at or
    under its limit, at least one served token compared, and (for a paced
    mix) every request due in the window finished. Returns ``(correct,
    compared)``, each compared number beside its limit."""
    compared = {k: {"value": read[k], "limit": float(lim)}
                for k, lim in limits.items()}
    compared["served_tokens_compared"] = {"value": read["served_tokens"],
                                          "limit": 1}
    if unfinished is not None:
        compared["unfinished_requests"] = {"value": unfinished, "limit": 0}
    correct = (read["served_tokens"] >= 1
               and (unfinished is None or unfinished == 0)
               and all(read[k] <= float(lim) for k, lim in limits.items()))
    return bool(correct), compared
