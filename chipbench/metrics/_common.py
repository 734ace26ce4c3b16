"""Shared arithmetic of the metric readers (not a metric itself)."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with a share q of the
    sample at or below it."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def ttft(run, r) -> float:
    """Due time to first token; a request never served counts as waiting
    until the run gave up on it."""
    return (r.first if r.first is not None else run.give_up) - r.due


def tpot(run, r):
    """Mean gap between tokens after the first, or None for one-token
    answers. An unfinished request counts as finishing when the run gave
    up, over the tokens it was asked for (from its due time, if it never
    got a first token)."""
    n = len(r.tokens) if r.done is not None else r.max_new
    if n < 2:
        return None
    first = r.first if r.first is not None else r.due
    done = r.done if r.done is not None else run.give_up
    return (done - first) / (n - 1)


def decode_context(run):
    """(rows, live tokens) of the decode work in the window: the row count
    from the engine's counters; the mean live context per row from the
    requests that decoded in the window (a request of prompt L and n tokens
    decodes n - 1 rows at lengths L + 1 ... L + n - 1)."""
    t0, t1 = run.window_t
    num = den = 0.0
    for r in run.all_recs:
        if r.admitted is None or r.admitted > t1:
            continue
        if r.done is not None and r.done < t0:
            continue
        n = len(r.tokens) if r.done is not None else r.max_new
        L = len(r.prompt)
        rows = max(n - 1, 0)
        num += rows * L + rows * (rows + 1) / 2
        den += rows
    rows = run.delta["decode_rows"]
    if den == 0 or rows == 0:
        return rows, 0.0
    return rows, rows * num / den


def prefill_work(run):
    """(computed, attended, context) prefill tokens in the window: the
    computed count from the engine's counters, the rest scaled from the
    requests admitted in or just before the window (prompt L, of which the
    prefix cache served s: tokens s .. L - 1 are computed, token p attends
    p + 1 keys, and the L-token context is read once)."""
    t0, t1 = run.window_t
    comp = att = ctx = 0.0
    for r in run.all_recs:
        if r.admitted is None or r.admitted > t1:
            continue
        if r.first is not None and r.first < t0:
            continue
        L, s = len(r.prompt), r.shared
        comp += L - s
        att += (L * (L + 1) - s * (s + 1)) / 2
        ctx += L
    computed = run.delta["prefill_tokens"]
    if comp == 0 or computed == 0:
        return computed, 0.0, 0.0
    f = computed / comp
    return computed, att * f, ctx * f
