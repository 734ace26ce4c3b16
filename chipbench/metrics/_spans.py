"""Shared arithmetic of the readers of the program's own spans (not a
metric itself).

The program records its spans (``repro.core.tracing``) while the profiler
runs, timed with ``time.perf_counter_ns()``. One anchor maps them onto the
trace's clock: the benchmark's ``window`` span starts microseconds after
``run.window_t[0]`` is read, so a span at ``ns`` on the host clock sits at
``run.trace.t0 + ns - run.window_t[0] * 1e9`` in the trace. A program that
has no spans yields no records, and its readers return None.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from chipbench.trace import _union as union

Interval = Tuple[float, float]


def program_records(run) -> Optional[List[list]]:
    """``[name, start_ns, end_ns, parent, attrs]`` of every closed span the
    program recorded since the window opened (earlier traced runs in the
    same process left theirs before it), or None where there is none."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    t0 = run.window_t[0] * 1e9
    recs = [r for r in tracing.records() if r[1] >= t0 and r[2] >= r[1]]
    return recs or None


def to_trace_ns(run, ns: float) -> float:
    return run.trace.t0 + (ns - run.window_t[0] * 1e9)


def idle_intervals(red) -> Optional[List[Interval]]:
    """The window's idle intervals (trace ns) on the first device used, as
    ``Reduction.idle_gaps`` takes them; None without a used device."""
    planes = red.used_planes
    if not planes:
        return None
    out, t = [], red.t0
    for a, b in union([(a, b) for _, a, b in red.device[planes[0]]]):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if red.t1 > t:
        out.append((t, red.t1))
    return out


def overlap_ns(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two lists of intervals."""
    a, b = union(a), union(b)
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot
