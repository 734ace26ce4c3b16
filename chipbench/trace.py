"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

``events_from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote,
with ``jax.profiler.ProfileData``, into a plain event list: the device
operations of each TPU (the "XLA Ops" line of each ``/device:TPU:<n>``
plane) and the benchmark's own host spans (``jax.profiler.TraceAnnotation``
names listed in ``SPANS``). ``reduce`` works on that list alone, so it can
be tested on a recorded excerpt (``chipbench/testdata``).

- busy: the union of a device's operation intervals inside the window,
  averaged over the devices used;
- kernel time: the summed durations of operations whose name matches a
  pattern, inside the window;
- idle gaps: the complement of the union inside the window, each labelled
  with the innermost benchmark span that covers its midpoint.
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "window"
SPANS = (WINDOW_SPAN, "generator", "pump", "pump.admit", "pump.harvest",
         "pump.dispatch", "pump.collect")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"


def op_name(text: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..)`` -> ``fusion.3``: the TPU trace
    names an operation by its whole HLO text."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def events_from_xplane(path: str) -> dict:
    """{"device": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[span, start_ns, dur_ns], ...]}"""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: dict = {"device": {}, "host": []}
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    evs += [[op_name(e.name), float(e.start_ns),
                             float(e.duration_ns)] for e in line.events]
            out["device"][plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, float(e.start_ns),
                                 float(e.duration_ns)]
                                for e in line.events if e.name in SPANS]
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _label(spans, t: float) -> str:
    """Innermost (shortest) benchmark span covering time t."""
    best, best_d = "outside_spans", None
    for name, s, d in spans:
        if name != WINDOW_SPAN and s <= t <= s + d and (
                best_d is None or d < best_d):
            best, best_d = name, d
    return best


class Reduction:
    def __init__(self, events: dict):
        wins = [(s, s + d) for n, s, d in events["host"] if n == WINDOW_SPAN]
        if not wins:
            raise ValueError("trace has no window span")
        self.t0, self.t1 = wins[0]
        self.window_s = (self.t1 - self.t0) * 1e-9
        self.spans = [h for h in events["host"]
                      if h[1] + h[2] >= self.t0 and h[1] <= self.t1]
        self.device: Dict[str, List[Tuple[str, float, float]]] = {}
        for plane, evs in events["device"].items():
            clipped = []
            for name, s, d in evs:
                a, b = max(s, self.t0), min(s + d, self.t1)
                if b > a:
                    clipped.append((name, a, b))
            self.device[plane] = clipped

    @property
    def used_planes(self) -> List[str]:
        return [p for p, evs in self.device.items() if evs]

    def busy_s(self) -> float:
        planes = self.used_planes
        if not planes:
            return 0.0
        tot = 0.0
        for p in planes:
            tot += sum(b - a for a, b in _union(
                [(a, b) for _, a, b in self.device[p]]))
        return tot * 1e-9 / len(planes)

    def kernel_s(self, pattern: str) -> float:
        """Device seconds of operations whose name matches ``pattern``,
        summed over the events and averaged over the devices used."""
        rx = re.compile(pattern)
        planes = self.used_planes
        if not planes:
            return 0.0
        tot = sum(b - a for p in planes for n, a, b in self.device[p]
                  if rx.search(n))
        return tot * 1e-9 / len(planes)

    def self_times(self, plane: str) -> Dict[str, float]:
        """Seconds each operation ran with no operation nested inside it
        (a loop's own time excludes its body's operations)."""
        evs = sorted(self.device[plane], key=lambda e: (e[1], -e[2]))
        out: Dict[str, float] = {}
        stack: List[list] = []           # [name, start, end, child time]

        def close(item):
            name, a, b, child = item
            out[name] = out.get(name, 0.0) + max(0.0, (b - a) - child) * 1e-9
            if stack:
                stack[-1][3] += b - a

        for name, a, b in evs:
            while stack and stack[-1][2] <= a:
                close(stack.pop())
            stack.append([name, a, b, 0.0])
        while stack:
            close(stack.pop())
        return out

    def top_ops(self, k: int = 10) -> List[list]:
        """The k operations with the most self time, averaged over the
        devices used."""
        agg: Dict[str, float] = {}
        planes = self.used_planes
        for p in planes:
            for n, s in self.self_times(p).items():
                agg[n] = agg.get(n, 0.0) + s / len(planes)
        return [[n, s] for n, s in sorted(agg.items(), key=lambda x: -x[1])
                [:k]]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The k longest idle gaps of the first device used, labelled."""
        planes = self.used_planes
        if not planes:
            return []
        busy = _union([(a, b) for _, a, b in self.device[planes[0]]])
        gaps, t = [], self.t0
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if self.t1 > t:
            gaps.append((t, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[_label(self.spans, (a + b) / 2), (b - a) * 1e-9]
                for a, b in gaps[:k]]

    def idle_by_span(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for name, s in self.idle_gaps(k=10 ** 9):
            out[name] = out.get(name, 0.0) + s
        return out


def excerpt(events: dict, max_device: int = 400,
            around: str = r"paged_append_attention") -> dict:
    """A small slice of a trace for the tests: ``max_device`` device
    operations of the window, starting a little before the first one whose
    name matches ``around``, the host spans that overlap them, and a window
    span cut to them."""
    red = Reduction(events)
    out: dict = {"device": {}, "host": []}
    t_lo, t_hi = None, None
    rx = re.compile(around)
    for plane, evs in events["device"].items():
        inside = sorted([e for e in evs if e[1] >= red.t0],
                        key=lambda e: e[1])
        first = next((i for i, e in enumerate(inside) if rx.search(e[0])), 0)
        lo = max(0, first - max_device // 4)
        part = inside[lo:lo + max_device]
        out["device"][plane] = part
        if part:
            a, b = part[0][1], max(e[1] + e[2] for e in part)
            t_lo = a if t_lo is None else min(t_lo, a)
            t_hi = b if t_hi is None else max(t_hi, b)
    if t_lo is None:
        t_lo = t_hi = red.t0
    out["host"] = [[WINDOW_SPAN, t_lo, t_hi - t_lo]] + [
        h for h in events["host"]
        if h[0] != WINDOW_SPAN and h[1] <= t_hi and h[1] + h[2] >= t_lo]
    return out


def save_json(obj, path: str) -> None:
    with open(path, "w") as f:
        json.dump(obj, f)


def load_json(path: str) -> Optional[dict]:
    with open(path) as f:
        return json.load(f)
