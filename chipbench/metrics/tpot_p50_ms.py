"""Median, over the window's requests of two tokens or more, of
(last-token time - first-token time) / (tokens - 1), in ms (host clock)."""
from _common import percentile, tpot


def read(run, name):
    vals = [v for v in (tpot(run, r) for r in run.recs) if v is not None]
    if not vals:
        return None
    return 1e3 * percentile(vals, 0.5)
