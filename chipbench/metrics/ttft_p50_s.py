"""Median time to first token over every request due in the
window, from its due time (host clock). Unserved requests count as misses."""
from _common import percentile, ttft


def read(run, name):
    if not run.recs:
        return None
    return percentile([ttft(run, r) for r in run.recs], 0.5)
