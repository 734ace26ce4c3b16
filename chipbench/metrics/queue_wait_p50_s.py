"""Median of the scheduler's queue wait (due time to admission,
``Completion.queue_wait_s``) over the window's finished requests."""
from _common import percentile


def read(run, name):
    vals = [r.queue_wait for r in run.recs if r.queue_wait is not None]
    return percentile(vals, 0.5) if vals else None
