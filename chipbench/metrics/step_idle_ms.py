"""Device idle time per engine step that falls inside the program's
``sched.pump`` spans (``TierScheduler.pump``, one scheduling round from
admission to the final ``collect``), in ms: the overlap of the first used
device's idle intervals in the traced window with those spans, over the
engine steps dispatched in the window. Idle outside the pump (the
benchmark's generator, sleeps between arrivals) does not count."""
from _spans import idle_intervals, overlap_ns, program_records, to_trace_ns


def read(run, name):
    recs = program_records(run)
    steps = run.delta["steps"]
    if run.trace is None or recs is None or not steps:
        return None
    idle = idle_intervals(run.trace)
    pumps = [(to_trace_ns(run, s), to_trace_ns(run, e))
             for n, s, e, _, _ in recs if n == "sched.pump"]
    if idle is None or not pumps:
        return None
    return overlap_ns(idle, pumps) * 1e-6 / steps
