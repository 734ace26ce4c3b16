"""Mean decode rows per engine step over ``max_batch``, in %, over the
window (engine counters)."""


def read(run, name):
    steps = run.delta["steps"]
    if not steps:
        return None
    mb = run.cell["config"]["engine"]["max_batch"]
    return 100.0 * run.delta["decode_rows"] / steps / mb
