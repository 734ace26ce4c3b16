"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window, in % (profiler trace)."""


def read(run, name):
    if run.trace is None or not run.trace.used_planes:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)
