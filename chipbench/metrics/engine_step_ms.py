"""Mean wall time per engine step: the window over the steps the engine
dispatched in it, in ms (host clock around ``TierScheduler.pump``)."""


def read(run, name):
    steps = run.delta["steps"]
    return 1e3 * run.window_s / steps if steps else None
