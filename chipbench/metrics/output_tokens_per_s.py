"""Output tokens the engine produced inside the window, over the window
(host clock): decode rows dispatched plus first tokens of finished
prefills, from the engine's counters."""


def read(run, name):
    return run.delta["produced"] / run.window_s
