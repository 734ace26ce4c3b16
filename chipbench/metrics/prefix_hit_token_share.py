"""Prompt tokens served from cached pages over prompt tokens placed in the
arena (served from cache plus prefilled) in the window, in % (engine
counters)."""


def read(run, name):
    shared = run.delta["prefix_tokens_shared"]
    total = shared + run.delta["prefill_tokens"]
    return 100.0 * shared / total if total else None
