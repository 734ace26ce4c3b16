"""Faults planted under the timed path, to show that ``correct`` sees them.

Each takes the built ``ServingEngine`` (a run's ``after_engine`` hook) and
breaks one thing the cells exercise. Used by the CPU tests at toy widths
and by ``chipbench/control.py --fault`` at a cell's own size on the chip;
never by a benchmark run."""
from __future__ import annotations


def alter_tokens(eng):
    """Every sampled token is replaced by the next id."""
    orig, vocab = eng._sample, eng.cfg.vocab

    def sample(*a):
        return (orig(*a) + 1) % vocab

    eng._sample = sample


def state_unchanged(eng):
    """Every step returns the arena it was given, its writes dropped."""
    def keep(fn):
        def step(params, cache, *a):
            out = fn(params, cache, *a)
            return out[:-1] + (cache,)
        return step
    eng._fused = keep(eng._fused)
    eng._prefill_paged = keep(eng._prefill_paged)
    eng._decode = keep(eng._decode)


def wrong_prefix_pages(eng):
    """A prefix-cache hit maps cached pages of other prompts in place of
    its own; requests that miss are untouched."""
    prefix = eng._prefix
    orig = prefix.match

    def match(tokens):
        pages, tail = orig(tokens)
        if pages:
            mine = set(pages)
            others = [pid for pid, _ in prefix._blocks.values()
                      if pid not in mine]
            if others:
                pages = [others[i % len(others)] for i in range(len(pages))]
        return pages, tail

    prefix.match = match


FAULTS = {"alter-tokens": alter_tokens, "state-unchanged": state_unchanged,
          "wrong-prefix-pages": wrong_prefix_pages}
