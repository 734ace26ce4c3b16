"""Median over the window's requests of the time from the end of the
request's ``engine.admit`` span (attribute ``rid``) to the start of the
``engine.dispatch`` span that launches its first prefill chunk
(``chunk_rid == rid`` and ``first_chunk``), in s: the steps an admitted
request waits while other requests' chunks run (``_pick_chunk`` advances
one request per step).

Spans are recorded only while the window is traced. A request admitted
after that has no ``engine.admit`` span and is left out. One whose first
chunk launched after it counts as waiting until the window's end (a lower
bound); one that never got a first token, until the run gave up on it, as
``ttft`` counts it."""
from _common import percentile
from _spans import program_records


def read(run, name):
    recs = program_records(run)
    if run.trace is None or recs is None:
        return None
    admitted, first = {}, {}
    for n, s, e, _, a in recs:
        if n == "engine.admit" and "rid" in a:
            admitted[a["rid"]] = e
        elif n == "engine.dispatch" and a.get("first_chunk"):
            first.setdefault(a["chunk_rid"], s)
    waits = []
    for r in run.recs:
        if r.rid not in admitted:
            continue
        if r.rid in first:
            start = first[r.rid]
        elif r.first is None:
            start = run.give_up * 1e9
        else:
            start = run.window_t[1] * 1e9
        waits.append(max(start - admitted[r.rid], 0.0) * 1e-9)
    return percentile(waits, 0.5) if waits else None
