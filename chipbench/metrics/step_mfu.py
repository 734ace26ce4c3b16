"""Model FLOPs of the tokens computed in the window (prefill tokens not
served from cache, plus decode rows), with attention over the live context
and the head for each decode row and each prefill chunk, over window times
the chip's bf16 peak, in %."""
from _common import decode_context, prefill_work


def read(run, name):
    if not run.peaks:
        return None
    rows, live = decode_context(run)
    computed, attended, _ = prefill_work(run)
    a = run.arch
    att = (a.decode_attention(run.dims, rows, live)[0]
           + a.append_attention(run.dims, computed, attended, 0)[0])
    total = a.model_step(run.dims, rows + computed,
                         rows + run.delta["prefill_chunks"], att)
    return 100.0 * total / (run.window_s * run.peaks["bf16_flops"])
