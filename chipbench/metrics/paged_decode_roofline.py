"""Share of its roofline the paged decode kernel reaches in the traced
window: max(FLOPs / peak FLOP/s, bytes / peak bytes/s) over the kernel's
device time, in %. Work counts the live context of each decode row (the
architecture's ``decode_attention``); time is the summed duration of the
trace events whose name matches PATTERN."""
from _common import decode_context

PATTERN = r"paged_decode_attention"


def read(run, name):
    if run.trace is None or not run.peaks:
        return None
    t = run.trace.kernel_s(PATTERN)
    rows, live = decode_context(run)
    if t <= 0 or rows == 0:
        return None
    f, b = run.arch.decode_attention(run.dims, rows, live)
    bound = max(f / run.peaks["bf16_flops"], b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / t
