"""Share of its roofline the paged append (chunked prefill) kernel reaches
in the traced window: max(FLOPs / peak, bytes / bandwidth) over its device
time, in %. Work counts causal attention of the computed prompt tokens and
each request's context read once (the architecture's ``append_attention``);
time is the summed duration of trace events matching PATTERN."""
from _common import prefill_work

PATTERN = r"paged_append_attention"


def read(run, name):
    if run.trace is None or not run.peaks:
        return None
    t = run.trace.kernel_s(PATTERN)
    computed, attended, context = prefill_work(run)
    if t <= 0 or computed == 0:
        return None
    f, b = run.arch.append_attention(run.dims, computed, attended, context)
    bound = max(f / run.peaks["bf16_flops"], b / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / t
