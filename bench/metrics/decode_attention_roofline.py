"""Least time the window's decode tokens needed in attention (their live
context's keys and values read once) over the device time of the decode
attention kernel (``paged_attention``) in the trace (kernels layer)."""
from harness import flops

KERNELS = ("paged_attention",)


def read(run):
    if run.trace is None or not run.peak:
        return None
    t = run.trace.kernel_s(KERNELS)
    if t <= 0:
        return None
    m, pad = run.model, run.mix["prompt_pad"]
    fl = by = 0.0
    for c in run.completions.values():
        for j, ts in enumerate(c.token_ts):
            if j and 0 <= ts <= run.window_end:
                fl += flops.attention_flops(m, pad + j - 1, 1)
                by += flops.attention_bytes(m, pad + j - 1, 1)
    if by == 0:
        return None
    return 100.0 * flops.least_seconds(fl, by, run.peak) / t
