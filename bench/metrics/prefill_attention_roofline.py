"""Least time the window's prefills needed in attention (causal FLOPs over
the uncached prompt tokens, or their queries, keys and values moved once)
over the device time of the prefill attention kernels (``paged_prefill``,
``flash_attention``) in the trace (kernels layer)."""
from harness import flops

KERNELS = ("paged_prefill", "flash_attention")


def read(run):
    if run.trace is None or not run.peak:
        return None
    t = run.trace.kernel_s(KERNELS)
    if t <= 0:
        return None
    fl = by = 0.0
    for rid, c in run.completions.items():
        if rid in run.admits and 0 <= c.first_token_t <= run.window_end:
            cached, prompt = run.admits[rid]
            fl += flops.attention_flops(run.model, cached, prompt - cached)
            by += flops.attention_bytes(run.model, cached, prompt - cached)
    if fl == 0:
        return None
    return 100.0 * flops.least_seconds(fl, by, run.peak) / t
