"""The whole step's share of the chip's peak: matmul and attention FLOPs
of every token the window processed (uncached prompt tokens and decoded
tokens) over the window's wall seconds and the bf16 peak."""
from harness import flops


def read(run):
    if not run.peak:
        return None
    m, pad, end = run.model, run.mix["prompt_pad"], run.window_end
    head = 2.0 * m["hidden_size"] * m["vocab_size"]
    layers = flops.matmul_flops_per_token(m) - head
    total = 0.0
    for rid, c in run.completions.items():
        if rid in run.admits and 0 <= c.first_token_t <= end:
            cached, prompt = run.admits[rid]
            new = prompt - cached
            total += (new * layers + head
                      + flops.attention_flops(m, cached, new))
        for j, ts in enumerate(c.token_ts):
            if j and 0 <= ts <= end:
                total += layers + head + flops.attention_flops(
                    m, pad + j - 1, 1)
    if total == 0 or end <= 0:
        return None
    return 100.0 * total / end / run.peak["flops_bf16"]
