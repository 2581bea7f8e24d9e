"""Share of the prompt tokens admitted in the window that the prefix cache
served (KV cache layer, the handler's admission counters)."""


def read(run):
    rows = [run.admits[r.rid] for r in run.sent
            if r.rid in run.admits and r.arrival_t < run.window_end]
    prompt = sum(p for _, p in rows)
    if prompt == 0:
        return None
    return 100.0 * sum(c for c, _ in rows) / prompt
