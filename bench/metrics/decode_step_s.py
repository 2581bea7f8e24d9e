"""Wall seconds per sequential step of the dispatches that decode: their
summed wall time over their summed scan steps, in the window (model step
layer, host clock around each dispatch to block_until_ready)."""


def read(run):
    rows = [d for d in run.dispatches
            if d.label == "step" and d.start < run.window_end and d.seq_steps]
    steps = sum(d.seq_steps for d in rows)
    if steps == 0:
        return None
    return sum(d.end - d.start for d in rows) / steps
