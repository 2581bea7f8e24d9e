"""Share of the window in which the host ran the handler's own work: the
window less the wall time of every dispatch and of every wait of the
clock for the next arrival or completion (handler layer, host clock)."""


def _inside(a, b, end):
    return max(0.0, min(b, end) - max(a, 0.0))


def read(run):
    end = run.window_end
    if not run.dispatches or end <= 0:
        return None
    busy = sum(_inside(d.start, d.end, end) for d in run.dispatches)
    waits = sum(_inside(a, b, end) for a, b in run.waits)
    return 100.0 * max(0.0, end - busy - waits) / end
