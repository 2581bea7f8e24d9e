"""Median wait in the queue of the requests due in the window: the time
each first took an engine slot (``ServeRequest.slot_t``, stamped by the
handler's admission) less its due time (handler admission layer)."""
import numpy as np


def read(run):
    waits = [r.slot_t - r.arrival_t for r in run.sent
             if r.arrival_t < run.seconds
             and getattr(r, "slot_t", None) is not None]
    if not waits:
        return None
    return float(np.percentile(waits, 50))
