"""90th percentile of the time to first token over every request due in
the window, on the wall clock from the client's side (handler layer: the
tail that admission behind a running dispatch and the suffix scans make).
It stands beside the cell's median, whose spread a bound can hold."""
import numpy as np


def read(run):
    due = [run.completions[r.rid] for r in run.sent
           if r.arrival_t < run.seconds and r.rid in run.completions]
    if not due:
        return None
    return float(np.percentile([c.first_token_t - c.arrival_t for c in due],
                               90))
