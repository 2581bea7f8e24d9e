"""Share of the decode batch's slot-steps that held a live request: the
live decode rows times the scan steps of each engine step dispatched in
the window, over its slots times its steps (model step layer: the decode
cohort, from the stats of the program's ``serve.dispatch`` spans).  A row
holds its slot through a mixed step's whole scan, though it decodes only
its window's tokens there."""

def read(run):
    # the program trace: absent where the run was not traced, or where
    # the harness does not read the program's spans
    trace = getattr(run, "program", None)
    if trace is None:
        return None
    live = held = 0
    for s in trace.named("serve.dispatch"):
        m = s.meta
        if (m.get("label") == "step" and s.start >= trace.start
                and "rows" in m):
            live += m["rows"] * m["steps"]
            held += m["slots"] * m["steps"]
    if held == 0:
        return None
    return 100.0 * live / held
