"""Median host seconds an engine step costs the handler: its
``serve.step.build`` span plus its ``serve.step.fold`` span, matched by
their ``step`` stat, over the steps built and folded in the window
(handler host layer, the program's spans in the profiler trace)."""
import numpy as np


def read(run):
    # the program trace: absent where the run was not traced, or where
    # the harness does not read the program's spans
    trace = getattr(run, "program", None)
    if trace is None:
        return None
    a, b = trace.start, trace.end
    build = {s.meta.get("step"): s.end - s.start
             for s in trace.named("serve.step.build") if s.start >= a}
    per_step = [build[s.meta.get("step")] + s.end - s.start
                for s in trace.named("serve.step.fold")
                if s.end <= b and s.meta.get("step") in build]
    if not per_step:
        return None
    return float(np.median(per_step)) * 1e-9
