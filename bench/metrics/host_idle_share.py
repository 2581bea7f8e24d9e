"""Share of the window with no device op while the host runs the
handler's own work: idle device time whose innermost host span is a
``serve.*`` span other than ``serve.wait`` (device/handler boundary, the
program's spans and the device ops of one profiler trace)."""
from harness import program_trace as pt


def read(run):
    # the program trace: absent where the run was not traced, or where
    # the harness does not read the program's spans
    trace = getattr(run, "program", None)
    if trace is None or trace.window_s <= 0 or not trace.spans:
        return None
    host = sum(v for k, v in trace.idle_by_span().items()
               if k.startswith(pt.PREFIX) and k != pt.WAIT)
    return 100.0 * host / trace.window_s
