#!/usr/bin/env python3
"""One traced run of a cell that also reads the program's own spans.

    python3 bench/trace_program.py --workload <cell> --seed <n> \\
        --seconds <s> [--fixture <path> --fixture-at <s> --fixture-ms <ms>]

Run from the repository root.  The run is ``run.py --trace 1``'s, with
the HLO protos kept in the trace, and so is the last line of standard
output, with one more key, ``program``: the per-layer metrics that read
the serving program's ``serve.*`` spans from the same profiler trace
(``metrics/step_host_s.py``, ``host_idle_share.py``,
``batch_occupancy.py``), the window's seconds and idle seconds, and the
breakdown's ``idle_by_span`` and ``device_by_program``, ten of each, the
most first (``harness/program_trace.py``).  ``--fixture`` also writes
``--fixture-ms`` of the trace, from the first step built
``--fixture-at`` seconds or more into the window, as a test fixture with
those readers' values on it.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from unittest import mock  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
PROGRAM_METRICS = ("step_host_s", "host_idle_share", "batch_occupancy")


def values(program) -> dict:
    """The program-span metrics and breakdown of a ``ProgramTrace``."""
    from harness import spec
    run = types.SimpleNamespace(program=program)
    return {
        "metrics": {m: spec.metric_reader(m)(run) for m in PROGRAM_METRICS},
        "window_s": program.window_s,
        "idle_s": sum(program.idle_by_span().values()),
        "breakdown": program.breakdown(),
    }


def fixture(program, at_s: float, ms: float, source: str) -> dict:
    """``ms`` of ``program`` from the first engine step built ``at_s`` or
    more into its window (from ``at_s`` where none is): the ops and spans
    that overlap it, times in ns from its start, and the values the
    readers give on it."""
    from harness import program_trace as pt
    a = program.start + int(at_s * 1e9)
    a = min((s.start for s in program.named("serve.step.build")
             if s.start >= a), default=a)
    b = a + int(ms * 1e6)
    ops = [[o.name, o.start - a, o.end - a, o.program, o.scope]
           for o in program.ops if o.end > a and o.start < b]
    spans = [[pt.tr.WINDOW_SPAN, 0, b - a, {}]] + [
        [s.name, s.start - a, s.end - a, s.meta]
        for s in program.spans if s.end > a and s.start < b]
    cut = pt.ProgramTrace.build(
        [pt.DeviceOp(n, s, e, p, c) for n, s, e, p, c in ops],
        [pt.HostSpan(n, s, e, m) for n, s, e, m in spans])
    return {"source": source, "ops": ops, "spans": spans,
            "expected": values(cut)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture")
    ap.add_argument("--fixture-at", type=float, default=2.0)
    ap.add_argument("--fixture-ms", type=float, default=100.0)
    args = ap.parse_args()
    sys.path.insert(0, HERE)
    import run as bench
    if not bench.boot():
        return 2
    from harness import cell as C
    from harness import program_trace as pt
    from harness import spec
    from harness import trace as tr
    read = {}
    load = tr.load

    def load_both(trace_dir):
        t0 = time.perf_counter()
        read["program"] = pt.load(trace_dir)
        C.log(f"program trace: {len(read['program'].ops)} device ops, "
              f"{len(read['program'].spans)} host spans in the window, "
              f"read in {time.perf_counter() - t0:.3f} s")
        return load(trace_dir)

    import jax
    start_trace = jax.profiler.start_trace

    def start_with_hlo(log_dir, *a, profiler_options=None, **kw):
        # the HLO protos give each device op its named scope; they are
        # written when the trace stops, outside the window
        profiler_options.enable_hlo_proto = True
        return start_trace(log_dir, *a, profiler_options=profiler_options,
                           **kw)

    cell = spec.load_cell(args.workload)
    with mock.patch.object(tr, "load", load_both), \
            mock.patch.object(jax.profiler, "start_trace", start_with_hlo):
        result = C.run_cell(cell, args.seed, args.seconds, True, T_PROCESS)
    program = read["program"]
    result["program"] = values(program)
    if args.fixture:
        source = (f"{jax.devices()[0].device_kind}, {cell.name}, seed "
                  f"{args.seed}, {args.fixture_ms:g} ms from the first step "
                  f"built {args.fixture_at:g} s or more into the window")
        with open(args.fixture, "w") as f:
            json.dump(fixture(program, args.fixture_at, args.fixture_ms,
                              source), f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
