#!/usr/bin/env python3
"""Offered-load sweep of an open-loop cell, to find the highest rate it
sustains (the knee) once, when the cell is defined.

    python bench/sweep.py --workload <cell> --seconds <s> --rates <r> ...

One process on the chip: set-up once, then the cell's traffic at each rate
for one window.  A rate is sustained when every request due in the window
got its first token by the close plus one decode of the longest output,
and the backlog at the close does not grow with the rate.  Prints one JSON
line per rate.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    if not run.boot():
        return 2
    from harness import cell as C
    from harness import serving, spec, traffic
    cell = spec.load_cell(args.workload)
    C.device_info(True, cell.chips)
    meter = serving.CompileMeter()
    backend = C.setup(cell, args.seed, meter)
    mix = cell.traffic
    for rate in args.rates:
        specs = traffic.generate(mix, cell.model, args.seed, args.seconds, rate)
        n0 = meter.n
        rec, handler, wall, _, sent = serving.serve_window(
            backend, mix, specs, args.seconds, False)
        del handler
        gc.collect()
        done = {c.rid: c for c in rec.completions}
        r = C.Run(cell.model, mix, args.seconds, rec.window_end, sent, done,
                  rec.dispatches, rec.admits, {})
        e2e = C.end_to_end(r)
        waiting = sum(1 for q in sent if q.arrival_t < args.seconds
                      and done[q.rid].first_token_t > args.seconds)
        print(json.dumps({"rate": rate, "sent": len(sent),
                          "waiting_at_close": waiting, "run_wall_s": wall,
                          "compiles": meter.n - n0, **e2e}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
