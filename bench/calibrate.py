#!/usr/bin/env python3
"""Readings the outputs limit of a cell is set from.

    python bench/calibrate.py --workload <cell> --seconds <s> --seeds <n> ...

In one process on the chip: set-up once, then for each seed new weights,
the cell's traffic for a short window at the cell's own load, and on the
sample a run checks, the widest reference gap of the served tokens (the
program's reading) and of the tokens the int8 control puts first (the
control's reading).  The benchmark's own runs never run the control.
Prints one line per seed and, last, a JSON summary.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    if not run.boot():
        return 2
    from harness import cell as C
    from harness import serving, spec, traffic
    from harness.weights import make_params
    cell = spec.load_cell(args.workload)
    C.device_info(True, cell.chips)
    meter = serving.CompileMeter()
    backend = C.setup(cell, args.seeds[0], meter)
    mix = cell.traffic
    rows = []
    for seed in args.seeds:
        backend.params = None
        gc.collect()
        backend.params = make_params(cell.model, seed)
        specs = traffic.generate(mix, cell.model, seed, args.seconds)
        rec, handler, _, _, sent = serving.serve_window(
            backend, mix, specs, args.seconds, False)
        del handler
        gc.collect()
        done = {c.rid: c for c in rec.completions}
        r = C.Run(cell.model, mix, args.seconds, rec.window_end, sent, done,
                  rec.dispatches, rec.admits, {})
        prog, ctrl, n = C.check_outputs(backend, cell, r, seed, control=True)
        rows.append({"seed": seed, "program": prog, "control": ctrl,
                     "tokens": n, "finished": len(done), "sent": len(sent)})
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    print(json.dumps({
        "workload": cell.name, "seconds": args.seconds,
        "program_max": {k: max(r["program"][k] for r in rows)
                        for k in ("max", "mean")},
        "control_min": {k: min(r["control"][k] for r in rows)
                        for k in ("max", "mean")},
        "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
