#!/usr/bin/env python3
"""Chip benchmark of the serving stack: one cell of BENCHMARK.json a run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the repository root.  The cell's model is served at its published
widths through ClientHandler's default paged path on one chip, with
random weights and traffic from the seed, on the wall clock.  Standard
error carries the run's log and, last, each checked number beside its
limit; the last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the end-to-end metrics, or with
``--trace 1`` the per-layer ones), ``device``, with a trace ``breakdown``,
and ``checks`` last.  Exits non-zero, printing no result, without an
accelerator or without the repository's ``src``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# JAX's persistent compilation cache: a fixed directory inside this
# checkout, never one named after a process or a time
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def boot() -> bool:
    """Put the harness and the program on the import path and keep JAX's
    compilation cache in the checkout; False without the program."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"no program under {src}", file=sys.stderr)
        return False
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # libtpu logs to a fixed directory under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, src]
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return True


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if not boot():
        return 2
    from harness import cell as C
    from harness import spec
    result = C.run_cell(spec.load_cell(args.workload), args.seed,
                        args.seconds, bool(args.trace), T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
