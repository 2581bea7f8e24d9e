"""One run of one cell: set-up, the measured window, the outputs check and
the result line.  ``run.py`` is the command; tests call ``run_cell``."""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from harness import flops, spec, traffic
from harness.reference import first_choices, gaps, served_sequences
from harness.serving import (CompileMeter, build_backend, serve_window,
                             warm_up)
from harness.weights import check_layout, make_params

LIMITS_DIR = os.path.join(spec.BENCH_DIR, "limits")
TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def limits(cell: str) -> Dict:
    with open(os.path.join(LIMITS_DIR, cell + ".json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader reads (``metrics/<name>.py``)."""

    model: Dict
    mix: Dict
    seconds: float
    window_end: float              # wall seconds the window lasted
    sent: List                     # ServeRequests sent
    completions: Dict[int, object]  # rid -> ServeCompletion
    dispatches: List               # harness.serving.Dispatch
    admits: Dict[int, tuple]       # rid -> (cached tokens, prompt tokens)
    peak: Dict[str, float]         # the chip's published peaks
    waits: List = dataclasses.field(default_factory=list)  # clock waits
    trace: Optional[object] = None  # harness.trace.Summary


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(run: Run) -> Dict[str, float]:
    """Every end-to-end number, from the client's side on the wall clock."""
    t = run.seconds
    due = [r for r in run.sent if r.arrival_t < t]
    done = [run.completions[r.rid] for r in due if r.rid in run.completions]
    delivered = sum(1 for c in run.completions.values()
                    for ts in c.token_ts if ts <= t)
    out = {"output_tok_per_s": delivered / t}
    ttft = [c.first_token_t - c.arrival_t for c in done]
    itl = [g for c in done for g in np.diff(c.token_ts)]
    if ttft:
        out["ttft_p90_s"] = percentile(ttft, 90)
        out["ttft_p50_s"] = percentile(ttft, 50)
    if itl:
        out["itl_p99_s"] = percentile(itl, 99)
        out["itl_p50_s"] = percentile(itl, 50)
    out["requests_due"] = len(due)
    out["gaps"] = len(itl)
    return out


def check_outputs(backend, cell, run: Run, seed: int, control: bool = False):
    """Served tokens against the float32 reference, on a sample drawn from
    the seed; with ``control`` also the gaps of the tokens the int8 control
    puts first at the same positions.  Returns ({"max", "mean"} of the
    program's gaps, the same of the control's or None, tokens checked)."""
    import jax
    mix = cell.traffic
    fin = [r for r in run.sent if r.rid in run.completions]
    n_tok = {rid: len(c.tokens) for rid, c in run.completions.items()}
    cached = {rid: a[0] for rid, a in run.admits.items()}
    sample = traffic.check_sample(fin, n_tok, seed, mix, cached)
    outs = [np.asarray(run.completions[s.rid].tokens, np.int64)
            for s in sample]
    rows, k = mix["check"]["max_requests"], mix["output_tokens"][1]
    seqs, at = served_sequences([s.prompt for s in sample], outs,
                                mix["prompt_pad"], rows, k)
    served = np.zeros((rows, k), np.int32)
    valid = np.zeros((rows, k), bool)
    for r, o in enumerate(outs):
        served[r, :len(o)] = o
        valid[r, :len(o)] = True
    fam = spec.family(cell.model)
    t0 = time.perf_counter()
    ref = fam.Reference(cell.model).logits(backend.params, seqs, at)
    jax.block_until_ready(ref)
    log(f"reference: {len(sample)} rows of {seqs.shape[1]} tokens in "
        f"{time.perf_counter() - t0:.3f} s")

    def stats(g):
        return {"max": float(g.max()), "mean": float(g.sum() / valid.sum())}

    prog = stats(gaps(ref, served, valid))
    ctrl = None
    if control:
        low = fam.Reference(cell.model, quant="int8").logits(
            backend.params, seqs, at)
        ctrl = stats(gaps(ref, first_choices(low), valid))
    return prog, ctrl, int(valid.sum())


def device_info(require_chip: bool, chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_chip and (d.platform == "cpu" or len(devs) < chips):
        raise SystemExit(f"needs {chips} accelerator chip(s); JAX finds "
                         f"{len(devs)} {d.platform} device(s)")
    return d, len(devs)


def setup(cell, seed: int, meter: CompileMeter):
    """Weights from the seed, the backend over them, every shape warmed."""
    mix = cell.traffic
    pc = spec.program_config(cell.model, mix["capacity"])
    params = make_params(cell.model, seed)
    check_layout(cell.model, params)
    backend = build_backend(pc, mix["capacity"], params)
    del params
    n = warm_up(backend, mix, cell.model, seed)
    gc.collect()
    log(f"set-up: warm-up served {n} requests, {meter.n} compiles "
        f"({meter.seconds:.3f} s) so far")
    return backend


def run_cell(cell, seed: int, seconds: float, tracing: bool, t_process: float,
             require_chip: bool = True,
             tamper: Optional[Callable] = None) -> Dict:
    """The whole run; returns the result object (the last stdout line).
    ``tamper(backend)`` lets a test break the timed path underneath."""
    dev, n_dev = device_info(require_chip, cell.chips)
    peak = flops.peaks(dev.device_kind) if dev.platform != "cpu" else {}
    meter = CompileMeter()
    backend = setup(cell, seed, meter)
    if tamper is not None:
        tamper(backend)
    mix = cell.traffic
    specs = traffic.generate(mix, cell.model, seed, seconds)
    compiles0, compile_s0 = meter.n, meter.seconds
    trace_dir = os.path.join(TRACE_DIR, cell.name)
    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = time.perf_counter() - t_process
    rec, handler, wall, makespan, sent = serve_window(
        backend, mix, specs, seconds, tracing, trace_dir)
    compiles = meter.n - compiles0
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    rejected = handler.queue.rejected
    del handler
    gc.collect()
    completions = {c.rid: c for c in rec.completions}
    run = Run(cell.model, mix, seconds, rec.window_end, sent, completions,
              rec.dispatches, rec.admits, peak, rec.clock.waits)
    e2e = end_to_end(run)
    vocab = cell.model["vocab_size"]
    bad = 0
    for r in sent:
        c = completions.get(r.rid)
        toks = np.asarray(c.tokens if c is not None else [], np.int64)
        if (c is None or len(toks) != r.max_new_tokens or toks.min() < 0
                or toks.max() >= vocab or len(c.token_ts) != len(toks)):
            bad += 1
    log(f"window: {len(sent)} requests sent, {len(completions)} finished, "
        f"{bad} failed, {rejected} refused; {len(rec.dispatches)} "
        f"dispatches; compiles in the window {compiles} "
        f"({meter.seconds - compile_s0:.3f} s)")
    late = [r.admitted_t - r.arrival_t for r in sent
            if r.admitted_t is not None]
    log(f"timeline: modeled delay {rec.modeled_s:.6f} s; handler makespan "
        f"{makespan:.4f} s against {wall:.4f} s of wall clock around run; "
        f"generator late by up to {max(late, default=0.0):.4f} s")
    if rec.modeled_s > 1e-3 * seconds or makespan > wall + 1e-3:
        raise RuntimeError("the handler's timeline holds time that was not "
                           "spent on the wall clock")
    t_ref = time.perf_counter()
    gap, _, n_checked = check_outputs(backend, cell, run, seed)
    lim = limits(cell.name)
    log(f"outputs check: {n_checked} served tokens, reference gap max "
        f"{gap['max']:.5f}, mean {gap['mean']:.6f} std "
        f"({time.perf_counter() - t_ref:.3f} s)")
    checks = {f"gap_{k}_std": {"value": gap[k], "limit": lim[f"gap_{k}_std"]}
              for k in ("max", "mean") if f"gap_{k}_std" in lim}
    checks["failed_requests"] = {"value": bad + rejected, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev, "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": len(sent),
              "failed": bad + rejected}
    if tracing:
        from harness import trace as tr
        t_tr = time.perf_counter()
        summ = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: {len(summ.ops)} device ops in the window, read in "
            f"{time.perf_counter() - t_tr:.3f} s")
        run.trace = summ
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summ.busy_s, window_s=summ.window_s)
        result["metrics"] = metrics
        result["breakdown"] = summ.breakdown()
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {name: {"value": e2e[name], "unit": units[name]}
                   for name in units if name in e2e}
        metrics["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}
        result["metrics"] = metrics
    result["device"] = device
    result["info"] = {
        "seed": seed, "seconds": seconds, "sent": len(sent),
        "finished": len(completions), "dispatches": len(rec.dispatches),
        "compiles_in_window": compiles, "modeled_delay_s": rec.modeled_s,
        "handler_makespan_s": makespan, "wall_s": wall,
        "window_end_s": rec.window_end, "checked_tokens": n_checked,
        "gap_max_std": gap["max"],
        **{k: e2e[k] for k in e2e if k not in result["metrics"]}}
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    return result
