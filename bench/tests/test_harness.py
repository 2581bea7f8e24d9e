"""CPU tests of the benchmark harness at a tiny size, the Pallas kernels
in interpret mode.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
from unittest import mock

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, HERE, os.path.join(os.path.dirname(BENCH), "src")]

import tiny  # noqa: E402
from harness import cell as C  # noqa: E402
from harness import flops, serving, spec, traffic  # noqa: E402
from harness import trace as tr  # noqa: E402
from harness.clock import WallClock  # noqa: E402

SECONDS = 1.5
# the limit of the cell whose traffic the tiny mix copies
LIMITS = C.limits("smollm-360m.chat-shared")


# ------------------------------------------------------------------ traffic
def test_traffic_is_deterministic_for_a_seed():
    seed = 2 ** 31 + 12345
    a = traffic.generate(tiny.MIX, tiny.MODEL, seed, 10.0)
    b = traffic.generate(tiny.MIX, tiny.MODEL, seed, 10.0)
    assert len(a) == len(b) == 120
    for x, y in zip(a, b):
        assert (x.rid, x.max_new_tokens, x.arrival_t, x.prefix) == \
            (y.rid, y.max_new_tokens, y.arrival_t, y.prefix)
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_seeds_share_the_schedule_and_differ_in_tokens():
    a = traffic.generate(tiny.MIX, tiny.MODEL, 1, 10.0)
    b = traffic.generate(tiny.MIX, tiny.MODEL, 2, 10.0)
    for x, y in zip(a, b):
        assert (x.max_new_tokens, len(x.prompt), x.prefix, x.arrival_t) == \
            (y.max_new_tokens, len(y.prompt), y.prefix, y.arrival_t)
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    c = traffic.generate(dict(tiny.MIX, schedule_seed=2), tiny.MODEL, 1,
                         10.0)
    assert sorted(s.max_new_tokens for s in a) == \
        sorted(s.max_new_tokens for s in c)
    assert [s.max_new_tokens for s in a] != [s.max_new_tokens for s in c]
    gaps = np.diff([s.arrival_t for s in a] + [10.0])
    assert a[0].arrival_t == 0.0 and gaps.min() > 0
    lo, hi = tiny.MIX["output_tokens"]
    assert min(s.max_new_tokens for s in a) == lo
    assert max(s.max_new_tokens for s in a) == hi


def test_shared_prefixes_follow_zipf_shares():
    np.testing.assert_array_equal(traffic.zipf_counts(100, 4, 1.1),
                                  [50, 24, 15, 11])
    reqs = traffic.generate(tiny.MIX, tiny.MODEL, 3, 10.0)
    head = {s.prefix: s.prompt[:20].tobytes() for s in reqs}
    for s in reqs:
        assert s.prompt[:20].tobytes() == head[s.prefix]
        assert len(s.prompt) <= tiny.MIX["prompt_pad"]


@pytest.mark.parametrize("shared", [True, False])
def test_every_prompt_opens_with_the_configured_ids(shared):
    mix = tiny.MIX if shared else dict(tiny.MIX, shared_prefix=None)
    reqs = traffic.generate(mix, tiny.MODEL, 5, 10.0)
    for s in reqs:
        np.testing.assert_array_equal(s.prompt[:3], [1, 2, 3])
    # past the opening ids, prompts of no shared prefix are their own
    rest = {s.prompt[3:].tobytes() for s in reqs if s.prefix < 0}
    assert len(rest) == sum(s.prefix < 0 for s in reqs)
    plain = traffic.generate(dict(mix, open_with=None), tiny.MODEL, 5, 10.0)
    assert [len(s.prompt) for s in plain] == [len(s.prompt) for s in reqs]


def test_the_sample_checks_a_request_behind_a_cached_prefix():
    mix = dict(tiny.MIX, check={"tokens": 10, "max_requests": 2})
    reqs = traffic.generate(mix, tiny.MODEL, 5, 10.0)
    n_tok = {s.rid: (50 if s.rid == 0 else 5) for s in reqs}
    cached = {s.rid: (3 if s.rid == 17 else 0) for s in reqs}
    for seed in (1, 2, 3):
        got = traffic.check_sample(reqs, n_tok, seed, mix, cached)
        assert [s.rid for s in got] == [0, 17]


def test_closed_loop_holds_back_all_but_one_request_per_client():
    mix = dict(tiny.MIX, loop="closed", clients=3, pool=10)
    reqs = traffic.generate(mix, tiny.MODEL, 4, 10.0)
    assert [s.arrival_t for s in reqs[:3]] == [0.0] * 3
    assert all(math.isinf(s.arrival_t) for s in reqs[3:])


def test_prompts_longer_than_the_pad_are_refused():
    with pytest.raises(ValueError, match="prompt_pad"):
        traffic.generate(dict(tiny.MIX, prompt_pad=16), tiny.MODEL, 1, 1.0)


# -------------------------------------------------------------------- flops
def test_flop_and_byte_counts_match_hand_worked_shapes():
    m = tiny.MODEL     # L=2, D=64, H=4, G=2, N=16, F=128, V=512
    # per layer: q 64*4*16 + k,v 2*64*2*16 + o 4*16*64 + mlp 3*64*128
    assert flops.matmul_flops_per_token(m) == 2 * (2 * 36864 + 64 * 512)
    assert flops.kv_bytes_per_token(m) == 2 * 2 * 2 * 16 * 2
    # 2 new tokens after 3 cached attend to 4 and 5 positions
    assert flops.attention_flops(m, 3, 2) == 4 * 2 * 4 * 16 * 9
    # queries and outputs of 2 tokens, keys and values of 5, 2 layers
    assert flops.attention_bytes(m, 3, 2) == 2 * 16 * 2 * (2 * 4 * 2
                                                          + 2 * 2 * 5)
    peak = flops.peaks("TPU v5 lite")
    assert flops.least_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    assert flops.least_seconds(1.0, 819e9, peak) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        flops.peaks("no such chip")


# -------------------------------------------------------------------- trace
def _summary():
    ms = 1_000_000
    ops = [tr.Op("while", 0, 20 * ms),
           tr.Op("fusion", 0, 5 * ms),
           tr.Op("paged_attention", 5 * ms, 20 * ms),
           tr.Op("flash_attention", 30 * ms, 40 * ms),
           tr.Op("late", 95 * ms, 120 * ms)]
    spans = [tr.Span("bench_window", 0, 100 * ms),
             tr.Span("dispatch_step", 0, 21 * ms),
             tr.Span("handler_host", 21 * ms, 29 * ms),
             tr.Span("clock_wait", 22 * ms, 28 * ms),
             tr.Span("dispatch_prefill", 29 * ms, 41 * ms),
             tr.Span("handler_host", 41 * ms, 95 * ms)]
    return tr.Summary.build(ops, spans)


def test_trace_reduction_on_hand_made_events():
    s = _summary()
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.035)       # 0-20, 30-40, 95-100
    assert s.kernel_s(["paged_attention"]) == pytest.approx(0.015)
    assert s.kernel_s(["paged_prefill", "flash_attention"]) == \
        pytest.approx(0.010)
    gaps = s.idle_gaps()
    assert gaps[0] == ("handler_host", pytest.approx(0.055))
    assert gaps[1] == ("clock_wait", pytest.approx(0.010))
    # the while loop's own time is what its body's ops leave
    assert dict(s.top_ops()) == pytest.approx(
        {"paged_attention": 0.015, "flash_attention": 0.010,
         "fusion": 0.005, "late": 0.005, "while": 0.0})
    assert set(s.breakdown()) == {"device_ops", "idle_gaps"}


def test_trace_reduction_on_a_recorded_trace():
    path = os.path.join(HERE, "data", "recorded_trace.json")
    with open(path) as f:
        rec = json.load(f)
    s = tr.Summary.build(
        [tr.Op(n, int(a), int(b)) for n, a, b in rec["ops"]],
        [tr.Span(n, int(a), int(b)) for n, a, b in rec["spans"]])
    want = rec["expected"]
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    for name, secs in want["kernel_s"].items():
        assert s.kernel_s([name]) == pytest.approx(secs, rel=1e-9)
    # busy time by brute force over the recorded ops, 1 us resolution
    a, b = s.start, s.end
    grid = np.zeros((b - a) // 1000 + 1, bool)
    for o in s.ops:
        grid[(o.start - a) // 1000:(o.end - a) // 1000] = True
    assert s.busy_s == pytest.approx(grid.sum() * 1e-6, rel=0.01)
    # the longest idle stretch is the handler's host work between steps
    assert s.idle_gaps()[0][0] == "handler_host"
    assert s.top_ops(1)[0][0] == "copy"


# ---------------------------------------------------------------- the window
@pytest.fixture(scope="module")
def tiny_backend():
    """The tiny model, set up once, its attention in the Pallas
    interpreter."""
    tiny.register()
    cell = tiny.cell()
    meter = serving.CompileMeter()
    with mock.patch("repro.kernels.ops.serving_impl", lambda: "interpret"):
        backend = C.setup(cell, 5, meter)
    assert backend.ctx.impl == "interpret"
    return cell, backend


def test_wall_clock_waits_for_the_time_it_is_asked_for():
    clock = WallClock()
    clock.start()
    fired = []
    clock.at(0.05, lambda: fired.append(clock.now()))
    clock.advance_to(0.05)
    assert clock.now() >= 0.05 and fired and fired[0] >= 0.05
    assert len(clock.waits) == 1


def test_tokens_are_stamped_after_their_dispatch_ended(tiny_backend):
    cell, backend = tiny_backend
    specs = traffic.generate(cell.traffic, tiny.MODEL, 9, SECONDS)
    rec, _, wall, makespan, sent = serving.serve_window(
        backend, cell.traffic, specs, SECONDS, False)
    assert len(rec.completions) == len(sent) == len(specs)
    assert rec.modeled_s < 1e-3 and makespan <= wall
    ends = np.array([d.end for d in rec.dispatches])
    for c in rec.completions:
        assert len(c.token_ts) == len(c.tokens)
        assert c.first_token_t >= c.arrival_t
        for ts in c.token_ts:
            # stamped by the host after a dispatch came back, never while
            # one ran
            assert (ends <= ts).any()
            assert not any(d.start < ts < d.end for d in rec.dispatches)


def _run(cell, backend, tamper=None, seed=11):
    with mock.patch.object(C, "setup", lambda *a: backend), \
            mock.patch.object(C, "limits",
                              lambda name: LIMITS):
        return C.run_cell(cell, seed, SECONDS, False, time.perf_counter(),
                          require_chip=False, tamper=tamper)


def test_a_sound_run_is_correct(tiny_backend):
    cell, backend = tiny_backend
    res = _run(cell, backend)
    assert res["correct"] and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"output_tok_per_s", "ttft_p90_s",
                                   "itl_p99_s", "setup_s"}
    assert res["info"]["compiles_in_window"] == 0


def _wrap_decode(backend, alter):
    """Break every decode program underneath the handler."""
    saved = dict(backend._paged_win_fns), dict(backend._paged_mix_fns)
    for key, fn in saved[0].items():
        backend._paged_win_fns[key] = alter(fn, "window")
    for key, fn in saved[1].items():
        backend._paged_mix_fns[key] = alter(fn, "mixed")
    return saved


def _restore(backend, saved):
    backend._paged_win_fns.update(saved[0])
    backend._paged_mix_fns.update(saved[1])


def test_a_token_altered_where_it_is_produced_is_caught(tiny_backend):
    cell, backend = tiny_backend

    def alter(fn, kind):
        def wrapped(*a):
            out = fn(*a)
            return ((out[0] + 1) % 512,) + tuple(out[1:])
        return wrapped

    saved = {}
    try:
        res = _run(cell, backend,
                   tamper=lambda b: saved.update(s=_wrap_decode(b, alter)))
    finally:
        _restore(backend, saved["s"])
    assert not res["correct"]
    assert res["checks"]["gap_mean_std"]["value"] > LIMITS["gap_mean_std"]


def test_a_step_that_leaves_the_cache_unchanged_is_caught(tiny_backend):
    cell, backend = tiny_backend

    def alter(fn, kind):
        def wrapped(params, pool, *a):
            out = fn(params, pool, *a)
            return tuple(out[:-1]) + (pool,)
        return wrapped

    saved = {}
    try:
        res = _run(cell, backend,
                   tamper=lambda b: saved.update(s=_wrap_decode(b, alter)))
    finally:
        _restore(backend, saved["s"])
    assert not res["correct"]


def test_the_benchmark_file_names_only_what_exists():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert os.path.exists(os.path.join(C.LIMITS_DIR, w["name"] + ".json"))
        assert cell.traffic["max_batch"] >= 1
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))


def test_the_int8_control_fails_the_outputs_check():
    """The control (the reference at int8 weights put in the program's
    place) against the float32 reference, and the served bf16 path, at the
    published widths and depth of smollm-360m on the CPU, with short
    prompts: pooled over three seeds the control reads above chat-shared's
    limit and the program below it."""
    cell = spec.load_cell("smollm-360m.chat-shared")
    mix = dict(tiny.MIX, prompt_pad=32, capacity=48, rate_per_s=8.0,
               check={"tokens": 100, "max_requests": 8})
    cell = spec.Cell(cell.name, cell.config_name, "short", 1, cell.model,
                     mix, [], [])
    backend = C.setup(cell, 6, serving.CompileMeter())
    from harness.weights import make_params
    prog, ctrl, n = [], [], []
    for seed in (6, 7, 8):
        backend.params = None
        backend.params = make_params(cell.model, seed)
        specs = traffic.generate(mix, cell.model, seed, 2.0)
        rec, _, _, _, sent = serving.serve_window(backend, mix, specs, 2.0,
                                                  False)
        run = C.Run(cell.model, mix, 2.0, rec.window_end, sent,
                    {c.rid: c for c in rec.completions}, rec.dispatches,
                    rec.admits, {})
        p, c, k = C.check_outputs(backend, cell, run, seed, control=True)
        prog.append(p["mean"] * k)
        ctrl.append(c["mean"] * k)
        n.append(k)
    limit = C.limits(cell.name)["gap_mean_std"]
    assert sum(ctrl) / sum(n) > limit > sum(prog) / sum(n)
