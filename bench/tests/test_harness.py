"""CPU tests of the benchmark harness at a tiny size, the Pallas kernels
in interpret mode.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
import time
from types import SimpleNamespace
from unittest import mock

import jax
import jax.numpy as jnp
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
from harness.weights import make_params  # noqa: E402

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


# ----------------------------------------------------------------- families
SMOLLM = "smollm-360m.chat-shared"


def test_the_dense_family_keeps_the_numbers_it_had_in_the_harness():
    """Counts, weights and reference logits as they were read before the
    dense family moved out of the harness into its own module."""
    sm = spec.load_cell(SMOLLM).model
    assert flops.matmul_flops_per_token(sm) == 723517440.0
    assert flops.kv_bytes_per_token(sm) == 40960.0
    assert flops.attention_flops(sm, 512, 1) == 63037440.0
    assert flops.attention_flops(sm, 0, 512) == 16137584640.0
    assert flops.attention_bytes(sm, 512, 1) == 21135360.0
    tiny.register()
    p = make_params(tiny.MODEL, 7)
    leaves = jax.tree.leaves(p)
    assert len(leaves) == 11
    total = sum(jnp.sum(x.astype(jnp.float32)) for x in leaves)
    np.testing.assert_allclose(float(total), -91.65525817871094, rtol=1e-6)
    seqs = np.arange(80).reshape(2, 40) % 512
    at = np.array([[30, 35], [31, 39]])
    ref = spec.family(tiny.MODEL).Reference
    out = ref(tiny.MODEL).logits(p, seqs, at)
    assert [o.shape for o in out] == [(2, 2, 512)]
    np.testing.assert_allclose(float(sum(jnp.sum(o) for o in out)),
                               8.153661727905273, rtol=1e-6)
    low = ref(tiny.MODEL, quant="int8").logits(p, seqs, at)
    np.testing.assert_allclose(float(sum(jnp.sum(o) for o in low)),
                               9.593378067016602, rtol=1e-6)


TOY_FAMILY = '''
"""A family that exists only in a test: it records what the harness asks
of it and answers with numbers of its own."""
import jax.numpy as jnp

CALLS = []


def program_fields(model, capacity=None):
    CALLS.append(("program_fields", capacity))
    if capacity is not None and capacity > model["toy_capacity"]:
        raise ValueError("capacity over the toy's own")
    return {"d_model": model["hidden_size"],
            "n_layers": model["num_hidden_layers"]}


def shapes(m):
    CALLS.append(("shapes",))
    return {"toy": ((3, m["hidden_size"]), m["hidden_size"]),
            "gain": ((5,), None)}


class Reference:
    def __init__(self, m, quant=None):
        CALLS.append(("Reference", quant))
        self.m = m

    def logits(self, params, seqs, at):
        CALLS.append(("logits", params["toy"].shape))
        # every position puts token 7 first
        return [jnp.zeros(at.shape + (self.m["vocab_size"],)).at[..., 7]
                .set(1.0)]


def matmul_flops_per_token(m):
    return 11.0


def kv_bytes_per_token(m):
    return 13.0


def attention_flops(m, cached, new):
    return 17.0 * cached + new


def attention_bytes(m, cached, new):
    return 19.0 * cached + new
'''


def test_a_family_joins_through_an_added_module(tmp_path, monkeypatch):
    """A configuration whose architecture names a module that only an added
    file holds gets that module's program check, weight tree, reference
    and counts through the harness's own entry points."""
    (tmp_path / "ToyForCausalLM.py").write_text(TOY_FAMILY)
    monkeypatch.setattr(spec, "FAMILIES_DIR", str(tmp_path))
    tiny.register()
    model = dict(tiny.MODEL, architectures=["ToyForCausalLM"],
                 toy_capacity=40)
    toy = spec.family(model)
    assert spec.family(model) is toy
    # program check: the toy's two fields, and its own capacity rule
    assert spec.program_config(model, 32).name == "bench-tiny"
    with pytest.raises(ValueError, match="toy's own"):
        spec.program_config(model, 48)
    with pytest.raises(ValueError, match="d_model"):
        spec.program_config(dict(model, hidden_size=65), 32)
    # weight tree
    p = make_params(model, 3)
    assert {k: (v.shape, v.dtype) for k, v in p.items()} == {
        "toy": ((3, 64), jnp.bfloat16), "gain": ((5,), jnp.bfloat16)}
    # counts, through the readers' module
    assert flops.matmul_flops_per_token(model) == 11.0
    assert flops.kv_bytes_per_token(model) == 13.0
    assert flops.attention_flops(model, 2, 1) == 35.0
    assert flops.attention_bytes(model, 2, 1) == 39.0
    # the outputs check's reference and control
    mix = tiny.MIX
    sent = traffic.generate(mix, model, 3, 1.0)
    done = {s.rid: SimpleNamespace(tokens=[7] * s.max_new_tokens)
            for s in sent}
    run = C.Run(model, mix, 1.0, 1.0, sent, done, [], {}, {})
    cell = tiny.cell(model=model)
    prog, ctrl, n = C.check_outputs(SimpleNamespace(params=p), cell, run, 3,
                                    control=True)
    assert n > 0 and prog == ctrl == {"max": 0.0, "mean": 0.0}
    assert ("Reference", None) in toy.CALLS
    assert ("Reference", "int8") in toy.CALLS
    assert ("logits", (3, 64)) in toy.CALLS
    for c in done.values():
        c.tokens[0] = 3
    prog, _, _ = C.check_outputs(SimpleNamespace(params=p), cell, run, 3)
    assert prog["max"] > 0


def test_an_unknown_architecture_fails_before_any_weight_is_made(tmp_path):
    model = dict(spec.load_cell(SMOLLM).model,
                 architectures=["NoSuchForCausalLM"])
    (tmp_path / "model.json").write_text(json.dumps(model))
    bench = spec.load_benchmark()
    bench["configs"] = [dict(bench["configs"][0], file="model.json")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(KeyError, match="NoSuchForCausalLM.*LlamaForCausalLM"):
        spec.load_cell(SMOLLM, root=str(tmp_path))
    cell = tiny.cell(model=dict(tiny.MODEL,
                                architectures=["NoSuchForCausalLM"]))
    with mock.patch.object(C, "make_params",
                           side_effect=AssertionError("weights made")):
        with pytest.raises(KeyError, match="NoSuchForCausalLM"):
            C.setup(cell, 1, serving.CompileMeter())
    unnamed = {k: v for k, v in tiny.MODEL.items() if k != "architectures"}
    for m in (unnamed, dict(tiny.MODEL, architectures=[])):
        with pytest.raises(KeyError, match="no family module"):
            spec.program_config(m, 48)
        with pytest.raises(KeyError):
            make_params(m, 1)


# every field the dense program check compares, and a value that differs
DENSE_FIELDS = {
    "d_model": 961, "d_ff": 2561, "n_heads": 16, "n_kv_heads": 3,
    "head_dim": 60, "n_layers": 31, "vocab_size": 49153,
    "rope_theta": 500000.0, "tie_embeddings": False, "mlp_act": "gelu",
    "mlp_gated": False, "norm_type": "layernorm", "qkv_bias": True,
    "window": 4096, "family": "moe", "causal": False, "dtype": "float32",
    "logit_softcap": 50.0, "final_softcap": 30.0, "scale_embeddings": True,
    "block_pattern": ("attn", "rglru"),
}


def test_the_dense_family_checks_every_field_it_checked_before():
    sm = spec.load_cell(SMOLLM).model
    fields = spec.family(sm).program_fields(sm, 640)
    assert set(fields) == set(DENSE_FIELDS)
    assert spec.program_config(sm, 640).name == "smollm-360m"


@pytest.mark.parametrize("field", sorted(DENSE_FIELDS))
def test_a_registered_config_that_differs_in_one_field_is_refused(
        field, monkeypatch):
    from repro import configs
    sm = spec.load_cell(SMOLLM).model
    base = configs.get_config("smollm-360m")
    assert getattr(base, field) != DENSE_FIELDS[field]
    monkeypatch.setitem(configs._REGISTRY, "bench-differs",
                        dataclasses.replace(base, name="bench-differs",
                                            **{field: DENSE_FIELDS[field]}))
    with pytest.raises(ValueError, match=f"'{field}'"):
        spec.program_config(dict(sm, registry="bench-differs"), 640)


def test_a_capacity_past_the_published_window_is_refused():
    sm = dict(spec.load_cell(SMOLLM).model, sliding_window=512)
    assert spec.program_config(sm, 512).name == "smollm-360m"
    with pytest.raises(ValueError, match="sliding window 512"):
        spec.program_config(sm, 640)


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
