"""The serving path's spans and stamps on the profiler's clock, and the
benchmark's readers of them.

A tiny handler runs under ``jax.profiler.trace``; its ``serve.*`` spans
are read back from the trace.  The readers in ``bench/`` are checked on
hand-made events with hand-worked answers, and on a recorded chip trace.
"""
import gc
import glob
import json
import os
import sys
import time
import types
import weakref

import jax
import numpy as np
import pytest

from repro.core import Dispatcher, VirtualClock
from repro.core.clones import ClonePool, CloneState
from repro.core.scheduler import ServeRequest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import program_trace as pt  # noqa: E402
from harness import spec  # noqa: E402

MS = 1_000_000


def _host_spans(trace_dir):
    """(line, name, start, end, stats) of every ``serve.*`` host event."""
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    s = int(ev.start_ns)
                    out.append((line.name, ev.name, s,
                                s + int(ev.duration_ns), dict(ev.stats)))
    return out


def _traced_run(tmp_path, handler, reqs):
    with jax.profiler.trace(str(tmp_path)):
        report = handler.run(reqs)
    return report, _host_spans(tmp_path)


def _fake_handler(**kw):
    from test_handler import FakeBackend
    from repro.launch.serve import ClientHandler
    return ClientHandler(FakeBackend(), prompt_pad=4, max_secondaries=0,
                         executor=lambda c, f, a: (f(*a), 0.1), **kw)


# ------------------------------------------------------------------ program
def test_each_engine_step_builds_dispatches_and_folds_in_order(tmp_path):
    h = _fake_handler(max_batch=2)
    reqs = [ServeRequest(i, np.arange(4, dtype=np.int32) + i, 3 + i,
                         arrival_t=0.15 * i) for i in range(5)]
    report, spans = _traced_run(tmp_path, h, reqs)
    assert len(report.completions) == 5
    by_step = {}
    for _, name, s, e, m in spans:
        if name in ("serve.step.build", "serve.dispatch", "serve.step.fold"):
            by_step.setdefault(m["step"], []).append((name, s, e, m))
    assert sorted(by_step) == list(range(1, h.steps_built + 1))
    for step, got in by_step.items():
        got.sort(key=lambda x: x[1])
        assert [g[0] for g in got] == ["serve.step.build", "serve.dispatch",
                                       "serve.step.fold"]
        (_, _, b_end, build), (_, d_start, d_end, disp), (_, f_start, _, _) \
            = got
        assert b_end <= d_start and d_end <= f_start
        assert disp["label"] in ("step", "prefill")
        assert 0 <= disp["rows"] <= disp["slots"] == 2
        assert disp["steps"] >= 1
        if disp["rows"]:                       # a decoding or mixed step
            # each live row's context spans 1..ceil(capacity / bs) blocks
            max_blk = -(-h.backend.capacity // h.block_size)
            assert disp["rows"] <= disp["kv_blocks"] <= disp["rows"] * max_blk
        else:
            assert "kv_blocks" not in disp
        assert {"joins", "sfx", "cow"} <= set(build)
    # one thread's spans nest: any two are disjoint or one holds the other
    for line in {sp[0] for sp in spans}:
        ivs = sorted((s, e) for ln, _, s, e, _ in spans if ln == line)
        stack = []
        for s, e in ivs:
            while stack and stack[-1] <= s:
                stack.pop()
            assert not stack or e <= stack[-1]
            stack.append(e)
    names = {sp[1] for sp in spans}
    assert {"serve.arrivals", "serve.admit", "serve.wait"} <= names
    admits = [sp[4] for sp in spans if sp[1] == "serve.admit"]
    assert sorted(a["rid"] for a in admits) == list(range(5))
    assert all(a["prompt"] == 4 and a["cached"] >= 0 for a in admits)
    arrived = sum(sp[4]["n"] for sp in spans if sp[1] == "serve.arrivals")
    assert arrived == 5


def test_a_span_costs_microseconds_without_a_profiler_session():
    from repro.core.tracing import span
    assert not jax.profiler.TraceAnnotation.is_enabled()
    n = 10_000
    t0 = time.perf_counter()
    for i in range(n):
        with span("serve.dispatch", label="step", step=i) as sp:
            sp.set_metadata(rows=3)
    # about a microsecond each on a CPU core; the bound leaves room for a
    # loaded machine
    assert (time.perf_counter() - t0) / n < 20e-6


def test_slot_stamp_lies_between_arrival_and_first_token():
    h = _fake_handler(max_batch=2)
    reqs = [ServeRequest(i, np.zeros(4, np.int32), 4, arrival_t=0.05 * i)
            for i in range(6)]
    report = h.run(reqs)
    first = {c.rid: c.first_token_t for c in report.completions}
    assert len(first) == 6
    for r in reqs:
        assert r.arrival_t <= r.admitted_t <= r.slot_t <= first[r.rid]
    # six requests on two slots: the last ones waited for a free slot
    assert max(r.slot_t - r.arrival_t for r in reqs) >= 0.1


def test_preemption_keeps_the_first_slot_stamp(monkeypatch):
    h = _fake_handler(max_batch=4, num_blocks=5, block_size=4)
    reqs = [ServeRequest(i, np.zeros(4, np.int32), 9, arrival_t=0.0)
            for i in range(6)]
    stamps = {}
    admit = h._admit

    def admitted(engine, req):
        admit(engine, req)
        stamps.setdefault(req.rid, []).append(req.slot_t)

    monkeypatch.setattr(h, "_admit", admitted)
    report = h.run(reqs)
    assert report.preemptions > 0 and len(report.completions) == 6
    again = [rid for rid, s in stamps.items() if len(s) > 1]
    assert again                     # some request took a slot twice
    first = {c.rid: c.first_token_t for c in report.completions}
    for r in reqs:
        assert set(stamps[r.rid]) == {r.slot_t}
        assert r.arrival_t <= r.slot_t <= first[r.rid]


def _dispatcher():
    clock = VirtualClock()
    pool = ClonePool(clock=clock)
    clone, = pool.provision("main", 1, state=CloneState.RUNNING)
    return clock, Dispatcher(pool, clock), clone


def test_a_finished_task_is_freed_by_reference_counting_alone():
    _, disp, clone = _dispatcher()
    payload = np.zeros(1024)

    def fn(x):
        return x + 1

    gc.disable()
    try:
        task = disp.submit(clone, fn, (payload,),
                           executor=lambda c, f, a: (f(*a), 0.5))
        value = weakref.ref(task.value)
        ref = weakref.ref(task)
        disp.wait([task])
        assert task.done and task._event is None
        del task
        assert ref() is None and value() is None
    finally:
        gc.enable()


def test_a_cancelled_task_is_freed_too():
    clock, disp, clone = _dispatcher()
    gc.disable()
    try:
        task = disp.submit(clone, lambda: np.zeros(8), (),
                           executor=lambda c, f, a: (f(*a), 1.0))
        ref = weakref.ref(task)
        assert disp.cancel(task)
        clock.advance_to(2.0)            # the revoked event is pruned
        del task
        assert ref() is None
    finally:
        gc.enable()


# ------------------------------------------------------------------ readers
def _run(ops=(), spans=(), window=(0, 100 * MS)):
    """A run holding a program trace of hand-made events."""
    trace = pt.ProgramTrace.build(
        [pt.DeviceOp(*o) for o in ops],
        [pt.HostSpan(pt.tr.WINDOW_SPAN, window[0], window[1], {})]
        + [pt.HostSpan(*s) for s in spans])
    return types.SimpleNamespace(program=trace)


def _hand_made():
    """Two engine steps in a 100 ms window.  Device busy 10-40 ms and
    60-80 ms; host: step 1 builds 0-8, dispatches 8-42, folds 42-48;
    the handler waits 48-55 (the clock sleeps 49-54); step 2 builds 55-58,
    dispatches 58-82, folds 82-90 (the fold holds an admission 84-86)."""
    ops = [("fusion", 10 * MS, 40 * MS, "jit_decode_window", "decode"),
           ("copy", 12 * MS, 20 * MS, "jit_decode_window", "decode"),
           ("copy", 60 * MS, 70 * MS, "jit_copy_into", "copy_blocks"),
           ("while", 70 * MS, 80 * MS, "jit_mixed", "")]
    spans = [
        ("serve.step.build", 0, 8 * MS, {"step": 1}),
        ("serve.dispatch", 8 * MS, 42 * MS,
         {"label": "step", "step": 1, "rows": 3, "steps": 1, "slots": 16}),
        ("serve.step.fold", 42 * MS, 48 * MS, {"step": 1, "finished": 0}),
        ("serve.wait", 48 * MS, 55 * MS, {}),
        ("clock_wait", 49 * MS, 54 * MS, {}),
        ("serve.step.build", 55 * MS, 58 * MS, {"step": 2}),
        ("serve.dispatch", 58 * MS, 82 * MS,
         {"label": "step", "step": 2, "rows": 4, "steps": 16, "slots": 16}),
        ("serve.step.fold", 82 * MS, 90 * MS, {"step": 2, "finished": 1}),
        ("serve.admit", 84 * MS, 86 * MS, {"rid": 7}),
    ]
    return _run(ops, spans)


def test_idle_by_span_on_hand_made_events():
    run = _hand_made()
    got = run.program.idle_by_span()
    want = {"serve.step.build": 0.008 + 0.003,
            "serve.dispatch": 0.002 + 0.002 + 0.002 + 0.002,
            "serve.step.fold": 0.006 + 0.002 + 0.004,
            "serve.wait": 0.002, "clock_wait": 0.005, "serve.admit": 0.002,
            "no_span": 0.010}
    assert got == pytest.approx(want)
    assert list(got)[0] == "serve.step.fold"
    idle = 0.1 - 0.050
    assert sum(got.values()) == pytest.approx(idle)


def test_device_by_program_on_hand_made_events():
    trace = _hand_made().program
    got = trace.device_by_program()
    assert got == pytest.approx({"jit_decode_window/decode": 0.030,
                                 "jit_copy_into/copy_blocks": 0.010,
                                 "jit_mixed": 0.010})
    top = trace.breakdown(n=2)
    assert [k for k, _ in top["device_by_program"]] == [
        "jit_decode_window/decode", "jit_copy_into/copy_blocks"]
    assert [k for k, _ in top["idle_by_span"]] == ["serve.step.fold",
                                                   "serve.step.build"]


def test_step_host_s_on_hand_made_events():
    read = spec.metric_reader("step_host_s")
    # step 1: 8 + 6 ms, step 2: 3 + 8 ms; the median of two is their mean
    assert read(_hand_made()) == pytest.approx(0.0125)
    # a step whose fold ends after the window closes is left out
    assert read(_run(spans=[
        ("serve.step.build", 0, 8 * MS, {"step": 1}),
        ("serve.step.fold", 95 * MS, 120 * MS, {"step": 1})])) is None
    assert read(types.SimpleNamespace()) is None


def test_host_idle_share_on_hand_made_events():
    read = spec.metric_reader("host_idle_share")
    # idle under build, dispatch, fold and admit: 11 + 8 + 12 + 2 ms
    assert read(_hand_made()) == pytest.approx(33.0)
    device_idle = 100.0 * (1 - 0.050 / 0.1)
    assert read(_hand_made()) <= device_idle
    assert read(types.SimpleNamespace()) is None


def test_batch_occupancy_on_hand_made_events():
    read = spec.metric_reader("batch_occupancy")
    # (3 x 1 + 4 x 16) live slot-steps over 16 x 17 held
    assert read(_hand_made()) == pytest.approx(100.0 * 67 / 272)
    prefill_only = _run(spans=[("serve.dispatch", 0, MS, {
        "label": "prefill", "step": 1, "rows": 0, "steps": 1, "slots": 4})])
    assert read(prefill_only) is None
    assert read(types.SimpleNamespace()) is None


def test_queue_wait_p50_s_reads_the_slot_stamps():
    read = spec.metric_reader("queue_wait_p50_s")
    sent = [types.SimpleNamespace(arrival_t=a, slot_t=s)
            for a, s in ((0.0, 0.1), (1.0, 1.5), (2.0, 2.2), (9.0, 12.0))]
    # the request due after the window's 5 s is left out
    assert read(types.SimpleNamespace(sent=sent, seconds=5.0)) == \
        pytest.approx(0.2)
    # a program that stamps no slot: nothing to read
    old = [types.SimpleNamespace(arrival_t=0.0)]
    assert read(types.SimpleNamespace(sent=old, seconds=5.0)) is None


@pytest.mark.parametrize("op_name,scope", [
    ("jit(mixed)/while/body/closed_call/decode/dot_general", "decode"),
    ("jit(mixed)/while/body/closed_call/prefill_chunk/while/body/"
     "closed_call/jit(paged_prefill)/paged_prefill/pallas_call",
     "prefill_chunk"),
    ("jit(copy_into)/jit(copy_blocks)/copy_blocks/scatter", "copy_blocks"),
    ("jit(prefill_into)/kv_scatter/scatter", "kv_scatter"),
    ("jit(decode_window)/transpose", ""),
    ("jit(decode_window)/while/body/add", ""),
    ("", ""),
])
def test_the_outer_scope_of_an_op_name(op_name, scope):
    assert pt.scope_of(op_name) == scope


def test_hlo_op_names_give_each_instruction_its_scope(tmp_path):
    def step(x):
        with jax.named_scope("decode"):
            y = jax.numpy.sin(x) @ x
        with jax.named_scope("kv_scatter"):
            return y.at[0].set(1.0)

    fn = jax.jit(step)
    x = jax.numpy.ones((16, 16))
    fn(x).block_until_ready()                  # compiled before the trace
    opts = jax.profiler.ProfileOptions()
    opts.enable_hlo_proto = True
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    names = pt.hlo_op_names(path)
    module, = [m for m in names if pt.program_name(m) == "jit_step"]
    scopes = {pt.scope_of(op) for op in names[module].values()}
    assert {"decode", "kv_scatter"} <= scopes


def test_program_names_drop_the_module_id():
    assert pt.program_name("jit_decode_window(17)") == "jit_decode_window"
    assert pt.program_name("jit_mixed") == "jit_mixed"


def test_a_fixture_cut_from_a_trace_reads_on_its_own_slice():
    import trace_program
    trace = _hand_made().program
    whole = trace_program.fixture(trace, 0.0, 100.0, "hand-made")
    assert whole["expected"] == trace_program.values(trace)
    json.dumps(whole)
    # from 40 ms the cut starts at step 2's build, 55 ms: 3 ms of build and
    # 2 ms of dispatch before the device runs again at 60 ms
    cut = trace_program.fixture(trace, 0.040, 20.0, "hand-made")
    assert cut["spans"][0] == ["bench_window", 0, 20 * MS, {}]
    idle = dict(cut["expected"]["breakdown"]["idle_by_span"])
    assert idle == pytest.approx({"serve.step.build": 0.003,
                                  "serve.dispatch": 0.002})
    # no step is built after 90 ms: the cut starts there, past every span
    tail = trace_program.fixture(trace, 0.090, 10.0, "hand-made")
    assert tail["expected"]["breakdown"]["idle_by_span"] == [
        ["no_span", pytest.approx(0.010)]]


# ---------------------------------------------------------- recorded trace
def _recorded():
    path = os.path.join(BENCH, "tests", "data", "recorded_trace_spans.json")
    with open(path) as f:
        rec = json.load(f)
    trace = pt.ProgramTrace.build(
        [pt.DeviceOp(*o) for o in rec["ops"]],
        [pt.HostSpan(*s) for s in rec["spans"]])
    return rec, types.SimpleNamespace(program=trace)


def test_readers_on_a_recorded_chip_trace():
    rec, run = _recorded()
    want = rec["expected"]
    trace = run.program
    for name, value in want["metrics"].items():
        got = spec.metric_reader(name)(run)
        assert got == (None if value is None
                       else pytest.approx(value, rel=1e-9)), name
    got = trace.breakdown()
    for key in ("idle_by_span", "device_by_program"):
        names, secs = zip(*want["breakdown"][key])
        assert [k for k, _ in got[key]] == list(names)
        assert [v for _, v in got[key]] == pytest.approx(secs, rel=1e-9)
    assert sum(trace.idle_by_span().values()) == pytest.approx(
        want["idle_s"], rel=1e-9)
    names = {s.name for s in trace.spans}
    assert {"serve.step.build", "serve.dispatch", "serve.step.fold"} <= names


def test_recorded_idle_adds_up_to_the_window_less_busy_time():
    _, run = _recorded()
    trace = run.program
    busy = sum(e - s for s, e in pt.tr._union(
        [(o.start, o.end) for o in trace.ops])) * 1e-9
    assert sum(trace.idle_by_span().values()) == pytest.approx(
        trace.window_s - busy, rel=1e-9)
    # every op's self time is counted once, under its program
    assert sum(trace.device_by_program().values()) == pytest.approx(
        busy, rel=1e-6)
    assert any(k.startswith("jit_") for k in trace.device_by_program())
