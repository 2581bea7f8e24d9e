"""Drives the system under test: ``ClientHandler``'s default paged path on
one chip, with the benchmark's weights, on the benchmark's wall clock.

What the benchmark takes from the program: ``LMBackend``, ``ClientHandler``
(constructor, ``run``, its ``dispatcher.submit`` and the counters
``prefix_hit_tokens`` / ``prompt_tokens``), ``ServeRequest`` and the
completions' stamps.  Two private hooks are wrapped on the instance: the
closed loop learns of each completion from ``_engine_step_done``, and the
cached length of each admission is read around ``_admit``.  One fault of
the program is patched, not only observed (``free_finished_steps``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional
from unittest import mock

import jax
import numpy as np

from harness import traffic
from harness.clock import WallClock
from harness.traffic import Spec, rng_for


class CompileMeter:
    """Counts backend compiles and their seconds in this process."""

    def __init__(self):
        self.n, self.seconds = 0, 0.0

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)


def build_backend(pc, capacity: int, params):
    """``LMBackend`` over the benchmark's weights (the program's own random
    init is never run)."""
    from repro.launch import serve
    with mock.patch.object(serve.model, "init", lambda cfg, key: params):
        return serve.LMBackend(pc, capacity=capacity)


def make_handler(backend, mix: Dict, executor: Callable, clock=None):
    """The handler as every run builds it: the program's defaults, one
    clone and no secondaries, a link whose modeled delay is negligible."""
    from repro.launch.serve import ClientHandler
    return ClientHandler(backend, link="ici", max_batch=mix["max_batch"],
                         prompt_pad=mix["prompt_pad"], max_secondaries=0,
                         executor=executor, clock=clock)


def free_finished_steps(handler) -> None:
    """Drop each step's task once the handler has folded it.

    This patches a fault of the program, and every number the benchmark
    reports is that of a handler so patched: a finished ``CloneTask`` and
    its completion event reference each other, so the task's arguments
    and results (whole KV pools) wait for Python's full garbage
    collection, and at full size fill the chip's memory in seconds.  Once
    the program breaks that cycle this changes nothing, and can go."""
    fold = handler._engine_step_done

    def folded(engine, task, completions):
        try:
            return fold(engine, task, completions)
        finally:
            task._event = None

    handler._engine_step_done = folded


def serve_request(s: Spec):
    from repro.core.scheduler import ServeRequest
    return ServeRequest(s.rid, s.prompt, s.max_new_tokens,
                        arrival_t=s.arrival_t)


def run_once(clone, fn, args):
    """Each dispatch once, to ``block_until_ready``."""
    del clone
    return jax.block_until_ready(fn(*args))


# ------------------------------------------------------------------ warm-up
def warm_up(backend, mix: Dict, model: Dict, seed: int) -> int:
    """Run every program shape this mix can reach through a throwaway
    handler that shares the backend's jitted functions, so the window
    compiles nothing and starts with an empty prefix cache.

    The handler runs on its own virtual clock with every dispatch billed
    one virtual second, so the schedule below is exact: bursts of k cold
    prompts for every k up to max_batch (the prefill's rows are padded to
    a power of two, but the host builds that padding with eager ops whose
    shapes follow k), each opening with a token no other warm-up prompt
    opens with.  Then, for each prefix a window's prompt can find cached
    (a shared prefix, and the ids every prompt opens with, which the
    prefix cache matches partway into a cached block), bursts of k prompts
    that find it, for each power-of-two k: beside a decoding request
    (mixed step, copy-on-write) and with nothing decoding (suffix
    prefill).  Returns the number of requests served."""
    vocab, b, pad = model["vocab_size"], mix["max_batch"], mix["prompt_pad"]
    decodes = mix["output_tokens"][1] > 1
    rng = rng_for(seed, 4)
    head = traffic.opening(mix, model)
    firsts = iter(int(t) for t in rng.permutation(vocab)
                  if t not in head[:1])
    pow2 = [1 << i for i in range(int(math.log2(b)) + 1)]
    if pow2[-1] < b:
        pow2.append(b)
    reqs, t = [], 0.0

    def add(prompt, new, at):
        reqs.append(Spec(len(reqs), prompt, new, at))

    def fresh(n, lead=()):
        """n tokens opening with ``lead``, else with an unused token."""
        lead = np.asarray(lead if len(lead) else [next(firsts)], np.int32)
        return np.concatenate(
            [lead, rng.integers(0, vocab, n - len(lead), dtype=np.int32)])

    for k in range(1, b + 1):
        for _ in range(k):
            add(fresh(pad), 2 if decodes else 1, t)
        t += 10.0
    cached = []
    shared = mix.get("shared_prefix")
    if shared:
        cached.append(fresh(shared["tokens"]))
    if len(head):
        cached.append(head)
    for prefix in cached:
        def hit():
            return fresh(pad, prefix)

        anchor_at = t
        add(hit(), min(mix["capacity"] - pad, 8 + 4 * len(pow2)), t)
        t += 4.0
        for k in pow2:                     # beside a decoding request
            for _ in range(min(k, b - 1)):
                add(hit(), 1, t)
            t += 4.0
        t = max(t, anchor_at + mix["capacity"] - pad) + 10.0
        for k in pow2:                     # with nothing decoding
            for _ in range(k):
                add(hit(), 1, t)
            t += 10.0

    def billed(clone, fn, args):
        return run_once(clone, fn, args), 1.0

    handler = make_handler(backend, mix, billed)
    free_finished_steps(handler)
    report = handler.run([serve_request(s) for s in reqs])
    if len(report.completions) != len(reqs):
        raise RuntimeError(f"warm-up served {len(report.completions)} of "
                           f"{len(reqs)} requests")
    return len(reqs)


# ------------------------------------------------------------------- window
@dataclasses.dataclass
class Dispatch:
    label: str
    start: float          # wall seconds after the window opened
    end: float
    seq_steps: int


def spans(tracing: bool) -> Callable:
    """``span(name)``: a host span in the profiler's trace when tracing."""
    if not tracing:
        return lambda name: contextlib.nullcontext()
    return jax.profiler.TraceAnnotation


class Recorder:
    """Wraps the handler's dispatches and hooks; keeps what metrics read."""

    def __init__(self, clock: WallClock, close_at: float, tracing: bool):
        self.clock = clock
        self.close_at = close_at
        self.tracing = tracing
        self.span = spans(tracing)
        self.dispatches: List[Dispatch] = []
        self.modeled_s = 0.0             # modeled seconds on the timeline
        self.admits: Dict[int, tuple] = {}   # rid -> (cached, prompt)
        self.window_end: Optional[float] = None
        self.completions: List = []      # the handler's own list
        self._host = None
        self._window = None

    # host spans: one over the window, and one over each stretch of host
    # work between two dispatches (entered and left by hand: they are
    # sequential on this thread, never overlapping a dispatch span)
    def open_window(self) -> None:
        if self.tracing:
            self._window = jax.profiler.TraceAnnotation("bench_window")
            self._window.__enter__()

    def tick(self) -> None:
        if self.window_end is None and self.clock.now() >= self.close_at:
            self.window_end = self.clock.now()
            if self._window is not None:
                self._host_end()
                self._window.__exit__(None, None, None)
                self._window = None

    def _host_begin(self) -> None:
        if self._window is not None and self._host is None:
            self._host = jax.profiler.TraceAnnotation("handler_host")
            self._host.__enter__()

    def _host_end(self) -> None:
        if self._host is not None:
            self._host.__exit__(None, None, None)
            self._host = None

    def finish(self) -> None:
        self.tick()
        if self.window_end is None:       # the run ended before the close
            self.window_end = self.close_at
        self._host_end()
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None

    def attach(self, handler, release: Optional[Callable] = None) -> None:
        submit = handler.dispatcher.submit

        def recorded(clone, fn, args=(), *, executor=None, extra_delay=0.0,
                     label=""):
            self.tick()
            self._host_end()
            t0 = self.clock.now()
            with self.span(f"dispatch_{label}"):
                task = submit(clone, fn, args, executor=executor,
                              extra_delay=extra_delay, label=label)
            self.dispatches.append(Dispatch(label, t0, self.clock.now(),
                                            int(getattr(fn, "seq_steps", 0))))
            self.modeled_s += task.extra_delay + task.venue_seconds
            self._host_begin()
            return task

        handler.dispatcher.submit = recorded
        admit = handler._admit

        def admitted(engine, req):
            c0, p0 = handler.prefix_hit_tokens, handler.prompt_tokens
            admit(engine, req)
            self.admits[req.rid] = (handler.prefix_hit_tokens - c0,
                                    handler.prompt_tokens - p0)

        handler._admit = admitted
        done = handler._engine_step_done

        def step_done(engine, task, completions):
            self.completions = completions
            n = len(completions)
            alive = done(engine, task, completions)
            if release is not None:
                for c in completions[n:]:
                    release(c, self.clock.now())
            return alive

        handler._engine_step_done = step_done


class AllServed(Exception):
    """Every request the closed loop sent has completed and the window has
    closed: ``run`` is left, with requests never sent still queued in it."""


class ClosedLoop:
    """Clients that each send their next request the moment their last one
    completes, until the window closes: the requests the generator holds
    back (arrival ``inf``) go out in order, one per completion."""

    def __init__(self, reqs, close_at: float):
        self.pending = [r for r in reqs if math.isinf(r.arrival_t)]
        self.close_at = close_at
        self.sent = len(reqs) - len(self.pending)
        self.completed = 0

    def __call__(self, completion, now: float) -> None:
        self.completed += 1
        if now < self.close_at and self.pending:
            self.pending.pop(0).arrival_t = now
            self.sent += 1
        if self.pending and self.completed == self.sent:
            raise AllServed


def serve_window(backend, mix: Dict, specs: List[Spec], seconds: float,
                 tracing: bool, trace_dir: Optional[str] = None):
    """Serve ``specs`` on the wall clock; the window is [0, seconds).
    Returns the recorder (with the completions), the handler, the seconds
    ``run`` took by the benchmark's own clock and by the handler's, and the
    requests sent."""
    clock = WallClock(spans(tracing))
    rec = Recorder(clock, seconds, tracing)

    def executor(clone, fn, args):
        return run_once(clone, fn, args), 0.0

    handler = make_handler(backend, mix, executor, clock)
    free_finished_steps(handler)
    reqs = [serve_request(s) for s in specs]
    loop = ClosedLoop(reqs, seconds) if mix["loop"] == "closed" else None
    rec.attach(handler, loop)
    if tracing:
        # device ops and the benchmark's own spans; no Python call tracing,
        # which would slow the host work being measured
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        rec.open_window()
        clock.start()
        t0 = time.perf_counter()
        try:
            makespan = handler.run(reqs).makespan_s
        except AllServed:
            makespan = clock.now()
        wall = time.perf_counter() - t0
        rec.finish()
    finally:
        if tracing:
            jax.profiler.stop_trace()
    sent = [r for r in reqs if not math.isinf(r.arrival_t)]
    return rec, handler, wall, makespan, sent
