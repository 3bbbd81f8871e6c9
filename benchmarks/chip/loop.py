"""Drive an engine with a run's requests, and stamp what a client sees.

``drive`` is the load generator and the client in one thread: it sends each
request when it is due (open loop) or when its caller's last request has
finished (closed loop), calls ``engine.step()`` whenever there is work, and
after every step stamps each new token with the host clock, as a caller of
``Engine.generate`` would receive it. Latency is timed from the due time, so
a stall delays every later request's clock.

From the window's open, every request is handed to the engine the moment
the loop sees it due, as ``Engine.generate`` and the gateway do; the
engine's scheduler admits every waiting request into the free slots at its
next step. In the warm-up before it, which is set-up, the loop hands over
at most ``warm_max_group`` requests per step, so that the run's first
requests, due at once, are admitted in groups whose programs set-up has
built (38 long prompts in one group ran out of the chip's memory
beside the engine's cache).

The open loop is copied from ``benchmarks/fig_latency.py`` (``open_loop``)
and the compile counter from ``chip_smoke.py`` (``CompileCounter``), so that
a later change to those files cannot move this yardstick.
"""
from __future__ import annotations

import faulthandler
import sys
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Sequence, Tuple

import jax


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (a cache hit skips the backend compile)."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclass
class Sent:
    """One request as the client saw it (host clock, ``perf_counter``)."""

    spec: object                     # loadgen.Spec
    request: object                  # the engine's Request
    due: float                       # absolute due time
    noticed: float = 0.0             # the loop saw it due (or sent)
    stamps: List[float] = field(default_factory=list)  # one per token seen


@dataclass
class StepLog:
    """Host-side facts of one ``engine.step()`` call."""

    t_end: float
    decode_ctx: List[int]      # context length of every row it decoded
    admitted_prompts: List[int]  # prompt lengths admitted in it


@dataclass
class Drive:
    sent: List[Sent]
    steps: List[StepLog]
    t_open: float
    t_close: float
    lateness: List[float]      # generator: noticed minus due, per request


def drive(engine, specs, make_request: Callable, traffic: dict,
          t_open: float, t_close: float,
          marks: Sequence[Tuple[float, Callable[[], None]]] = (),
          stall_dump_s: float = 0.0) -> Drive:
    """Run the loop from now until ``t_close`` (host clock).

    ``specs``: an iterator of ``loadgen.Spec`` in sending order (due offsets
    are relative to ``t_open``). ``make_request(spec, due)`` builds the
    engine's request. Host activity is marked for a profiler trace
    (``client.submit``, ``client.idle``, ``engine.step``). ``marks``:
    ``(time, fn)`` pairs; each ``fn`` is called once, at the first loop
    turn at or after its time, or at the close. ``stall_dump_s`` > 0: an
    ``engine.step()`` in the window that runs longer than that prints
    every thread's Python stack to standard error while it is stuck."""
    span = jax.profiler.TraceAnnotation
    closed = traffic["kind"] == "closed"
    specs = iter(specs)
    ready: Deque[Sent] = deque()      # due, not yet handed to the engine
    future: Deque = deque()           # open loop: not yet due
    live: Dict[int, Sent] = {}        # handed over, not finished
    sent: List[Sent] = []
    steps: List[StepLog] = []
    lateness: List[float] = []
    admitted = set()
    marks = deque(sorted(marks, key=lambda m: m[0]))

    def enqueue(spec, due):
        s = Sent(spec=spec, request=make_request(spec, due), due=due,
                 noticed=time.perf_counter())
        lateness.append(max(0.0, s.noticed - due))
        ready.append(s)
        sent.append(s)

    if closed:
        for _ in range(traffic["clients"]):
            spec = next(specs)
            enqueue(spec, t_open + spec.due)
    else:
        future.extend(specs)
    sched = engine.scheduler
    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        while marks and now >= marks[0][0]:
            marks.popleft()[1]()
        while future and t_open + future[0].due <= now:
            spec = future.popleft()
            enqueue(spec, t_open + spec.due)
        if ready:
            n = len(ready) if now >= t_open else traffic["warm_max_group"]
            with span("client.submit"):
                batch = [ready.popleft() for _ in range(min(n, len(ready)))]
                engine.submit([s.request for s in batch])
                for s in batch:
                    live[id(s.request)] = s
        if not (sched.has_work or engine.in_flight):
            with span("client.idle"):
                wait = (t_open + future[0].due - now) if future else 1e-3
                time.sleep(min(1e-3, max(0.0, wait)))
            continue
        watch = stall_dump_s > 0 and now >= t_open
        if watch:
            faulthandler.dump_traceback_later(stall_dump_s, file=sys.stderr)
        with span("engine.step"):
            engine.step()
        if watch:
            faulthandler.cancel_dump_traceback_later()
        t = time.perf_counter()
        ctx, new_prompts, done = [], [], []
        for key, s in live.items():
            r = s.request
            if r.slot >= 0 and key not in admitted:
                admitted.add(key)
                new_prompts.append(len(r.prompt))
            while len(s.stamps) < len(r.output):
                s.stamps.append(t)
            if r.slot >= 0 and r.output and r.finish_reason is None:
                ctx.append(len(r.prompt) + len(r.output))
            if r.finish_reason is not None:
                done.append(key)
        for key in done:
            live.pop(key)
            admitted.discard(key)
            if closed:
                spec = next(specs)
                enqueue(spec, t)
        steps.append(StepLog(t_end=t, decode_ctx=ctx,
                             admitted_prompts=new_prompts))
    while marks:                      # marks due at or after the close
        marks.popleft()[1]()
    return Drive(sent=sent, steps=steps, t_open=t_open, t_close=t_close,
                 lateness=lateness)

