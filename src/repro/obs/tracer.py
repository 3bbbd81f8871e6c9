"""Span-based step tracer + bounded flight recorder (DESIGN.md §17).

One tracer per engine (plus one on the gateway) records *typed spans* on
a single monotonic clock — ``time.perf_counter``, the clock every other
timestamp in the repo (request arrivals, stage busy times, pool
fetch/sample splits) is already taken on — into a ``deque(maxlen=N)``
ring buffer: a flight recorder that always holds the most recent window
and never grows, so it can stay attached to a long-lived gateway replica.

Span taxonomy (:data:`SPAN_KINDS`): the timing decomposition the paper's
argument is made of, one kind per seam —

    ``prefill``       admission prefill program (both engines)
    ``forward``       decode forward, dispatch → host materialization
    ``stage``         one (stage, microbatch) pipeline forward (honest,
                      ``block_until_ready``)
    ``d2h_transfer``  a pool worker's ``device_get`` wait (in-flight
                      compute + D2H copy)
    ``host_sample``   a pool worker's CPU sampling, fetch excluded
    ``pool_stall``    the engine blocking on a sampler-pool ticket —
                      the paper's "pool too slow for the slack"
    ``commit``        scheduler.commit of a step's tokens
    ``queue_wait``    a request's arrival → admission wait
    ``decision``      a controller action (instant event, §15)
    ``schedule``      ``scheduler.schedule()`` at the top of an engine step
    ``admit_decide``  the admission's first-token ``DecisionPlane.step``
    ``admit_insert``  scattering admitted rows into the batch state
    ``admit_fetch``   the host blocking on the admitted rows' first tokens
    ``chunk``         one prompt-chunk program for the mid-prefill rows
    ``dispatch``      building a step's operands and dispatching its program
    ``drain``         the host blocking on the oldest in-flight result
    ``request``       one request's wire-level life on the gateway
    ``kv_migrate``    one migration's export gather or import scatter
                      (prefill/decode disaggregation, §18)
    ``handoff_wait``  export stamp → import install of one migrating
                      request — the KV's time in flight between engines

Threading: the engine thread, every pool worker thread, and the gateway
loop record into the same tracer. ``deque.append`` is atomic under the
GIL, so recording needs no lock; each event carries a ``track`` (default:
the recording thread's name) that becomes its own timeline row in the
Chrome-trace export — overlap between the pool workers' ``host_sample``
spans and the engine track's next ``forward``/``stage`` span is the
paper's Eq. 4 claim, made visually inspectable.

Two sinks (:meth:`StepTracer.phase`): a *synchronous* phase — work the
calling thread does from entry to exit — always enters a
``jax.profiler.TraceAnnotation`` named ``obs.<kind>``, so a profiler trace
shows it on the host line beside the device's ops, and is also recorded
into the ring when the tracer is enabled. *Asynchronous* spans
(``forward``: dispatch to host materialization, across steps;
``queue_wait``: arrival to admission) have no thread that holds them open
and stay ring-only, recorded after the fact with :meth:`StepTracer.add`.

Overhead discipline: a disabled tracer's :meth:`StepTracer.span` returns
one shared no-op context manager (no allocation) and ``add``/``instant``
return immediately; instrumentation sites that build f-string names
guard on :attr:`StepTracer.enabled` so a production engine pays a single
attribute check per site.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, List, NamedTuple, Optional, Tuple

#: the typed span taxonomy (DESIGN.md §17) — unknown kinds are rejected
#: at record time so a typo'd instrumentation site fails loudly in tests,
#: not silently as an un-filterable category.
SPAN_KINDS = frozenset({
    "prefill", "forward", "stage", "d2h_transfer", "host_sample",
    "pool_stall", "commit", "queue_wait", "decision", "request",
    "kv_migrate", "handoff_wait", "schedule", "admit_decide",
    "admit_insert", "admit_fetch", "chunk", "dispatch", "drain",
})

_annotation = None      # jax.profiler.TraceAnnotation, imported on first use


def _trace_annotation():
    """JAX is imported only when a phase is first entered: the gateway
    shares this module and stays stdlib-only while it does not trace."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class SpanEvent(NamedTuple):
    """One recorded span (``ph="X"``) or instant event (``ph="i"``).
    Timestamps are ``time.perf_counter`` seconds; ``args`` is a sorted
    tuple of (key, value) pairs so events stay hashable/immutable."""

    kind: str                       # SPAN_KINDS entry (Chrome trace `cat`)
    name: str                       # display name (falls back to kind)
    ph: str                         # "X" complete | "i" instant
    ts: float                       # start, perf_counter seconds
    dur: float                      # seconds (0.0 for instants)
    track: str                      # timeline row (thread / stage / role)
    args: Tuple[Tuple[str, object], ...] = ()

    @property
    def end(self) -> float:
        return self.ts + self.dur


class _NullSpan:
    """Shared no-op context manager — the disabled tracer's entire cost."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """Live span context manager: stamps entry/exit on the tracer's clock
    and records on exit (so nested spans land after their parents start
    and strictly inside them — one clock, no cross-clock skew)."""

    __slots__ = ("_tr", "_kind", "_name", "_track", "_args", "_t0")

    def __init__(self, tracer: "StepTracer", kind: str, name: Optional[str],
                 track: Optional[str], args: dict):
        self._tr = tracer
        self._kind = kind
        self._name = name
        self._track = track
        self._args = args

    def __enter__(self) -> "_Span":
        self._t0 = self._tr.clock()
        return self

    def __exit__(self, *exc) -> bool:
        tr = self._tr
        tr.add(self._kind, self._t0, tr.clock(), name=self._name,
               track=self._track, **self._args)
        return False


class _Phase:
    """Synchronous phase: a profiler annotation ``obs.<kind>`` always, a
    ring span when the tracer is enabled. ``t0``/``t1`` are the tracer
    clock's stamps at entry and exit, set either way, so a site that
    reports its own decomposition (a pool worker's fetch/sample split)
    reads the very stamps the ring records."""

    __slots__ = ("_tr", "_kind", "_name", "_track", "_args", "_ann",
                 "t0", "t1")

    def __init__(self, tracer: "StepTracer", kind: str, name: Optional[str],
                 track: Optional[str], args: dict):
        if kind not in SPAN_KINDS:
            raise ValueError(f"unknown span kind {kind!r}; taxonomy: "
                             f"{sorted(SPAN_KINDS)} (DESIGN.md §17)")
        self._tr = tracer
        self._kind = kind
        self._name = name
        self._track = track
        self._args = args

    def __enter__(self) -> "_Phase":
        self._ann = _trace_annotation()("obs." + self._kind, **self._args)
        self._ann.__enter__()
        self.t0 = self._tr.clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = self._tr.clock()
        self._ann.__exit__(*exc)
        tr = self._tr
        if tr.enabled:
            tr._record(self._kind, self._name, "X", self.t0,
                       max(0.0, self.t1 - self.t0), self._track, self._args)
        return False


class StepTracer:
    """Flight recorder of :class:`SpanEvent` items in a bounded ring
    buffer (``capacity`` most recent events; oldest evicted first).

    ``enabled=False`` (the engines' default) makes every record path a
    near-free early return; flip it on per run (``serve.py --trace-out``)
    or per instance (the obs test suite). ``clock`` is injectable for
    tests but must be shared by every tracer whose events are exported
    together — the Chrome trace merges sources on raw timestamps.
    """

    def __init__(self, capacity: int = 16384, enabled: bool = True,
                 clock: Callable[[], float] = time.perf_counter):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.clock = clock
        self._enabled = bool(enabled)
        self._buf: deque = deque(maxlen=self.capacity)

    # -- switches -------------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    # -- recording ------------------------------------------------------------
    def span(self, kind: str, name: Optional[str] = None,
             track: Optional[str] = None, **args):
        """Context manager timing its body; disabled tracers return the
        shared :data:`NULL_SPAN` (zero allocation)."""
        if not self._enabled:
            return NULL_SPAN
        return _Span(self, kind, name, track, args)

    def phase(self, kind: str, name: Optional[str] = None,
              track: Optional[str] = None, **args) -> _Phase:
        """Context manager for a synchronous phase: always a profiler
        annotation ``obs.<kind>`` carrying ``args`` (small integers) as
        its metadata, plus a ring span when enabled. Without a profiler
        session the annotation costs one object and one "is a trace
        active" check."""
        return _Phase(self, kind, name, track, args)

    def add(self, kind: str, t0: float, t1: float,
            name: Optional[str] = None, track: Optional[str] = None,
            **args) -> None:
        """Record a span from explicit clock stamps — the path for sites
        that already measured (pool workers' fetch/sample split, stage
        busy times, request arrival→admission waits)."""
        if not self._enabled:
            return
        self._record(kind, name, "X", t0, max(0.0, t1 - t0), track, args)

    def instant(self, kind: str, name: Optional[str] = None,
                track: Optional[str] = None, **args) -> None:
        """Record a zero-duration marker (controller decisions)."""
        if not self._enabled:
            return
        self._record(kind, name, "i", self.clock(), 0.0, track, args)

    def _record(self, kind: str, name: Optional[str], ph: str, ts: float,
                dur: float, track: Optional[str], args: dict) -> None:
        if kind not in SPAN_KINDS:
            raise ValueError(f"unknown span kind {kind!r}; taxonomy: "
                             f"{sorted(SPAN_KINDS)} (DESIGN.md §17)")
        if track is None:
            track = threading.current_thread().name
        # deque.append with maxlen is atomic under the GIL: engine thread,
        # pool workers, and the gateway loop record without a lock
        self._buf.append(SpanEvent(
            kind=kind, name=name or kind, ph=ph, ts=float(ts),
            dur=float(dur), track=track,
            args=tuple(sorted(args.items()))))

    # -- reading --------------------------------------------------------------
    def events(self) -> List[SpanEvent]:
        """Snapshot of the ring buffer, oldest first."""
        return list(self._buf)

    def clear(self) -> None:
        self._buf.clear()

    def __len__(self) -> int:
        return len(self._buf)


#: shared disabled tracer — the default wiring for components that accept
#: a tracer but were constructed without one (e.g. a bare HostSamplerPool).
#: Never enable it: every un-wired component in the process shares it.
NULL_TRACER = StepTracer(capacity=1, enabled=False)


__all__ = ["SPAN_KINDS", "SpanEvent", "StepTracer", "NULL_TRACER",
           "NULL_SPAN"]
