"""Named host spans of the served path, on the profiler's clock.

    with span(RUN, ticket, totals) as run:
        ...
    run.t0, run.t1  # the perf_counter readings the span took

Each span opens a :class:`jax.profiler.TraceAnnotation`, so in a profiler
trace it lands on the host plane beside the device's ``XLA Modules`` and
``XLA Ops`` events, on one clock. A span with a ticket reads
``engine.run#ticket=12#`` in the trace (readers strip the ``#...#``). On
exit it adds its duration to a :class:`SpanTotals`, the one the service
owns; a span opened without totals or a ticket takes its parent's, so the
runner's spans (``engine.dispatch`` ... ``engine.derived``) join the
request that opened them. Spans are always on: with the profiler off a
span costs a few microseconds, against a served call of milliseconds.

Counters (:func:`count`) add whole numbers to the same totals, under the
innermost open span's owner: ``spmv.slots`` and ``spmv.pieces`` per served
SpMV, from its plan's layout, and ``derived.hits`` / ``derived.misses`` per
lookup of the ops' derived-stats memo.

XLA compiles (JAX's ``/jax/core/compile/backend_compile_duration`` event,
persistent-cache reads included) are counted in the same totals, under the
outermost open span of the compiling thread: the pipeline stage.
``engine.compile`` is a cold plan's first call; a compile under
``engine.run`` is a recompile on the warm path.
"""
from __future__ import annotations

import collections
import threading
import time

import jax
from jax.profiler import TraceAnnotation

SUBMIT = "engine.submit"  # client: admission, dedup hash, enqueue
SCHEDULE = "engine.schedule"  # scheduler: a snapshot's grouping and plans; a group's placement
COMPILE = "engine.compile"  # a cold group's first call
RUN = "engine.run"  # one request on its worker, parent of the four below
DISPATCH = "engine.dispatch"  # plan-cache lookup (first call) + the executor call, unready
DEVICE = "engine.device"  # block_until_ready on the result
DERIVED = "engine.derived"  # traffic, bytes moved, metrics, prediction
RESOLVE = "engine.resolve"  # the future's resolve and the service's bookkeeping
NAMES = (SUBMIT, SCHEDULE, COMPILE, RUN, DISPATCH, DEVICE, DERIVED, RESOLVE)

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_open = threading.local()  # .stack: this thread's open spans, outermost first
_listener_lock = threading.Lock()
_listening = False


def _stack() -> list:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


def _on_event(event: str, duration: float, **_) -> None:
    if event != _BACKEND_COMPILE:
        return
    stack = getattr(_open, "stack", None)
    if stack and stack[0].totals is not None:
        stack[0].totals.add_compile(stack[0].name, duration)


def _listen() -> None:
    """Register the compile listener once per process (JAX keeps it)."""
    global _listening
    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            _listening = True


class SpanTotals:
    """Seconds and count per span name, and XLA compiles (count, seconds)
    per pipeline stage, over the owner's life. Its own lock: spans close
    on the client, scheduler and worker threads."""

    def __init__(self):
        _listen()
        self._lock = threading.Lock()
        self._seconds: collections.Counter = collections.Counter()
        self._counts: collections.Counter = collections.Counter()
        self._compiles: collections.Counter = collections.Counter()
        self._compile_seconds: collections.Counter = collections.Counter()
        self._counters: collections.Counter = collections.Counter()

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self._seconds[name] += seconds
            self._counts[name] += 1

    def add_compile(self, stage: str, seconds: float) -> None:
        with self._lock:
            self._compiles[stage] += 1
            self._compile_seconds[stage] += seconds

    def add_counts(self, counters: "dict[str, int]") -> None:
        with self._lock:
            self._counters.update(counters)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "span_seconds": dict(self._seconds),
                "span_counts": dict(self._counts),
                "xla_compiles": dict(self._compiles),
                "xla_compile_seconds": dict(self._compile_seconds),
                "counters": dict(self._counters),
            }


def count(counters: "dict[str, int]") -> None:
    """Add ``counters`` to the totals of this thread's innermost open span
    (on the served path, the request's); outside a span, nothing."""
    stack = getattr(_open, "stack", None)
    if stack and stack[-1].totals is not None:
        stack[-1].totals.add_counts(counters)


class span:
    """Context manager for one named span; ``t0``/``t1`` are its
    ``time.perf_counter()`` readings at entry and exit."""

    __slots__ = ("name", "ticket", "totals", "t0", "t1", "_annotation")

    def __init__(self, name: str, ticket: "int | None" = None,
                 totals: "SpanTotals | None" = None):
        self.name = name
        self.ticket = ticket
        self.totals = totals
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "span":
        stack = _stack()
        if stack:
            parent = stack[-1]
            if self.ticket is None:
                self.ticket = parent.ticket
            if self.totals is None:
                self.totals = parent.totals
        if self.ticket is None:
            self._annotation = TraceAnnotation(self.name)
        else:
            self._annotation = TraceAnnotation(self.name, ticket=self.ticket)
        self._annotation.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc_info) -> None:
        self.t1 = time.perf_counter()
        _stack().pop()
        self._annotation.__exit__(*exc_info)
        if self.totals is not None:
            self.totals.add(self.name, self.t1 - self.t0)
