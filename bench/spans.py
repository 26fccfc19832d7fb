"""Reduce the program's own host spans in a profiler trace.

The engine opens a ``TraceAnnotation`` at each boundary of the served path
(``engine.submit``, ``engine.schedule``, ``engine.compile``, ``engine.run``
and inside it ``engine.dispatch``, ``engine.device``, ``engine.derived``,
``engine.resolve``), each under its request's ticket: the trace reads
``engine.run#ticket=12#``, or the name with a ``ticket`` stat. They share
the device's clock. Inside the ``bench.window`` span this gives:

- per span name: total seconds, count, and self seconds (the duration less
  the named spans nested in it on the same thread), over the spans that
  start in the window;
- per ticket: the start and end of each of the request's spans;
- the device's idle time attributed piecewise: each idle gap (the window
  less the union of ``XLA Ops``) inside a running module is
  ``device.between_ops``; elsewhere it is cut at span boundaries and each
  piece goes to the innermost (latest started) open ``engine.*`` or
  ``bench.*`` span, else ``host.other``. The pieces sum to window - busy.

A program without these spans gives empty tables, and the readers of its
metrics return ``None``.
"""
from __future__ import annotations

import collections
import dataclasses
import heapq

from bench.trace import DEVICE_PREFIX, IN_PROGRAM, WINDOW_SPAN, _inside, _self_times, merge

NAMED = ("engine.", "bench.")
SUBMIT, DISPATCH = "engine.submit", "engine.dispatch"


@dataclasses.dataclass
class SpanSummary:
    window_s: float
    seconds: dict[str, float]
    counts: dict[str, int]
    self_s: dict[str, float]
    tickets: dict[int, dict[str, tuple[int, int]]]  # ticket -> name -> (start_ns, end_ns)
    idle_s: dict[str, float]  # idle seconds by what held them, mean over chips

    def mean_ms(self, name: str) -> float | None:
        count = self.counts.get(name)
        return self.seconds[name] / count * 1e3 if count else None

    def serve_ms(self) -> float | None:
        """Mean over tickets of ``engine.submit`` start to ``engine.dispatch``
        end: admission, queue wait, scheduling, plan build, cache lookup and
        dispatch, until the device has the work."""
        spans = [t for t in self.tickets.values() if SUBMIT in t and DISPATCH in t]
        if not spans:
            return None
        return sum(t[DISPATCH][1] - t[SUBMIT][0] for t in spans) / len(spans) * 1e-6

    def idle_pct(self, name: str) -> float | None:
        """Share, in %, of the window the device sat idle while ``name`` was
        the innermost open span; None where the trace has no such span."""
        if name not in self.counts:
            return None
        return 100.0 * self.idle_s.get(name, 0.0) / self.window_s


def _named(event):
    """(name, ticket) of an ``engine.*``/``bench.*`` event, else None."""
    name, _, tail = event.name.partition("#")
    if not name.startswith(NAMED):
        return None
    ticket = dict(getattr(event, "stats", ()) or ()).get("ticket")
    if ticket is None:
        for pair in tail.rstrip("#").split(","):
            key, _, value = pair.partition("=")
            if key == "ticket":
                ticket = int(value)
    return name, ticket


def reduce_spans(planes) -> SpanSummary:
    """``planes`` as :func:`bench.trace.reduce_planes` takes them."""
    threads, devices = [], []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append({line.name: list(line.events) for line in plane.lines})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for e in line.events:
                    named = _named(e)
                    if named is not None:
                        spans.append((named[0], named[1], e.start_ns, e.end_ns))
                if spans:
                    threads.append(spans)
    windows = [(s, t) for spans in threads for name, _, s, t in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"trace has {len(windows)} {WINDOW_SPAN} spans; expected 1")
    w0, w1 = windows[0]

    seconds: collections.Counter = collections.Counter()
    counts: collections.Counter = collections.Counter()
    self_s: collections.Counter = collections.Counter()
    tickets: dict = collections.defaultdict(dict)
    for spans in threads:
        # trace._self_times nests by (start, end); the whole span rides as its name
        ordered = sorted(spans, key=lambda x: (x[2], -x[3]))
        for (name, ticket, s, t), own in _self_times([(x, x[2], x[3]) for x in ordered]):
            if w0 <= s < w1:
                seconds[name] += (t - s) * 1e-9
                counts[name] += 1
                self_s[name] += own
                if ticket is not None:
                    tickets[ticket].setdefault(name, (s, t))

    open_spans = [(name, s, t) for spans in threads for name, _, s, t in spans
                  if name != WINDOW_SPAN]
    idle_s: collections.Counter = collections.Counter()
    for lines in devices:
        busy = merge((s, t) for _, s, t in _inside(lines.get("XLA Ops", []), w0, w1))
        modules = merge((s, t) for _, s, t in _inside(lines.get("XLA Modules", []), w0, w1))
        edges = [w0, *(x for interval in busy for x in interval), w1]
        gaps = [(s, t) for s, t in zip(edges[::2], edges[1::2]) if t > s]
        for name, ns in attribute(gaps, modules, open_spans):
            idle_s[name] += ns * 1e-9
    n = max(1, len(devices))
    return SpanSummary(
        window_s=(w1 - w0) * 1e-9,
        seconds=dict(seconds),
        counts=dict(counts),
        self_s=dict(self_s),
        tickets=dict(tickets),
        idle_s={k: v / n for k, v in idle_s.items()},
    )


def attribute(gaps, modules, spans):
    """Cut each idle gap (sorted, disjoint ``(start, end)``) into pieces and
    name each: ``IN_PROGRAM`` inside a running module (``modules`` sorted,
    disjoint), else the innermost open span of ``spans`` (``(name, start,
    end)``, any thread), else ``host.other``. Yields (name, ns)."""
    bounds = sorted([(s, 1, i) for i, (_, s, _) in enumerate(spans)]
                    + [(t, 0, i) for i, (_, _, t) in enumerate(spans)])
    heap: list = []  # (-start, end, index) of opened spans
    closed: set = set()
    k = m = 0
    for gs, gt in gaps:
        cursor = gs
        while cursor < gt:
            while m < len(modules) and modules[m][1] <= cursor:
                m += 1
            if m < len(modules) and modules[m][0] <= cursor:
                end = min(gt, modules[m][1])
                yield IN_PROGRAM, end - cursor
                cursor = end
                continue
            while k < len(bounds) and bounds[k][0] <= cursor:
                _, opens, i = bounds[k]
                if opens:
                    heapq.heappush(heap, (-spans[i][1], spans[i][2], i))
                else:
                    closed.add(i)
                k += 1
            while heap and heap[0][2] in closed:
                heapq.heappop(heap)
            end = gt if m == len(modules) else min(gt, modules[m][0])
            if k < len(bounds):
                end = min(end, bounds[k][0])
            yield (spans[heap[0][2]][0] if heap else "host.other"), end - cursor
            cursor = end
