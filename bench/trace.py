"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

On a TPU the trace holds one plane per chip, ``/device:TPU:<i>``, with an
``XLA Modules`` line (one event per program execution, named
``jit_<function>(<fingerprint>)``) and an ``XLA Ops`` line (one event per
operation), and a ``/host:CPU`` plane whose lines carry the benchmark's
``TraceAnnotation`` spans (``bench.*``). All events share one clock.

- window: the ``bench.window`` span on the host.
- busy: the union of the ``XLA Ops`` intervals inside the window, per chip.
- module time: the summed duration of each module's executions inside it.
- op time: each op's own time, less the ops nested in it.
- idle gaps: the window minus busy. A gap inside a running program is
  ``device.between_ops``; any other is named by the innermost ``bench.*``
  host span (other than the window) open at its midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import heapq

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
IN_PROGRAM = "device.between_ops"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over chips
    module_s: dict[str, float]  # summed over chips
    op_s: dict[str, float]  # mean over chips
    idle_s: dict[str, float]  # idle time by what held it, mean over chips

    def modules_s(self, prefix: str = "") -> float:
        return sum(s for name, s in self.module_s.items() if name.startswith(prefix))

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self, top: int = 10) -> dict:
        def largest(table):
            return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": largest(self.op_s), "idle_gaps": largest(self.idle_s)}


def merge(intervals) -> list:
    """Sorted, disjoint union of (start, end) pairs."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def _op_name(event_name: str) -> str:
    """``%fusion.2 = f32[640]... fusion(...)`` -> ``fusion.2``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _inside(events, w0, w1):
    for e in events:
        s, t = max(e.start_ns, w0), min(e.end_ns, w1)
        if t > s:
            yield e.name, s, t


def reduce_planes(planes) -> TraceSummary:
    """``planes``: objects with ``name`` and ``lines``, each line with
    ``name`` and ``events`` (``name``, ``start_ns``, ``end_ns``), as
    ``jax.profiler.ProfileData`` gives them."""
    host_spans, devices = [], []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append({line.name: list(line.events) for line in plane.lines})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans += [(e.name, e.start_ns, e.end_ns)
                               for e in line.events if e.name.startswith("bench.")]
    windows = [(s, t) for name, s, t in host_spans if name == WINDOW_SPAN]
    if len(windows) != 1 or not devices:
        raise ValueError(f"trace has {len(windows)} {WINDOW_SPAN} spans and "
                         f"{len(devices)} TPU planes; expected 1 and at least 1")
    w0, w1 = windows[0]
    inner = [(n, s, t) for n, s, t in host_spans if n != WINDOW_SPAN and t > w0 and s < w1]
    busy_ns = 0.0
    module_s: collections.Counter = collections.Counter()
    op_s: collections.Counter = collections.Counter()
    idle_s: collections.Counter = collections.Counter()
    for lines in devices:
        ops = sorted(_inside(lines.get("XLA Ops", []), w0, w1), key=lambda o: (o[1], -o[2]))
        for name, seconds in _self_times(ops):
            op_s[_op_name(name)] += seconds
        ops = [(s, t) for _, s, t in ops]
        modules = []
        for name, s, t in _inside(lines.get("XLA Modules", []), w0, w1):
            modules.append((s, t))
            module_s[name.split("(", 1)[0]] += (t - s) * 1e-9
        busy = merge(ops)
        busy_ns += sum(t - s for s, t in busy)
        gaps, cursor = [], w0
        for s, t in busy + [[w1, w1]]:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, t)
        for name, seconds in _label_gaps(gaps, merge(modules), inner):
            idle_s[name] += seconds
    n = len(devices)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_ns * 1e-9 / n,
        module_s=dict(module_s),
        op_s={k: v / n for k, v in op_s.items()},
        idle_s={k: v / n for k, v in idle_s.items()},
    )


def _self_times(ops):
    """(name, seconds) of each op less the ops nested inside it (a
    ``while`` holds its body's ops); ``ops`` sorted by start, longest first."""
    stack: list = []  # [name, end, self_ns]
    for name, s, t in ops:
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            yield done[0], done[2] * 1e-9
        if stack:
            stack[-1][2] -= t - s
        stack.append([name, t, t - s])
    for name, _, self_ns in stack:
        yield name, self_ns * 1e-9


def _label_gaps(gaps, modules, spans):
    """Name each idle gap (given in time order) by what held it:
    ``IN_PROGRAM`` where a program was running on the device, else the
    innermost (latest started) host span open at the gap's midpoint, else
    ``host.other``. Yields (name, seconds)."""
    spans = sorted(spans, key=lambda span: span[1])
    open_spans: list = []  # heap of (-start, end, name)
    m = k = 0
    for s, t in gaps:
        mid = (s + t) / 2
        while m < len(modules) and modules[m][1] <= mid:
            m += 1
        if m < len(modules) and modules[m][0] <= mid:
            yield IN_PROGRAM, (t - s) * 1e-9
            continue
        while k < len(spans) and spans[k][1] <= mid:
            name, start, end = spans[k]
            heapq.heappush(open_spans, (-start, end, name))
            k += 1
        label = "host.other"
        while open_spans:
            _, end, name = open_spans[0]
            if end > mid:
                label = name
                break
            heapq.heappop(open_spans)
        yield label, (t - s) * 1e-9


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)
