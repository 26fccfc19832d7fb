"""``bench/spans.py`` on a hand-built trace and on the trace recorded on the
chip, and the ``derived_ms.spmv`` reader.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
from __future__ import annotations

import types
from pathlib import Path

import pytest

from bench import harness
from bench.spans import reduce_spans
from bench.trace import IN_PROGRAM, reduce_file

BENCH = Path(__file__).resolve().parents[1]
TRACE = BENCH / "testdata" / "probe_bfs_spmv.xplane.pb"


def _event(name, start, end, stats=()):
    return types.SimpleNamespace(name=name, start_ns=start, end_ns=end, stats=stats)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k, events=[_event(*e) for e in v]) for k, v in lines.items()])


def _two_products():
    """Two served products in a 100 ns window: the client, the scheduler
    and a worker on three threads; the first ticket in the trace's
    ``#ticket=N#`` suffix, the second in its ``ticket`` stat."""
    t2 = (("ticket", 2),)
    device = _plane("/device:TPU:0", {
        "XLA Modules": [("jit_spmv_local(1)", 10, 40), ("jit_spmv_local(1)", 70, 90)],
        "XLA Ops": [("%a = x", 10, 20), ("%b = y", 30, 40), ("%c = z", 70, 90)],
    })
    host = _plane("/host:CPU", {
        "client": [("bench.window", 0, 100),
                   ("bench.submit", 1, 5), ("engine.submit#ticket=1#", 2, 4),
                   ("bench.wait", 5, 52), ("bench.fetch", 52, 60),
                   ("bench.submit", 60, 64), ("engine.submit", 61, 63, t2),
                   ("bench.wait", 64, 100), ("PjitFunction(spmv_local)", 7, 9)],
        "scheduler": [("engine.schedule#ticket=1#", 4, 7), ("engine.schedule", 63, 66, t2)],
        "worker": [("engine.run#ticket=1#", 7, 51), ("engine.dispatch#ticket=1#", 7, 10),
                   ("engine.device#ticket=1#", 10, 40), ("engine.derived#ticket=1#", 40, 48),
                   ("engine.resolve#ticket=1#", 48, 50),
                   ("engine.run", 66, 99, t2), ("engine.dispatch", 66, 70, t2),
                   ("engine.device", 70, 90, t2), ("engine.derived", 90, 97, t2),
                   ("engine.resolve", 97, 99, t2)],
    })
    return [device, host]


def test_spans_on_a_hand_built_trace():
    s = reduce_spans(_two_products())
    assert s.window_s == pytest.approx(100e-9)
    assert s.counts["engine.run"] == 2 and s.counts["bench.wait"] == 2
    assert s.seconds["engine.run"] == pytest.approx((44 + 33) * 1e-9)
    # self time: the duration less the named spans nested in it on its thread
    assert s.self_s["engine.run"] == pytest.approx(1e-9)
    assert s.self_s["bench.submit"] == pytest.approx(4e-9)
    assert s.self_s["engine.derived"] == pytest.approx(15e-9)
    assert s.tickets[1]["engine.derived"] == (40, 48)
    assert set(s.tickets[2]) == {"engine.submit", "engine.schedule", "engine.run",
                                 "engine.dispatch", "engine.device", "engine.derived",
                                 "engine.resolve"}
    assert s.mean_ms("engine.derived") == pytest.approx(7.5e-6)
    assert s.serve_ms() == pytest.approx((8 + 9) / 2 * 1e-6)
    # each idle piece goes to the latest started open span, across threads
    assert s.idle_s == pytest.approx({
        "host.other": 1e-9, "bench.submit": 2e-9, "engine.submit": 4e-9,
        "engine.schedule": 2e-9, "bench.wait": 6e-9, "engine.dispatch": 7e-9,
        "engine.derived": 15e-9, "engine.resolve": 4e-9, "engine.run": 1e-9,
        "bench.fetch": 8e-9, IN_PROGRAM: 10e-9,
    })
    assert sum(s.idle_s.values()) == pytest.approx(60e-9)  # window - busy
    assert s.idle_pct("engine.derived") == pytest.approx(15.0)


def test_a_program_without_spans_reads_none():
    planes = _two_products()
    planes[1].lines = planes[1].lines[:1]
    planes[1].lines[0].events = [e for e in planes[1].lines[0].events
                                 if not e.name.startswith("engine.")]
    s = reduce_spans(planes)
    assert s.mean_ms("engine.derived") is None and s.serve_ms() is None
    assert s.idle_pct("engine.derived") is None
    assert sum(s.idle_s.values()) == pytest.approx(60e-9)


def test_idle_attribution_on_the_chip_trace_sums_to_window_less_busy():
    """The recorded trace predates the engine's spans: only ``bench.*``
    spans name its gaps, and they tile window - busy as the midpoint rule
    of ``bench/trace.py`` does."""
    from jax.profiler import ProfileData

    s = reduce_spans(list(ProfileData.from_file(str(TRACE)).planes))
    t = reduce_file(str(TRACE))
    assert s.window_s == pytest.approx(t.window_s)
    assert sum(s.idle_s.values()) == pytest.approx(t.window_s - t.busy_s)
    assert set(s.idle_s) <= {"bench.bfs", "bench.spmv", IN_PROGRAM, "host.other"}
    assert not any(name.startswith("engine.") for name in s.counts)


@pytest.mark.parametrize("stats,expect", [
    ({}, None),
    ({"span_seconds": {"engine.derived": 0.03}, "span_counts": {"engine.derived": 2}}, 15.0),
])
def test_derived_ms_reads_the_service_span_totals(stats, expect):
    reader = harness.load_module(BENCH / "metrics" / "derived_ms.spmv.py")
    run = types.SimpleNamespace(service_stats=stats)
    assert reader.read(run) == (None if expect is None else pytest.approx(expect))
