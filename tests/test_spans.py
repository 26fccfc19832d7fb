"""The engine's spans and compile counter (``repro.engine.spans``): every
boundary of the served path opens a named span under the request's ticket,
on the profiler's clock; the service keeps their totals; XLA compiles are
counted per pipeline stage; programs carry the name ``<op>_<substrate>``."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import partition_ell
from repro.engine import EngineService, PlanCache, Request, SpMVInputs, build_plan
from repro.engine.cache import _named
from repro.engine.spans import (
    COMPILE, DERIVED, DEVICE, DISPATCH, NAMES, RESOLVE, RUN, SCHEDULE, SUBMIT,
)
from repro.sparse import laplacian_2d

PER_REQUEST = (SUBMIT, RUN, DISPATCH, DEVICE, DERIVED, RESOLVE)


def _inputs(side: int, seed: int = 0) -> SpMVInputs:
    x = np.random.default_rng(seed).standard_normal(side * side).astype(np.float32)
    return SpMVInputs(partition_ell(laplacian_2d(side), 8), jnp.asarray(x))


def _events(trace_dir: Path) -> list:
    """(name, ticket, thread, start_ns, end_ns) of the host plane's events."""
    from jax.profiler import ProfileData

    (path,) = trace_dir.rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ticket = dict(e.stats).get("ticket")
                    out.append((e.name.split("#")[0], ticket, line.name, e.start_ns, e.end_ns))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One SpMV served through a started service inside a CPU profiler trace."""
    trace_dir = tmp_path_factory.mktemp("trace")
    inputs = _inputs(10)
    with EngineService(cache=PlanCache()) as svc:
        jax.profiler.start_trace(str(trace_dir))
        try:
            future = svc.submit(Request("spmv", inputs))
            np.asarray(future.result(timeout=300).result)
        finally:
            jax.profiler.stop_trace()
    return future.ticket, _events(trace_dir)


def test_request_opens_every_span_under_its_ticket(traced):
    ticket, events = traced
    mine = {name: (thread, s, t) for name, tk, thread, s, t in events
            if name in NAMES and tk == ticket}
    # a fresh cache: the request is its group's cold, compiling call
    assert set(mine) == set(NAMES)
    run_thread, run_start, run_end = mine[RUN]
    for name in (DISPATCH, DEVICE, DERIVED, RESOLVE):
        thread, s, t = mine[name]
        assert thread == run_thread and run_start <= s <= t <= run_end, name
    order = [DISPATCH, DEVICE, DERIVED, RESOLVE]
    assert [mine[n][1] for n in order] == sorted(mine[n][1] for n in order)
    assert mine[SUBMIT][1] <= mine[SCHEDULE][1] <= mine[COMPILE][1] <= run_start


def test_trace_holds_the_named_program(traced):
    _, events = traced
    assert any(name == "PjitFunction(spmv_local)" for name, *_ in events)
    plan = build_plan("spmv", _inputs(10))
    assert "jit_spmv_local" in jax.jit(_named(plan)).lower(*plan.args).as_text()


def test_named_executor_pins_no_inputs():
    inputs = _inputs(6)
    call = _named(build_plan("spmv", inputs))
    captured = [cell.cell_contents for cell in call.__closure__]
    assert all(c is not inputs and c is not inputs.x for c in captured)
    assert call.__name__ == "spmv_local"


def test_span_counts_match_the_requests_served():
    requests = 3
    with EngineService(cache=PlanCache()) as svc:
        for i in range(requests):
            svc.submit(Request("spmv", _inputs(8, seed=i))).result(timeout=300)
        stats = svc.stats()
    counts = stats.to_dict()["span_counts"]
    assert {name: counts[name] for name in PER_REQUEST} == dict.fromkeys(PER_REQUEST, requests)
    assert counts[COMPILE] == 1  # one plan key: only the first call is cold
    # one request per snapshot: its grouping, then its one group's placement
    assert counts[SCHEDULE] == 2 * requests
    assert set(stats.span_seconds) == set(counts)
    assert all(seconds > 0 for seconds in stats.span_seconds.values())


def test_span_counts_in_batch_mode():
    svc = EngineService(cache=PlanCache())
    for i in range(2):
        svc.submit(Request("spmv", _inputs(8, seed=i)))
    svc.drain()
    counts = svc.stats().span_counts
    assert {name: counts[name] for name in PER_REQUEST} == dict.fromkeys(PER_REQUEST, 2)
    assert counts[COMPILE] == 1 and counts[SCHEDULE] == 1


def test_derived_stats_are_computed_once_per_matrix():
    """Each request wraps the one matrix in a new ``SpMVInputs``: the memo
    misses once per derived stat (traffic, bytes, layout) on the first
    request and hits on every later one."""
    first = _inputs(9)
    requests, stats_per_request = 5, 3
    with EngineService(cache=PlanCache()) as svc:
        for i in range(requests):
            x = first.x if i == 0 else jnp.full_like(first.x, i)
            svc.submit(Request("spmv", SpMVInputs(first.a, x))).result(timeout=300)
        counters = svc.stats().counters
    assert counters["derived.misses"] == stats_per_request
    assert counters["derived.hits"] == (requests - 1) * stats_per_request


def test_compiles_are_counted_under_the_compile_stage_not_the_warm_path():
    inputs = _inputs(13)  # a shape no other test compiles
    with EngineService(cache=PlanCache()) as svc:
        svc.submit(Request("spmv", inputs)).result(timeout=300)
        cold = svc.stats()
        svc.submit(Request("spmv", _inputs(13, seed=1))).result(timeout=300)
        warm = svc.stats()
    assert cold.xla_compiles.get(COMPILE, 0) >= 1
    assert cold.xla_compile_seconds[COMPILE] > 0
    assert RUN not in cold.xla_compiles
    assert warm.xla_compiles == cold.xla_compiles
