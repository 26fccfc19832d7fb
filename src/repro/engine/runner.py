"""The engine's plan -> compile -> execute pipeline behind ``engine.run``.

    result, report = run(SpMVOp(), SpMVInputs(a, x), strategy, substrate="mesh")

The stages are individually exposed (DESIGN.md §1):

- :func:`build_plan`  — bind op + inputs + strategy to a substrate executor
  (``strategy="auto"`` routes through the traffic-model autotuner).
- :func:`compile_plan` — resolve the executor through a
  :class:`~repro.engine.cache.PlanCache`; a hit reuses the jitted executor.
- :func:`execute` / :func:`run` — timed execution. Defaults
  (``iters=3, warmup=1``) report *steady-state* medians with compile cost
  split into ``RunReport.compile_seconds``; pass ``iters=1, warmup=0`` to
  time a single cold call (compile included in ``seconds`` on a cache miss).

Each call opens the spans ``engine.dispatch`` (until the executor returns
unready arrays) and ``engine.device`` (``block_until_ready``), and the
report's derived stats run under ``engine.derived`` (:mod:`.spans`), which
also adds the op's per-request counters (``SpMVOp.counters``, and the
derived-stats memo's ``derived.hits`` / ``derived.misses``) to the
request's span totals.
"""
from __future__ import annotations

from typing import Any

import jax

from ..core.strategies import MigratoryStrategy
from . import ops as _ops  # noqa: F401  (imports register the built-in OpSpecs)
from .api import ExecutionPlan, MigratoryOp, RunReport
from .cache import CompiledPlan, PlanCache, default_cache
from .registry import default_registry
from .request import Request, coerce_request
from .spans import DERIVED, DEVICE, DISPATCH, count, span
from .substrate import Substrate, get_substrate


def resolve_op(op: "MigratoryOp | str") -> MigratoryOp:
    """Name -> MigratoryOp via the registry's OpSpec; instances pass through."""
    if isinstance(op, str):
        return default_registry().op_spec(op).factory()
    return op


def resolve_strategy(
    op: MigratoryOp,
    inputs: Any,
    strategy: "MigratoryStrategy | str | None",
    substrate: "Substrate | str" = "local",
) -> MigratoryStrategy:
    """None -> paper defaults; ``"auto"`` -> autotuner pick (ranked in
    predicted seconds for ``substrate`` when a calibrated machine file is
    present, in traffic units otherwise)."""
    if strategy is None:
        return MigratoryStrategy()
    if isinstance(strategy, str):
        if strategy != "auto":
            raise ValueError(f"unknown strategy {strategy!r}; expected 'auto'")
        from .autotune import choose_strategy

        return choose_strategy(op, inputs, substrate)
    return strategy


def _bind_plan(
    op: MigratoryOp, inputs: Any, strategy: Any, sub: Substrate
) -> ExecutionPlan:
    """op.plan + the substrate's planning overrides: a substrate whose
    executors the tracer cannot see (``jit_plans=False``, e.g. cluster
    forwarding over sockets) forces the plan eager regardless of what the
    op declared."""
    plan = op.plan(inputs, resolve_strategy(op, inputs, strategy, sub), sub)
    if not sub.jit_plans:
        plan.jit = False
    return plan


def build_plan(
    op: "MigratoryOp | str",
    inputs: Any,
    strategy: "MigratoryStrategy | str | None" = None,
    substrate: "Substrate | str" = "local",
) -> ExecutionPlan:
    """Stage 1: plan. Resolve op/strategy/substrate and bind the inputs."""
    op = resolve_op(op)
    sub = get_substrate(substrate)
    return _bind_plan(op, inputs, strategy, sub)


def compile_plan(
    plan: ExecutionPlan,
    cache: PlanCache | None = None,
    *,
    slot: "int | None" = None,
) -> CompiledPlan:
    """Stage 2: compile. Resolve the plan's executor through the cache —
    for keyed plans the first resolution wraps it in ``jax.jit``, so the
    cached artifact is a fused executable. ``slot`` tags the entry with the
    executor-pool slot doing the resolving (placement pinning, §1b)."""
    return (default_cache() if cache is None else cache).get(plan, slot=slot)


def _timed_call(
    compiled: "CompiledPlan | ExecutionPlan",
    times: list[float],
    cache: PlanCache | None = None,
    slot: "int | None" = None,
) -> tuple[CompiledPlan, Any]:
    """One call, its seconds appended to ``times``, under the spans
    ``engine.dispatch`` (until the executor returns unready arrays) and
    ``engine.device`` (``block_until_ready``). A bare plan is looked up in
    the cache inside the dispatch span. Returns ``(compiled, result)``."""
    with span(DISPATCH) as dispatch:
        if isinstance(compiled, ExecutionPlan):
            compiled = compile_plan(compiled, cache, slot=slot)
        pending = compiled()
    with span(DEVICE) as device:
        result = jax.block_until_ready(pending)
    times.append(device.t1 - dispatch.t0)
    return compiled, result


def execute(
    compiled: "CompiledPlan | ExecutionPlan",
    *,
    iters: int = 3,
    warmup: int = 1,
    cache: PlanCache | None = None,
    slot: "int | None" = None,
) -> tuple[Any, float, float]:
    """Stage 3: execute. Returns ``(result, seconds, compile_seconds)``.

    ``seconds`` is the median of ``iters`` timed calls after ``warmup``
    unmeasured ones. On a cache miss the first call traces + compiles; it is
    recorded as ``compile_seconds`` and doubles as the first warmup call —
    or, with ``warmup=0``, lands inside the timed set so a single cold call
    is timed compile-inclusive (the pre-cache engine's behavior). A bare
    plan is resolved through the cache inside the first call.
    """
    return _execute(compiled, iters, warmup, cache, slot)[1:]


def _execute(
    compiled: "CompiledPlan | ExecutionPlan",
    iters: int,
    warmup: int,
    cache: PlanCache | None,
    slot: "int | None",
) -> tuple[CompiledPlan, Any, float, float]:
    """:func:`execute`, also returning the :class:`CompiledPlan` it ran."""
    first: list[float] = []
    compiled, result = _timed_call(compiled, first, cache, slot)
    compile_seconds = 0.0
    if not compiled.cache_hit:
        compile_seconds = first[0]
        (default_cache() if cache is None else cache).note_compiled(compiled, compile_seconds)
    # the first call is the first warmup, or with warmup=0 the first timed one
    timed = [] if warmup > 0 else first
    for _ in range(warmup - 1):
        _, result = _timed_call(compiled, [])
    for _ in range(max(1, iters) - len(timed)):
        _, result = _timed_call(compiled, timed)
    timed.sort()
    return compiled, result, timed[len(timed) // 2], compile_seconds


def single_call(
    plan: ExecutionPlan,
    op: MigratoryOp,
    *,
    cache: PlanCache | None = None,
    slot: "int | None" = None,
) -> tuple[Any, RunReport]:
    """One timed call through the cache — the unit of work of the async
    service's pipeline stages (DESIGN.md §1d).

    On a *cold* plan this call is the **compile** stage: the single timed
    call traces + compiles, and the report carries
    ``cache_hit=False, seconds == compile_seconds``. On a *warm* plan it is
    the **execute** stage: a pure steady-state call with
    ``cache_hit=True, compile_seconds=0.0``. The split lets the service
    overlap the compile of one plan-key group with the execution of others
    while each request still runs exactly the call sequence the synchronous
    path would have run — parity is structural, not incidental.

    ``slot`` is the placement tag: the executor-pool worker making the call.
    A compiling call pins the cache entry to it; a stolen execution passes
    its own slot but the pin stays with the compiling worker (§1b).
    """
    return run_plan(plan, op, iters=1, warmup=0, cache=cache, slot=slot)


def run_plan(
    plan: ExecutionPlan,
    op: MigratoryOp,
    *,
    iters: int = 3,
    warmup: int = 1,
    cache: PlanCache | None = None,
    slot: "int | None" = None,
) -> tuple[Any, RunReport]:
    """Compile + execute an already-built plan and assemble its RunReport;
    the derived stats, and the op's ``counters`` where it has them, run
    under the span ``engine.derived``."""
    compiled, result, seconds, compile_seconds = _execute(plan, iters, warmup, cache, slot)
    # model honesty columns (DESIGN.md §1f): only a *calibrated* machine
    # file produces predictions — without one the report is bit-identical
    # to the pre-calibration schema (the columns stay None and are omitted
    # from to_dict), and the lookup is one cached profile check
    from ..machine.perfmodel import maybe_predict_plan_seconds

    with span(DERIVED):
        predicted = maybe_predict_plan_seconds(op, plan)
        report = RunReport.from_parts(
            op=op.name,
            strategy=plan.strategy,
            substrate=plan.substrate,
            seconds=seconds,
            traffic=op.traffic(plan),
            bytes_moved=op.bytes_moved(plan),
            metrics=op.metrics(plan, result, seconds),
            cache_hit=compiled.cache_hit,
            compile_seconds=compile_seconds,
            predicted_seconds=predicted,
        )
        if hasattr(op, "counters"):
            count(op.counters(plan))
    return result, report


def run_request(
    request: Request,
    *,
    iters: int = 3,
    warmup: int = 1,
    cache: PlanCache | None = None,
) -> tuple[Any, RunReport]:
    """Execute one :class:`~repro.engine.request.Request`; return
    ``(result, RunReport)``. The non-deprecated core behind :func:`run` —
    ``request.qos``/``request.timeout`` are serving-plane fields and are
    ignored here (the caller is already blocking on this one request)."""
    op = resolve_op(request.op)
    sub = get_substrate(
        request.substrate if request.substrate is not None else "local"
    )
    plan = _bind_plan(op, request.inputs, request.strategy, sub)
    return run_plan(plan, op, iters=iters, warmup=warmup, cache=cache)


def run(
    op: "Request | MigratoryOp | str",
    inputs: Any = None,
    strategy: "MigratoryStrategy | str | None" = None,
    substrate: "Substrate | str | None" = None,
    *,
    iters: int = 3,
    warmup: int = 1,
    cache: PlanCache | None = None,
) -> tuple[Any, RunReport]:
    """Execute one request; return ``(result, RunReport)``.

    The entry shape is a :class:`~repro.engine.request.Request`:

        y, report = run(Request("spmv", SpMVInputs(a, x), "auto", "mesh"))

    ``op``: the Request — or, deprecated, a MigratoryOp instance/name with
    the fields spread as arguments (emits ``DeprecationWarning``; behavior
    is identical via :func:`run_request`).
    ``strategy``: a MigratoryStrategy, ``None`` (paper defaults), or
    ``"auto"`` (traffic-model autotuner, engine/autotune.py).
    ``substrate``: a Substrate instance or name ("local" | "mesh" | "pallas").
    ``iters``/``warmup``: the defaults time steady state (median of 3 after
    1 warmup) with compile split out; ``iters=1, warmup=0`` times one cold
    call, compile included on a cache miss.
    ``cache``: plan cache override (default: the process-wide cache).
    """
    request = coerce_request(op, inputs, strategy, substrate, entry="run")
    return run_request(request, iters=iters, warmup=warmup, cache=cache)
