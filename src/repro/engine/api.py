"""Engine API types: the ``MigratoryOp`` protocol, ``ExecutionPlan``, and the
unified ``RunReport`` record (DESIGN.md §1).

The paper's thesis is that one set of strategies (S1 replication, S2
migrate-vs-remote-write, S3 layout) applies uniformly to SpMV, BFS, and
graph alignment. The engine makes that uniformity structural: every
distributed op is a :class:`MigratoryOp` planned onto a
:class:`~repro.engine.substrate.Substrate`, compiled once per
shape/strategy/substrate signature (DESIGN.md §1b), and every run yields one
serializable :class:`RunReport` combining wall time, the paper's traffic
model, effective bandwidth, and compile-vs-steady-state accounting.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable, Protocol, runtime_checkable

import jax

from ..core.strategies import MigratoryStrategy, TrafficStats


class OpNotSupportedError(NotImplementedError):
    """Raised when a substrate cannot execute an op (e.g. BFS on pallas).

    Since the kernel registry (DESIGN.md §1e) this is *derived from registry
    absence*: ``Substrate.kernel(op_name)`` raises it when no kernel is
    registered for ``(op_name, substrate_kind)`` — at plan time, not deep in
    execution — and kernels may also raise it for runtime capability limits
    (device count, unsupported task shapes)."""


def strategy_dict(strategy: MigratoryStrategy) -> dict[str, Any]:
    """Flatten a strategy into plain-JSON form for reports."""
    return {
        "comm": strategy.comm.value,
        "replicate_x": strategy.replicate_x,
        "layout": strategy.layout.value,
        "scheme": strategy.scheme.value,
        "grain": strategy.grain,
    }


def args_signature(args: Any) -> tuple:
    """Shape/dtype (never value) signature of a plan's argument pytree.

    Two argument sets with equal signatures can share a compiled executor:
    array leaves contribute ``(shape, dtype)``, non-array leaves their repr
    (they are compile-time constants), and the treedef pins the container
    structure (including pytree aux data such as matrix shapes and bucket
    grids).
    """
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = tuple(
        (tuple(leaf.shape), str(leaf.dtype))
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
        else ("pyleaf", repr(leaf))
        for leaf in leaves
    )
    return (str(treedef), sig)


def plan_key(
    op: str, substrate, strategy: MigratoryStrategy, args: Any,
    static: tuple = (),
) -> tuple:
    """The compiled-plan cache key: op name x substrate fingerprint x full
    strategy x static scalars x argument shape/dtype signature."""
    return (
        op,
        substrate.cache_fingerprint(),
        strategy.cache_key(),
        static,
        args_signature(args),
    )


@dataclasses.dataclass
class ExecutionPlan:
    """A strategy + substrate bound to concrete inputs, ready to compile.

    ``executor`` is a pure function of ``args`` (the array pytrees) — it
    closes only over compile-time statics (strategy, substrate, scalar
    parameters), all of which are pinned by ``key``, so the plan cache may
    hand the same executor to any later plan with an equal ``key``.
    ``meta`` holds static facts about the inputs (sizes, nnz, ...) plus
    anything the op caches between :meth:`MigratoryOp.traffic` and metric
    computation. ``key=None`` marks a plan as uncacheable.

    ``jit=True`` (the default) lets the compile stage wrap the executor in
    ``jax.jit`` when it enters the plan cache, so the cached artifact is one
    fused XLA executable instead of an op-by-op eager trace — ops whose
    executors do host-side work the tracer cannot see must set it False.
    """

    op: str
    strategy: MigratoryStrategy
    substrate: str
    inputs: Any
    executor: Callable[..., Any]
    args: tuple = ()
    meta: dict[str, Any] = dataclasses.field(default_factory=dict)
    key: tuple | None = None
    jit: bool = True

    def run(self) -> Any:
        """Execute this plan's own executor on its own arguments."""
        return self.executor(*self.args)


@runtime_checkable
class MigratoryOp(Protocol):
    """A distributed operation the engine knows how to run and account for.

    An op may also define ``counters(plan) -> dict[str, int]``: per-request
    counts read from the plan (``SpMVOp``: ``spmv.slots``, ``spmv.pieces``),
    which the runner adds to the service's span totals."""

    name: str

    def plan(self, inputs: Any, strategy: MigratoryStrategy, substrate) -> ExecutionPlan:
        """Bind inputs + strategy to a substrate-specific executor."""

    def traffic(self, plan: ExecutionPlan) -> TrafficStats:
        """Paper-model communication traffic for this plan."""

    def bytes_moved(self, plan: ExecutionPlan) -> int:
        """Bytes the paper's effective-bandwidth formula charges one run."""

    def metrics(self, plan: ExecutionPlan, result: Any, seconds: float) -> dict[str, Any]:
        """Op-specific derived metrics (MTEPS, recall, modeled makespan, ...)."""


@dataclasses.dataclass
class RunReport:
    """One run, one record: unifies wall time, TrafficStats, the per-op stats
    (BFS rounds / GSANA plan model), effective bandwidth, and the plan
    cache's compile accounting (``cache_hit``, ``compile_seconds``).

    ``compile_seconds`` is the time of the plan's cold call, the service's
    ``engine.compile`` stage: trace, XLA compile (or persistent-cache read)
    and the first execution, 0.0 on a cache hit. The XLA compile seconds
    alone are the engine's compile counter
    (``ServiceStats.xla_compile_seconds``, :mod:`~repro.engine.spans`).

    ``predicted_seconds``/``model_error`` are the calibration plane's
    honesty columns (DESIGN.md §1f): the performance model's wall-seconds
    prediction for this plan and its ratio to the measurement
    (predicted / measured, 1.0 = perfect). Both stay None — and absent from
    ``to_dict`` — unless a calibrated machine file was present."""

    op: str
    strategy: dict[str, Any]
    substrate: str
    seconds: float
    traffic: TrafficStats
    bytes_moved: int
    effective_gbps: float
    cache_hit: bool = False
    compile_seconds: float = 0.0
    metrics: dict[str, Any] = dataclasses.field(default_factory=dict)
    predicted_seconds: "float | None" = None
    model_error: "float | None" = None

    def to_dict(self) -> dict[str, Any]:
        """Flat, JSON-ready form — the unified benchmark row schema.

        Op metrics may not shadow schema columns (an op metric named e.g.
        ``seconds`` would silently corrupt benchmark trajectories).
        """
        row = {
            "op": self.op,
            **{f"strategy_{k}": v for k, v in self.strategy.items()},
            "substrate": self.substrate,
            "seconds": self.seconds,
            "us_per_call": self.seconds * 1e6,
            "cache_hit": self.cache_hit,
            "compile_seconds": self.compile_seconds,
            "migrations": self.traffic.migrations,
            "remote_writes": self.traffic.remote_writes,
            "collective_bytes": self.traffic.collective_bytes,
            "traffic_bytes": self.traffic.total_bytes,
            "bytes_moved": self.bytes_moved,
            "effective_gbps": self.effective_gbps,
        }
        if self.predicted_seconds is not None:
            row["predicted_seconds"] = self.predicted_seconds
        if self.model_error is not None:
            row["model_error"] = self.model_error
        clash = sorted(set(row) & set(self.metrics))
        if clash:
            raise ValueError(
                f"op metrics {clash} collide with RunReport schema columns; "
                "rename the op metric"
            )
        row.update(self.metrics)
        return row

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), default=str)

    @classmethod
    def from_parts(
        cls,
        op: str,
        strategy: MigratoryStrategy,
        substrate: str,
        seconds: float,
        traffic: TrafficStats,
        bytes_moved: int,
        metrics: dict[str, Any] | None = None,
        cache_hit: bool = False,
        compile_seconds: float = 0.0,
        predicted_seconds: "float | None" = None,
    ) -> "RunReport":
        return cls(
            op=op,
            strategy=strategy_dict(strategy),
            substrate=substrate,
            seconds=seconds,
            traffic=traffic,
            bytes_moved=bytes_moved,
            effective_gbps=bytes_moved / max(seconds, 1e-12) / 1e9,
            cache_hit=cache_hit,
            compile_seconds=compile_seconds,
            metrics=metrics or {},
            predicted_seconds=predicted_seconds,
            model_error=(
                None if predicted_seconds is None
                else predicted_seconds / max(seconds, 1e-12)
            ),
        )
