"""Unified MigratoryOp engine: one substrate-dispatched entry point for the
paper's three irregular algorithms, with built-in traffic & bandwidth
accounting and an explicit plan -> compile -> execute pipeline
(DESIGN.md §1).

    from repro.engine import Request, run, SpMVOp, SpMVInputs
    y, report = run(Request(SpMVOp(), SpMVInputs(a, x), strategy, "mesh"))
    y, report = run(Request("spmv", SpMVInputs(a, x), "auto"))  # autotuned
    print(report.to_json())   # seconds + traffic + cache_hit/compile_seconds

``Request`` is the one entry shape for ``run`` and ``EngineService.submit``
(per-request ``qos``/``timeout`` ride it); the legacy kwargs spellings
still work but emit :class:`DeprecationWarning` (DESIGN.md §1g).

Ops implement :class:`MigratoryOp`; backends implement :class:`Substrate`
and register with :func:`register_substrate`. Ops and substrates meet only
in the :class:`KernelRegistry` (:mod:`repro.engine.registry`): kernels are
``(op, substrate_kind)`` entries (``@kernel("spmv", "mesh")``), ops are
:class:`OpSpec` registrations, and :func:`capabilities` is the
introspection table of who runs what — ``moe_dispatch``
(:mod:`repro.engine.moe_op`) is the fourth op, registered without touching
any substrate class. Compiled executors are cached per
shape/strategy/substrate signature (:mod:`repro.engine.cache`); the
strategy grid is ranked analytically (:mod:`repro.engine.autotune`) with
measured probes persisted across sessions (:mod:`repro.engine.probes`);
serving goes through :class:`EngineService` (:mod:`repro.engine.service`) —
batched drain or the async worker loop with admission control, a value-keyed
response dedup cache, and an overlapped compile/execute pipeline.
"""
from .api import (
    ExecutionPlan,
    MigratoryOp,
    OpNotSupportedError,
    RunReport,
    args_signature,
    plan_key,
    strategy_dict,
)
from .autotune import (
    AutotuneResult,
    RankedCandidate,
    autotune,
    candidate_grid,
    choose_strategy,
    rank_strategies,
)
from .cache import CompiledPlan, PlanCache, default_cache
from .probes import ProbeStore, default_probe_store
from .ops import (
    OPS,
    GRAIN_CANDIDATES,
    BFSInputs,
    BFSOp,
    GSANAInputs,
    GSANAOp,
    SpMVInputs,
    SpMVOp,
)
from .registry import (
    KernelRegistry,
    OpSpec,
    capabilities,
    default_registry,
    kernel,
    placement_table,
    register_op,
)
from .moe_op import (
    MoEDispatchInputs,
    MoEDispatchOp,
    moe_dispatch_cost_model,
    moe_dispatch_grid,
    moe_dispatch_reference,
    moe_dispatch_traffic,
)
from .decode import DecodeServer
from .decode_op import (
    MoEDecodeInputs,
    MoEDecodeOp,
    moe_decode_cost_model,
    moe_decode_reference,
    moe_decode_traffic,
)
from .request import Request
from .wire import (
    WIRE_VERSION,
    SegmentTable,
    WireError,
    canonical_bytes,
    collect_blob_digests,
    content_digest,
    decode_value,
    encode_value,
)
from .runner import (
    build_plan,
    compile_plan,
    execute,
    resolve_op,
    resolve_strategy,
    run,
    run_plan,
    run_request,
    single_call,
)
from .service import (
    AdmissionError,
    EngineService,
    ServiceFuture,
    ServiceRequest,
    ServiceResponse,
    ServiceStats,
    ServiceStopped,
    ServiceTimeout,
)
from .substrate import (
    LocalSubstrate,
    MeshSubstrate,
    PallasSubstrate,
    Substrate,
    get_substrate,
    list_substrates,
    register_substrate,
    substrate_for_mesh,
)

__all__ = [
    "AdmissionError", "AutotuneResult", "BFSInputs", "BFSOp", "CompiledPlan",
    "DecodeServer",
    "EngineService", "ExecutionPlan", "GRAIN_CANDIDATES", "GSANAInputs",
    "GSANAOp", "KernelRegistry", "LocalSubstrate", "MeshSubstrate",
    "MigratoryOp", "MoEDecodeInputs", "MoEDecodeOp",
    "MoEDispatchInputs", "MoEDispatchOp", "OPS", "OpSpec",
    "OpNotSupportedError", "PallasSubstrate",
    "PlanCache", "ProbeStore",
    "RankedCandidate", "Request",
    "RunReport", "SegmentTable", "ServiceFuture", "ServiceRequest",
    "ServiceResponse",
    "ServiceStats", "ServiceStopped", "ServiceTimeout",
    "SpMVInputs", "SpMVOp", "Substrate",
    "WIRE_VERSION", "WireError",
    "args_signature", "autotune", "build_plan", "candidate_grid",
    "canonical_bytes", "collect_blob_digests", "content_digest",
    "capabilities", "choose_strategy", "compile_plan", "decode_value",
    "default_cache",
    "default_probe_store", "default_registry", "encode_value", "execute",
    "get_substrate",
    "kernel", "list_substrates",
    "moe_decode_cost_model", "moe_decode_reference", "moe_decode_traffic",
    "moe_dispatch_cost_model",
    "moe_dispatch_grid", "moe_dispatch_reference", "moe_dispatch_traffic",
    "placement_table", "plan_key", "rank_strategies", "register_op",
    "register_substrate",
    "resolve_op", "resolve_strategy", "run", "run_plan", "run_request",
    "single_call",
    "strategy_dict", "substrate_for_mesh",
]
