"""The op x substrate kernel registry (DESIGN.md §1e).

The paper's thesis is that migratory-thread programming is a *family* of
strategies for irregular algorithms, not a fixed menu of three. The engine
therefore keeps ops and substrates decoupled: an op contributes an
:class:`OpSpec` (how to build it, how to rank strategies for it), a backend
contributes kernels — concrete ``(op_name, substrate_kind)`` entry points —
and the registry is the only place the two meet. Adding a workload never
edits a substrate class; adding a backend never edits an op:

    from repro.engine.registry import OpSpec, kernel, register_op

    @kernel("moe_dispatch", "local")
    def _moe_local(substrate, x, router, *, strategy, **statics): ...

    @kernel("moe_dispatch", "mesh")
    def _moe_mesh(substrate, x, router, *, strategy, **statics): ...

    register_op(OpSpec(name="moe_dispatch", factory=MoEDispatchOp,
                       inputs_type=MoEDispatchInputs,
                       cost_model=moe_dispatch_cost_model,
                       grid=moe_dispatch_grid))

``Substrate.kernel(op_name)`` resolves through :func:`resolve_kernel`;
absence *is* the capability signal — it raises
:class:`~repro.engine.api.OpNotSupportedError`, so "does this backend run
this op" is a registry lookup, not a method override. The
:func:`capabilities` table is the introspection view CI diff-checks against
the registered kernels (``benchmarks/capabilities_check.py``).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable

from .api import OpNotSupportedError

# A kernel is a plain function: (substrate, *args, **statics) -> result.
# The substrate instance arrives first so kernels can use backend handles
# (mesh_for(), interpret flags) without subclassing anything.
Kernel = Callable[..., Any]


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """Everything the engine needs to serve one op, minus the kernels.

    ``factory`` builds the :class:`~repro.engine.api.MigratoryOp` adapter
    (plan/traffic/bytes_moved/metrics). ``inputs_type`` is the op's input
    dataclass (documentation + introspection). ``cost_model`` is the
    autotuner's analytic factory ``inputs -> (strategy -> CostEstimate)``;
    registering a spec installs it into ``core.cost`` so
    ``cost_model_for(name, inputs)`` serves every registered op from one
    lookup. ``grid`` is a zero-argument callable yielding the op's autotune
    candidate strategies (None: the default S1 x S2 x S3 cross product).
    """

    name: str
    factory: Callable[[], Any]
    inputs_type: "type | None" = None
    cost_model: "Callable[[Any], Any] | None" = None
    grid: "Callable[[], list] | None" = None


class KernelRegistry:
    """Thread-safe ``(op_name, substrate_kind) -> kernel`` table plus the
    op-spec table. One default instance serves the process; tests may build
    private registries."""

    def __init__(self):
        self._lock = threading.RLock()
        self._specs: dict[str, OpSpec] = {}
        self._kernels: dict[tuple[str, str], Kernel] = {}

    # -- ops -------------------------------------------------------------------

    def register_op(self, spec: OpSpec, *, replace: bool = False) -> OpSpec:
        with self._lock:
            if spec.name in self._specs and not replace:
                raise ValueError(f"op {spec.name!r} already registered")
            self._specs[spec.name] = spec
        if spec.cost_model is not None:
            from ..core.cost import register_cost_model

            register_cost_model(spec.name, spec.cost_model)
        return spec

    def op_spec(self, name: str) -> OpSpec:
        with self._lock:
            try:
                return self._specs[name]
            except KeyError:
                raise ValueError(
                    f"unknown op {name!r}; known: {sorted(self._specs)}"
                ) from None

    def ops(self) -> list[str]:
        with self._lock:
            return sorted(self._specs)

    # -- kernels ---------------------------------------------------------------

    def register_kernel(
        self, op_name: str, substrate_kind: str, fn: Kernel, *, replace: bool = False
    ) -> Kernel:
        key = (op_name, substrate_kind)
        with self._lock:
            if key in self._kernels and not replace:
                raise ValueError(f"kernel {key} already registered")
            self._kernels[key] = fn
        return fn

    def resolve_kernel(self, op_name: str, substrate_kind: str) -> Kernel:
        """The dispatch point: missing entry == unsupported capability."""
        with self._lock:
            fn = self._kernels.get((op_name, substrate_kind))
        if fn is None:
            raise OpNotSupportedError(
                f"no kernel registered for op {op_name!r} on substrate "
                f"{substrate_kind!r} (registered kernels for this op: "
                f"{[k for o, k in self.kernels() if o == op_name]})"
            )
        return fn

    def has_kernel(self, op_name: str, substrate_kind: str) -> bool:
        with self._lock:
            return (op_name, substrate_kind) in self._kernels

    def kernels(self) -> list[tuple[str, str]]:
        with self._lock:
            return sorted(self._kernels)

    def kernel_kinds(self) -> list[str]:
        """Every substrate kind any kernel was registered under."""
        with self._lock:
            return sorted({kind for _, kind in self._kernels})


_DEFAULT_REGISTRY = KernelRegistry()


def default_registry() -> KernelRegistry:
    """The process-wide registry every engine entry point dispatches through."""
    return _DEFAULT_REGISTRY


def register_op(spec: OpSpec, *, replace: bool = False) -> OpSpec:
    return _DEFAULT_REGISTRY.register_op(spec, replace=replace)


def kernel(op_name: str, substrate_kind: str, *, replace: bool = False):
    """Decorator: ``@kernel("spmv", "mesh")`` registers the function as the
    mesh backend's SpMV entry point in the default registry."""

    def deco(fn: Kernel) -> Kernel:
        return _DEFAULT_REGISTRY.register_kernel(
            op_name, substrate_kind, fn, replace=replace
        )

    return deco


def capabilities() -> dict[str, dict[str, bool]]:
    """The op x substrate capability table: for every registered op, which
    registered substrates resolve a kernel for it.

    Columns are the *substrate registry's* names (``list_substrates()``),
    resolved through a real instance's ``substrate_kind`` — so the table
    reflects what ``engine.run(op, ..., substrate=name)`` would actually
    dispatch, and CI can diff it against the raw kernel table to catch
    kernels registered under kinds no substrate serves.
    """
    from .substrate import get_substrate, list_substrates

    reg = _DEFAULT_REGISTRY
    table: dict[str, dict[str, bool]] = {}
    kinds = {name: get_substrate(name).substrate_kind for name in list_substrates()}
    for op_name in reg.ops():
        table[op_name] = {
            name: reg.has_kernel(op_name, kind) for name, kind in kinds.items()
        }
    return table


def placement_table() -> dict[str, dict[str, Any]]:
    """The substrate placement view the executor pool routes by: for every
    registered substrate, its kernel-lookup kind, its placement policy
    (``"affinity"`` = plan-key groups pin to one slot, never stolen;
    ``"spread"`` = round-robin + work stealing), and how many independent
    execution slots it can drive on this host (``placement_slots()`` —
    device count on mesh, core count on local/pallas). The
    :class:`~repro.engine.service.EngineService` sizes ``workers="auto"``
    pools from this and benchmark artifacts record it, so a throughput
    number is always interpretable against the channels that produced it.
    """
    from .substrate import get_substrate, list_substrates

    table: dict[str, dict[str, Any]] = {}
    for name in list_substrates():
        sub = get_substrate(name)
        table[name] = {
            "kind": sub.substrate_kind,
            "policy": sub.placement_policy,
            "slots": sub.placement_slots(),
        }
    return table
