"""Substrates: where a MigratoryOp's plan executes (DESIGN.md §1, §1e).

Three built-in backends, mirroring the realizations the paper compares:

- ``local``  — single-device vmap emulation with the distributed semantics
  (the correctness oracle; what the Emu sees as one node).
- ``mesh``   — ``shard_map`` over a 1-D nodelet axis (the Chick's nodelets
  as TPU shards): replication, all_gather pulls, all_to_all pushes.
- ``pallas`` — routes GSANA's PAIR similarity to the Pallas
  ``kernels/topk_sim`` kernel; it registers no SpMV or BFS kernel.

A substrate no longer implements one method per op. Its per-op entry points
are *kernels* registered against its ``substrate_kind`` in the
:mod:`~repro.engine.registry` (``@kernel("spmv", "mesh")`` below);
``Substrate.kernel(op_name)`` resolves them, and a missing registration
raises :class:`~repro.engine.api.OpNotSupportedError`. New backends
register with :func:`register_substrate` and gain every op whose kernels
they register; new ops (e.g. ``moe_dispatch``, engine/moe_op.py) register
kernels against existing kinds without touching the classes here. The old
``substrate.spmv(...)``-style per-op methods are gone (removed with the
:class:`~repro.engine.request.Request` redesign, DESIGN.md §1g) — resolve
kernels with ``substrate.kernel(op_name)``.
"""
from __future__ import annotations

import functools
import os
from typing import Callable

import jax

from ..core.bfs import bfs_local, bfs_mesh
from ..core.gsana import NEG, compute_similarity, compute_similarity_mesh
from ..core.spmv import spmv_local, spmv_mesh
from ..core.strategies import MigratoryStrategy, Scheme
from .api import OpNotSupportedError
from .registry import default_registry, kernel


class Substrate:
    """Execution backend for MigratoryOps.

    Identity: ``name`` labels the instance in reports/registries;
    ``substrate_kind`` (defaults to ``name``) is the registry key kernels
    are looked up under — a subclass specializing behavior but reusing a
    parent's kernels may pin ``kind`` to the parent's.

    Placement (the EngineService executor pool, DESIGN.md §1d): a substrate
    advertises how many *independent execution channels* it can drive
    (:meth:`placement_slots` — the Emu analogue is nodelets, the
    memory-channels study's is channels), a :attr:`placement_policy` for
    routing plan-key groups onto pool workers, and an optional per-slot
    *variant* (:meth:`placement_variant`) — a substrate instance whose
    executions are disjoint from other slots' (e.g. a mesh device window),
    so independent groups placed on different slots genuinely run in
    parallel instead of contending for the same devices.
    """

    name: str = "abstract"
    kind: "str | None" = None
    #: "spread": groups round-robin over pool workers and idle workers may
    #: steal queued/straggling work. "affinity": a plan-key group is pinned
    #: to one slot (its compiled executable targets that slot's devices) and
    #: is never stolen.
    placement_policy: str = "spread"
    #: False marks every plan built against this substrate uncompilable by
    #: ``jax.jit`` — its executors do host-side work the tracer cannot see
    #: (e.g. the cluster substrate's socket round trip). The planner flips
    #: ``ExecutionPlan.jit`` off so the plan cache keeps such plans eager.
    jit_plans: bool = True

    def placement_slots(self) -> int:
        """How many pool workers this substrate can keep independently busy.
        The pool sizes itself as ``min(workers, placement_slots())`` when
        asked for ``workers="auto"``."""
        return 1

    def placement_variant(self, slot: int, n_slots: int) -> "Substrate":
        """The substrate instance slot ``slot`` of ``n_slots`` should plan
        against. Default: ``self`` (all slots share one backend). Backends
        that can carve disjoint execution channels (mesh device windows)
        return a variant whose ``cache_fingerprint`` embeds the slot, so the
        slot's compiled plans are keyed — and therefore pinned — to it."""
        del slot, n_slots
        return self

    @property
    def substrate_kind(self) -> str:
        """The registry key kernels are looked up under. Explicit ``kind``
        wins; otherwise the MRO is walked for the nearest class whose own
        ``name`` has kernels registered — so a renamed subclass
        (``class FastLocal(LocalSubstrate): name = "fast_local"``) keeps
        inheriting its parent's kernels, matching the pre-registry
        subclassing contract."""
        if self.kind is not None:
            return self.kind
        kinds = set(default_registry().kernel_kinds())
        for klass in type(self).__mro__:
            own_name = klass.__dict__.get("name")
            if own_name and own_name in kinds:
                return own_name
        return self.name

    def kernel(self, op_name: str) -> Callable:
        """Resolve this backend's kernel for ``op_name`` (bound to self),
        traced under ``jax.named_scope("<op>.<substrate>")`` so its XLA ops
        carry that scope. Raises :class:`OpNotSupportedError` when no kernel
        is registered — capability *is* registry presence."""
        fn = default_registry().resolve_kernel(op_name, self.substrate_kind)
        scope = f"{op_name}.{self.name}"

        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(scope):
                return fn(self, *args, **kwargs)

        return scoped

    def supports(self, op_name: str) -> bool:
        return default_registry().has_kernel(op_name, self.substrate_kind)

    def cache_fingerprint(self) -> tuple:
        """Hashable identity for the compiled-plan cache: two substrate
        instances with equal fingerprints are interchangeable executors."""
        return (self.name,)


def _one_device_slots() -> int:
    """Executor slots for a substrate that runs on one device. On the CPU
    backend, executions from different workers overlap in XLA's intra-op
    pool, so size to the host's cores; an accelerator is one device, so
    one slot."""
    if jax.default_backend() == "cpu":
        return max(1, os.cpu_count() or 1)
    return 1


class LocalSubstrate(Substrate):
    """Single-device emulation — identical semantics to the mesh paths."""

    name = "local"

    def placement_slots(self) -> int:
        return _one_device_slots()


class MeshSubstrate(Substrate):
    """``shard_map`` over a nodelet axis. With no explicit mesh, builds a
    1-D nodelet mesh matching the input's partition count (requires that
    many jax devices).

    ``device_window`` is the executor pool's per-slot carving: a variant
    bound to a window resolves ``mesh_for(p)`` over those devices (when
    they suffice), so plans placed on different slots execute on disjoint
    devices — the paper's independent-nodelet parallelism realized as
    device-affine workers. The window is part of the cache fingerprint:
    a slot's compiled executables are keyed to its devices.
    """

    name = "mesh"
    placement_policy = "affinity"

    def __init__(
        self,
        mesh: jax.sharding.Mesh | None = None,
        axis_name: str = "nodelet",
        device_window: "tuple | None" = None,
    ):
        self.mesh = mesh
        self.axis_name = axis_name
        self.device_window = tuple(device_window) if device_window else None

    def cache_fingerprint(self) -> tuple:
        mesh_id = None
        if self.mesh is not None:
            mesh_id = (
                tuple(self.mesh.shape.items()),
                tuple(str(d) for d in self.mesh.devices.flat),
            )
        window_id = (
            tuple(str(d) for d in self.device_window) if self.device_window else None
        )
        return (self.name, self.axis_name, mesh_id, window_id)

    def placement_slots(self) -> int:
        """Independent channels = devices: an explicit mesh is one committed
        channel set; otherwise every host device is a potential window."""
        if self.mesh is not None:
            return 1
        return max(1, len(jax.devices()))

    def placement_variant(self, slot: int, n_slots: int) -> "MeshSubstrate":
        """Slot ``slot``'s device window: the ``slot``-th of ``n_slots``
        equal contiguous device blocks. With an explicit mesh (committed
        devices) or a single slot there is nothing to carve."""
        if self.mesh is not None or n_slots <= 1:
            return self
        devices = jax.devices()
        width = len(devices) // n_slots
        if width < 1:
            return self  # fewer devices than slots: all slots share everything
        lo = (slot % n_slots) * width
        return MeshSubstrate(
            None, self.axis_name, device_window=tuple(devices[lo : lo + width])
        )

    def mesh_for(self, p: int) -> jax.sharding.Mesh:
        """The mesh kernels run on: the explicit one; else the slot's device
        window when it is wide enough; else a 1-D nodelet mesh of ``p`` host
        devices. Public so out-of-tree kernels (e.g. engine/moe_op.py)
        resolve meshes the same way the built-ins do."""
        if self.mesh is not None:
            return self.mesh
        if self.device_window is not None:
            if p <= len(self.device_window):
                return jax.make_mesh(
                    (p,), (self.axis_name,),
                    axis_types=(jax.sharding.AxisType.Auto,),
                    devices=self.device_window[:p],
                )
            # the plan spans more nodelets than this slot's window: fall
            # back to the global device mesh, audibly — such plans share
            # devices across slots (no disjoint-channel parallelism) and,
            # because the window is part of the cache fingerprint, compile
            # once per slot they land on. Partition inputs to <= n_dev //
            # workers nodelets to stay inside the windows.
            import warnings

            warnings.warn(
                f"plan needs {p} nodelets but the placement window has "
                f"{len(self.device_window)} device(s); executing on the "
                "global device mesh — pool slots will NOT be disjoint for "
                "this plan",
                stacklevel=2,
            )
        from ..launch.mesh import make_nodelet_mesh

        if len(jax.devices()) < p:
            raise OpNotSupportedError(
                f"mesh substrate needs {p} devices for {p} nodelets, "
                f"have {len(jax.devices())} (pass an explicit mesh or use 'local')"
            )
        return make_nodelet_mesh(p)

    # pre-registry spelling, kept for out-of-tree callers
    _mesh_for = mesh_for


class PallasSubstrate(Substrate):
    """Routes GSANA's PAIR similarity to the Pallas ``kernels/topk_sim``
    kernel. ``interpret=None`` (default) resolves from the backend — native
    lowering on TPU/GPU, interpret mode elsewhere
    (:mod:`repro.kernels.runtime`); an explicit bool pins it. The resolved
    value is part of the cache fingerprint, so plans compiled under one mode
    never serve the other."""

    name = "pallas"

    def __init__(self, interpret: "bool | None" = None):
        from ..kernels.runtime import resolve_interpret

        self.interpret = resolve_interpret(interpret)

    def cache_fingerprint(self) -> tuple:
        return (self.name, self.interpret)

    def placement_slots(self) -> int:
        return _one_device_slots()


# -- built-in kernels ----------------------------------------------------------
# The algorithm code lives in repro.core.*; these adapters bind it to a
# backend. Registered here (not on the classes) so capability is data.


@kernel("spmv", "local")
def _spmv_local(sub: Substrate, a, x, *, strategy):
    return spmv_local(a, x, strategy)


@kernel("bfs", "local")
def _bfs_local(sub: Substrate, g, root, *, strategy, max_rounds=None):
    return bfs_local(g, root, strategy, max_rounds)


@kernel("gsana", "local")
def _gsana_local(sub: Substrate, vs1, vs2, b1, b2, k, *, strategy):
    return compute_similarity(vs1, vs2, b1, b2, k, strategy.scheme)


@kernel("spmv", "mesh")
def _spmv_mesh(sub: MeshSubstrate, a, x, *, strategy):
    return spmv_mesh(a, x, strategy, sub.mesh_for(a.P), sub.axis_name)


@kernel("bfs", "mesh")
def _bfs_mesh(sub: MeshSubstrate, g, root, *, strategy, max_rounds=None):
    return bfs_mesh(
        g, root, strategy, max_rounds, mesh=sub.mesh_for(g.P), axis_name=sub.axis_name,
    )


@kernel("gsana", "mesh")
def _gsana_mesh(sub: MeshSubstrate, vs1, vs2, b1, b2, k, *, strategy):
    # task distribution over however many devices the host mesh offers
    mesh = sub.mesh
    if mesh is None:
        from ..launch.mesh import make_nodelet_mesh

        n_dev = len(jax.devices())
        if n_dev < 2:
            raise OpNotSupportedError(
                "mesh substrate needs >1 device to distribute gsana tasks "
                "(pass an explicit mesh or use 'local')"
            )
        mesh = make_nodelet_mesh(n_dev)
    return compute_similarity_mesh(
        vs1, vs2, b1, b2, k, strategy.scheme, mesh=mesh, axis_name=sub.axis_name,
    )


@kernel("gsana", "pallas")
def _gsana_pallas(sub: PallasSubstrate, vs1, vs2, b1, b2, k, *, strategy):
    import jax.numpy as jnp
    import numpy as np

    from ..core.gsana import DEFAULT_VOCAB, _merge_pair_topk, _scatter_vertex_major  # noqa: PLC0415
    from ..core.gsana_data import neighbor_buckets
    from ..kernels.topk_sim.ops import topk_sim_pairs

    if strategy.scheme != Scheme.PAIR:
        raise OpNotSupportedError(
            "pallas gsana kernel implements the PAIR task shape only"
        )
    grid2 = b2.grid * b2.grid
    nb = neighbor_buckets(b2.grid)
    pair_b2 = jnp.asarray(np.repeat(np.arange(grid2), 9))
    pair_b1 = jnp.asarray(nb.reshape(-1))
    scores, u_ids = topk_sim_pairs(
        vs1, vs2, b1, b2, pair_b2, pair_b1,
        vocab=DEFAULT_VOCAB, k=min(k, b1.cap), interpret=sub.interpret,
    )
    scores = jnp.where(jnp.isfinite(scores), scores, NEG)
    cand_b, score_b = _merge_pair_topk(u_ids, scores, grid2, k)
    return _scatter_vertex_major(cand_b, score_b, b2, vs2.n, k)


# -- registry ------------------------------------------------------------------

_REGISTRY: dict[str, Callable[[], Substrate]] = {}


def register_substrate(name: str, factory: Callable[[], Substrate]) -> None:
    _REGISTRY[name] = factory


def list_substrates() -> list[str]:
    return sorted(_REGISTRY)


def get_substrate(substrate: "Substrate | str") -> Substrate:
    """Resolve a substrate instance from a name or pass an instance through."""
    if isinstance(substrate, Substrate):
        return substrate
    try:
        return _REGISTRY[substrate]()
    except KeyError:
        raise ValueError(
            f"unknown substrate {substrate!r}; registered: {list_substrates()}"
        ) from None


def substrate_for_mesh(
    mesh: jax.sharding.Mesh | None, axis_name: str = "nodelet"
) -> Substrate:
    """Legacy-shim resolution: a mesh means the mesh substrate, no mesh means
    local. The one place the old ``mesh=None`` convention is interpreted."""
    if mesh is None:
        return LocalSubstrate()
    return MeshSubstrate(mesh, axis_name)


register_substrate("local", LocalSubstrate)
register_substrate("mesh", MeshSubstrate)
register_substrate("pallas", PallasSubstrate)
