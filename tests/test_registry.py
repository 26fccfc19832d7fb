"""Kernel registry: op x substrate completeness matrix, capabilities
introspection, OpSpec-driven dispatch, and legacy-shim delegation.

ISSUE 4 acceptance: every ``(op, substrate)`` pair either resolves a kernel
(with local/mesh bit-identical parity, pinned in the subprocess test below
for the new ``moe_dispatch`` op; engine parity for the original three lives
in test_engine.py) or raises ``OpNotSupportedError`` cleanly — including
``moe_dispatch``, which registers without touching any Substrate subclass.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Comm, MigratoryStrategy, cost_model_for, partition_ell
from repro.engine import (
    OPS,
    KernelRegistry,
    MoEDispatchInputs,
    OpNotSupportedError,
    OpSpec,
    SpMVInputs,
    capabilities,
    candidate_grid,
    get_substrate,
    list_substrates,
    run,
)
from repro.sparse import laplacian_2d

ALL_OPS = ("spmv", "bfs", "gsana", "moe_dispatch")
ALL_SUBSTRATES = ("local", "mesh", "pallas")


# -- completeness matrix -------------------------------------------------------


@pytest.mark.parametrize("op_name", ALL_OPS)
@pytest.mark.parametrize("sub_name", ALL_SUBSTRATES)
def test_every_pair_resolves_or_raises_cleanly(op_name, sub_name):
    """The matrix: kernel lookup either yields a callable or raises
    OpNotSupportedError — never KeyError, never AttributeError."""
    sub = get_substrate(sub_name)
    if capabilities()[op_name][sub_name]:
        kern = sub.kernel(op_name)
        assert callable(kern)
        assert sub.supports(op_name)
    else:
        assert not sub.supports(op_name)
        with pytest.raises(OpNotSupportedError):
            sub.kernel(op_name)


def test_capabilities_table_shape():
    """Rows = every registered op, columns = every registered substrate; the
    known support facts hold (pallas runs gsana only).
    Compared over the three core substrates — importing ``repro.cluster``
    anywhere in the session legitimately adds a ``cluster`` column (its
    cells mirror the workers' kind, ``local`` when no cluster is active)."""
    table = capabilities()
    assert set(ALL_OPS) <= set(table)
    for op_name, row in table.items():
        assert set(row) == set(list_substrates())

    def core(op_name):
        return {k: table[op_name][k] for k in ("local", "mesh", "pallas")}

    assert core("spmv") == {"local": True, "mesh": True, "pallas": False}
    assert core("bfs") == {"local": True, "mesh": True, "pallas": False}
    assert core("gsana") == {"local": True, "mesh": True, "pallas": True}
    assert core("moe_dispatch") == {"local": True, "mesh": True, "pallas": False}
    if "cluster" in list_substrates():
        assert table["spmv"]["cluster"] is True  # workers serve local kernels


def test_capabilities_agrees_with_kernel_table():
    """The exact drift check CI runs (one implementation, not a test-local
    copy): no unservable op, no unreachable kernel, table == resolution."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.capabilities_check import check

    assert check() == []


# -- OpSpec-driven dispatch ----------------------------------------------------


def test_ops_view_is_live_and_registry_backed():
    """The legacy OPS mapping reflects the registry, including ops
    registered after the engine was imported (moe_dispatch)."""
    assert set(ALL_OPS) <= set(OPS)
    assert OPS["spmv"]().name == "spmv"
    assert OPS["moe_dispatch"]().name == "moe_dispatch"
    assert "no_such_op" not in OPS
    with pytest.raises(KeyError):
        OPS["no_such_op"]


def test_unknown_op_and_duplicate_registration():
    with pytest.raises(ValueError, match="unknown op"):
        run("hyetograph", None, None, "local")
    reg = KernelRegistry()
    reg.register_kernel("x", "local", lambda sub: None)
    with pytest.raises(ValueError, match="already registered"):
        reg.register_kernel("x", "local", lambda sub: None)
    reg.register_kernel("x", "local", lambda sub: 42, replace=True)
    assert reg.resolve_kernel("x", "local")(None) == 42
    spec = OpSpec(name="x", factory=object)
    reg.register_op(spec)
    with pytest.raises(ValueError, match="already registered"):
        reg.register_op(spec)


def test_opspec_grid_drives_autotuner():
    """candidate_grid comes from the registered OpSpec: SpMV sweeps grains,
    BFS/GSANA use the default cross product, moe_dispatch varies only S2."""
    assert len(candidate_grid("spmv")) == 2 * 2 * 2 * 2 * 4
    assert len(candidate_grid("bfs")) == 2 * 2 * 2 * 2
    moe = candidate_grid("moe_dispatch")
    assert len(moe) == 2
    assert {st.comm for st in moe} == {Comm.MIGRATE, Comm.REMOTE_WRITE}


def test_opspec_cost_model_registered_into_core():
    """Registering an OpSpec with a cost_model makes core.cost serve it —
    moe_dispatch is autotunable through the same lookup as the paper ops."""
    rng = np.random.default_rng(0)
    inputs = MoEDispatchInputs(
        x=jnp.asarray(rng.standard_normal((64, 16)).astype(np.float32)),
        router=jnp.asarray(rng.standard_normal((16, 16)).astype(np.float32)),
    )
    model = cost_model_for("moe_dispatch", inputs)
    est = model(MigratoryStrategy())
    assert est.traffic_bytes >= 0
    assert "dispatch_mode" in est.detail


# -- legacy shims (removed with the Request redesign) --------------------------


def test_legacy_method_shims_are_gone():
    """The pre-registry per-op methods (``substrate.spmv(...)``) were
    deleted — kernels resolve only through the registry, and a missing
    registration is a typed capability error."""
    sub = get_substrate("local")
    for legacy in ("spmv", "bfs", "gsana"):
        assert not hasattr(sub, legacy), f"legacy shim {legacy} resurfaced"
    # the registry path still serves the op
    a = laplacian_2d(8)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(64).astype(np.float32))
    inputs = SpMVInputs(partition_ell(a, 8), x)
    y_kern = sub.kernel("spmv")(inputs.a, x, strategy=MigratoryStrategy())
    assert np.asarray(y_kern).size == x.size
    with pytest.raises(OpNotSupportedError):
        get_substrate("pallas").kernel("moe_dispatch")


# -- moe_dispatch local/mesh parity (subprocess, 8 forced host devices) --------

MOE_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax.numpy as jnp
from repro.core import Comm, MigratoryStrategy
from repro.engine import MoEDispatchInputs, run

rng = np.random.default_rng(1)
# divisible (ep modes) and non-divisible (tp fallback) expert/nodelet shapes
for (T, D, E, P) in [(128, 32, 16, 8), (256, 48, 8, 4), (120, 16, 6, 4)]:
    mi = MoEDispatchInputs(
        x=jnp.asarray(rng.standard_normal((T, D)).astype(np.float32)),
        router=jnp.asarray(rng.standard_normal((D, E)).astype(np.float32)),
        nodelets=P)
    for comm in (Comm.MIGRATE, Comm.REMOTE_WRITE):
        st = MigratoryStrategy(comm=comm)
        yl, rl = run("moe_dispatch", mi, st, "local")
        ym, rm = run("moe_dispatch", mi, st, "mesh")
        assert np.array_equal(np.asarray(yl), np.asarray(ym)), (T, E, P, comm)
        assert rl.traffic.total_bytes == rm.traffic.total_bytes
        assert rl.metrics["dispatch_mode"] == rm.metrics["dispatch_mode"]
print("MOE-PARITY-OK")
"""


@pytest.mark.slow
def test_moe_local_mesh_parity_subprocess():
    """ISSUE 4 acceptance: the fourth op's local and mesh kernels are
    bit-identical across push/pull/tp modes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", MOE_PARITY_SCRIPT], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "MOE-PARITY-OK" in r.stdout


def test_renamed_subclass_inherits_parent_kernels():
    """A subclass that only renames itself keeps its parent's kernels (the
    pre-registry subclassing contract): substrate_kind walks the MRO to the
    nearest class with registered kernels; explicit kind= still wins."""
    from repro.engine import LocalSubstrate

    class FastLocal(LocalSubstrate):
        name = "fast_local"

    sub = FastLocal()
    assert sub.substrate_kind == "local"
    assert sub.supports("spmv") and sub.supports("moe_dispatch")
    assert callable(sub.kernel("bfs"))

    class PinnedKind(LocalSubstrate):
        name = "pinned"
        kind = "pallas"

    assert PinnedKind().substrate_kind == "pallas"
    assert not PinnedKind().supports("moe_dispatch")  # pallas has no moe kernel
    assert PinnedKind().supports("gsana")  # ("gsana", "pallas") registered


def test_one_device_substrates_take_one_slot_on_an_accelerator(monkeypatch):
    """``workers="auto"`` sizes the pool from placement slots: host cores on
    the CPU backend, but one slot on a chip, not a thread per host core all
    driving the same device."""
    import jax

    from repro.engine.substrate import LocalSubstrate, PallasSubstrate

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert LocalSubstrate().placement_slots() == 1
    assert PallasSubstrate().placement_slots() == 1
