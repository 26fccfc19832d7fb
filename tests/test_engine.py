"""Engine: MigratoryOp/Substrate/RunReport — substrate parity, traffic
accounting parity with the legacy per-algorithm functions, and the report
schema."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Comm,
    Layout,
    MigratoryStrategy,
    Scheme,
    bfs_traffic,
    bucketize,
    ceil_div,
    effective_bandwidth,
    gather_result,
    generate_alignment_pair,
    partition_ell,
    pick_grid,
    plan_stats,
    layout_hcb,
    round_up,
    spmv_bytes_moved,
    spmv_traffic,
)
from repro.engine import (
    BFSInputs,
    BFSOp,
    GSANAInputs,
    GSANAOp,
    RunReport,
    SpMVInputs,
    SpMVOp,
    get_substrate,
    list_substrates,
    register_substrate,
    run,
)
from repro.sparse import (
    edges_to_csr,
    erdos_renyi_edges,
    laplacian_2d,
    partition_graph,
    spmv_csr_ref,
)


# -- shared small problems -----------------------------------------------------


@pytest.fixture(scope="module")
def spmv_problem():
    a = laplacian_2d(12)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(144).astype(np.float32))
    return a, SpMVInputs(partition_ell(a, 8), x)


@pytest.fixture(scope="module")
def bfs_problem():
    g = edges_to_csr(erdos_renyi_edges(8, 6, seed=2), 256)
    return BFSInputs(partition_graph(g, 8), 3)


@pytest.fixture(scope="module")
def gsana_problem():
    vs1, vs2, pi = generate_alignment_pair(384, seed=11)
    grid = pick_grid(384, 32)
    cap = max(bucketize(vs1, grid).cap, bucketize(vs2, grid).cap)
    return GSANAInputs(
        vs1, vs2, bucketize(vs1, grid, cap=cap), bucketize(vs2, grid, cap=cap),
        ground_truth=pi,
    )


# -- util ----------------------------------------------------------------------


def test_ceil_div_round_up():
    assert ceil_div(0, 4) == 0
    assert ceil_div(1, 4) == 1
    assert ceil_div(8, 4) == 2
    assert ceil_div(9, 4) == 3
    assert round_up(0, 8) == 0
    assert round_up(1, 8) == 8
    assert round_up(16, 8) == 16
    # the quadruple-negation expression it replaced in partition_ell
    for n, p, pad in [(144, 8, 1), (37, 8, 4), (1000, 64, 8), (5, 3, 2)]:
        assert round_up(ceil_div(n, p), pad) == -(-(-(-n // p)) // pad) * pad


# -- engine.run on the local substrate -----------------------------------------


@pytest.mark.parametrize("replicate", [True, False])
def test_spmv_local_matches_ref_and_legacy_traffic(spmv_problem, replicate):
    a, inputs = spmv_problem
    st = MigratoryStrategy(replicate_x=replicate)
    y, report = run(SpMVOp(), inputs, st, "local")
    np.testing.assert_allclose(
        np.asarray(gather_result(y, 144)), np.asarray(spmv_csr_ref(a, inputs.x)),
        atol=1e-4,
    )
    legacy = spmv_traffic(inputs.a, st)
    assert report.traffic.migrations == legacy.migrations
    assert report.traffic.remote_writes == legacy.remote_writes
    # effective bandwidth consistent with the legacy formula at this timing
    assert report.effective_gbps * 1e9 == pytest.approx(
        effective_bandwidth(inputs.a, 144, report.seconds), rel=1e-6
    )


@pytest.mark.parametrize("comm", [Comm.MIGRATE, Comm.REMOTE_WRITE])
def test_bfs_local_matches_legacy_traffic(bfs_problem, comm):
    st = MigratoryStrategy(comm=comm)
    parents, report = run(BFSOp(), bfs_problem, st, "local")
    legacy = bfs_traffic(bfs_problem.g, bfs_problem.root, st)
    assert report.traffic.migrations == legacy.traffic.migrations
    assert report.traffic.remote_writes == legacy.traffic.remote_writes
    assert report.metrics["rounds"] == legacy.rounds
    assert report.metrics["edges_traversed"] == legacy.edges_traversed
    assert report.metrics["reached"] == int((np.asarray(parents) >= 0).sum())


# -- derived-stats memo: keyed on the data a stat reads --------------------------


def _counting(monkeypatch, name: str) -> list:
    """Wrap ``repro.engine.ops.<name>`` so each call appends to the list."""
    import repro.engine.ops as ops_mod

    calls, fn = [], getattr(ops_mod, name)

    def wrapper(*args, **kwargs):
        calls.append(args)
        return fn(*args, **kwargs)

    monkeypatch.setattr(ops_mod, name, wrapper)
    return calls


SPMV_STATS = {
    "traffic_replicated_x": ("spmv_traffic", MigratoryStrategy(replicate_x=True)),
    "traffic_striped_x": ("spmv_traffic", MigratoryStrategy(replicate_x=False)),
    "bytes_moved": ("spmv_bytes_moved", MigratoryStrategy()),
}


@pytest.mark.parametrize("case", sorted(SPMV_STATS))
def test_spmv_derived_stats_are_keyed_on_the_matrix(monkeypatch, case):
    """Two ``SpMVInputs`` over one matrix with different ``x`` compute a
    stat once; a second matrix of the same shape computes it again."""
    name, st = SPMV_STATS[case]
    calls = _counting(monkeypatch, name)
    op, sub = SpMVOp(), get_substrate("local")

    def stat(a, x):
        plan = op.plan(SpMVInputs(a, x), st, sub)
        return op.traffic(plan) if name == "spmv_traffic" else op.bytes_moved(plan)

    a, b = (partition_ell(laplacian_2d(11), 8) for _ in range(2))
    x = np.random.default_rng(1).standard_normal(121).astype(np.float32)
    first = stat(a, jnp.asarray(x))
    assert stat(a, jnp.asarray(2 * x)) == first and len(calls) == 1
    expected = spmv_traffic(a, st) if name == "spmv_traffic" else spmv_bytes_moved(a, 121)
    assert first == expected
    assert stat(b, jnp.asarray(x)) == expected and len(calls) == 2


def test_bfs_replay_is_keyed_on_the_graph_and_root(monkeypatch):
    """One graph at one root replays once, whatever inputs object (or
    ``max_rounds``, which the replay does not read) wraps it; another root
    replays again."""
    calls = _counting(monkeypatch, "bfs_traffic")
    op, sub, st = BFSOp(), get_substrate("local"), MigratoryStrategy()
    g = partition_graph(edges_to_csr(erdos_renyi_edges(8, 6, seed=5), 256), 8)

    def replay(inputs):
        return op._stats(op.plan(inputs, st, sub))

    first = replay(BFSInputs(g, 3))
    assert replay(BFSInputs(g, 3)) is first
    assert replay(BFSInputs(g, 3, max_rounds=2)) is first and len(calls) == 1
    assert first == bfs_traffic(g, 3, st)
    other = replay(BFSInputs(g, 7))
    assert len(calls) == 2 and other == bfs_traffic(g, 7, st)


def test_gsana_local_matches_legacy_plan_stats(gsana_problem):
    st = MigratoryStrategy(layout=Layout.HCB, scheme=Scheme.PAIR)
    (cand, score), report = run(GSANAOp(), gsana_problem, st, "local")
    assert report.metrics["recall_at_k"] > 0.9
    i = gsana_problem
    legacy = plan_stats(
        i.vs1, i.vs2, i.b1, i.b2, layout_hcb(i.b1, i.b2, i.nodelets),
        Scheme.PAIR, i.nodelets, threads_per_nodelet=i.threads_per_nodelet,
    )
    assert report.traffic.migrations == legacy.traffic.migrations
    assert report.metrics["model_makespan"] == legacy.makespan
    assert report.metrics["total_comparisons"] == legacy.total_comparisons


def test_run_by_op_name(spmv_problem):
    _, inputs = spmv_problem
    y, report = run("spmv", inputs, MigratoryStrategy(), "local")
    assert report.op == "spmv" and report.substrate == "local"


# -- pallas substrate (GSANA through kernels/topk_sim) -----------------------


def test_gsana_pallas_matches_local(gsana_problem):
    st = MigratoryStrategy(scheme=Scheme.PAIR)
    (c_l, s_l), _ = run(GSANAOp(), gsana_problem, st, "local")
    (c_p, s_p), _ = run(GSANAOp(), gsana_problem, st, "pallas")
    fin = np.isfinite(np.asarray(s_l))
    np.testing.assert_allclose(
        np.asarray(s_l)[fin], np.asarray(s_p)[fin], atol=1e-5
    )


# -- registry + report schema --------------------------------------------------


def test_substrate_registry():
    assert {"local", "mesh", "pallas"} <= set(list_substrates())
    with pytest.raises(ValueError):
        get_substrate("no-such-substrate")
    from repro.engine.substrate import _REGISTRY

    register_substrate("local2", type(get_substrate("local")))
    try:
        assert "local2" in list_substrates()
    finally:
        _REGISTRY.pop("local2", None)


def test_report_schema_roundtrip(bfs_problem):
    _, report = run(BFSOp(), bfs_problem, MigratoryStrategy(), "local")
    d = json.loads(report.to_json())
    for key in (
        "op", "substrate", "seconds", "us_per_call", "migrations",
        "remote_writes", "traffic_bytes", "bytes_moved", "effective_gbps",
        "strategy_comm", "strategy_replicate_x", "strategy_layout",
        "strategy_scheme", "mteps", "rounds", "cache_hit", "compile_seconds",
    ):
        assert key in d, key
    assert d["op"] == "bfs"
    assert d["strategy_comm"] == "remote_write"
    assert isinstance(report, RunReport)


def test_benchmark_rows_use_unified_schema(spmv_problem):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.util import emit_report

    _, inputs = spmv_problem
    _, report = run(SpMVOp(), inputs, MigratoryStrategy(), "local")
    row = emit_report("bench_x", "case_y", report, extra_key=1)
    assert row["bench"] == "bench_x" and row["case"] == "case_y"
    assert row["op"] == "spmv" and row["extra_key"] == 1
    assert "effective_gbps" in row and "migrations" in row


# -- local vs mesh parity (subprocess, 8 forced host devices) ------------------

PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax.numpy as jnp
from repro.core import Comm, MigratoryStrategy, Scheme, bucketize, \
    generate_alignment_pair, partition_ell, pick_grid
from repro.engine import (BFSInputs, BFSOp, GSANAInputs, GSANAOp, SpMVInputs,
                          SpMVOp, run)
from repro.sparse import edges_to_csr, erdos_renyi_edges, laplacian_2d, \
    partition_graph, skewed_matrix

a = laplacian_2d(16)
x = jnp.asarray(np.random.default_rng(0).standard_normal(256).astype(np.float32))
si = SpMVInputs(partition_ell(a, 8), x)
# hub rows split into owner-local pieces, folded back by both substrates
sh = SpMVInputs(partition_ell(skewed_matrix(256, 6.0, 120, seed=3), 8), x)
assert sh.a.row_of is not None
g = edges_to_csr(erdos_renyi_edges(9, 8, seed=1), 512)
bi = BFSInputs(partition_graph(g, 8), 3)
vs1, vs2, pi = generate_alignment_pair(384, seed=11)
grid = pick_grid(384, 32)
cap = max(bucketize(vs1, grid).cap, bucketize(vs2, grid).cap)
gi = GSANAInputs(vs1, vs2, bucketize(vs1, grid, cap=cap),
                 bucketize(vs2, grid, cap=cap))

# all four (replicate_x, comm) strategy combinations, all three ops
for replicate in (True, False):
    for comm in (Comm.MIGRATE, Comm.REMOTE_WRITE):
        st = MigratoryStrategy(replicate_x=replicate, comm=comm)
        yl, rl = run(SpMVOp(), si, st, "local")
        ym, rm = run(SpMVOp(), si, st, "mesh")
        assert np.array_equal(np.asarray(yl), np.asarray(ym)), ("spmv", replicate, comm)
        assert rl.traffic.migrations == rm.traffic.migrations
        hl, _ = run(SpMVOp(), sh, st, "local")
        hm, _ = run(SpMVOp(), sh, st, "mesh")
        assert np.array_equal(np.asarray(hl), np.asarray(hm)), ("hub spmv", replicate, comm)

        pl, _ = run(BFSOp(), bi, st, "local")
        pm, _ = run(BFSOp(), bi, st, "mesh")
        assert np.array_equal(np.asarray(pl), np.asarray(pm)), ("bfs", replicate, comm)

for scheme in (Scheme.ALL, Scheme.PAIR):
    st = MigratoryStrategy(scheme=scheme)
    (cl, sl), _ = run(GSANAOp(), gi, st, "local")
    (cm, sm), _ = run(GSANAOp(), gi, st, "mesh")
    assert np.array_equal(np.asarray(cl), np.asarray(cm)), ("gsana cand", scheme)
    assert np.array_equal(np.asarray(sl), np.asarray(sm)), ("gsana score", scheme)
print("ENGINE-PARITY-OK")
"""


@pytest.mark.slow
def test_local_mesh_parity_subprocess():
    """ISSUE acceptance: local and mesh substrates produce bit-identical
    results for SpMV (the Laplacian and a matrix whose hub rows are split)
    /BFS/GSANA across the strategy grid."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", PARITY_SCRIPT], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "ENGINE-PARITY-OK" in r.stdout
