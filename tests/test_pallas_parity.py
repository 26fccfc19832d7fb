"""Pallas fast-path parity + block-size autotune axis (ISSUE 7).

Engine-level parity across the strategy x block-size grid: ``("spmv",
"pallas")`` tolerance-pinned (float accumulation order differs per block),
``("bfs", "pallas")`` bit-identical (integer min-scatter). Plus the CSR
stripe variant on skewed rows, the backend-aware interpret default, and
calibrated predicted-seconds ranking over the Pallas grain axis."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Comm, MigratoryStrategy, partition_ell
from repro.engine import (
    PALLAS_BLOCK_CANDIDATES,
    BFSInputs,
    BFSOp,
    SpMVInputs,
    SpMVOp,
    candidate_grid,
    rank_strategies,
    run,
)
from repro.kernels.runtime import default_interpret, resolve_interpret
from repro.kernels.spmv.ops import STRIPE_WASTE_THRESHOLD, spmv
from repro.kernels.spmv.ref import spmv_ell_reference
from repro.kernels.spmv.stripe import build_stripe_plan, spmv_ell_stripes
from repro.machine.machine import DEFAULT_PROFILE
from repro.sparse import (
    edges_to_csr,
    ell_from_csr,
    erdos_renyi_edges,
    laplacian_2d,
    partition_graph,
    skewed_matrix,
    spmv_csr_ref,
)


@pytest.fixture(scope="module")
def spmv_problem():
    a = laplacian_2d(12)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(144).astype(np.float32))
    return SpMVInputs(partition_ell(a, 8), x)


@pytest.fixture(scope="module")
def bfs_problem():
    g = edges_to_csr(erdos_renyi_edges(8, 6, seed=2), 256)
    return BFSInputs(partition_graph(g, 8), 3)


# -- engine parity across the strategy x block-size grid -----------------------


@pytest.mark.parametrize("grain", PALLAS_BLOCK_CANDIDATES)
@pytest.mark.parametrize("comm", [Comm.MIGRATE, Comm.REMOTE_WRITE])
def test_spmv_pallas_parity_across_grid(spmv_problem, grain, comm):
    st = MigratoryStrategy(comm=comm, grain=grain)
    y_local, _ = run(SpMVOp(), spmv_problem, st, "local")
    y_pallas, report = run(SpMVOp(), spmv_problem, st, "pallas")
    np.testing.assert_allclose(
        np.asarray(y_local), np.asarray(y_pallas), rtol=1e-5, atol=1e-5
    )
    assert report.substrate == "pallas"


@pytest.mark.parametrize("grain", PALLAS_BLOCK_CANDIDATES)
def test_bfs_pallas_parity_across_grid(bfs_problem, grain):
    st = MigratoryStrategy(grain=grain)
    p_local, _ = run(BFSOp(), bfs_problem, st, "local")
    p_pallas, _ = run(BFSOp(), bfs_problem, st, "pallas")
    np.testing.assert_array_equal(np.asarray(p_local), np.asarray(p_pallas))


# -- CSR stripe variant on skewed rows -----------------------------------------


@pytest.fixture(scope="module")
def skewed_ell():
    a = skewed_matrix(512, avg_deg=4.0, max_deg=128, seed=9)
    e = ell_from_csr(a)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(512).astype(np.float32))
    return a, e, x


def test_stripe_plan_shapes(skewed_ell):
    _, e, _ = skewed_ell
    plan = build_stripe_plan(e.cols, block_rows=64)
    assert plan.n_rows == e.cols.shape[0] and plan.k_full == e.cols.shape[1]
    covered = sorted(r for b in plan.buckets for r in np.asarray(b.rows).tolist())
    assert covered == list(range(plan.n_rows))  # every row in exactly one stripe
    for b in plan.buckets:
        # stripe widths are powers of two, capped at the full ELL width
        assert b.k == plan.k_full or b.k & max(0, b.k - 1) == 0
    # skewed rows leave the dense ELL mostly padding -> stripes shed it
    assert plan.waste_ratio >= STRIPE_WASTE_THRESHOLD
    assert plan.padded_slots < e.cols.shape[0] * e.cols.shape[1]


def test_stripe_spmv_matches_reference(skewed_ell):
    a, e, x = skewed_ell
    want = np.asarray(spmv_csr_ref(a, x))
    for block_rows in (32, 64, 200):
        got = np.asarray(spmv_ell_stripes(e.cols, e.vals, x, block_rows=block_rows))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_spmv_variant_auto_picks_stripes_when_skewed(skewed_ell):
    a, e, x = skewed_ell
    want = np.asarray(spmv_csr_ref(a, x))
    got = np.asarray(spmv(e.cols, e.vals, x, grain=64, variant="auto"))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # uniform rows stay on the dense ELL kernel; both variants agree there
    u = partition_ell(laplacian_2d(8), 1)
    xu = jnp.asarray(np.random.default_rng(2).standard_normal(64).astype(np.float32))
    assert build_stripe_plan(u.cols[0], block_rows=16).waste_ratio < STRIPE_WASTE_THRESHOLD
    np.testing.assert_allclose(
        np.asarray(spmv(u.cols[0], u.vals[0], xu, grain=16, variant="auto")),
        np.asarray(spmv_ell_reference(u.cols[0], u.vals[0], xu)),
        rtol=1e-5, atol=1e-5,
    )
    with pytest.raises(ValueError, match="variant"):
        spmv(e.cols, e.vals, x, variant="csr5")


# -- backend-aware interpret default -------------------------------------------


def test_default_interpret_is_backend_aware():
    assert default_interpret("tpu") is False
    assert default_interpret("gpu") is False
    assert default_interpret("cpu") is True
    assert resolve_interpret(True) is True and resolve_interpret(False) is False
    # None resolves from the live backend; on the CPU test host that is
    # interpret mode, and PallasSubstrate bakes the resolved value in
    assert resolve_interpret(None) == default_interpret(jax.default_backend())
    from repro.engine import PallasSubstrate

    assert PallasSubstrate().interpret == default_interpret(jax.default_backend())
    assert PallasSubstrate(interpret=False).interpret is False


def test_no_pallas_wrapper_defaults_to_interpret_mode():
    """Every Pallas entry point defaults to ``interpret=None`` (resolved from
    the backend), so a TPU never runs the interpreter unasked — the LM
    layer's flash call included, which passes no ``interpret`` at all."""
    import inspect

    from repro.kernels.bfs.kernel import bfs_expand_pallas
    from repro.kernels.bfs.ops import bfs_expand, bfs_pallas
    from repro.kernels.flash_attention.kernel import flash_attention_folded
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.spmv.kernel import spmv_ell_pallas
    from repro.kernels.spmv.ops import spmv
    from repro.kernels.topk_sim.kernel import topk_sim_pallas
    from repro.kernels.topk_sim.ops import topk_sim_pairs

    for fn in (
        bfs_expand_pallas, bfs_expand, bfs_pallas, flash_attention_folded,
        flash_attention, spmv_ell_pallas, spmv, spmv_ell_stripes,
        topk_sim_pallas, topk_sim_pairs,
    ):
        assert inspect.signature(fn).parameters["interpret"].default is None, fn


# -- calibrated predicted-seconds ranking over the grain axis ------------------


def test_calibrated_ranking_orders_pallas_block_sizes(spmv_problem, bfs_problem):
    """With a calibrated machine file the autotuner ranks the Pallas grid
    in predicted seconds, and every block-size candidate gets its own
    prediction (the substrate-targeted working set varies with grain)."""
    profile = dataclasses.replace(DEFAULT_PROFILE, calibrated=True)
    for op, inputs in ((SpMVOp(), spmv_problem), (BFSOp(), bfs_problem)):
        grid = candidate_grid(op.name, "pallas")
        assert {st.grain for st in grid} == set(PALLAS_BLOCK_CANDIDATES)
        ranked = rank_strategies(op, inputs, grid, substrate="pallas", machine=profile)
        secs = [e.predicted_seconds for e in ranked]
        assert all(s is not None and s > 0 for s in secs)
        assert secs == sorted(secs)
        # the grain axis is visible to the model: per-launch working sets
        # differ across block sizes, so predictions are not all ties
        by_grain = {
            e.strategy.grain: e.detail["substrate_memory"]["pallas"]["bytes_per_launch"]
            for e in ranked
        }
        assert len(set(by_grain.values())) > 1
        # uncalibrated stays bit-identical to traffic-unit ranking
        plain = rank_strategies(op, inputs, grid, substrate="pallas")
        assert all(e.predicted_seconds is None for e in plain)
