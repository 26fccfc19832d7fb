"""Core SpMV: S1 replication strategy — correctness across strategies/grains."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import (
    MigratoryStrategy, effective_bandwidth, gather_result, partition_ell, spmv,
    spmv_traffic, stripe_vector, unstripe_vector,
)
from repro.sparse import CSR, laplacian_2d, skewed_matrix, spmv_csr_ref


def _degrees_0_to_k(k: int) -> np.ndarray:
    """96 x 84 dense matrix whose rows take every degree 0..k, so its ELL
    planes (K = k) hold padding slots (col -1) and empty rows."""
    rng = np.random.default_rng(k)
    d = np.zeros((96, 84), np.float32)
    for r in range(96):
        deg = r % (k + 1)
        d[r, rng.choice(84, deg, replace=False)] = rng.standard_normal(deg)
    return d


MATRICES = {
    "laplacian": lambda: np.asarray(laplacian_2d(12).to_dense()),  # 144 x 144, K = 5
    "deg0to1": lambda: _degrees_0_to_k(1),
    "deg0to5": lambda: _degrees_0_to_k(5),
    "deg0to7": lambda: _degrees_0_to_k(7),
}


@pytest.mark.parametrize("matrix", list(MATRICES))
@pytest.mark.parametrize("replicate", [True, False])
@pytest.mark.parametrize("grain", [1, 4, 5, 16, None])
def test_spmv_strategies_match_ref(replicate, grain, matrix):
    """The local kernel's K-major row chunks against a float64 dense product,
    across strategies and grains: one row per task, a grain that divides
    R_p (12 for the 96-row matrices), a ragged last chunk, a grain above
    R_p, and the dynamic grain."""
    d = MATRICES[matrix]()
    n_rows, n_cols = d.shape
    rng = np.random.default_rng(0)
    x = rng.standard_normal(n_cols).astype(np.float32)
    pe = partition_ell(CSR.from_dense(d), 8)
    st_ = MigratoryStrategy(replicate_x=replicate, grain=grain)
    xin = jnp.asarray(x) if replicate else stripe_vector(jnp.asarray(x), 8)
    y = np.asarray(gather_result(spmv(pe, xin, st_), n_rows))
    ref = d.astype(np.float64) @ x.astype(np.float64)
    # float32 sums of at most K terms: well inside 1e-6 of sum |terms|
    bound = 1e-6 * (np.abs(d).astype(np.float64) @ np.abs(x).astype(np.float64))
    assert np.all(np.abs(y - ref) <= bound)
    assert np.all(y[~d.any(axis=1)] == 0)


def test_replication_eliminates_migrations():
    """Paper §5.1: replication removes per-element cross-nodelet reads."""
    a = laplacian_2d(16)
    pe = partition_ell(a, 8)
    t_rep = spmv_traffic(pe, MigratoryStrategy(replicate_x=True))
    t_str = spmv_traffic(pe, MigratoryStrategy(replicate_x=False))
    assert t_rep.migrations == 0
    assert t_str.migrations > 0


def test_striped_vector_roundtrip():
    x = jnp.arange(37, dtype=jnp.float32)
    xs = stripe_vector(x, 8)
    assert xs.shape == (8, 5)
    assert np.allclose(np.asarray(unstripe_vector(xs, 37)), np.asarray(x))


def test_skewed_matrix_spmv():
    """High-max-degree (Table 3 pathology) still computes correctly."""
    a = skewed_matrix(400, 6.0, 120, seed=3)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(400).astype(np.float32))
    pe = partition_ell(a, 8)
    y = gather_result(spmv(pe, x, MigratoryStrategy()), 400)
    assert np.allclose(np.asarray(y), np.asarray(spmv_csr_ref(a, x)), atol=1e-3)


def test_effective_bandwidth_formula():
    a = laplacian_2d(8)
    pe = partition_ell(a, 4)
    bw = effective_bandwidth(pe, 64, seconds=1.0)
    # nnz*(4+4) + (64+64)*4 bytes
    assert bw == a.nnz * 8 + 128 * 4


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(8, 64),
    p=st.sampled_from([2, 4, 8]),
    density=st.floats(0.05, 0.5),
    replicate=st.booleans(),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_spmv_invariant_to_strategy(n, p, density, replicate, seed):
    """Invariant: the strategy changes communication, never the result."""
    rng = np.random.default_rng(seed)
    d = (rng.random((n, n)) < density) * rng.standard_normal((n, n)).astype(np.float32)
    a = CSR.from_dense(d)
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    pe = partition_ell(a, p)
    st_ = MigratoryStrategy(replicate_x=replicate, grain=rng.integers(1, 8))
    xin = x if replicate else stripe_vector(x, p)
    y = gather_result(spmv(pe, xin, st_), n)
    assert np.allclose(np.asarray(y), d @ np.asarray(x), atol=1e-3)


# -- rows split into owner-local pieces ----------------------------------------


def _hub_matrix(seed: int) -> np.ndarray:
    """Rows of every length from 0 to 60 among 200 rows of one or two
    nonzeros, shuffled: padding to the longest row would hold about 20
    times the nonzeros, so the layout splits at a K far below 60."""
    rng = np.random.default_rng(seed)
    lens = rng.permutation(np.concatenate([np.arange(61), rng.integers(1, 3, 200)]))
    d = np.zeros((len(lens), 96), np.float32)
    for r, deg in enumerate(lens):
        d[r, rng.choice(96, deg, replace=False)] = rng.standard_normal(deg)
    return d


def _lens(d: np.ndarray) -> np.ndarray:
    return (d != 0).sum(axis=1)


def _served(pe, x: np.ndarray, strategy, substrate: str = "local") -> np.ndarray:
    from repro.engine import Request, SpMVInputs, SpMVOp, run

    xin = jnp.asarray(x)
    y, _ = run(Request(SpMVOp(), SpMVInputs(pe, xin), strategy, substrate), iters=1, warmup=0)
    return np.asarray(gather_result(y, pe.shape[0]))


def _assert_matches_ref(d: np.ndarray, x: np.ndarray, y: np.ndarray) -> None:
    """Under ``test_spmv_strategies_match_ref``'s bound."""
    ref = d.astype(np.float64) @ x.astype(np.float64)
    bound = 1e-6 * (np.abs(d).astype(np.float64) @ np.abs(x).astype(np.float64))
    assert np.all(np.abs(y - ref) <= bound)
    assert np.all(y[~d.any(axis=1)] == 0)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("replicate", [True, False])
@pytest.mark.parametrize("grain", [1, 7, None])
def test_hub_rows_split_and_served_match_ref(seed, replicate, grain):
    """A matrix whose rows take every length from 0 to several times the
    chosen K, served through ``Request`` on ``local``, against a float64
    dense product."""
    d = _hub_matrix(seed)
    pe = partition_ell(CSR.from_dense(d), 8)
    assert pe.row_of is not None and _lens(d).max() >= 4 * pe.k
    x = np.random.default_rng(seed + 10).standard_normal(d.shape[1]).astype(np.float32)
    _assert_matches_ref(d, x, _served(pe, x, MigratoryStrategy(replicate_x=replicate, grain=grain)))


def _planes_row_by_row(d: np.ndarray, p: int, k: int):
    """The unsplit planes as the layout step built them one row at a time."""
    n = d.shape[0]
    rp = -(-n // p)
    cols = np.full((p, rp, k), -1, np.int32)
    vals = np.zeros((p, rp, k), np.float32)
    for r in range(n):
        (nz,) = np.nonzero(d[r])
        cols[r % p, r // p, : len(nz)] = nz
        vals[r % p, r // p, : len(nz)] = d[r, nz]
    return cols, vals


@pytest.mark.parametrize("matrix", list(MATRICES))
def test_unsplit_layout_has_no_row_map_and_the_same_planes(matrix):
    """Padding to the longest row holds at most twice the nonzeros in each
    of these (the Laplacian: 720 slots for 672 nonzeros; the 0-to-k
    matrices exactly twice), so their layout is today's, bit for bit."""
    d = MATRICES[matrix]()
    pe = partition_ell(CSR.from_dense(d), 8)
    assert pe.row_of is None and pe.k == max(_lens(d).max(), 1)
    cols, vals = _planes_row_by_row(d, 8, pe.k)
    assert np.array_equal(np.asarray(pe.cols), cols)
    assert np.array_equal(np.asarray(pe.vals), vals)


def test_ell_width_minimises_padded_slots_plus_pieces():
    from repro.core.spmv import ell_width

    lens = _lens(_hub_matrix(0))
    nonempty = int((lens > 0).sum())

    def cost(k):
        ell_rows = int(np.ceil(lens / k).sum())
        return k * ell_rows + ell_rows - nonempty

    assert ell_width(lens, 8) == min(range(1, lens.max() + 1), key=cost)
    assert ell_width(np.full(64, 5), 8) == 5  # the Laplacian's interior rows
    assert ell_width(np.array([3] + [1] * 99), 1) == 1  # 300 slots for 102 nonzeros


@pytest.mark.parametrize("k", [1, 4, 13])
def test_explicit_k_below_the_longest_row_splits_at_k(k):
    """Where the layout step once raised, it now splits at ``k``."""
    d = _hub_matrix(3)
    pe = partition_ell(CSR.from_dense(d), 4, k=k)
    assert pe.k == k and pe.row_of is not None
    assert pe.rows_per_nodelet == max(
        int(np.ceil(_lens(d)[q::4] / k).sum()) for q in range(4)
    )
    x = np.random.default_rng(k).standard_normal(d.shape[1]).astype(np.float32)
    _assert_matches_ref(d, x, _served(pe, x, MigratoryStrategy()))


def test_layout_counts_are_the_layouts_own():
    from repro.core.spmv import spmv_layout_counts

    d = _hub_matrix(4)
    pe = partition_ell(CSR.from_dense(d), 8)
    pieces = int(np.maximum(np.ceil(_lens(d) / pe.k) - 1, 0).sum())
    assert spmv_layout_counts(pe) == {"spmv.slots": pe.cols.size, "spmv.pieces": pieces}
    lap = partition_ell(laplacian_2d(12), 8)
    assert spmv_layout_counts(lap) == {"spmv.slots": 8 * 18 * 5, "spmv.pieces": 0}


def test_service_counts_slots_and_pieces_per_request():
    """``spmv.slots`` and ``spmv.pieces`` join the service's span totals
    once per served request, from the plan's layout."""
    from repro.core.spmv import spmv_layout_counts
    from repro.engine import EngineService, Request, SpMVInputs, SpMVOp

    pe = partition_ell(CSR.from_dense(_hub_matrix(5)), 8)
    x = jnp.ones(pe.shape[1], jnp.float32)
    with EngineService() as svc:
        for _ in range(3):
            svc.submit(Request(SpMVOp(), SpMVInputs(pe, x))).result()
        stats = svc.stats()
    counts = spmv_layout_counts(pe)
    assert counts["spmv.pieces"] > 0
    assert stats.counters == {name: 3 * v for name, v in counts.items()}
    assert stats.to_dict()["counters"] == stats.counters


def test_pallas_substrate_folds_the_pieces():
    d = _hub_matrix(6)
    pe = partition_ell(CSR.from_dense(d), 8)
    assert pe.row_of is not None
    x = np.random.default_rng(6).standard_normal(d.shape[1]).astype(np.float32)
    _assert_matches_ref(d, x, _served(pe, x, MigratoryStrategy(grain=64), "pallas"))


def _layout_of_lengths(lens, p: int, k: int):
    rng = np.random.default_rng(len(lens))
    d = np.zeros((len(lens), max(lens)), np.float32)
    for r, deg in enumerate(lens):
        d[r, rng.choice(d.shape[1], deg, replace=False)] = 1.0
    return partition_ell(CSR.from_dense(d), p, k=k)


FOLD_LAYOUTS = {
    # rows with no nonzero hold no ELL row in a split layout
    "empty_rows": ([0, 5, 0, 0, 7, 1, 0, 3, 0, 9, 0, 0, 2, 0, 0, 4], 4, 2),
    # row 0 in 16 = 2^4 pieces, then in 17: the scan's last step must reach back
    "pow2_pieces": ([32, 1, 1, 1, 3, 0, 2, 1], 4, 2),
    "pow2_plus_1_pieces": ([34, 1, 1, 1, 3, 0, 2, 1], 4, 2),
    # row 0's 64 pieces are every ELL row of nodelet 0 (row 4 is empty)
    "hub_fills_nodelet": ([128, 3, 2, 1, 0, 2, 1, 1], 4, 2),
    # 21 rows over 4 nodelets: slot 5 of nodelets 1 to 3 lies past n
    "padding_past_n": ([3, 0, 8, 1, 2, 2, 0, 5, 1, 1, 4, 0, 6, 1, 2, 3, 0, 1, 2, 30, 1], 4, 3),
}


@pytest.mark.parametrize("layout", list(FOLD_LAYOUTS))
def test_fold_pieces_matches_float64_add_at(layout):
    """The segmented scan and gather of ``fold_pieces`` against a float64
    ``np.add.at`` of every ELL row's sum onto the slot ``row_of`` names:
    within 1e-6 of the sum of the magnitudes added, and exactly 0 on a slot
    that has no ELL row (an empty row, or a slot past n)."""
    import jax

    from repro.core.spmv import fold_pieces

    lens, p, k = FOLD_LAYOUTS[layout]
    pe = _layout_of_lengths(lens, p, k)
    assert pe.row_of is not None
    row_of = np.asarray(pe.row_of)
    rp = -(-len(lens) // p)
    if layout == "hub_fills_nodelet":
        assert (row_of[0] == 0).all()
    y = np.random.default_rng(7).standard_normal(row_of.shape).astype(np.float32)
    ref, mag = np.zeros((p, rp + 1)), np.zeros((p, rp + 1))
    for q in range(p):
        np.add.at(ref[q], row_of[q], y[q].astype(np.float64))
        np.add.at(mag[q], row_of[q], np.abs(y[q]).astype(np.float64))
    out = np.asarray(jax.jit(fold_pieces)(jnp.asarray(y), pe))
    assert out.shape == (p, rp)
    assert np.all(np.abs(out - ref[:, :rp]) <= 1e-6 * mag[:, :rp])
    held = np.zeros(p * rp, bool)
    held[: len(lens)] = np.asarray(lens) > 0
    held = held.reshape(rp, p).T
    assert np.array_equal(np.asarray(pe.last_ell) >= 0, held)
    assert np.all(out[~held] == 0)


def test_plan_key_covers_the_split():
    from repro.engine import SpMVInputs, SpMVOp, build_plan

    a = CSR.from_dense(_hub_matrix(7))
    x = jnp.ones(a.shape[1], jnp.float32)
    split = build_plan(SpMVOp(), SpMVInputs(partition_ell(a, 8), x))
    padded = build_plan(SpMVOp(), SpMVInputs(partition_ell(a, 8, k=60), x))
    assert split.key != padded.key
