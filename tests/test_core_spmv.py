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


def _rows_of_lengths(lens, n_cols: int, rng) -> np.ndarray:
    """A dense matrix whose row r holds ``lens[r]`` random values in random
    columns."""
    d = np.zeros((len(lens), n_cols), np.float32)
    for r, deg in enumerate(lens):
        d[r, rng.choice(n_cols, deg, replace=False)] = rng.standard_normal(deg)
    return d


def _degrees_0_to_k(k: int) -> np.ndarray:
    """96 x 84 dense matrix whose rows take every degree 0..k, so its ELL
    planes (K = k) hold padding slots (col -1) and empty rows."""
    return _rows_of_lengths(np.arange(96) % (k + 1), 84, np.random.default_rng(k))


def _dense(build):
    return lambda: CSR.from_dense(build())


MATRICES = {
    "laplacian": lambda: laplacian_2d(12),  # 144 x 144, K = 5
    "deg0to1": _dense(lambda: _degrees_0_to_k(1)),
    "deg0to5": _dense(lambda: _degrees_0_to_k(5)),
    "deg0to7": _dense(lambda: _degrees_0_to_k(7)),
}
# Padding to the longest row holds at most twice the nonzeros in each of
# these, so none of them splits a row.
UNSPLIT_MATRICES = {
    **MATRICES,
    # 72 x 1024, K = 16: R_p = 9, so grain 4 gives K-minor chunks, the last padded
    "k16": _dense(lambda: _rows_of_lengths(np.full(72, 16), 1024, np.random.default_rng(16))),
    # 256 x 32, K = 1: one nonzero a row, no padding slot and no padding row
    "k1": _dense(lambda: _rows_of_lengths(np.ones(256, int), 32, np.random.default_rng(1))),
    # 100 x 128, K = 7: R_p = 13, and four nodelets hold a row past n
    "rows100": _dense(
        lambda: _rows_of_lengths(7 - np.arange(100) % 8 // 6, 128, np.random.default_rng(100))
    ),
    # bfloat16 values, held and multiplied in bfloat16
    "deg0to7_bf16": lambda: CSR.from_dense(_degrees_0_to_k(7).astype(jnp.bfloat16)),
    # 45 x 16 with no nonzero: planes of padding alone, K = 1
    "empty": lambda: CSR.from_dense(np.zeros((45, 16), np.float32)),
    # 130 x 130 grid, 16,900 rows: R_p = 2,113 over 8 nodelets, so the cells'
    # grains (64 to 2,048) and the dynamic grain (4) all pad the last chunk
    "laplacian130": lambda: laplacian_2d(130),
}
# the grains the cells run: the dynamic grain is 1,024 at the Laplacian
# cell's 4,194,304 rows, and Stanford's config sets 2,048
CELL_GRAINS = (64, 256, 1024, 2048)
SPMV_CASES = [
    *((grain, matrix) for grain in (1, 4, 5, 16, None) for matrix in MATRICES),
    (4, "k16"),
    (256, "k1"),
    (8, "rows100"),
    (1, "deg0to7_bf16"),
    (16, "deg0to7_bf16"),
    (None, "empty"),
    *((grain, "laplacian130") for grain in (*CELL_GRAINS, None)),
]


def _assert_matches_ref(a: CSR, pe, x: np.ndarray, y: np.ndarray) -> None:
    """``y`` against the float64 product of ``a``'s and ``x``'s values: within
    a bound on the sum of the magnitudes of each row's terms, and exactly 0
    on an empty row. float32 sums of at most K terms stay well inside 1e-6 of
    it. In bfloat16 (unit roundoff 2^-8) each product rounds once, an ELL
    row's K - 1 sums once each in any order, and the fold's scan adds at
    most ceil(log2 R_p') times more: (K + 1 + that) 2^-8 bounds them."""
    lens = np.diff(np.asarray(a.indptr))
    rows = np.repeat(np.arange(a.shape[0]), lens)
    terms = np.asarray(a.data).astype(np.float64) * x.astype(np.float64)[np.asarray(a.indices)]
    ref = np.bincount(rows, terms, minlength=a.shape[0])
    mag = np.bincount(rows, np.abs(terms), minlength=a.shape[0])
    tol = 1e-6
    if a.data.dtype != jnp.float32:
        fold_adds = int(np.ceil(np.log2(pe.rows_per_nodelet))) if pe.row_of is not None else 0
        tol = (pe.k + 1 + fold_adds) * 2.0**-8
    y = y.astype(np.float64)
    assert np.all(np.abs(y - ref) <= tol * mag)
    assert np.all(y[lens == 0] == 0)


@pytest.mark.parametrize(
    "grain,replicate,matrix",
    [(grain, replicate, matrix) for grain, matrix in SPMV_CASES for replicate in (True, False)],
)
def test_spmv_strategies_match_ref(replicate, grain, matrix):
    """The local kernel's row chunks against a float64 product, across
    strategies and grains: one row per task, a grain that divides R_p (12
    for the 96-row matrices), a ragged last chunk, a grain above R_p, the
    dynamic grain, and the cells' grains on a matrix whose R_p they leave a
    padded last chunk of."""
    a = UNSPLIT_MATRICES[matrix]()
    dtype = a.data.dtype
    x = np.random.default_rng(0).standard_normal(a.shape[1]).astype(np.float32).astype(dtype)
    pe = partition_ell(a, 8)
    assert pe.row_of is None
    st_ = MigratoryStrategy(replicate_x=replicate, grain=grain)
    xin = jnp.asarray(x) if replicate else stripe_vector(jnp.asarray(x), 8)
    y = np.asarray(gather_result(spmv(pe, xin, st_), a.shape[0]))
    assert y.dtype == dtype
    _assert_matches_ref(a, pe, x, y)


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


def _hub_matrix(seed: int, longest: int = 60, short_rows: int = 200) -> np.ndarray:
    """Rows of every length from 0 to ``longest`` among ``short_rows`` rows
    of one or two nonzeros, shuffled: padding to the longest row would hold
    about 20 times the nonzeros, so the layout splits at a K far below 60."""
    rng = np.random.default_rng(seed)
    lens = rng.permutation(np.concatenate([np.arange(longest + 1), rng.integers(1, 3, short_rows)]))
    return _rows_of_lengths(lens, 96, rng)


def _lens(d: np.ndarray) -> np.ndarray:
    return (d != 0).sum(axis=1)


def _served(pe, x: np.ndarray, strategy) -> np.ndarray:
    from repro.engine import Request, SpMVInputs, SpMVOp, run

    xin = jnp.asarray(x)
    y, _ = run(Request(SpMVOp(), SpMVInputs(pe, xin), strategy, "local"), iters=1, warmup=0)
    return np.asarray(gather_result(y, pe.shape[0]))


HUB_LAYOUTS = {
    # (seed, shape, K, value dtype): K None is the layout's own choice
    **{seed: (seed, {}, None, np.float32) for seed in (0, 1, 2)},
    # 17,061 rows split at K = 2 into R_p' = 2,290 ELL rows a nodelet, so the
    # cells' grains and the dynamic grain (4) all pad the last chunk
    "tall": (3, {"short_rows": 16_800}, None, np.float32),
    # split at K = 16 into R_p' = 81: grain 4 gives K-minor chunks and grain
    # 16 K-major ones, the last padded
    "k16": (3, {"longest": 90}, 16, np.float32),
    # bfloat16 values: the pieces' sums fold in bfloat16
    "bf16": (0, {}, None, jnp.bfloat16),
}
HUB_CASES = [
    *((grain, hub) for grain in (1, 7, None) for hub in (0, 1, 2)),
    *((grain, "tall") for grain in (*CELL_GRAINS, None)),
    (4, "k16"),
    (16, "k16"),
    (None, "bf16"),
]


@pytest.mark.parametrize(
    "grain,replicate,hub",
    [(grain, replicate, hub) for grain, hub in HUB_CASES for replicate in (True, False)],
)
def test_hub_rows_split_and_served_match_ref(hub, replicate, grain):
    """A matrix whose rows take every length from 0 to several times the
    chosen K, served through ``Request`` on ``local``, against a float64
    product."""
    seed, shape, k, dtype = HUB_LAYOUTS[hub]
    d = _hub_matrix(seed, **shape).astype(dtype)
    a = CSR.from_dense(d)
    pe = partition_ell(a, 8, k=k)
    assert pe.row_of is not None and _lens(d).max() >= 4 * pe.k
    x = np.random.default_rng(seed + 10).standard_normal(d.shape[1]).astype(np.float32).astype(dtype)
    _assert_matches_ref(a, pe, x, _served(pe, x, MigratoryStrategy(replicate_x=replicate, grain=grain)))


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
    a = MATRICES[matrix]()
    d = np.asarray(a.to_dense())
    pe = partition_ell(a, 8)
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


@pytest.mark.parametrize("k", [1, 4, 13, 59])
def test_explicit_k_below_the_longest_row_splits_at_k(k):
    """Where the layout step once raised, it now splits at ``k``; at 59 only
    the one row of 60 splits, in two."""
    d = _hub_matrix(3)
    a = CSR.from_dense(d)
    pe = partition_ell(a, 4, k=k)
    assert pe.k == k and pe.row_of is not None
    assert pe.rows_per_nodelet == max(
        int(np.ceil(_lens(d)[q::4] / k).sum()) for q in range(4)
    )
    x = np.random.default_rng(k).standard_normal(d.shape[1]).astype(np.float32)
    _assert_matches_ref(a, pe, x, _served(pe, x, MigratoryStrategy()))


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
    layout = {name: v for name, v in stats.counters.items() if name.startswith("spmv.")}
    assert layout == {name: 3 * v for name, v in counts.items()}
    assert stats.to_dict()["counters"] == stats.counters


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
    # R_p' = 2: the scan's one step
    "two_ell_rows": ([3, 1, 0, 2], 2, 2),
    # K = 1: every nonzero an ELL row of its own
    "every_nonzero_a_piece": ([5, 2, 0, 3, 1, 4, 0, 2], 4, 1),
    # Stanford's K = 3 and a row of 4,500 pieces: 13 steps, the last reaching
    # 4,096 ELL rows back inside one row
    "stanford_k_hub": ([13_500, 3, 2, 1, 0, 4, 7, 1], 4, 3),
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
