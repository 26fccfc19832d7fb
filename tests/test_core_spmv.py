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
