"""Sparse substrate: formats, generators, conversions."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.sparse import (
    CSR, edges_to_csr, erdos_renyi_edges, laplacian_2d,
    partition_graph, rmat_edges, skewed_matrix, spmv_csr_ref,
)


def test_laplacian_structure():
    a = laplacian_2d(8)
    assert a.shape == (64, 64)
    d = np.asarray(a.to_dense())
    assert np.allclose(d, d.T)
    assert (np.diag(d) == 4).all()
    # interior rows have 5 nonzeros (pentadiagonal)
    lens = np.diff(np.asarray(a.indptr))
    assert lens.max() == 5 and lens.min() == 3


def test_csr_dense_roundtrip():
    rng = np.random.default_rng(0)
    d = (rng.random((13, 17)) < 0.2) * rng.standard_normal((13, 17)).astype(np.float32)
    a = CSR.from_dense(d)
    assert np.allclose(np.asarray(a.to_dense()), d)


def test_generators_shapes():
    e = erdos_renyi_edges(8, 4, seed=0)
    assert e.shape == (4 * 256, 2) and e.max() < 256
    r = rmat_edges(8, 4, seed=0)
    assert r.shape == (4 * 256, 2) and r.max() < 256
    # RMAT should be more skewed than ER
    g_er = edges_to_csr(e, 256)
    g_rm = edges_to_csr(r, 256)
    er_max = np.diff(np.asarray(g_er.indptr)).max()
    rm_max = np.diff(np.asarray(g_rm.indptr)).max()
    assert rm_max > er_max


def test_skewed_matrix_signature():
    m = skewed_matrix(3000, 8.0, 600, seed=0)
    lens = np.diff(np.asarray(m.indptr))
    assert lens.max() >= 400  # hubs present (dedup can shave a bit)
    assert 2.0 < lens.mean() < 24.0


def test_partition_graph_roundtrip():
    g = edges_to_csr(erdos_renyi_edges(7, 4, seed=2), 128)
    pg = partition_graph(g, 8)
    # every edge present exactly once at its owner
    indptr = np.asarray(g.indptr)
    indices = np.asarray(g.indices)
    adj = np.asarray(pg.adj)
    for v in range(128):
        nbrs = sorted(indices[indptr[v]:indptr[v + 1]].tolist())
        row = adj[v % 8, v // 8]
        assert sorted(row[row >= 0].tolist()) == nbrs


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(2, 40),
    density=st.floats(0.05, 0.6),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_csr_spmv_matches_dense(n, density, seed):
    rng = np.random.default_rng(seed)
    d = (rng.random((n, n)) < density) * rng.standard_normal((n, n)).astype(np.float32)
    a = CSR.from_dense(d)
    x = rng.standard_normal(n).astype(np.float32)
    assert np.allclose(np.asarray(spmv_csr_ref(a, jnp.asarray(x))), d @ x, atol=1e-4)
