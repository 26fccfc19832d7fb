"""Graph500 BFS cell (``bench/kinds/kron_bfs.py``): its graph holds to the
configuration, and its work counts and rate read what they say, on the CPU.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
from __future__ import annotations

import types
from pathlib import Path

import numpy as np
import pytest
import scipy.sparse as sp

from bench import gen, harness, reference
from bench.kinds import kron_bfs

ROOT = Path(__file__).resolve().parents[2]
CONFIG = harness.read_json(ROOT / "bench" / "configs" / "graph500_kron.json")
METRICS = ROOT / "bench" / "metrics"


def _host(edges, n: int) -> gen.HostCSR:
    return gen.undirected_csr(np.array(edges, dtype=np.int64), n)


def test_work_of_a_search_on_a_hand_built_graph():
    # a path 0-1-2 with a chord 0-2, and an edge 3-4 apart from it
    host = _host([[0, 1], [1, 2], [0, 2], [3, 4]], 6)
    parents = reference.bfs_parents(host.indptr, host.indices, host.n, 0)
    np.testing.assert_array_equal(parents, [0, 0, 0, -1, -1, -1])
    # three edges, six directed entries at 4 B, three vertices at 4 B + 4 B
    assert kron_bfs.search_work(host, parents) == {
        "traversed_edges": 3, "useful_bytes": 6 * 4 + 3 * 8}
    far = reference.bfs_parents(host.indptr, host.indices, host.n, 4)
    assert kron_bfs.search_work(host, far) == {"traversed_edges": 1, "useful_bytes": 2 * 4 + 2 * 8}


def test_depths_of_a_hand_built_tree():
    host = _host([[0, 1], [1, 2], [2, 3], [0, 4], [5, 6]], 7)
    parents = reference.bfs_parents(host.indptr, host.indices, host.n, 1)
    np.testing.assert_array_equal(kron_bfs.depths(parents, 1), [1, 0, 1, 2, 2, -1, -1])


def test_bfs_mteps_is_the_window_rate_of_the_completed_searches():
    read = harness.load_module(METRICS / "bfs_mteps.py").read
    run = types.SimpleNamespace(window=types.SimpleNamespace(seconds=4.0), trace=None)
    run.total = lambda key: harness.Run.total(run, key)
    run.work = [{"traversed_edges": 3_000_000}, {}, {"traversed_edges": 1_000_000}]
    assert read(run) == pytest.approx(1.0)
    run.work = [{}]
    assert read(run) is None


def test_bfs_roofline_reads_the_trace_and_nothing_without_it():
    read = harness.load_module(METRICS / "bfs_roofline.py").read
    trace = types.SimpleNamespace(modules_s=lambda: 2.0)
    run = types.SimpleNamespace(trace=trace, peaks={"hbm_bytes_per_s": 1e9},
                                total=lambda key: 1e9)
    assert read(run) == pytest.approx(50.0)
    assert read(types.SimpleNamespace(trace=None)) is None


def test_graph_holds_to_its_configuration():
    """At scale 15 (host arrays only): the sizes the configuration states,
    the keys at labels 0 to 3 in one component, and every key's search the
    same under two seeds."""
    a = kron_bfs.kron_graph(CONFIG, 2**31 + 17)
    b = kron_bfs.kron_graph(CONFIG, 5)
    assert a.n == 32_768 and a.nnz == b.nnz == 883_054
    assert a.degrees().max() == 6_096 and CONFIG["k"] >= 6_096 and CONFIG["k"] % 128 == 0
    assert not np.array_equal(a.indices, b.indices)
    for key, depth in enumerate([5, 5, 4, 4]):
        for host in (a, b):
            parents = reference.bfs_parents(host.indptr, host.indices, host.n, key)
            assert (parents >= 0).sum() == 24_247
            assert kron_bfs.search_work(host, parents)["traversed_edges"] == 441_521
            assert kron_bfs.depths(parents, key).max() == depth


def test_the_graph_is_scipy_symmetric_without_loops():
    host = kron_bfs.kron_graph({**CONFIG, "scale": 8}, 2**31 + 1)
    m = sp.csr_matrix((host.data, host.indices, host.indptr), shape=(host.n, host.n))
    assert (m != m.T).nnz == 0 and m.diagonal().sum() == 0 and m.max() == 1
