"""Core BFS: S2 remote-write strategy — correctness + traffic ordering."""
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import (
    Comm, MigratoryStrategy, bfs, bfs_effective_bandwidth, bfs_traffic, teps,
    validate_parents,
)
from repro.sparse import edges_to_csr, erdos_renyi_edges, partition_graph, rmat_edges


def _ref_bfs_levels(adj_csr, root):
    """Plain numpy BFS levels oracle."""
    indptr = np.asarray(adj_csr.indptr)
    indices = np.asarray(adj_csr.indices)
    n = adj_csr.n_rows
    level = np.full(n, -1)
    level[root] = 0
    frontier = [root]
    l = 0
    while frontier:
        nxt = []
        for u in frontier:
            for v in indices[indptr[u]:indptr[u + 1]]:
                if level[v] < 0:
                    level[v] = l + 1
                    nxt.append(v)
        frontier = nxt
        l += 1
    return level


def _random_digraph(n: int, k: int, seed: int):
    """A directed graph of ``n`` vertices, each with ``k`` random out-edge
    slots of which about one in ``n + 1`` is empty (duplicates and self loops
    dropped)."""
    adj = np.random.default_rng(seed).integers(-1, n, size=(n, k))
    src = np.repeat(np.arange(n), k)
    keep = adj.reshape(-1) >= 0
    return edges_to_csr(np.stack([src[keep], adj.reshape(-1)[keep]], 1), n, symmetrize=False)


BFS_GRAPHS = {
    "er": lambda: edges_to_csr(erdos_renyi_edges(8, 8, seed=0), 256),
    "rmat": lambda: edges_to_csr(rmat_edges(8, 8, seed=0), 256),
    "er_ef6": lambda: edges_to_csr(erdos_renyi_edges(8, 6, seed=5), 256),
    # directed; 100 and 37 vertices leave padding vertices on the nodelets,
    # and one out-edge a vertex makes a traversal a chain of rounds
    **{
        f"digraph{n}x{k}": (lambda n=n, k=k: _random_digraph(n, k, seed=n * k))
        for n, k in ((64, 4), (100, 6), (256, 1), (37, 8))
    },
    # vertex 0 points at the other 44 and nothing points back: from 0 one
    # round reaches every vertex, from a leaf none
    "star45": lambda: edges_to_csr(
        np.stack([np.zeros(44, int), np.arange(1, 45)], 1), 45, symmetrize=False
    ),
}
COMMS = {"migrate": Comm.MIGRATE, "remote_write": Comm.REMOTE_WRITE}
REACHABILITY_CASES = [
    # the first two under the default strategy (remote write)
    pytest.param("er", 0, "remote_write", None, id="er-8"),
    pytest.param("rmat", 0, "remote_write", None, id="rmat-8"),
    pytest.param("er", 0, "migrate", None, id="er-8-migrate"),
    pytest.param("rmat", 0, "migrate", None, id="rmat-8-migrate"),
    *(
        pytest.param(graph, root, comm, max_rounds, id=f"{graph}-{root}-{comm}{tag}")
        for graph, roots, max_rounds, tag in (
            ("er_ef6", (0, 3, 200), None, ""),
            *((name, (0,), None, "") for name in BFS_GRAPHS if name.startswith("digraph")),
            ("star45", (0, 7), None, ""),
            # stopped before the frontier empties: depths 4 and 8
            ("er_ef6", (0,), 2, "-2rounds"),
            ("digraph256x1", (0,), 3, "-3rounds"),
        )
        for root in roots
        for comm in COMMS
    ),
]


@pytest.mark.parametrize("graph,root,comm,max_rounds", REACHABILITY_CASES)
def test_bfs_matches_reference_reachability(graph, root, comm, max_rounds):
    """The served BFS under each S2 strategy against a host BFS: the same
    vertices reached (those within ``max_rounds`` levels, where it is set),
    each parent an edge of a valid tree."""
    g = BFS_GRAPHS[graph]()
    pg = partition_graph(g, 8)
    parents = np.asarray(
        bfs(pg, root, MigratoryStrategy(comm=COMMS[comm]), max_rounds=max_rounds)
    )
    ref_level = _ref_bfs_levels(g, root)
    if max_rounds is not None:
        ref_level[ref_level > max_rounds] = -1
    assert ((parents >= 0) == (ref_level >= 0)).all()
    assert validate_parents(pg, root, parents)


def test_bfs_parent_levels_are_minimal():
    """Level-synchronous min-merge must produce shortest-path levels."""
    n = 256
    g = edges_to_csr(erdos_renyi_edges(8, 4, seed=5), n)
    pg = partition_graph(g, 4)
    parents = np.asarray(bfs(pg, 7))
    ref_level = _ref_bfs_levels(g, 7)
    # derive level from parent chain
    for v in range(n):
        if parents[v] < 0 or v == 7:
            continue
        lv, u = 0, v
        while u != 7 and lv <= n:
            u = parents[u]
            lv += 1
        assert lv == ref_level[v], f"vertex {v}: {lv} != {ref_level[v]}"


@pytest.mark.parametrize("corrupt", ["root", "missing_edge", "cycle", "out_of_range"])
def test_validate_parents_rejects_invalid_trees(corrupt):
    """The checker behind the chip smoke's BFS phases must refuse a tree
    that is not one: a wrong root, a parent edge absent from the graph, a
    cycle cut off from the root, a parent id past the last vertex."""
    n = 256
    g = edges_to_csr(erdos_renyi_edges(8, 4, seed=5), n)
    pg = partition_graph(g, 4)
    parents = np.asarray(bfs(pg, 7)).copy()
    assert validate_parents(pg, 7, parents)
    indptr, indices = np.asarray(g.indptr), np.asarray(g.indices)
    reached = [v for v in np.flatnonzero(parents >= 0) if v != 7]
    if corrupt == "root":
        parents[7] = reached[0]
    elif corrupt == "missing_edge":
        v = reached[0]
        nbrs = set(indices[indptr[v] : indptr[v + 1]].tolist())
        parents[v] = next(u for u in reached if u != v and u not in nbrs)
    elif corrupt == "cycle":
        # two adjacent non-root vertices point at each other
        v = reached[0]
        u = next(int(w) for w in indices[indptr[v] : indptr[v + 1]] if w != 7)
        parents[v], parents[u] = u, v
    else:
        parents[reached[0]] = n
    assert not validate_parents(pg, 7, parents)


def test_remote_write_traffic_beats_migrate():
    """Paper Fig. 7: put packets are far cheaper than thread migrations."""
    g = edges_to_csr(erdos_renyi_edges(10, 16, seed=1), 1024)
    pg = partition_graph(g, 8)
    t_mig = bfs_traffic(pg, 0, MigratoryStrategy(comm=Comm.MIGRATE))
    t_rw = bfs_traffic(pg, 0, MigratoryStrategy(comm=Comm.REMOTE_WRITE))
    assert t_rw.traffic.total_bytes < t_mig.traffic.total_bytes / 5
    assert t_mig.rounds == t_rw.rounds
    assert t_mig.edges_traversed == t_rw.edges_traversed


def test_metrics():
    assert teps(100, 2.0) == 50.0
    assert bfs_effective_bandwidth(10, 1.0) == 16 * 1024 * 16


@settings(max_examples=15, deadline=None)
@given(
    scale=st.integers(5, 8),
    ef=st.integers(2, 8),
    p=st.sampled_from([2, 4, 8]),
    root_seed=st.integers(0, 10**6),
)
def test_property_bfs_tree_valid(scale, ef, p, root_seed):
    """Invariant: any produced parent array is a valid BFS tree with full
    reachable coverage, regardless of partitioning."""
    n = 1 << scale
    g = edges_to_csr(erdos_renyi_edges(scale, ef, seed=root_seed % 17), n)
    pg = partition_graph(g, p)
    root = root_seed % n
    parents = np.asarray(bfs(pg, root))
    assert validate_parents(pg, root, parents)
    ref = _ref_bfs_levels(g, root)
    assert ((parents >= 0) == (ref >= 0)).all()
