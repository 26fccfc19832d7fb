"""Graph500 BFS served through ``EngineService``, one search per request.

The graph is drawn once from the configuration's ``graph_seed`` (the same
in every run, as a deployment searches one graph): Graph500's Kronecker
generator at ``scale`` and ``edge_factor``, made undirected without self
loops or duplicate edges, and ``keys`` search keys drawn among the vertices
with an edge. ``--seed`` draws Graph500's random relabelling, which gives
key ``i`` the label ``i`` (:func:`bench.gen.relabel`), so the graph, its
programs and each key's search are the same in every run. The program's
``partition_graph`` lays it out over ``nodelets`` at padded width ``k``.

Request ``i`` searches from key ``i % keys``. Each key's program is warmed
on an edgeless graph of the same shapes (one round, not a whole search),
which leaves the device before the real adjacency comes to it.

After the window every answered search is compared, vertex by vertex, with
the min-merge tree of :func:`bench.reference.bfs_parents`.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, reference
from repro.core.strategies import MigratoryStrategy
from repro.engine import BFSInputs, BFSOp, Request
from repro.sparse.csr import CSR
from repro.sparse.graph import PartitionedGraph, partition_graph


def kron_graph(config: dict, seed: int) -> gen.HostCSR:
    """The configuration's graph, relabelled from ``seed`` so that search
    key ``i`` is vertex ``i``."""
    rng = np.random.default_rng(config["graph_seed"])
    n = 1 << config["scale"]
    edges = gen.kronecker_edges(rng, config["scale"], config["edge_factor"], *config["initiator"])
    degrees = gen.undirected_csr(edges, n).degrees()
    keys = rng.choice(np.flatnonzero(degrees > 0), size=config["keys"], replace=False)
    return gen.undirected_csr(gen.relabel(edges, n, np.random.default_rng(seed), keys), n)


def layout(host: gen.HostCSR, config: dict) -> PartitionedGraph:
    """The program's layout of ``host``, on the device."""
    csr = CSR(indptr=host.indptr, indices=host.indices, data=host.data, shape=(host.n, host.n))
    return partition_graph(csr, config["nodelets"], k=config["k"])


def depths(parents: np.ndarray, root: int) -> np.ndarray:
    """Level of every vertex in the BFS tree ``parents`` (-1 where unreached)."""
    level = np.full(len(parents), -1, dtype=np.int64)
    level[root] = 0
    todo = np.flatnonzero((parents >= 0) & (level < 0))
    while todo.size:
        ready = level[parents[todo]] >= 0
        level[todo[ready]] = level[parents[todo[ready]]] + 1
        todo = todo[~ready]
    return level


def search_work(host: gen.HostCSR, parents: np.ndarray) -> dict:
    """What one search from a key does, counted from the problem and not
    from the padded layout: the edges of the key's component, each counted
    once, as Graph500 counts traversed edges; and the useful bytes, each of
    the component's directed entries read once (4 B) and each of its
    vertices' offset read and parent written (4 B + 4 B)."""
    reached = parents >= 0
    entries = int(host.degrees()[reached].sum())
    return {"traversed_edges": entries // 2,
            "useful_bytes": entries * 4 + int(reached.sum()) * 8}


class Cell:
    def __init__(self, config: dict, seed: int):
        t0 = time.perf_counter()
        self.config = config
        self.limits = config["limits"]
        self.keys = config["keys"]
        self.host = kron_graph(config, seed)
        self.strategy = MigratoryStrategy()
        self.graph: PartitionedGraph | None = None
        self.answers: list[tuple[int, np.ndarray]] = []
        self._refs: dict[int, np.ndarray] = {}
        self.setup = {"generate_s": time.perf_counter() - t0}

    def _request(self, g: PartitionedGraph, key: int, max_rounds: int | None = None):
        return Request(BFSOp(), BFSInputs(g, key, max_rounds), self.strategy)

    def warm_requests(self):
        """Each key's program on an edgeless graph of the real one's shapes,
        then the real graph to the device: it never holds two adjacencies."""
        t0 = time.perf_counter()
        p, k, n = self.config["nodelets"], self.config["k"], self.host.n
        vp = -(-n // p)
        empty = PartitionedGraph(adj=jnp.full((p, vp, k), -1, jnp.int32),
                                 deg=jnp.zeros((p, vp), jnp.int32), n_vertices=n)
        for key in range(self.keys):
            yield self._request(empty, key)
        empty.adj.delete()
        empty.deg.delete()
        t1 = time.perf_counter()
        self.graph = layout(self.host, self.config)
        t2 = time.perf_counter()
        jax.block_until_ready(self.graph.adj)
        self.setup.update(warm_s=t1 - t0, partition_graph_s=t2 - t1,
                          transfer_wait_s=time.perf_counter() - t2)
        print("layout " + json.dumps({
            "vertices": n, "directed_entries": self.host.nnz,
            "hub_degree": int(self.host.degrees().max()), "k": self.graph.k,
            "adjacency_bytes": self.graph.adj.nbytes, **self.setup,
        }), file=sys.stderr, flush=True)

    def request(self, i: int):
        tag = i % self.keys
        return self._request(self.graph, tag), tag

    def keep(self, i: int, tag: int, parents: np.ndarray) -> None:
        self.answers.append((tag, parents))

    def release(self) -> None:
        self.graph = None

    def reference(self, key: int) -> np.ndarray:
        if key not in self._refs:
            self._refs[key] = reference.bfs_parents(
                self.host.indptr, self.host.indices, self.host.n, key)
        return self._refs[key]

    def _mismatch(self, key: int, parents: np.ndarray) -> int:
        return int(np.count_nonzero(np.asarray(parents, np.int64) != self.reference(key)))

    def check(self, attempted: int) -> dict:
        mismatch = sum(self._mismatch(key, parents) for key, parents in self.answers)
        return {
            "parent_mismatch": (mismatch, self.limits["parent_mismatch"]),
            "missing_answers": (attempted - len(self.answers), self.limits["missing_answers"]),
        }

    def work(self, tag: int) -> dict:
        return search_work(self.host, self.reference(tag))

    def control(self, service) -> dict:
        """The served search stopped one round short of each key's depth:
        it breaks the stated guarantee that every vertex the key reaches
        has its parent."""
        mismatch = 0
        for key in range(self.keys):
            depth = int(depths(self.reference(key), key).max())
            response = service.submit(self._request(self.graph, key, max(1, depth - 1)))
            mismatch += self._mismatch(key, np.asarray(response.result().result))
        return {"parent_mismatch": mismatch}
