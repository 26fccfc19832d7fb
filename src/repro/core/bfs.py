"""Graph500 BFS: migrating threads (Alg. 1) vs remote writes (Alg. 2).

Paper §3.2: the migrate version reads ``P[d]`` remotely (a thread migration
per traversed edge) and CASes; the remote-write version blindly pushes the
proposed parent into a shadow array ``nP`` (small one-sided packets, later
writes overwrite earlier ones) and commits in a local scan — two phases, no
atomics. We keep Alg. 2's two-phase structure exactly, replacing the
nondeterministic overwrite with a deterministic ``min`` merge (any proposed
parent is a valid BFS parent; see DESIGN.md §10).

TPU realization (DESIGN.md §2):
- ``migrate``  = pull: per round, ``all_gather`` the parent array to every
  shard (and all_gather the per-shard proposal partials back) — data moves to
  compute, twice.
- ``remote_write`` = push: each shard computes a dense proposal partial for
  the whole vertex space from purely local state and pushes it with a
  reduce-scatter(min) (implemented as all_to_all + local min); the owner
  commits locally. ~P× less traffic per round, no parent pull.

Both strategies produce identical parent trees (level-synchronous min-merge);
they differ in communication structure — which is the paper's point.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..sparse.graph import PartitionedGraph
from .strategies import Comm, MigratoryStrategy, TrafficStats

UNVISITED = jnp.iinfo(jnp.int32).max  # internal sentinel (min-merge friendly)


def _adj_global(g: PartitionedGraph) -> jax.Array:
    """(P, V_p, K) nodelet-major -> (N_pad, K) global-vertex-major view."""
    p, vp, k = g.adj.shape
    return jnp.transpose(g.adj, (1, 0, 2)).reshape(vp * p, k)


def _expand_dense(adj: jax.Array, frontier: jax.Array, n_pad: int) -> jax.Array:
    """One frontier expansion: dense proposal array nP (N_pad,) via min-scatter.

    For every frontier vertex s and neighbor d: propose parent s for d.
    Invalid slots scatter UNVISITED (a no-op for min).
    """
    n, k = adj.shape
    src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, k))
    valid = frontier[:, None] & (adj >= 0)
    dst = jnp.where(valid, adj, 0)
    prop = jnp.where(valid, src, UNVISITED)
    return jnp.full((n_pad,), UNVISITED, dtype=jnp.int32).at[dst.reshape(-1)].min(
        prop.reshape(-1), mode="drop"
    )


@partial(jax.jit, static_argnames=("max_rounds",))
def _bfs_local(adj: jax.Array, root: jax.Array, max_rounds: int) -> jax.Array:
    """Level-synchronous BFS on a single device (semantics oracle for both
    strategies — Alg. 1 and Alg. 2 compute the same tree here)."""
    n = adj.shape[0]
    parents0 = jnp.full((n,), UNVISITED, dtype=jnp.int32).at[root].set(root)
    frontier0 = jnp.zeros((n,), dtype=bool).at[root].set(True)

    def cond(state):
        _, frontier, it = state
        return jnp.logical_and(frontier.any(), it < max_rounds)

    def body(state):
        parents, frontier, it = state
        nP = _expand_dense(adj, frontier, n)
        newly = (parents == UNVISITED) & (nP != UNVISITED)
        parents = jnp.where(newly, nP, parents)
        return parents, newly, it + 1

    parents, _, _ = jax.lax.while_loop(cond, body, (parents0, frontier0, 0))
    return parents


def _finalize_parents(g: PartitionedGraph, parents: jax.Array) -> jax.Array:
    """Trim padding and map the internal UNVISITED sentinel to -1."""
    parents = parents[: g.n_vertices]
    return jnp.where(parents == UNVISITED, -1, parents)


def bfs_local(
    g: PartitionedGraph,
    root: int,
    strategy: MigratoryStrategy | None = None,
    max_rounds: int | None = None,
) -> jax.Array:
    """``local`` substrate: the single-device semantics oracle (both S2
    strategies compute the same tree here). (n_vertices,) int32, -1 unreached.
    """
    del strategy  # both comm strategies share the local oracle
    max_rounds = max_rounds or g.P * g.v_per_nodelet
    return _finalize_parents(g, _bfs_local(_adj_global(g), jnp.int32(root), max_rounds))


def bfs_mesh(
    g: PartitionedGraph,
    root: int,
    strategy: MigratoryStrategy | None = None,
    max_rounds: int | None = None,
    *,
    mesh: jax.sharding.Mesh,
    axis_name: str = "nodelet",
) -> jax.Array:
    """``mesh`` substrate: the strategy-specific distributed implementation
    over ``axis_name`` (Alg. 1 pull vs Alg. 2 push)."""
    strategy = strategy or MigratoryStrategy()
    max_rounds = max_rounds or g.P * g.v_per_nodelet
    return _finalize_parents(
        g, _bfs_distributed(g, root, strategy, mesh, axis_name, max_rounds)
    )


def bfs(
    g: PartitionedGraph,
    root: int,
    strategy: MigratoryStrategy | None = None,
    *,
    mesh: jax.sharding.Mesh | None = None,
    axis_name: str = "nodelet",
    max_rounds: int | None = None,
) -> jax.Array:
    """Deprecated shim — use ``repro.engine.run(BFSOp(), ...)`` instead.

    Kept so pre-engine call sites keep working: forwards to the engine's
    substrate resolution (``local`` without a mesh, ``mesh`` with one).
    """
    from ..engine.substrate import substrate_for_mesh

    return substrate_for_mesh(mesh, axis_name).kernel("bfs")(
        g, root, strategy=strategy or MigratoryStrategy(), max_rounds=max_rounds
    )


def _bfs_distributed(g, root, strategy, mesh, axis_name, max_rounds):
    """Distributed BFS over the nodelet mesh axis.

    State per shard: its slice of the (vertex-major) parent/frontier arrays.
    Vertex-major layout: global vertex v -> shard v // V_p, slot v % V_p
    (block distribution over the padded global order).
    """
    from jax.sharding import PartitionSpec as P_

    p, vp, k = g.adj.shape
    n_pad = p * vp
    vs = n_pad // p  # vertices per shard (block)
    adj_g = _adj_global(g)  # (N_pad, K) -> sharded on rows
    push = strategy.comm == Comm.REMOTE_WRITE

    def body(adj_s):  # adj_s: (vs, K) local adjacency rows
        shard = jax.lax.axis_index(axis_name)
        lo = shard * vs
        vids = lo + jnp.arange(vs, dtype=jnp.int32)
        parents0 = jnp.where(vids == root, jnp.int32(root), UNVISITED)
        frontier0 = vids == root

        def cond(state):
            _, _, it, alive = state
            return jnp.logical_and(alive, it < max_rounds)

        def round_body(state):
            parents, frontier, it, _ = state
            if push:
                # Alg. 2: blind dense push from local state only.
                src = lo + jnp.broadcast_to(
                    jnp.arange(vs, dtype=jnp.int32)[:, None], (vs, k)
                )
                valid = frontier[:, None] & (adj_s >= 0)
                dst = jnp.where(valid, adj_s, 0)
                prop = jnp.where(valid, src, UNVISITED)
                partial_nP = (
                    jnp.full((n_pad,), UNVISITED, dtype=jnp.int32)
                    .at[dst.reshape(-1)]
                    .min(prop.reshape(-1), mode="drop")
                )
                # reduce-scatter(min) == all_to_all + local min: the remote write
                blocks = partial_nP.reshape(p, vs)
                recv = jax.lax.all_to_all(blocks, axis_name, 0, 0, tiled=True)
                nP = jnp.min(recv.reshape(p, vs), axis=0)
            else:
                # Alg. 1: pull everything — gather parents (the per-edge read
                # of P[d] that migrates the thread), expand with the visited
                # filter, gather everyone's partials back (migrate analogue).
                par_full = jax.lax.all_gather(parents, axis_name, tiled=True)
                src = lo + jnp.broadcast_to(
                    jnp.arange(vs, dtype=jnp.int32)[:, None], (vs, k)
                )
                valid = frontier[:, None] & (adj_s >= 0)
                dst = jnp.where(valid, adj_s, 0)
                # the remote read: P[d] == UNVISITED check before the CAS
                valid = valid & (par_full[dst] == UNVISITED)
                prop = jnp.where(valid, src, UNVISITED)
                nP_partial = (
                    jnp.full((n_pad,), UNVISITED, dtype=jnp.int32)
                    .at[dst.reshape(-1)]
                    .min(prop.reshape(-1), mode="drop")
                )
                # claims still must reach the owner: second gather + min
                all_parts = jax.lax.all_gather(nP_partial, axis_name)  # (P, N_pad)
                nP_full = jnp.min(all_parts, axis=0)
                nP = jax.lax.dynamic_slice(nP_full, (lo,), (vs,))
            newly = (parents == UNVISITED) & (nP != UNVISITED)
            parents = jnp.where(newly, nP, parents)
            alive = jax.lax.psum(newly.sum(), axis_name) > 0
            return parents, newly, it + 1, alive

        parents, _, _, _ = jax.lax.while_loop(
            cond, round_body, (parents0, frontier0, 0, jnp.bool_(True))
        )
        return parents

    f = jax.shard_map(
        body, mesh=mesh, in_specs=(P_(axis_name),), out_specs=P_(axis_name),
        check_vma=False,
    )
    return f(adj_g)


# -- paper-model traffic accounting (numpy simulator) -------------------------


@dataclasses.dataclass
class BFSRunStats:
    rounds: int
    edges_traversed: int
    traffic: TrafficStats


def bfs_traffic(g: PartitionedGraph, root: int, strategy: MigratoryStrategy) -> BFSRunStats:
    """Replay BFS in numpy, counting the paper's traffic units.

    migrate (Alg. 1): one thread migration per traversed edge whose
    destination lives on a remote nodelet (read of P[d] moves the thread
    there), plus the hop back ("ping-pong", §7) — counted as 2 migrations.
    remote_write (Alg. 2): one small packet per traversed edge with a remote
    destination; no migrations.
    """
    p, vp, k = g.adj.shape
    adj = np.transpose(np.asarray(g.adj), (1, 0, 2)).reshape(vp * p, k)
    n = g.n_vertices
    owner = np.arange(vp * p) % p  # striped ownership (paper layout)
    parents = np.full(vp * p, -1, dtype=np.int64)
    parents[root] = root
    frontier = np.zeros(vp * p, dtype=bool)
    frontier[root] = True
    migrations = remote_writes = edges = rounds = 0
    while frontier.any():
        rounds += 1
        srcs = np.nonzero(frontier)[0]
        nbrs = adj[srcs]  # (f, K)
        valid = nbrs >= 0
        dst = nbrs[valid]
        src = np.repeat(srcs, valid.sum(axis=1))
        edges += len(dst)
        remote = owner[dst] != owner[src]
        if strategy.comm == Comm.MIGRATE:
            migrations += int(2 * remote.sum())
        else:
            remote_writes += int(remote.sum())
        nP = np.full(vp * p, np.iinfo(np.int64).max)
        np.minimum.at(nP, dst, src)
        newly = (parents == -1) & (nP != np.iinfo(np.int64).max)
        parents[newly] = nP[newly]
        frontier = newly
    return BFSRunStats(
        rounds=rounds,
        edges_traversed=edges,
        traffic=TrafficStats(migrations=migrations, remote_writes=remote_writes),
    )


def teps(n_edges_traversed: int, seconds: float) -> float:
    return n_edges_traversed / max(seconds, 1e-12)


def bfs_bytes_moved(n_edges: int) -> int:
    """Paper §5.2 unit of useful work: every traversed edge reads+writes one
    8-byte word (2 * 8 bytes per edge)."""
    return n_edges * 2 * 8


def bfs_effective_bandwidth(scale: int, seconds: float, edge_factor: int = 16) -> float:
    """Paper §5.2: BW = 16 * 2^scale * 2 * 8 / time = TEPS * 16."""
    return bfs_bytes_moved(edge_factor * (1 << scale)) / max(seconds, 1e-12)


def validate_parents(g: PartitionedGraph, root: int, parents: np.ndarray) -> bool:
    """Graph500-style validation: the root is its own parent, every parent
    edge exists in the graph, and following parents from every reached
    vertex leads to the root (no cycles, no detached subtrees).

    Vectorized over vertices (chunked edge lookups, pointer doubling for
    the root check), so it validates trees of millions of vertices."""
    p, vp, k = g.adj.shape
    adj = np.transpose(np.asarray(g.adj), (1, 0, 2)).reshape(vp * p, k)
    n = g.n_vertices
    parents = np.asarray(parents[:n]).astype(np.int64)
    if parents[root] != root or (parents >= n).any():
        return False
    children = np.flatnonzero(parents >= 0)
    children = children[children != root]
    for lo in range(0, len(children), 1 << 18):
        v = children[lo : lo + (1 << 18)]
        if not (adj[parents[v]] == v[:, None]).any(axis=1).all():
            return False
    # pointer doubling: after ceil(log2 n) + 1 squarings every vertex whose
    # parent chain reaches the root points at it; unreached vertices point
    # at themselves, so a chain through one never does
    anc = np.where(parents >= 0, parents, np.arange(n))
    for _ in range(max(1, n.bit_length()) + 1):
        anc = anc[anc]
    return bool((anc[children] == root).all())
