"""GSANA parallel similarity computation (paper §3.3, results §5.3).

Schemes (Alg. 3-5): ``ALL`` spawns one task per non-empty bucket B ∈ QT2 and
compares its vertices against all neighbor buckets B' ∈ QT1.Neig(B);
``PAIR`` spawns one task per ⟨B, B'⟩ pair (finer grain, better balance, more
merge work). Both compute identical top-k results.

Layouts (§3.3.2): ``BLK`` partitions vertices by ID and buckets round-robin
(placement-oblivious); ``HCB`` sorts buckets in Hilbert order and assigns
contiguous runs to nodelets with an edge-balancing pass, co-locating each
vertex (and its metadata) with its bucket.

On TPU the compute is a vmap over tasks; the scheme/layout choice drives the
*placement and traffic model* (modeled makespan + migrations, the paper's
§5.3 metrics) which benchmarks report next to measured wall time.

Similarity σ(u, v) (paper §5.3): degree Δ, vertex type τ, adjacent vertex
types τ_V, adjacent edge types τ_E, vertex attributes C_V — the last three
compare neighborhoods via sorted fixed-width arrays.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .gsana_data import Buckets, VertexSet, neighbor_buckets
from .hilbert import hilbert_order_of_buckets
from .strategies import Layout, Scheme, TrafficStats

NEG = -jnp.inf


# -- σ: the five similarity metrics -------------------------------------------


def _hist(a: jax.Array, vocab: int) -> jax.Array:
    """(..., K) sorted padded (-1) ids -> (..., vocab) multiset histogram.

    TPU-native reformulation (DESIGN.md §2): the Emu walks sorted arrays with
    fine-grained reads; the TPU turns the multiset into a dense histogram
    (one-hot reduce, VPU-aligned) so the intersection becomes an elementwise
    min + reduce.
    """
    oh = jax.nn.one_hot(jnp.where(a >= 0, a, vocab), vocab + 1, dtype=jnp.float32)
    return oh.sum(axis=-2)[..., :vocab]


def _overlap(a: jax.Array, b: jax.Array, vocab: int) -> jax.Array:
    """Multiset overlap |a ∩ b| / max(|a|, |b|) of sorted padded arrays.

    a: (A, Ka), b: (B, Kb) -> (A, B).
    """
    ha = _hist(a, vocab)  # (A, T)
    hb = _hist(b, vocab)  # (B, T)
    inter = jnp.minimum(ha[:, None, :], hb[None, :, :]).sum(-1)
    na = (a >= 0).sum(-1).astype(jnp.float32)
    nb = (b >= 0).sum(-1).astype(jnp.float32)
    denom = jnp.maximum(jnp.maximum(na[:, None], nb[None, :]), 1.0)
    return inter / denom


# vocab sizes (n_types, n_etypes, n_attr_vocab) for the histogram overlap;
# must cover the generator's vocabularies (gsana_data defaults: 8, 6, 64).
DEFAULT_VOCAB = (16, 16, 64)


def similarity_block(
    vs2: VertexSet, vs1: VertexSet, v_idx: jax.Array, u_idx: jax.Array,
    vocab: tuple[int, int, int] = DEFAULT_VOCAB,
) -> jax.Array:
    """σ for all pairs (v ∈ v_idx from G2) x (u ∈ u_idx from G1).

    v_idx: (A,) int32 (-1 pad), u_idx: (B,) int32 (-1 pad) -> (A, B) scores,
    -inf on padded slots.
    """
    vi = jnp.maximum(v_idx, 0)
    ui = jnp.maximum(u_idx, 0)
    dv = vs2.deg[vi].astype(jnp.float32)
    du = vs1.deg[ui].astype(jnp.float32)
    s_deg = 1.0 / (1.0 + jnp.abs(dv[:, None] - du[None, :]))  # Δ
    s_typ = (vs2.vtype[vi][:, None] == vs1.vtype[ui][None, :]).astype(jnp.float32)  # τ
    s_nt = _overlap(vs2.ntypes[vi], vs1.ntypes[ui], vocab[0])  # τ_V
    s_et = _overlap(vs2.etypes[vi], vs1.etypes[ui], vocab[1])  # τ_E
    s_at = _overlap(vs2.attrs[vi], vs1.attrs[ui], vocab[2])  # C_V
    score = 0.2 * (s_deg + s_typ + s_nt + s_et + s_at)
    valid = (v_idx >= 0)[:, None] & (u_idx >= 0)[None, :]
    return jnp.where(valid, score, NEG)


# -- parallel similarity computation (ALL / PAIR) ------------------------------
#
# Per-task closures are shared by the local (vmap over all tasks) and mesh
# (shard_map over per-nodelet task slices) substrates, so both produce
# bit-identical numbers — only the execution placement differs.


def _all_task(vs1: VertexSet, vs2: VertexSet, b1: Buckets, b2: Buckets, nb, k: int):
    """One ALL task (Alg. 3+4): bucket B ∈ QT2 vs all its neighbor buckets."""
    cap1 = b1.cap

    def task(bid):
        v_idx = b2.vid[bid]  # (cap2,)
        nbs = nb[bid]  # (9,)
        u_idx = jnp.where(nbs[:, None] >= 0, b1.vid[jnp.maximum(nbs, 0)], -1)
        u_idx = u_idx.reshape(9 * cap1)
        s = similarity_block(vs2, vs1, v_idx, u_idx)  # (cap2, 9*cap1)
        sc, loc = jax.lax.top_k(s, k)
        return jnp.where(sc > NEG, u_idx[loc], -1), sc

    return task


def _pair_task(vs1: VertexSet, vs2: VertexSet, b1: Buckets, b2: Buckets, nb, kk: int):
    """One PAIR task (Alg. 3+5): a single ⟨B, B'⟩ bucket pair."""

    def task(bid, j):
        v_idx = b2.vid[bid]
        nbs = nb[bid, j]
        u_idx = jnp.where(nbs >= 0, b1.vid[jnp.maximum(nbs, 0)], -1)
        s = similarity_block(vs2, vs1, v_idx, u_idx)  # (cap2, cap1)
        sc, loc = jax.lax.top_k(s, kk)
        return jnp.where(sc > NEG, u_idx[loc], -1), sc

    return task


def _merge_pair_topk(cands, scores, grid2: int, k: int):
    """Alg. 5's Merge: per-pair top-k lists -> per-bucket top-k."""
    kk = scores.shape[-1]
    cands = cands.reshape(grid2, 9, -1, kk).transpose(0, 2, 1, 3).reshape(grid2, -1, 9 * kk)
    scores = scores.reshape(grid2, 9, -1, kk).transpose(0, 2, 1, 3).reshape(grid2, -1, 9 * kk)
    sc, loc = jax.lax.top_k(scores, k)  # merge
    cand = jnp.take_along_axis(cands, loc, axis=-1)
    return jnp.where(sc > NEG, cand, -1), sc


@partial(jax.jit, static_argnames=("k",))
def compute_similarity_all(
    vs1: VertexSet, vs2: VertexSet, b1: Buckets, b2: Buckets, nb: jax.Array, k: int
):
    """ALL scheme: one task per bucket B ∈ QT2.

    Returns (cand (G², cap, k) global u ids, score (G², cap, k)).
    """
    task = _all_task(vs1, vs2, b1, b2, nb, k)
    return jax.vmap(task)(jnp.arange(b2.grid * b2.grid))


@partial(jax.jit, static_argnames=("k",))
def compute_similarity_pair(
    vs1: VertexSet, vs2: VertexSet, b1: Buckets, b2: Buckets, nb: jax.Array, k: int
):
    """PAIR scheme: one task per ⟨B, B'⟩ bucket pair + merge. Same results
    as ALL."""
    kk = min(k, b1.cap)  # per-pair priority-list width (Alg. 5)
    task = _pair_task(vs1, vs2, b1, b2, nb, kk)
    grid2 = b2.grid * b2.grid
    bids = jnp.repeat(jnp.arange(grid2), 9)
    js = jnp.tile(jnp.arange(9), grid2)
    cands, scores = jax.vmap(task)(bids, js)  # (G²*9, cap2, kk)
    return _merge_pair_topk(cands, scores, grid2, k)


def _scatter_vertex_major(cand_b, score_b, b2: Buckets, n2: int, k: int):
    """Bucket-major (G², cap, k) results -> per-vertex (n2, k) arrays."""
    vid = b2.vid.reshape(-1)
    ok = vid >= 0
    cand = jnp.zeros((n2, k), dtype=jnp.int32).at[jnp.where(ok, vid, 0)].set(
        jnp.where(ok[:, None], cand_b.reshape(-1, k), 0), mode="drop"
    )
    score = jnp.full((n2, k), NEG).at[jnp.where(ok, vid, 0)].set(
        jnp.where(ok[:, None], score_b.reshape(-1, k), NEG), mode="drop"
    )
    return cand, score


def compute_similarity(
    vs1: VertexSet, vs2: VertexSet, b1: Buckets, b2: Buckets, k: int = 4,
    scheme: Scheme = Scheme.PAIR,
):
    """``local`` substrate: top-k alignment candidates for every v ∈ V2.
    Returns per-vertex arrays (n2, k) cand / score."""
    nb = jnp.asarray(neighbor_buckets(b2.grid))
    if scheme == Scheme.ALL:
        cand_b, score_b = compute_similarity_all(vs1, vs2, b1, b2, nb, k)
    else:
        cand_b, score_b = compute_similarity_pair(vs1, vs2, b1, b2, nb, k)
    return _scatter_vertex_major(cand_b, score_b, b2, vs2.n, k)


def compute_similarity_mesh(
    vs1: VertexSet, vs2: VertexSet, b1: Buckets, b2: Buckets, k: int = 4,
    scheme: Scheme = Scheme.PAIR, *, mesh: jax.sharding.Mesh,
    axis_name: str = "nodelet",
):
    """``mesh`` substrate: the same task set sharded over ``axis_name``.

    Bucket metadata is replicated (the shared QT plane); each nodelet runs
    its slice of the task list — compute moves to tasks, which is why the
    scheme/layout choice shows up in the *traffic model*, not in collectives.
    Tasks are padded to a multiple of the axis size with repeats of task 0
    (sliced off afterwards). Results are bit-identical to the local substrate.
    """
    from jax.sharding import PartitionSpec as P_

    from .util import round_up

    nb = jnp.asarray(neighbor_buckets(b2.grid))
    p = mesh.shape[axis_name]
    grid2 = b2.grid * b2.grid
    if scheme == Scheme.ALL:
        task = _all_task(vs1, vs2, b1, b2, nb, k)
        n_tasks = round_up(grid2, p)
        ids = jnp.minimum(jnp.arange(n_tasks, dtype=jnp.int32), grid2 - 1)
        f = jax.shard_map(
            lambda s: jax.vmap(task)(s), mesh=mesh, in_specs=P_(axis_name),
            out_specs=P_(axis_name), check_vma=False,
        )
        cand_b, score_b = f(ids)
        cand_b, score_b = cand_b[:grid2], score_b[:grid2]
    else:
        kk = min(k, b1.cap)
        task = _pair_task(vs1, vs2, b1, b2, nb, kk)
        n_pairs = grid2 * 9
        pad = round_up(n_pairs, p) - n_pairs
        bids = jnp.pad(jnp.repeat(jnp.arange(grid2), 9), (0, pad))
        js = jnp.pad(jnp.tile(jnp.arange(9), grid2), (0, pad))
        f = jax.shard_map(
            lambda b, j: jax.vmap(task)(b, j), mesh=mesh,
            in_specs=(P_(axis_name), P_(axis_name)), out_specs=P_(axis_name),
            check_vma=False,
        )
        cands, scores = f(bids, js)
        cand_b, score_b = _merge_pair_topk(cands[:n_pairs], scores[:n_pairs], grid2, k)
    return _scatter_vertex_major(cand_b, score_b, b2, vs2.n, k)


def recall_at_k(cand: jax.Array, pi: np.ndarray) -> float:
    """Fraction of v ∈ V2 whose ground-truth partner is among its candidates."""
    truth = np.empty(len(pi), dtype=np.int64)  # truth[v2] = v1
    truth[pi] = np.arange(len(pi))
    hits = (np.asarray(cand) == truth[:, None]).any(axis=1)
    return float(hits.mean())


# -- layouts (BLK / HCB) and the placement/traffic model ----------------------


@dataclasses.dataclass(frozen=True)
class Placement:
    bucket_owner: np.ndarray  # (G²,) nodelet of each bucket (shared plane)
    vertex_owner1: np.ndarray  # (n1,)
    vertex_owner2: np.ndarray  # (n2,)


def layout_blk(b1: Buckets, b2: Buckets, n1: int, n2: int, p: int) -> Placement:
    """BLK: vertices by ID blocks, buckets round-robin — placement-oblivious."""
    grid2 = b1.grid * b1.grid
    return Placement(
        bucket_owner=np.arange(grid2) % p,
        vertex_owner1=(np.arange(n1) * p) // max(n1, 1),
        vertex_owner2=(np.arange(n2) * p) // max(n2, 1),
    )


def layout_hcb(b1: Buckets, b2: Buckets, p: int) -> Placement:
    """HCB: buckets in Hilbert order, contiguous runs per nodelet, balanced by
    estimated comparison load (the paper's edges-per-nodelet balancing)."""
    grid = b1.grid
    ranks = hilbert_order_of_buckets(grid)  # bucket -> hilbert rank
    order = np.argsort(ranks)  # rank -> bucket id
    nb = neighbor_buckets(grid)
    c1 = np.asarray(b1.count, dtype=np.int64)
    c2 = np.asarray(b2.count, dtype=np.int64)
    load = np.zeros(grid * grid, dtype=np.int64)
    for b in range(grid * grid):
        ns = nb[b]
        load[b] = c2[b] * c1[ns[ns >= 0]].sum()
    # greedy prefix split of the Hilbert sequence into p balanced segments
    total = load[order].sum()
    target = max(total / p, 1)
    owner = np.zeros(grid * grid, dtype=np.int64)
    acc, seg = 0, 0
    for rank_pos, b in enumerate(order):
        owner[b] = seg
        acc += load[b]
        if acc >= target * (seg + 1) and seg < p - 1:
            seg += 1
    vid1 = np.asarray(b1.vid)
    vid2 = np.asarray(b2.vid)
    n1 = int(vid1.max()) + 1 if (vid1 >= 0).any() else 0
    n2 = int(vid2.max()) + 1 if (vid2 >= 0).any() else 0
    vo1 = np.zeros(n1, dtype=np.int64)
    vo2 = np.zeros(n2, dtype=np.int64)
    for b in range(grid * grid):
        vs = vid1[b][vid1[b] >= 0]
        vo1[vs] = owner[b]
        vs = vid2[b][vid2[b] >= 0]
        vo2[vs] = owner[b]
    return Placement(bucket_owner=owner, vertex_owner1=vo1, vertex_owner2=vo2)


@dataclasses.dataclass
class PlanStats:
    """Modeled execution statistics for a (layout x scheme) configuration."""

    total_comparisons: int
    makespan: float  # modeled parallel time (comparison units)
    speedup_model: float  # total / makespan
    traffic: TrafficStats
    rw_total: int  # paper's Σ RW(σ(u,v)) read/write volume (words)


def rw_sigma(deg_u: np.ndarray, deg_v: np.ndarray, ka_u: np.ndarray, ka_v: np.ndarray):
    """Paper §5.3: RW(σ) = RW(τ)+RW(Δ)+RW(τ_V)+RW(τ_E)+RW(C_V)
    = 4 + 4 + (|N(u)|+|N(v)|+2) + (|N(u)|+|N(v)|+2) + (|A(u)|+|A(v)|+2)."""
    return 8 + 2 * (deg_u + deg_v + 2) + (ka_u + ka_v + 2)


def plan_stats(
    vs1: VertexSet, vs2: VertexSet, b1: Buckets, b2: Buckets,
    placement: Placement, scheme: Scheme, p: int, threads_per_nodelet: int = 64,
    migration_penalty: float = 0.3,
) -> PlanStats:
    """Replay the task schedule in numpy with the paper's cost model.

    Task cost = comparisons (+ penalty per remote-side read); tasks run on the
    owner nodelet of their QT2 bucket; within a nodelet, tasks are spread
    LPT-greedily over its worker threads. Makespan = max worker finish time.
    """
    grid = b2.grid
    nb = neighbor_buckets(grid)
    c1 = np.asarray(b1.count, dtype=np.int64)
    c2 = np.asarray(b2.count, dtype=np.int64)
    deg1 = np.asarray(vs1.deg, dtype=np.int64)
    deg2 = np.asarray(vs2.deg, dtype=np.int64)
    na1 = (np.asarray(vs1.attrs) >= 0).sum(axis=1)
    na2 = (np.asarray(vs2.attrs) >= 0).sum(axis=1)
    vid1 = np.asarray(b1.vid)
    vid2 = np.asarray(b2.vid)

    tasks: list[tuple[int, float]] = []  # (nodelet, cost)
    migrations = 0
    rw_total = 0
    total_cmp = 0
    for b in range(grid * grid):
        if c2[b] == 0:
            continue
        home = int(placement.bucket_owner[b])
        v_ids = vid2[b][vid2[b] >= 0]
        v_remote = (placement.vertex_owner2[v_ids] != home).sum()
        pair_costs = []
        for bp in nb[b]:
            if bp < 0 or c1[bp] == 0:
                continue
            u_ids = vid1[bp][vid1[bp] >= 0]
            cmp_count = len(v_ids) * len(u_ids)
            total_cmp += cmp_count
            rw = rw_sigma(
                deg1[u_ids][None, :], deg2[v_ids][:, None],
                na1[u_ids][None, :], na2[v_ids][:, None],
            ).sum()
            rw_total += int(rw)
            u_remote = (placement.vertex_owner1[u_ids] != home).sum()
            # each comparison touching a remote-side vertex migrates there+back
            mig = len(v_ids) * int(u_remote) + int(v_remote) * len(u_ids)
            migrations += mig
            cost = cmp_count + migration_penalty * mig
            pair_costs.append(cost)
        if not pair_costs:
            continue
        if scheme == Scheme.ALL:
            tasks.append((home, float(sum(pair_costs))))
        else:
            tasks.extend((home, float(cs)) for cs in pair_costs)

    # LPT within each nodelet's thread pool
    finish = np.zeros((p, threads_per_nodelet))
    for home, cost in sorted(tasks, key=lambda t: -t[1]):
        w = int(np.argmin(finish[home]))
        finish[home, w] += cost
    makespan = float(finish.max()) if tasks else 0.0
    total_cost = float(sum(c for _, c in tasks))
    return PlanStats(
        total_comparisons=total_cmp,
        makespan=max(makespan, 1e-9),
        speedup_model=total_cost / max(makespan, 1e-9),
        traffic=TrafficStats(migrations=int(migrations)),
        rw_total=int(rw_total),
    )


def gsana_rw_bytes(
    vs1: VertexSet, vs2: VertexSet, b1: Buckets, b2: Buckets,
    word_bytes: int = 8,
) -> int:
    """Paper §5.3 useful-work volume: Σ_tasks (|B| + |B||B'| + ΣΣ RW(σ)) × sizeof(u)."""
    grid = b2.grid
    nb = neighbor_buckets(grid)
    c1 = np.asarray(b1.count, dtype=np.int64)
    c2 = np.asarray(b2.count, dtype=np.int64)
    deg1 = np.asarray(vs1.deg, dtype=np.int64)
    deg2 = np.asarray(vs2.deg, dtype=np.int64)
    na1 = (np.asarray(vs1.attrs) >= 0).sum(axis=1)
    na2 = (np.asarray(vs2.attrs) >= 0).sum(axis=1)
    vid1 = np.asarray(b1.vid)
    vid2 = np.asarray(b2.vid)
    words = 0
    for b in range(grid * grid):
        if c2[b] == 0:
            continue
        v_ids = vid2[b][vid2[b] >= 0]
        for bp in nb[b]:
            if bp < 0 or c1[bp] == 0:
                continue
            u_ids = vid1[bp][vid1[bp] >= 0]
            rw = rw_sigma(
                deg1[u_ids][None, :], deg2[v_ids][:, None],
                na1[u_ids][None, :], na2[v_ids][:, None],
            ).sum()
            words += int(c2[b]) + int(c2[b]) * int(c1[bp]) + int(rw)
    return words * word_bytes


def gsana_effective_bw(
    vs1: VertexSet, vs2: VertexSet, b1: Buckets, b2: Buckets, seconds: float,
    word_bytes: int = 8,
) -> float:
    """Paper §5.3 bandwidth: the RW-model volume over wall time."""
    return gsana_rw_bytes(vs1, vs2, b1, b2, word_bytes) / max(seconds, 1e-12)
