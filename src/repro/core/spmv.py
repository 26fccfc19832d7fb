"""Distributed SpMV with the paper's replication strategy (S1, §3.1/§5.1).

Layout (paper Fig. 2): the row array is striped across ``P`` logical nodelets
(row ``r`` on nodelet ``r % P``); each row's nonzeros live with their row
(jagged arrays -> padded ELL planes per nodelet, see DESIGN.md §2). The input
vector ``x`` is either

- **replicated** on every nodelet (paper's winning strategy): zero per-element
  communication after a one-time broadcast, or
- **striped** (``x[j]`` on nodelet ``j % P``): every nonzero whose column
  lives remotely triggers a thread migration on the Emu == an ``all_gather``
  pull on TPU (the ``migrate`` realization of remote gets).

``grain`` = rows per task (paper Fig. 4): the local path executes row chunks
of ``grain`` rows with ``lax.map`` (sequential across chunks, vector within),
the Pallas kernel uses it as rows-per-program, and the distributed path uses
it as the rows-per-shard block factor.

The local path holds its row chunks K-major, ``(n_chunks, P, K, grain)``,
rows on the minor (lane) axis, and gathers ``x`` once per step over the
whole block. K is the largest row degree (5 for the 2-D Laplacian): with K
minor, a TPU ``(8, 128)`` tile would hold K real lanes in 128. Where K is
longer than the chunk (thousands for an unsplit power-law matrix against a
grain of a few rows) the chunks stay ``(n_chunks, P, grain, K)``: the
longer axis takes the lanes (DESIGN.md §2).

Where padding every row to the longest would hold more than twice the
nonzeros (a hub row of a web graph), :func:`partition_ell` splits the long
rows into pieces of a narrower K (:func:`ell_width`) on the row's own
nodelet, and every substrate folds the pieces' sums back onto their rows
with :func:`fold_pieces`, so the result keeps the ``(P, R_p)`` layout.

This module holds the *algorithm* (one function per substrate:
:func:`spmv_local`, :func:`spmv_mesh`); substrate selection lives in
:mod:`repro.engine` (DESIGN.md §1). :func:`spmv` is a deprecated shim.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..sparse.csr import CSR
from .strategies import MigratoryStrategy, TrafficStats
from .util import ceil_div


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PartitionedELL:
    """Per-nodelet padded ELL planes. Global row r <-> (p=r%P, slot=r//P).

    Without a split (``row_of`` and ``last_ell`` None) ELL row i of a
    nodelet is its slot i. With one, a row longer than K is cut into pieces
    of at most K nonzeros, each an ELL row on the row's own nodelet, in row
    order; ``row_of`` names the slot each ELL row adds into (R_p = ceil(N/P)
    for a padding row), and ``last_ell`` each slot's last ELL row (-1 for an
    empty row or a padding slot), where :func:`fold_pieces` reads its sum."""

    cols: jax.Array  # (P, R_p', K) int32 global col ids, -1 pad
    vals: jax.Array  # (P, R_p', K)
    shape: tuple[int, int]
    row_of: jax.Array | None = None  # (P, R_p') int32, sorted along R_p'
    last_ell: jax.Array | None = None  # (P, R_p) int32, -1 for no ELL row

    def tree_flatten(self):
        return (self.cols, self.vals, self.row_of, self.last_ell), self.shape

    @classmethod
    def tree_unflatten(cls, shape, leaves):
        cols, vals, row_of, last_ell = leaves
        return cls(cols, vals, shape, row_of, last_ell)

    @property
    def P(self) -> int:
        return self.cols.shape[0]

    @property
    def rows_per_nodelet(self) -> int:
        """ELL rows per nodelet, R_p': the rows the kernels' tasks cover."""
        return self.cols.shape[1]

    @property
    def k(self) -> int:
        return self.cols.shape[2]


def ell_width(lens: np.ndarray, p: int) -> int:
    """The ELL width K for rows of ``lens`` nonzeros striped over ``p``
    nodelets, from the row-degree histogram alone (DESIGN.md §2).

    The longest row, where padding every row to it holds at most twice the
    nonzeros. Otherwise the width that minimises padded slots plus pieces:
    a row of L nonzeros takes ceil(L / K) ELL rows, and each ELL row costs
    its K gathered slots and one partial sum folded onto its row. The
    gather costs per slot, padding included (PERF.md §6), so the slots,
    not the nonzeros, set the kernel's time."""
    lens = np.asarray(lens, dtype=np.int64)
    kmax, nnz = int(lens.max(initial=0)), int(lens.sum())
    if p * ceil_div(len(lens), p) * kmax <= 2 * nnz:
        return max(kmax, 1)
    lengths, counts = np.unique(lens[lens > 0], return_counts=True)
    # a width above 2 nnz / rows - 1 costs more than K = 1's 2 nnz
    widths = np.arange(1, min(kmax, 2 * nnz // int(counts.sum())) + 1)
    ell_rows = np.array([(counts * ceil_div(lengths, w)).sum() for w in widths])
    return int(widths[np.argmin((widths + 1) * ell_rows)])


def partition_ell(a: CSR, p: int, k: int | None = None) -> PartitionedELL:
    """Stripe a CSR matrix's rows over ``p`` nodelets as padded ELL planes
    of width ``k`` (default :func:`ell_width`); rows longer than ``k`` are
    split into owner-local pieces (:class:`PartitionedELL`)."""
    indptr = np.asarray(a.indptr).astype(np.int64)
    indices = np.asarray(a.indices)
    data = np.asarray(a.data)
    n = a.n_rows
    lens = np.diff(indptr)
    k = k or ell_width(lens, p)
    split = k < lens.max(initial=0)
    # ELL rows of each row: one for every row unsplit, empty ones too; split,
    # ceil(L / k), none for an empty row
    pieces = ceil_div(lens, k) if split else np.ones(n, dtype=np.int64)
    rp = ceil_div(n, p)
    per_slot = np.zeros(rp * p, dtype=np.int64)
    per_slot[:n] = pieces
    per_slot = per_slot.reshape(rp, p)  # [slot, nodelet], row r at [r // p, r % p]
    first = (np.cumsum(per_slot, axis=0) - per_slot).reshape(-1)  # row r's first ELL row
    rp_ell = max(int(per_slot.sum(axis=0).max()), 1) if split else rp
    rows = np.repeat(np.arange(n), lens)  # each nonzero's row
    pos = np.arange(len(rows)) - np.repeat(indptr[:-1], lens)  # its place in the row
    ell_row = first[rows] + pos // k
    cols = np.full((p, rp_ell, k), -1, dtype=np.int32)
    vals = np.zeros((p, rp_ell, k), dtype=data.dtype)
    cols[rows % p, ell_row, pos % k] = indices
    vals[rows % p, ell_row, pos % k] = data
    planes = {"cols": jnp.asarray(cols), "vals": jnp.asarray(vals), "shape": a.shape}
    if not split:
        return PartitionedELL(**planes)
    owner = np.repeat(np.arange(n), pieces)  # each ELL row's row
    piece = np.arange(len(owner)) - np.repeat(np.cumsum(pieces) - pieces, pieces)
    row_of = np.full((p, rp_ell), rp, dtype=np.int32)
    row_of[owner % p, first[owner] + piece] = owner // p
    (held,) = np.nonzero(pieces)
    last_ell = np.full((p, rp), -1, dtype=np.int32)
    last_ell[held % p, held // p] = first[held] + pieces[held] - 1
    return PartitionedELL(**planes, row_of=jnp.asarray(row_of), last_ell=jnp.asarray(last_ell))


def spmv_layout_counts(a: PartitionedELL) -> dict[str, int]:
    """``spmv.slots``: the padded index slots one product gathers;
    ``spmv.pieces``: the ELL rows the split adds, beyond one per split row."""
    pieces = 0
    if a.row_of is not None:
        used = int((np.asarray(a.row_of) < ceil_div(a.shape[0], a.P)).sum())
        pieces = used - int((np.asarray(a.last_ell) >= 0).sum())
    return {"spmv.slots": int(a.cols.size), "spmv.pieces": pieces}


def stripe_vector(x: jax.Array, p: int) -> jax.Array:
    """(N,) -> (P, N_p) striped layout, x[j] at (j % p, j // p). Pads with 0."""
    n = x.shape[0]
    npp = ceil_div(n, p)
    xp = jnp.pad(x, (0, npp * p - n))
    return xp.reshape(npp, p).T


def unstripe_vector(xs: jax.Array, n: int) -> jax.Array:
    p, npp = xs.shape
    return xs.T.reshape(p * npp)[:n]


def _k_major(rows: int, k: int) -> bool:
    """Whether a block of ``rows`` ELL rows of width ``k`` is held K-major,
    ``(..., K, rows)``: the longer of the two axes takes the minor (lane) axis.
    On a v5e the K-major chunks ran 10% faster at K 5, grain 1024, and 5%
    slower at K 3,626, grain 6 (PERF.md §6)."""
    return rows >= k


def fold_pieces(y: jax.Array, a: PartitionedELL) -> jax.Array:
    """Add each ELL row's sum ``y`` ``(P, R_p')`` onto the slot
    ``a.row_of`` names: ``(P, R_p)``. Without a split (``row_of`` None)
    ``y`` is already the result and nothing is added to the program.

    A row's pieces are contiguous and in row order, so its sum is a
    segmented inclusive scan along R_p' read at its last ELL row
    (``a.last_ell``): ceil(log2 R_p') Hillis-Steele steps, each adding the
    sum ``s`` ELL rows back where ``row_of`` equals there (``row_of`` is
    sorted, so equal means the same row), then one gather. XLA lowers a
    segment sum to a scatter-add, which cost 9.25 ms a product on a v5e at
    Table 3's Stanford (PERF.md §5)."""
    if a.row_of is None:
        return y
    row_of, s = a.row_of, 1
    while s < y.shape[1]:
        back = ((0, 0), (s, 0))
        same = jnp.pad(row_of[:, :-s], back, constant_values=-1) == row_of
        y = y + jnp.where(same, jnp.pad(y[:, :-s], back), 0)
        s *= 2
    last = a.last_ell
    return jnp.where(last >= 0, jnp.take_along_axis(y, jnp.maximum(last, 0), axis=1), 0)


def _rows_kernel(cols, vals, x_full, k_major: bool):
    """Compute a block of rows: one gather of ``x`` over the whole block,
    masked where ``cols`` pads with -1, summed over the ELL columns.
    ``cols``/``vals`` are ``(..., K, rows)`` if ``k_major`` else ``(..., rows, K)``."""
    xg = jnp.take(x_full, jnp.maximum(cols, 0), axis=0)
    return jnp.sum(jnp.where(cols >= 0, vals * xg, 0), axis=-2 if k_major else -1)


@partial(jax.jit, static_argnames=("grain",))
def _spmv_local(a: PartitionedELL, x_full: jax.Array, grain: int) -> jax.Array:
    """Single-device semantics path: ``lax.map`` over row chunks of ``grain``
    rows (the task structure the Emu sees), all nodelets in each step. The
    chunks are ``(n_chunks, P, K, grain)`` when ``grain >= K`` (rows on the
    lanes), else ``(n_chunks, P, grain, K)``."""
    P, rp, k = a.cols.shape
    g = max(1, min(grain, rp))
    n_chunks = ceil_div(rp, g)
    pad = n_chunks * g - rp
    k_major = _k_major(g, k)

    def chunks(plane, fill):
        plane = jnp.pad(plane, ((0, 0), (0, pad), (0, 0)), constant_values=fill)
        if k_major:
            return plane.transpose(0, 2, 1).reshape(P, k, n_chunks, g).transpose(2, 0, 1, 3)
        return plane.reshape(P, n_chunks, g, k).transpose(1, 0, 2, 3)

    y = jax.lax.map(
        lambda cv: _rows_kernel(cv[0], cv[1], x_full, k_major),
        (chunks(a.cols, -1), chunks(a.vals, 0)),
    )  # (n_chunks, P, g)
    y = y.transpose(1, 0, 2).reshape(P, n_chunks * g)[:, :rp]
    return fold_pieces(y, a)


def spmv_local(
    a: PartitionedELL, x: jax.Array, strategy: MigratoryStrategy
) -> jax.Array:
    """``local`` substrate: single-device emulation with the distributed
    path's semantics. ``x``: full (N,) if ``strategy.replicate_x`` else
    striped (P, N_p). Returns y in striped (P, R_p) layout."""
    grain = strategy.dynamic_grain(a.rows_per_nodelet)
    x_full = x if strategy.replicate_x else unstripe_vector(x, a.shape[1])
    return _spmv_local(a, x_full, grain)


def spmv_mesh(
    a: PartitionedELL,
    x: jax.Array,
    strategy: MigratoryStrategy,
    mesh: jax.sharding.Mesh,
    axis_name: str = "nodelet",
) -> jax.Array:
    """``mesh`` substrate: nodelet planes sharded over ``axis_name``. The
    non-replicated path pulls ``x`` with an ``all_gather`` (the migrate
    analogue). Same input/output conventions as :func:`spmv_local`."""
    from jax.sharding import PartitionSpec as P_

    n = a.shape[1]
    k_major = _k_major(*a.cols.shape[1:])

    def rows(a_p: PartitionedELL, x_full):
        c, v = (a_p.cols[0].T, a_p.vals[0].T) if k_major else (a_p.cols[0], a_p.vals[0])
        return fold_pieces(_rows_kernel(c, v, x_full, k_major)[None], a_p)

    if strategy.replicate_x:
        # x already local everywhere: pure local compute (paper's S1 win)
        body = rows
        in_specs = (P_(axis_name), P_())
    else:

        def body(a_p, x_striped):
            # migrate/pull: gather the striped vector (thread-migration analogue)
            xg = jax.lax.all_gather(x_striped, axis_name)  # (P, 1, N_p)
            return rows(a_p, unstripe_vector(xg[:, 0, :], n))

        in_specs = (P_(axis_name), P_(axis_name))

    f = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=P_(axis_name),
        check_vma=False,
    )
    return f(a, x)


def spmv(
    a: PartitionedELL,
    x: jax.Array,
    strategy: MigratoryStrategy,
    *,
    mesh: jax.sharding.Mesh | None = None,
    axis_name: str = "nodelet",
) -> jax.Array:
    """Deprecated shim — use ``repro.engine.run(SpMVOp(), ...)`` instead.

    Kept so pre-engine call sites keep working: forwards to the engine's
    substrate resolution (``local`` without a mesh, ``mesh`` with one).
    """
    from ..engine.substrate import substrate_for_mesh

    return substrate_for_mesh(mesh, axis_name).kernel("spmv")(
        a, x, strategy=strategy
    )


def gather_result(y_striped: jax.Array, n: int) -> jax.Array:
    """(P, R_p) striped result -> global (N,) row order."""
    return unstripe_vector(y_striped, n)


def spmv_traffic(a: PartitionedELL, strategy: MigratoryStrategy) -> TrafficStats:
    """Paper-model traffic: striped x costs one migration per nonzero whose
    column owner differs from the row's nodelet; replication costs none."""
    cols = np.asarray(a.cols)
    P = a.P
    if strategy.replicate_x:
        return TrafficStats(migrations=0, remote_writes=0)
    p_idx = np.arange(P)[:, None, None]
    remote = (cols >= 0) & ((cols % P) != p_idx)
    return TrafficStats(migrations=int(remote.sum()), remote_writes=0)


def spmv_bytes_moved(a: PartitionedELL, n: int, dtype_bytes: int = 4) -> int:
    """Bytes the paper's §5.1 bandwidth formula charges one SpMV with:
    sizeof(A) (true nonzeros: value + column index) + sizeof(x) + sizeof(y).
    """
    nnz = int((np.asarray(a.cols) >= 0).sum())
    return nnz * (dtype_bytes + 4) + (n + a.shape[0]) * dtype_bytes


def effective_bandwidth(a: PartitionedELL, n: int, seconds: float, dtype_bytes: int = 4) -> float:
    """Paper §5.1 metric: (sizeof(A) + sizeof(x) + sizeof(y)) / time."""
    return spmv_bytes_moved(a, n, dtype_bytes) / max(seconds, 1e-12)
