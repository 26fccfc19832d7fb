"""Plain references the benchmark compares the served answers with.

They share no code with the program and take nothing it made: they read
the benchmark's own host CSR arrays (``bench/gen.py``) and the same ``x``.

- :func:`bfs_parents`: level-synchronous BFS on the host. A vertex's
  parent is the least-numbered neighbour one level closer to the root,
  the tree the configuration states (the deterministic min-merge).
- :func:`spmv`: ``y = A @ x`` in float64.
"""
from __future__ import annotations

import numpy as np


def _frontier_edges(indptr: np.ndarray, indices: np.ndarray, frontier: np.ndarray):
    starts, ends = indptr[frontier], indptr[frontier + 1]
    counts = ends - starts
    src = np.repeat(frontier, counts)
    offsets = np.arange(counts.sum()) - np.repeat(np.cumsum(counts) - counts, counts)
    return src, indices[np.repeat(starts, counts) + offsets]


def bfs_parents(indptr, indices, n: int, root: int, pick=np.minimum) -> np.ndarray:
    """Parent of every vertex (-1 where unreached, ``root`` at the root).

    ``pick`` chooses among the candidate parents of one level:
    ``np.minimum`` gives the stated tree; ``np.maximum`` gives another valid
    BFS tree, the control that breaks the stated tie-break."""
    parents = np.full(n, -1, dtype=np.int64)
    parents[root] = root
    frontier = np.array([root], dtype=np.int64)
    while frontier.size:
        src, dst = _frontier_edges(indptr, indices, frontier)
        fresh = parents[dst] == -1
        src, dst = src[fresh], dst[fresh]
        if not dst.size:
            break
        best = np.full(n, -1 if pick is np.maximum else n, dtype=np.int64)
        pick.at(best, dst, src)
        frontier = np.unique(dst)
        parents[frontier] = best[frontier]
    return parents


def spmv(indptr, indices, data, x: np.ndarray) -> np.ndarray:
    """Float64 CSR product."""
    rows = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))
    return np.bincount(
        rows, weights=data.astype(np.float64) * x.astype(np.float64)[indices],
        minlength=len(indptr) - 1,
    )


def max_rel_err(y: np.ndarray, ref: np.ndarray) -> float:
    """Largest absolute error over the largest reference magnitude."""
    return float(np.max(np.abs(y.astype(np.float64) - ref)) / max(np.max(np.abs(ref)), 1e-300))
