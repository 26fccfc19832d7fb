"""Seeded input generators of the benchmark, independent of the program.

Vectorised copies of the Graph500 Kronecker (R-MAT) edge generator and the
5-point 2-D Laplacian, returning plain numpy CSR arrays. The program's own
layout step (``partition_graph``, ``partition_ell``) is applied to these by
the cell kinds, because users pay for it.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class HostCSR:
    """CSR arrays on the host: ``indptr`` int64, ``indices`` int32."""

    indptr: np.ndarray
    indices: np.ndarray
    data: np.ndarray
    n: int

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)


def _csr_from_sorted_keys(keys: np.ndarray, n: int, data: np.ndarray) -> HostCSR:
    rows = keys // n
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return HostCSR(indptr, (keys % n).astype(np.int32), data, n)


def kronecker_edges(
    rng: np.random.Generator, scale: int, edge_factor: int, a: float, b: float, c: float
) -> np.ndarray:
    """Graph500 Kronecker generator: ``edge_factor * 2**scale`` edges, each
    placed by ``scale`` independent quadrant draws with probabilities
    a, b, c, 1-a-b-c. Returns (m, 2) int64."""
    m = edge_factor << scale
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for _ in range(scale):
        u = rng.random(m)
        src = (src << 1) | (u >= a + b)
        dst = (dst << 1) | (((u >= a) & (u < a + b)) | (u >= a + b + c))
    return np.stack([src, dst], axis=1)


def undirected_csr(edges: np.ndarray, n: int) -> HostCSR:
    """Symmetric adjacency without self loops or duplicate edges."""
    e = edges[edges[:, 0] != edges[:, 1]]
    keys = np.unique(np.concatenate([e[:, 0] * n + e[:, 1], e[:, 1] * n + e[:, 0]]))
    return _csr_from_sorted_keys(keys, n, np.ones(len(keys), dtype=np.float32))


def relabel(edges: np.ndarray, n: int, rng: np.random.Generator, keys: np.ndarray) -> np.ndarray:
    """Graph500's random vertex relabelling, drawn so that search key ``i``
    gets label ``i``: the labels of the keys are then the same for every
    seed, while the graph and the keys themselves come from the seed."""
    perm = rng.permutation(n)
    # swap so that perm[keys[i]] == i, keeping perm a permutation
    for i, key in enumerate(keys):
        j = int(np.flatnonzero(perm == i)[0])
        perm[j], perm[key] = perm[key], perm[j]
    return perm[edges]


def laplacian_2d(n: int) -> HostCSR:
    """5-point stencil on an n x n grid: (n^2, n^2), 4 on the diagonal and
    -1 for each grid neighbour, float32."""
    big = n * n
    idx = np.arange(big, dtype=np.int64)
    r, c = np.divmod(idx, n)
    keys, vals = [idx * big + idx], [np.full(big, 4.0, dtype=np.float32)]
    for dr, dc in ((-1, 0), (1, 0), (0, -1), (0, 1)):
        ok = (r + dr >= 0) & (r + dr < n) & (c + dc >= 0) & (c + dc < n)
        keys.append(idx[ok] * big + (idx[ok] + dr * n + dc))
        vals.append(np.full(int(ok.sum()), -1.0, dtype=np.float32))
    keys = np.concatenate(keys)
    order = np.argsort(keys, kind="stable")
    return _csr_from_sorted_keys(keys[order], big, np.concatenate(vals)[order])
