"""Padded ELL / blocked-ELL formats.

The TPU re-think of the Emu's fine-grained jagged rows (DESIGN.md §2): the
Chick's NCDRAM is efficient at <64 B accesses, the TPU is not — so rows are
padded/blocked into MXU/VPU-aligned tiles. ``ELL`` is the dense-padded format
consumed by the Pallas SpMV kernel; padding slots carry ``col = -1`` and
``val = 0`` so they are arithmetic no-ops.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .csr import CSR


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ELL:
    """ELLPACK: (n_rows, k) column-index / value planes, row-major padded."""

    cols: jax.Array  # (n_rows, k) int32, -1 = padding
    vals: jax.Array  # (n_rows, k)
    shape: tuple[int, int]  # static logical shape

    def tree_flatten(self):
        return (self.cols, self.vals), self.shape

    @classmethod
    def tree_unflatten(cls, shape, leaves):
        return cls(*leaves, shape=shape)

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def k(self) -> int:
        return self.cols.shape[1]

    @property
    def nnz_padded(self) -> int:
        return self.cols.shape[0] * self.cols.shape[1]


def ell_from_csr(a: CSR, k: int | None = None, row_pad_to: int = 1) -> ELL:
    """Convert CSR -> padded ELL. ``k`` defaults to max row degree.

    ``row_pad_to`` pads the row count (for tile-aligned kernels).
    """
    indptr = np.asarray(a.indptr)
    indices = np.asarray(a.indices)
    data = np.asarray(a.data)
    n = a.n_rows
    lens = indptr[1:] - indptr[:-1]
    kmax = int(lens.max()) if n else 0
    if k is None:
        k = max(kmax, 1)
    if kmax > k:
        raise ValueError(f"k={k} < max row degree {kmax}; split rows first")
    n_pad = -(-n // row_pad_to) * row_pad_to
    cols = np.full((n_pad, k), -1, dtype=np.int32)
    vals = np.zeros((n_pad, k), dtype=data.dtype)
    for r in range(n):
        s, e = indptr[r], indptr[r + 1]
        cols[r, : e - s] = indices[s:e]
        vals[r, : e - s] = data[s:e]
    return ELL(cols=jnp.asarray(cols), vals=jnp.asarray(vals), shape=a.shape)


def spmv_ell_ref(a: ELL, x: jax.Array) -> jax.Array:
    """Reference ELL SpMV: masked gather + row-sum (pure jnp oracle)."""
    mask = a.cols >= 0
    xg = jnp.take(x, jnp.maximum(a.cols, 0), axis=0)
    y = jnp.sum(jnp.where(mask, a.vals * xg, 0), axis=1)
    return y[: a.n_rows]
