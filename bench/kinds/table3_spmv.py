"""Table 3's Stanford web graph at the paper's size, SpMV served through
``EngineService`` as a PageRank-style power iteration.

The matrix is drawn once from the configuration's ``matrix_seed`` (the same
in every run, as a deployment multiplies by one graph): ``rows`` rows whose
lengths follow a power law truncated at ``longest_row``, with exponent
``degree_exponent`` fitted so that they hold ``nonzeros`` in all, one row at
exactly ``longest_row``, and distinct columns drawn uniformly in each row.
The program's ``partition_ell`` lays it out, splitting the hub rows into
owner-local pieces; ``--seed`` draws the pool of ``x``. Request ``i``
multiplies by ``x[i % x_pool]`` with a new ``SpMVInputs``, ``grain`` ELL
rows per task; the sample of answers and the checks are
:mod:`bench.kinds.stencil_spmv`'s.
"""
from __future__ import annotations

import json
import sys

import jax.numpy as jnp
import numpy as np

from bench.gen import HostCSR
from bench.kinds import stencil_spmv
from repro.core.spmv import partition_ell, spmv_layout_counts
from repro.core.strategies import MigratoryStrategy
from repro.engine import Request, SpMVInputs, SpMVOp
from repro.sparse.csr import CSR


def power_law_lengths(rows: int, longest: int, exponent: float) -> np.ndarray:
    """Row lengths, ascending, at the ``rows`` mid-quantiles of
    P(L = d) ~ d^-exponent on 1..longest, the last set to ``longest``: the
    histogram is the law's own, with no sampling noise."""
    d = np.arange(1, longest + 1, dtype=np.float64)
    cdf = np.cumsum(d ** -exponent)
    lens = np.searchsorted(cdf / cdf[-1], (np.arange(rows) + 0.5) / rows) + 1
    lens[-1] = longest
    return lens


def uniform_rows(rng: np.random.Generator, lens: np.ndarray, n_cols: int) -> HostCSR:
    """Row r holds ``lens[r]`` distinct columns drawn uniformly, sorted, with
    standard normal float32 values: duplicates are drawn again until every
    row is full."""
    n = len(lens)
    keys = np.zeros(0, dtype=np.int64)
    short = lens
    while short.any():
        rows = np.repeat(np.arange(n, dtype=np.int64), short)
        keys = np.unique(np.concatenate([keys, rows * n_cols + rng.integers(n_cols, size=len(rows))]))
        short = lens - np.bincount(keys // n_cols, minlength=n)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=indptr[1:])
    data = rng.standard_normal(len(keys)).astype(np.float32)
    return HostCSR(indptr, (keys % n_cols).astype(np.int32), data, n)


def stanford(config: dict) -> HostCSR:
    rng = np.random.default_rng(config["matrix_seed"])
    lens = power_law_lengths(config["rows"], config["longest_row"], config["degree_exponent"])
    return uniform_rows(rng, rng.permutation(lens), config["rows"])


class Cell(stencil_spmv.Cell):
    def __init__(self, config: dict, seed: int):
        self.limits = config["limits"]
        self.sample_size = config["check_sample"]
        self.rng = np.random.default_rng(seed)
        self.host = stanford(config)
        n = self.host.n
        csr = CSR(indptr=self.host.indptr, indices=self.host.indices,
                  data=self.host.data, shape=(n, n))
        self.matrix = partition_ell(csr, config["nodelets"])
        print("layout " + json.dumps({
            "rows": n, "nonzeros": self.host.nnz, "k": self.matrix.k,
            "ell_rows_per_nodelet": self.matrix.rows_per_nodelet,
            **spmv_layout_counts(self.matrix),
        }), file=sys.stderr, flush=True)
        self.xs = stencil_spmv._x_pool(int(self.rng.integers(2**31)), config["x_pool"], n,
                                       jnp.float32)
        self.sample: list[tuple[int, np.ndarray]] = []
        self.answered = 0
        self.x_host: dict[int, np.ndarray] = {}
        self.strategy = MigratoryStrategy(grain=config["grain"])

    def request(self, i: int):
        tag = i % len(self.xs)
        return Request(SpMVOp(), SpMVInputs(self.matrix, self.xs[tag]), self.strategy), tag
