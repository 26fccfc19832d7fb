"""Sparse matrix-vector products served through ``EngineService``.

The matrix is the 5-point 2-D Laplacian on a ``grid_side`` square grid,
laid out by the program's ``partition_ell`` over ``nodelets``. A pool of
``x_pool`` vectors is drawn on the device from the seed in one jitted call.
Request ``i`` multiplies by ``x[i % x_pool]`` with a new ``SpMVInputs``,
as an iterative solver sends a new vector each iteration.

After the window a sample of ``check_sample`` answers, drawn from the seed,
is compared with the float64 product of :func:`bench.reference.spmv`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, reference
from repro.core.spmv import partition_ell
from repro.engine import Request, SpMVInputs, SpMVOp
from repro.sparse.csr import CSR


def _x_pool(seed: int, count: int, n: int, dtype):
    keys = jax.random.split(jax.random.key(seed), count)
    return jax.jit(lambda k: tuple(jax.random.normal(kk, (n,), dtype) for kk in k))(keys)


class Cell:
    def __init__(self, config: dict, seed: int):
        self.limits = config["limits"]
        self.sample_size = config["check_sample"]
        self.rng = np.random.default_rng(seed)
        self.host = gen.laplacian_2d(config["grid_side"])
        n = self.host.n
        csr = CSR(indptr=self.host.indptr, indices=self.host.indices,
                  data=self.host.data, shape=(n, n))
        self.matrix = partition_ell(csr, config["nodelets"])
        self.xs = _x_pool(int(self.rng.integers(2**31)), config["x_pool"], n, jnp.float32)
        self.sample: list[tuple[int, np.ndarray]] = []  # reservoir of (tag, y)
        self.answered = 0
        self.x_host: dict[int, np.ndarray] = {}

    def warm_requests(self) -> list:
        return [self.request(i)[0] for i in range(min(2, len(self.xs)))]

    def request(self, i: int):
        tag = i % len(self.xs)
        return Request(SpMVOp(), SpMVInputs(self.matrix, self.xs[tag])), tag

    def keep(self, i: int, tag: int, y: np.ndarray) -> None:
        """Reservoir sampling of the answers, drawn from the seed."""
        self.answered += 1
        if len(self.sample) < self.sample_size:
            self.sample.append((tag, y))
        elif (j := int(self.rng.integers(self.answered))) < self.sample_size:
            self.sample[j] = (tag, y)

    def release(self) -> None:
        for tag, _ in self.sample:
            if tag not in self.x_host:
                self.x_host[tag] = np.asarray(self.xs[tag])
        self.matrix = self.xs = None

    def _unstripe(self, y: np.ndarray) -> np.ndarray:
        """(P, R_p) striped rows, row r at (r % P, r // P) -> (N,)."""
        return y.T.reshape(-1)[: self.host.n]

    def _error(self, y: np.ndarray, x: np.ndarray) -> float:
        ref = reference.spmv(self.host.indptr, self.host.indices, self.host.data, x)
        return reference.max_rel_err(self._unstripe(y), ref)

    def check(self, attempted: int) -> dict:
        err = max((self._error(y, self.x_host[t]) for t, y in self.sample), default=0.0)
        return {
            "max_rel_err": (err, self.limits["max_rel_err"]),
            "missing_answers": (attempted - self.answered, self.limits["missing_answers"]),
        }

    def work(self, tag: int) -> dict:
        """Paper §5.1 useful bytes: value and column of every nonzero, x and y."""
        return {"useful_bytes": self.host.nnz * 8 + self.host.n * 4 * 2}

    def control(self, service) -> dict:
        """The served path with the matrix values and ``x`` in bfloat16, one
        step below the configuration's float32, on the same vectors."""
        low = jax.tree.map(
            lambda v: v.astype(jnp.bfloat16) if v.dtype == jnp.float32 else v, self.matrix
        )
        errs = []
        for x in self.xs:
            response = service.submit(Request(SpMVOp(), SpMVInputs(low, x.astype(jnp.bfloat16))))
            errs.append(self._error(np.asarray(response.result().result), np.asarray(x)))
        return {"max_rel_err": min(errs)}
