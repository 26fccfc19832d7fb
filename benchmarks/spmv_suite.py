"""SpMV benchmarks: paper Figs. 4-6 + Table 3, all through ``engine.run``.

- fig4_grain:       grain-size sweep, striped x (no replication)
- fig5_replication: same sweep with x replicated (S1)
- fig6_scaling:     single-node (8 nodelets) vs multi-node (64) thread sweep
- table3_realworld: degree-signature proxies of the paper's matrices,
                    incl. the Stanford/ins2 hub pathology padded to the
                    longest row, and split by ``partition_ell`` where it
                    splits (paper §5.1 future work)
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import MigratoryStrategy, partition_ell
from repro.engine import SpMVInputs, SpMVOp, run as engine_run
from repro.sparse import TABLE3_SIGNATURES, laplacian_2d, skewed_matrix

from .util import emit_report

GRID_SMALL = (24, 48, 96)  # n -> n^2-row Laplacians: 576, 2304, 9216 rows
GRAINS = (1, 4, 16, 64, 256)


def _problem(n: int, p: int = 8, k: int | None = None):
    a = laplacian_2d(n)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(n * n).astype(np.float32))
    return SpMVInputs(partition_ell(a, p, k=k), x)


def fig4_grain(full: bool = False, quick: bool = False):
    rows = []
    grids = (GRID_SMALL[0],) if quick else GRID_SMALL + ((160,) if full else ())
    grains = (1, 16) if quick else GRAINS
    for n in grids:
        inputs = _problem(n)
        for grain in grains:
            st = MigratoryStrategy(replicate_x=False, grain=grain)
            _, rep = engine_run(SpMVOp(), inputs, st, "local")
            rows.append(emit_report("fig4_spmv_grain", f"n={n}_grain={grain}", rep))
    return rows


def fig5_replication(full: bool = False, quick: bool = False):
    rows = []
    grids = (GRID_SMALL[0],) if quick else GRID_SMALL + ((160,) if full else ())
    grains = (1, 16) if quick else GRAINS
    for n in grids:
        inputs = _problem(n)
        for grain in grains:
            st = MigratoryStrategy(replicate_x=True, grain=grain)
            _, rep = engine_run(SpMVOp(), inputs, st, "local")
            rows.append(emit_report("fig5_spmv_replication", f"n={n}_grain={grain}", rep))
    return rows


def fig6_scaling(full: bool = False, quick: bool = False):
    rows = []
    n = 24 if quick else (160 if full else 96)
    threads_sweep = (64, 1024) if quick else (64, 256, 1024, 2048, 4096)
    for p, label in ((8, "SN_8nodelets"), (64, "MN_64nodelets")):
        inputs = _problem(n, p)
        for threads in threads_sweep:
            grain = max(1, (inputs.a.rows_per_nodelet * p) // threads)
            st = MigratoryStrategy(replicate_x=True, grain=grain)
            _, rep = engine_run(SpMVOp(), inputs, st, "local")
            rows.append(emit_report(
                "fig6_spmv_scaling", f"{label}_threads={threads}", rep,
            ))
    return rows


def table3_realworld(full: bool = False, quick: bool = False):
    rows = []
    sigs = TABLE3_SIGNATURES if full else TABLE3_SIGNATURES[::2] + TABLE3_SIGNATURES[-2:]
    if quick:
        sigs = sigs[:2]
    for name, n, avg, mx in sigs:
        n_eff = n if full else max(n // 4, 2000)
        a = skewed_matrix(n_eff, avg, min(mx, n_eff - 1), seed=1)
        lens = np.diff(np.asarray(a.indptr))
        kmax = int(lens.max())
        x = jnp.asarray(np.random.default_rng(0).standard_normal(n_eff).astype(np.float32))
        inputs = SpMVInputs(partition_ell(a, 8, k=kmax), x)
        st = MigratoryStrategy(replicate_x=True, grain=None)
        _, rep = engine_run(SpMVOp(), inputs, st, "local")
        rows.append(emit_report(
            "table3_spmv_realworld", name, rep,
            avg_deg=round(float(lens.mean()), 2), max_deg=kmax,
        ))
        split = partition_ell(a, 8)
        if split.row_of is not None:  # hub rows split into owner-local pieces
            _, rep2 = engine_run(SpMVOp(), SpMVInputs(split, x), st, "local")
            rows.append(emit_report(
                "table3_spmv_realworld", f"{name}+rowsplit", rep2, max_deg=split.k,
            ))
    return rows


def auto_strategy(full: bool = False, quick: bool = False):
    """``strategy="auto"``: the traffic-model autotuner's pick, end to end
    through the engine (the sweep analogue of paper §5.1's conclusion)."""
    rows = []
    grids = (GRID_SMALL[0],) if quick else GRID_SMALL[:2]
    for n in grids:
        inputs = _problem(n)
        _, rep = engine_run(SpMVOp(), inputs, "auto", "local")
        rows.append(emit_report("spmv_auto", f"n={n}", rep))
    return rows


def run(full: bool = False, quick: bool = False):
    return (
        fig4_grain(full, quick) + fig5_replication(full, quick)
        + fig6_scaling(full, quick) + table3_realworld(full, quick)
        + auto_strategy(full, quick)
    )
