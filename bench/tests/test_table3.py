"""Table 3's Stanford cell (``bench/kinds/table3_spmv.py``): the generator
holds to the paper's numbers, and ``correct`` comes out false when it
should, on the CPU at a small size.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import time
import types
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.control import readings
from bench.kinds import table3_spmv

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = {w["name"]: w for w in SPEC["workloads"]}["spmv.skewed.stanford"]
CONFIG = harness.read_json(ROOT / "bench" / "configs" / "table3_stanford.json")
SMALL = {"rows": 4000, "longest_row": 900}
SEED = 2**31 + 77
core_spmv = importlib.import_module("repro.core.spmv")


def test_generator_holds_to_table3():
    h = table3_spmv.stanford(CONFIG)
    assert h.n == CONFIG["rows"] == 281_903
    assert h.degrees().max() == CONFIG["longest_row"] == 38_606
    assert abs(h.nnz / CONFIG["nonzeros"] - 1) < 0.01
    rows = np.repeat(np.arange(h.n), h.degrees())
    keys = rows.astype(np.int64) * h.n + h.indices
    assert np.all(np.diff(keys) > 0)  # distinct, sorted columns in each row
    assert h.indices.min() >= 0 and h.indices.max() < h.n


def test_generator_repeats_under_its_seed():
    small = {**CONFIG, **SMALL}
    a, b = table3_spmv.stanford(small), table3_spmv.stanford(small)
    for field in ("indptr", "indices", "data"):
        assert np.array_equal(getattr(a, field), getattr(b, field))
    c = table3_spmv.stanford({**small, "matrix_seed": small["matrix_seed"] + 1})
    assert not np.array_equal(a.indices, c.indices)


def test_paper_size_layout_is_split_and_small():
    """At the paper's size the layout holds under 2.5 slots per nonzero;
    padded to its longest row it would hold about 4,700."""
    h = table3_spmv.stanford(CONFIG)
    csr = core_spmv.CSR(indptr=h.indptr, indices=h.indices, data=h.data, shape=(h.n, h.n))
    counts = core_spmv.spmv_layout_counts(core_spmv.partition_ell(csr, CONFIG["nodelets"]))
    assert counts["spmv.slots"] <= 2.5 * h.nnz and counts["spmv.pieces"] > 0


def _run() -> dict:
    return harness.run_cell(SPEC, CELL, SEED, 0.5, False, time.perf_counter(), SMALL)


def test_unbroken_run_is_correct():
    result = _run()
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0


def test_control_fails_a_limit_and_the_program_does_not():
    row = readings(CELL, SEED, 0.5, True, SMALL)
    assert all(row["program"][k] <= row["limits"][k] for k in row["program"])
    assert any(row["control"][k] > row["limits"][k] for k in row["control"])


def test_a_lost_piece_is_not_correct(monkeypatch):
    """The layout loses the values of one piece of a split row, the first
    one after its row's first piece: the answer misses those terms."""
    original = core_spmv.partition_ell

    def losing(a, p, k=None):
        pe = original(a, p, k)
        row_of = np.asarray(pe.row_of)
        q, i = np.argwhere(row_of[:, 1:] == row_of[:, :-1])[0]
        vals = np.asarray(pe.vals).copy()
        vals[q, i + 1] = 0
        return dataclasses.replace(pe, vals=jnp.asarray(vals))

    monkeypatch.setattr(core_spmv, "partition_ell", losing)
    assert _run()["correct"] is False


def test_slot_ns_reads_the_counter_and_nothing_without_it():
    read = harness.load_module(ROOT / "bench" / "metrics" / "slot_ns.spmv.py").read
    trace = types.SimpleNamespace(modules_s=lambda: 2.0)
    run = types.SimpleNamespace(trace=trace, service_stats={"counters": {"spmv.slots": 10**9}})
    assert read(run) == pytest.approx(2.0)
    assert read(types.SimpleNamespace(trace=trace, service_stats={})) is None
    assert read(types.SimpleNamespace(trace=None, service_stats=run.service_stats)) is None
