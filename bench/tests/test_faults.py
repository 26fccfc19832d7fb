"""``correct`` comes out false when it should, on the CPU at tiny sizes.

- The control (each kind's ``control``: the reference one step below the
  configuration's guarantee, in the program's place) fails a limit.
- A whole run with the timed path broken underneath (an answer altered
  where it is produced, a search whose rounds leave their state unchanged,
  or an answer never produced) reads ``correct: false``; the same run
  unbroken reads ``true``.

Both drive the harness past its look for a chip.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
from __future__ import annotations

import importlib
import json
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

import repro.engine.service as service_module
from bench import harness
from bench.control import readings

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in SPEC["workloads"]}
TINY = {
    "laplacian2d": {"grid_side": 32},
    "table3_stanford": {"rows": 4000, "longest_row": 900},
    "graph500_kron": {"scale": 8, "k": 256},
}
SEED = 2**31 + 99
# the package re-exports functions under these names; take the modules
core_spmv = importlib.import_module("repro.core.spmv")
core_bfs = importlib.import_module("repro.core.bfs")


def _run(name: str) -> dict:
    cell = CELLS[name]
    return harness.run_cell(SPEC, cell, SEED, 0.5, False, time.perf_counter(),
                            TINY[cell["config"]])


@pytest.mark.parametrize("name", ["spmv.lap.solver", "bfs.kron"])
def test_control_fails_a_limit_and_the_program_does_not(name):
    cell = CELLS[name]
    row = readings(cell, SEED, 0.5, True, TINY[cell["config"]])
    assert all(row["program"][k] <= row["limits"][k] for k in row["program"])
    assert any(row["control"][k] > row["limits"][k] for k in row["control"])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_unbroken_run_is_correct(name):
    result = _run(name)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"


def _alter_y(original):
    def broken(a, x_full, grain):
        return original(a, x_full, grain).at[0, 0].add(1.0)
    return broken


def _alter_parent(original):
    def broken(adj, root, max_rounds):
        return original(adj, root, max_rounds).at[0].add(1)
    return broken


def _rounds_change_nothing(original):
    def broken(adj, root, max_rounds):
        n = adj.shape[0]
        return jnp.full((n,), core_bfs.UNVISITED, jnp.int32).at[root].set(root)
    return broken


@pytest.mark.parametrize("name,module,attr,fault", [
    ("spmv.lap.solver", core_spmv, "_spmv_local", _alter_y),
    ("bfs.kron", core_bfs, "_bfs_local", _alter_parent),
    ("bfs.kron", core_bfs, "_bfs_local", _rounds_change_nothing),
])
def test_an_altered_answer_is_not_correct(monkeypatch, name, module, attr, fault):
    monkeypatch.setattr(module, attr, fault(getattr(module, attr)))
    assert _run(name)["correct"] is False


@pytest.mark.parametrize("name", ["spmv.lap.solver", "bfs.kron"])
def test_an_answer_that_never_comes_is_not_correct(monkeypatch, name):
    original = service_module.single_call
    calls = {"n": 0}

    def sometimes_fails(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 8:  # past the warm-up, inside the window
            raise RuntimeError("lost")
        return original(*args, **kwargs)

    monkeypatch.setattr(service_module, "single_call", sometimes_fails)
    result = _run(name)
    assert result["failed"] == 1 and result["correct"] is False
