"""The benchmark's own code, on the CPU at tiny sizes.

    PYTHONPATH=src python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse.csgraph import shortest_path

from bench import gen, harness, load, reference
from bench.trace import IN_PROGRAM, reduce_file, reduce_planes

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
TRACE = BENCH / "testdata" / "probe_bfs_spmv.xplane.pb"


# -- BENCHMARK.json and the files it names ----------------------------------


def test_every_config_traffic_kind_and_metric_is_found_by_name():
    for config in SPEC["configs"]:
        data = harness.read_json(ROOT / config["file"])
        assert config["file"] == f"bench/configs/{config['name']}.json"
        assert (BENCH / "kinds" / f"{data['kind']}.py").is_file()
        assert set(config["reduced"]) == set(data["reduced"])
        assert hasattr(harness.load_module(BENCH / "kinds" / f"{data['kind']}.py"), "Cell")
    for cell in SPEC["workloads"]:
        traffic = harness.read_json(BENCH / "traffic" / f"{cell['traffic']}.json")
        assert traffic["loop"] == "closed"
    for metric in SPEC["end_to_end"] + SPEC["per_layer"]:
        module = harness.load_module(BENCH / "metrics" / f"{metric['name']}.py")
        assert callable(module.read)


def test_spec_keeps_to_its_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[key]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    configs = {c["name"] for c in SPEC["configs"]}
    cells = {w["name"]: w for w in SPEC["workloads"]}
    assert {w["config"] for w in cells.values()} == configs
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(cells)
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads", cells))
    for name in cells:
        reported = [m for m in SPEC["end_to_end"] if name in m.get("workloads", cells)]
        assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
        assert harness.metrics_for(SPEC, name, trace=True)


# -- work counts --------------------------------------------------------------


def _host(dense) -> gen.HostCSR:
    m = sp.csr_matrix(np.asarray(dense, dtype=np.float32))
    return gen.HostCSR(m.indptr.astype(np.int64), m.indices.astype(np.int32),
                       m.data, m.shape[0])


def test_useful_bytes_on_a_hand_built_matrix():
    from bench.kinds import stencil_spmv

    cell = stencil_spmv.Cell.__new__(stencil_spmv.Cell)
    cell.host = _host([[2, 0, 1], [0, 3, 0], [0, 0, 0]])
    # three nonzeros at 4 B value + 4 B column, x and y at 3 x 4 B each
    assert cell.work(0) == {"useful_bytes": 3 * 8 + 3 * 4 + 3 * 4}


def test_laplacian_matches_the_five_point_stencil():
    h = gen.laplacian_2d(4)
    dense = sp.csr_matrix((h.data, h.indices, h.indptr), shape=(16, 16)).toarray()
    grid = np.arange(16).reshape(4, 4)
    expect = 4 * np.eye(16)
    for r in range(4):
        for c in range(4):
            for dr, dc in ((-1, 0), (1, 0), (0, -1), (0, 1)):
                if 0 <= r + dr < 4 and 0 <= c + dc < 4:
                    expect[grid[r, c], grid[r + dr, c + dc]] = -1
    np.testing.assert_array_equal(dense, expect)
    assert h.nnz == 16 * 5 - 4 * 4


def test_relabel_puts_the_keys_first_and_keeps_the_graph():
    rng = np.random.default_rng(2**31 + 11)
    edges = gen.kronecker_edges(rng, 8, 16, 0.57, 0.19, 0.19)
    before = gen.undirected_csr(edges, 256)
    keys = np.array([200, 17, 99])
    after = gen.undirected_csr(gen.relabel(edges, 256, rng, keys), 256)
    np.testing.assert_array_equal(after.degrees()[:3], before.degrees()[keys])
    assert sorted(after.degrees()) == sorted(before.degrees())


# -- plain references against scipy ---------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
def test_spmv_reference_matches_scipy(seed):
    rng = np.random.default_rng(seed)
    m = sp.random(40, 40, density=0.2, random_state=seed, format="csr", dtype=np.float32)
    x = rng.standard_normal(40).astype(np.float32)
    y = reference.spmv(m.indptr, m.indices, m.data, x)
    np.testing.assert_allclose(y, m.astype(np.float64) @ x.astype(np.float64), rtol=1e-12)


@pytest.mark.parametrize("seed", [0, 3, 2**31 + 7])
def test_bfs_reference_matches_scipy(seed):
    rng = np.random.default_rng(seed)
    host = gen.undirected_csr(gen.kronecker_edges(rng, 7, 4, 0.57, 0.19, 0.19), 128)
    m = sp.csr_matrix((host.data, host.indices, host.indptr), shape=(128, 128))
    root = int(np.flatnonzero(host.degrees() > 0)[0])
    parents = reference.bfs_parents(host.indptr, host.indices, 128, root)
    dist = shortest_path(m, unweighted=True, indices=root)
    reached = np.isfinite(dist)
    np.testing.assert_array_equal(parents >= 0, reached)
    for v in np.flatnonzero(reached):
        if v == root:
            assert parents[v] == root
            continue
        up = [u for u in host.indices[host.indptr[v]:host.indptr[v + 1]] if dist[u] == dist[v] - 1]
        assert parents[v] == min(up)
    other = reference.bfs_parents(host.indptr, host.indices, 128, root, np.maximum)
    np.testing.assert_array_equal(other >= 0, reached)


# -- trace reduction ------------------------------------------------------------


def _planes(device_lines: dict, host_events: list):
    def events(rows):
        return [types.SimpleNamespace(name=n, start_ns=s, end_ns=e) for n, s, e in rows]

    device = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name=k, events=events(v)) for k, v in device_lines.items()])
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        types.SimpleNamespace(name="python3", events=events(host_events))])
    return [device, host]


def test_reduction_on_a_hand_built_trace():
    planes = _planes(
        {"XLA Modules": [("jit_f(1)", 10, 40), ("jit_g(2)", 55, 75)],
         "XLA Ops": [("%while.1 = loop", 10, 40), ("%a = x", 12, 20), ("%b = y", 30, 40),
                     ("%c = z", 60, 65), ("%d = z", 70, 75), ("%early = z", 0, 5)]},
        [("bench.window", 5, 105), ("bench.wait", 5, 50), ("bench.fetch", 50, 80)],
    )
    t = reduce_planes(planes)
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s == pytest.approx(40e-9)
    assert t.idle_pct() == pytest.approx(60.0)
    assert t.module_s == pytest.approx({"jit_f": 30e-9, "jit_g": 20e-9})
    assert t.op_s == pytest.approx(
        {"while.1": 12e-9, "a": 8e-9, "b": 10e-9, "c": 5e-9, "d": 5e-9})
    assert t.idle_s == pytest.approx(
        {"bench.wait": 5e-9, "bench.fetch": 20e-9, IN_PROGRAM: 5e-9, "host.other": 30e-9})


def test_reduction_on_a_trace_recorded_on_the_chip():
    """Two BFS searches (scale 12) and two SpMV products (n=256) served on
    one v5e. Expected numbers were read from the trace's events by hand:
    the window span is 753,010,218 ns from 47,062,870; the four module
    executions last 331,855,105 + 2,897,731 + 331,854,483 + 2,897,841 ns,
    the first starting 5,937 ns before the window."""
    t = reduce_file(str(TRACE))
    assert t.window_s == pytest.approx(0.753010218)
    module_ns = 331_855_105 + 2_897_731 + 331_854_483 + 2_897_841 - 5_937
    assert t.modules_s() == pytest.approx(module_ns * 1e-9)
    assert set(t.module_s) == {"jit__lambda"}
    # ops tile the modules: busy equals module time to within the gaps between ops
    assert 0.99 * module_ns * 1e-9 < t.busy_s <= module_ns * 1e-9
    assert t.idle_pct() == pytest.approx(100 * (1 - t.busy_s / t.window_s))
    assert sum(t.idle_s.values()) == pytest.approx(t.window_s - t.busy_s)
    assert set(t.idle_s) <= {"bench.bfs", "bench.spmv", IN_PROGRAM}
    assert sum(t.op_s.values()) == pytest.approx(t.busy_s, rel=1e-6)
    top = t.breakdown()
    assert len(top["device_ops"]) == 10 and len(top["idle_gaps"]) <= 10


# -- the command ------------------------------------------------------------------


def _run(cwd: Path, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "spmv.lap.solver", "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_exits_nonzero_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode == 2 and proc.stdout == ""
    assert "TPU" in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0 and proc.stdout == ""
