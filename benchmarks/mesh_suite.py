"""Mesh-substrate bench smoke: the async EngineService serving shard_map
plans on 8 forced host devices, run in a subprocess so the parent process
keeps its single-device view (DESIGN.md §9 isolation rule).

The child forces ``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
starts the worker loop against the ``mesh`` substrate, submits every case
``repeats`` times, and writes RunReport rows + service/cache stats to
``experiments/mesh_bench_results.json`` (the mesh-8dev CI artifact). Both
the child and the parent assert the mesh-substrate plan cache saw a nonzero
hit-rate — the ROADMAP "cache-aware mesh/pallas benchmarks in CI" gate.

Registered as a slow suite: the default ``--quick`` smoke skips it; the
``mesh-8dev`` CI job runs it explicitly with ``--bench mesh --quick``.
"""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

from .util import cpu_child_env

RESULTS_PATH = (
    Path(__file__).resolve().parents[1] / "experiments" / "mesh_bench_results.json"
)

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
import json, sys
import numpy as np, jax, jax.numpy as jnp
from repro.core import Comm, MigratoryStrategy, partition_ell
from repro.engine import BFSInputs, EngineService, SpMVInputs
from repro.sparse import edges_to_csr, erdos_renyi_edges, laplacian_2d, partition_graph

out_path, n_grid, scale, repeats = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
)
assert len(jax.devices()) >= 8, f"forced-device count failed: {jax.devices()}"

rng = np.random.default_rng(0)
a = laplacian_2d(n_grid)
x = jnp.asarray(rng.standard_normal(n_grid * n_grid).astype(np.float32))
spmv_inputs = SpMVInputs(partition_ell(a, 8), x)
g = edges_to_csr(erdos_renyi_edges(scale, 6, seed=1), 1 << scale)
bfs_inputs = BFSInputs(partition_graph(g, 8), 0)
cases = [
    ("spmv_replicated", "spmv", spmv_inputs, MigratoryStrategy()),
    ("spmv_striped", "spmv", spmv_inputs, MigratoryStrategy(replicate_x=False)),
    ("bfs_push", "bfs", bfs_inputs, MigratoryStrategy(comm=Comm.REMOTE_WRITE)),
    ("bfs_pull", "bfs", bfs_inputs, MigratoryStrategy(comm=Comm.MIGRATE)),
]

svc = EngineService(substrate="mesh", max_queue_depth=256, batch_window=0.05)
svc.start()
futures = [
    (f"{name}_r{r}", svc.submit(op, inputs, st))
    for r in range(repeats)
    for name, op, inputs, st in cases
]
responses = [(case, fut.result(timeout=900)) for case, fut in futures]
svc.stop()

stats = svc.stats()
cache = svc.cache.stats()
from repro.machine import default_machine, default_machine_path
prof = default_machine()  # 8 forced devices: a parent calibration is stale here
rows = [{
    "bench": "mesh", "case": "_machine",
    "machine_file": str(default_machine_path()),
    "machine_calibrated": prof.calibrated,
    "machine_fingerprint": prof.fingerprint,
}]
rows += [
    {"bench": "mesh", "case": case, **resp.report.to_dict()}
    for case, resp in responses
]
rows.append({"bench": "mesh", "case": "_service", **stats.to_dict()})
rows.append({"bench": "mesh", "case": "_cache", **cache})
with open(out_path, "w") as f:
    json.dump(rows, f, indent=2, default=str)
assert all(resp.report.substrate == "mesh" for _, resp in responses)
assert cache["hits"] > 0, f"mesh plans saw zero cache hits: {cache}"
print("MESH-8DEV-OK", json.dumps({"hits": cache["hits"], "hit_rate": cache["hit_rate"]}))
"""


def run(full: bool = False, quick: bool = False):
    if quick:
        n_grid, scale, repeats = 12, 8, 2
    elif full:
        n_grid, scale, repeats = 32, 11, 4
    else:
        n_grid, scale, repeats = 24, 10, 3
    env = cpu_child_env()
    RESULTS_PATH.parent.mkdir(parents=True, exist_ok=True)
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT,
         str(RESULTS_PATH), str(n_grid), str(scale), str(repeats)],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    if proc.returncode != 0 or "MESH-8DEV-OK" not in proc.stdout:
        raise RuntimeError(
            f"mesh-8dev subprocess failed (rc={proc.returncode}):\n"
            f"stdout={proc.stdout}\nstderr={proc.stderr}"
        )
    rows = [
        {**row, "platform": "cpu"} for row in json.loads(RESULTS_PATH.read_text())
    ]
    cache_row = next(r for r in rows if r["case"] == "_cache")
    service_row = next(r for r in rows if r["case"] == "_service")
    if not cache_row["hits"] > 0:
        raise RuntimeError(f"mesh plan cache saw zero hits: {cache_row}")
    for row in rows:
        if row["case"].startswith("_"):
            continue
        print(
            f"mesh,{row['case']},{row.get('us_per_call', 0.0):.1f},"
            f"substrate={row.get('substrate')},cache_hit={row.get('cache_hit')}"
        )
    print(
        f"# mesh-8dev: {cache_row['hits']} hits "
        f"(hit rate {cache_row['hit_rate']:.0%}), "
        f"overlap_ratio={service_row['overlap_ratio']:.3f}, "
        f"wrote {RESULTS_PATH} ({len(rows)} rows)"
    )
    return rows
