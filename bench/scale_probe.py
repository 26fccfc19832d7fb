"""Time served requests at several sizes on the chip, and record one short
profiler trace, to size the cells and to learn the trace's layout.

    python3 bench/scale_probe.py --out chiprun_out/probe

For Kronecker scales 12 to 15 it builds the graph, lays it out with the
program's ``partition_graph``, and times served BFS requests through a
started ``EngineService`` (first call with its compile, then warm calls).
It times served SpMV requests on ``laplacian_2d(2048)`` with a new ``x`` each
time, and writes one traced window of a few small requests of each op with
a summary of the trace's planes, lines and event names. Prints one JSON
line per measurement.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import gen  # noqa: E402
from bench.harness import read_json  # noqa: E402
from bench.kinds import kron_bfs  # noqa: E402
from repro.core.spmv import partition_ell  # noqa: E402
from repro.engine import BFSInputs, BFSOp, EngineService, Request, SpMVInputs, SpMVOp  # noqa: E402
from repro.sparse.csr import CSR  # noqa: E402

# the padded adjacency width tried at each scale: a multiple of 128 above
# the hub's degree; scale 15 is the cell's own configuration
K_CAP = {12: 1536, 13: 2560, 14: 4096}


def emit(**row):
    print(json.dumps(row, default=str), flush=True)


def program_csr(h: gen.HostCSR) -> CSR:
    return CSR(indptr=h.indptr, indices=h.indices, data=h.data, shape=(h.n, h.n))


def kron_graph(scale: int, seed: int, roots: int):
    """The BFS cell's graph (``bench/kinds/kron_bfs.py``) at ``scale``,
    drawn from ``seed`` with ``roots`` keys, and its layout."""
    config = read_json(ROOT / "bench" / "configs" / "graph500_kron.json")
    config.update(scale=scale, graph_seed=seed, keys=roots, k=K_CAP.get(scale, config["k"]))
    host = kron_bfs.kron_graph(config, seed)
    return host, kron_bfs.layout(host, config)


def served(service, request, host_fetch=True) -> float:
    t0 = time.perf_counter()
    result = service.submit(request).result().result
    if host_fetch:
        np.asarray(result)
    return time.perf_counter() - t0


def probe_bfs(service, scale: int, seed: int) -> None:
    t0 = time.perf_counter()
    host, g = kron_graph(scale, seed, 2)
    jax.block_until_ready(g.adj)
    setup = time.perf_counter() - t0
    roots = 1 if scale == 15 else 2
    warm_calls = 1 if scale == 15 else 2
    for root in range(roots):
        cold = served(service, Request(BFSOp(), BFSInputs(g, root)))
        warm = [served(service, Request(BFSOp(), BFSInputs(g, root))) for _ in range(warm_calls)]
        emit(kind="bfs", scale=scale, k=g.k, nnz=host.nnz, root=root, setup_s=setup,
             cold_s=cold, warm_s=warm,
             peak_bytes=(jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"))
    del g


def probe_spmv(service, n: int, requests: int) -> None:
    t0 = time.perf_counter()
    h = gen.laplacian_2d(n)
    t1 = time.perf_counter()
    a = partition_ell(program_csr(h), 8)
    jax.block_until_ready(a.cols)
    t2 = time.perf_counter()
    xs = jax.random.normal(jax.random.key(seed_of(n)), (requests, h.n), jnp.float32)
    xs = [xs[i] for i in range(requests)]
    jax.block_until_ready(xs)
    times = [served(service, Request(SpMVOp(), SpMVInputs(a, x))) for x in xs]
    emit(kind="spmv", n=n, rows=h.n, nnz=h.nnz, gen_s=t1 - t0, partition_ell_s=t2 - t1,
         served_s=times)


def seed_of(n: int) -> int:
    return 1000 + n


def trace_summary(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            lines.append({
                "line": line.name, "events": len(events),
                "first_start_ns": min((e.start_ns for e in events), default=None),
                "last_end_ns": max((e.end_ns for e in events), default=None),
                "busy_ns": sum(e.duration_ns for e in events),
                "top_names": names.most_common(12),
                "first_events": [(e.name, e.start_ns, e.duration_ns,
                                  {k: str(v)[:80] for k, v in list(e.stats)[:6]})
                                 for e in events[:3]],
            })
        planes.append({"plane": plane.name, "stats": [(k, str(v)[:80]) for k, v in list(plane.stats)[:10]],
                       "lines": lines})
    return {"planes": planes}


def probe_trace(service, out: Path) -> None:
    host, g = kron_graph(12, 7, 1)
    a = partition_ell(program_csr(gen.laplacian_2d(256)), 8)
    x = jax.random.normal(jax.random.key(3), (256 * 256,), jnp.float32)
    served(service, Request(BFSOp(), BFSInputs(g, 0)))
    served(service, Request(SpMVOp(), SpMVInputs(a, x)))
    tdir = out / "trace"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tdir), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.bfs"):
                served(service, Request(BFSOp(), BFSInputs(g, 0)))
            with jax.profiler.TraceAnnotation("bench.spmv"):
                served(service, Request(SpMVOp(), SpMVInputs(a, x)))
    jax.profiler.stop_trace()
    path = glob.glob(str(tdir / "**" / "*.xplane.pb"), recursive=True)[0]
    summary = trace_summary(path)
    (out / "trace_summary.json").write_text(json.dumps(summary, indent=1, default=str))
    emit(kind="trace", path=path, bytes=Path(path).stat().st_size)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/probe")
    ap.add_argument("--scales", default="12,13,14,15")
    ap.add_argument("--spmv-n", type=int, default=2048)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("scale_probe: no TPU", file=sys.stderr)
        return 2
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    service = EngineService(workers="auto").start()
    try:
        probe_trace(service, out)
        for scale in (int(s) for s in args.scales.split(",")):
            probe_bfs(service, scale, seed=scale * 101)
        probe_spmv(service, args.spmv_n, 8)
    finally:
        service.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
