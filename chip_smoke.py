#!/usr/bin/env python3
"""Drive the served SpMV/BFS/GSANA path once on a TPU and check the results.

    python3 chip_smoke.py             # one chip: every phase below
    python3 chip_smoke.py --chips 4   # four chips: mesh phases only
    JAX_PLATFORMS=cpu python3 chip_smoke.py --tiny     # CPU rehearsal
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 chip_smoke.py --tiny --chips 4         # mesh rehearsal

Every request takes the library's serving path: a ``Request`` submitted to
a started ``EngineService`` (worker loop -> ``PlanCache`` -> substrate
kernel), on the ``local`` substrate unless a phase names another. One chip
runs, at sizes a graph or sparse user would call real:

- ``spmv``: ``laplacian_2d(2048)`` (4,194,304 rows) over P=8 nodelets,
  replicated and striped ``x``, against a float64 CSR product on the host;
- ``bfs_urand``: uniform-random graph, scale 22, edge factor 16, both S2
  strategies from fixed roots; every tree validated, reached counts against
  a host BFS;
- ``bfs_kron``: Graph500 Kronecker graph at the largest scale the padded
  ``(P, V_p, K)`` adjacency holds on one chip;
- ``gsana``: n=8192 PAIR alignment, bit-identical to the jitted core
  function, recall@4 against the planted ground truth;
- ``pallas``: GSANA PAIR through ``"pallas"`` (the ``topk_sim`` kernel) on
  the same inputs, against the ``local`` result;
- ``moe_decode``: ``DecodeServer`` continuous batching of ``serve-moe``
  against ``moe_decode_reference``.

``--chips 4`` runs mesh SpMV and mesh BFS over P=4 nodelets, and
``moe_dispatch`` ep_push and ep_pull, each against the ``local`` substrate
on the same inputs, and prints the devices each mesh spans.

Each phase prints one ``phase {...}`` line: sizes, bytes on the device,
the served path's XLA compiles per pipeline stage, requests, and whether
the results matched. Its times are one cold run, not measurements. The
last line is the device as JAX reports it, printed only on a TPU after
every phase matched. No TPU, a phase that raises, or a result that does
not match exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import scipy.sparse as sp  # noqa: E402
from scipy.sparse.csgraph import breadth_first_order  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (  # noqa: E402
    Comm,
    MigratoryStrategy,
    Scheme,
    bucketize,
    compute_similarity,
    gather_result,
    generate_alignment_pair,
    partition_ell,
    pick_grid,
    recall_at_k,
    validate_parents,
)
from repro.engine import (  # noqa: E402
    BFSInputs,
    BFSOp,
    DecodeServer,
    EngineService,
    GSANAInputs,
    GSANAOp,
    MoEDispatchInputs,
    Request,
    SpMVInputs,
    SpMVOp,
    placement_table,
)
from repro.engine.substrate import MeshSubstrate, PallasSubstrate  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.moe import moe_params  # noqa: E402
from repro.models.transformer import moe_decode_params  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.sparse import (  # noqa: E402
    edges_to_csr,
    erdos_renyi_edges,
    laplacian_2d,
    partition_graph,
    rmat_edges,
)

# float32 five-term row sums against a float64 product
SPMV_TOL = 1e-5
# local vs mesh on the same device type: same arithmetic, reduction order may differ
MESH_TOL = 1e-5
# the repo's own GSANA bars (tests/test_engine.py)
RECALL_FLOOR = 0.9
PALLAS_SCORE_ATOL = 1e-5

EP_PULL = MigratoryStrategy(comm=Comm.MIGRATE)
EP_PUSH = MigratoryStrategy(comm=Comm.REMOTE_WRITE)


class SmokeFailure(RuntimeError):
    """A phase ran but its results did not match."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    lap_n: int  # laplacian_2d grid side: lap_n**2 rows
    urand_scale: int
    kron_scale: int
    gsana_n: int
    roots: int
    mesh_urand_scale: int
    moe_tokens: int
    moe_d_model: int
    moe_d_ff: int


# From memory_analysis() of the local BFS program compiled for a v5e: urand
# scale 22 (K ~72) takes 1.2 GB of adjacency and 7.9 GB of temporaries, half
# the chip. Kronecker scale 16 pads K to its largest hub (~9,700): 2.5 GB of
# adjacency and 10.2 GB of temporaries; scale 17 (K ~15,800) would need
# 8.3 GB of adjacency alone (ROADMAP R1). The Kronecker phase runs with no
# other graph resident. Mesh BFS (four chips) stays at scale 16: its root
# is baked into the program, and compiling it for a v5e 2x2 took 6 s at
# scale 16, 33 s at 18 and 135 s at 20 per (root, strategy).
FULL = Sizes(
    lap_n=2048, urand_scale=22, kron_scale=16, gsana_n=8192, roots=2,
    mesh_urand_scale=16, moe_tokens=8192, moe_d_model=1024, moe_d_ff=2048,
)
TINY = Sizes(
    lap_n=32, urand_scale=10, kron_scale=9, gsana_n=512, roots=2,
    mesh_urand_scale=9, moe_tokens=256, moe_d_model=64, moe_d_ff=128,
)


def device_bytes() -> dict:
    """Bytes in use on the devices now, and the process's peak so far
    (None where the backend does not report them)."""
    stats = [d.memory_stats() or {} for d in jax.devices()]
    in_use = [s.get("bytes_in_use") for s in stats]
    peak = [s.get("peak_bytes_in_use") for s in stats]
    return {
        "device_bytes_in_use": None if None in in_use else sum(in_use),
        "device_peak_bytes": None if None in peak else sum(peak),
    }


def tree_bytes(tree) -> int:
    return int(sum(leaf.nbytes for leaf in jax.tree.leaves(tree) if hasattr(leaf, "nbytes")))


def serve(service: EngineService, requests: list) -> list:
    """Submit every request to the worker loop, then wait for each."""
    futures = [service.submit(request) for request in requests]
    return [future.result() for future in futures]


def first_call_seconds(responses: list) -> float:
    """The time of each new plan's cold call (``RunReport.compile_seconds``,
    the ``engine.compile`` call: trace, compile and first execution),
    summed."""
    return float(sum(r.report.compile_seconds for r in responses))


def clock(service: EngineService) -> tuple[float, dict, dict]:
    """Now, and the XLA compiles the service's served path has made so far
    (count and seconds per pipeline stage, persistent-cache reads
    included): the engine's compile counter."""
    stats = service.stats()
    return time.perf_counter(), stats.xla_compiles, stats.xla_compile_seconds


def rel_err(y: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(np.abs(y.astype(np.float64) - ref)) / max(np.max(np.abs(ref)), 1e-30))


def emit(service: EngineService, phase: str, start: tuple, matched: bool, **fields) -> None:
    now, compiles, seconds = clock(service)
    row = {
        "phase": phase,
        **fields,
        **device_bytes(),
        "xla_compiles": {stage: n - start[1].get(stage, 0) for stage, n in compiles.items()
                         if n > start[1].get(stage, 0)},
        "xla_compile_seconds": sum(seconds.values()) - sum(start[2].values()),
        "cold_wall_seconds_one_run": now - start[0],
        "matched": matched,
    }
    print("phase " + json.dumps(row, default=str), flush=True)
    if not matched:
        raise SmokeFailure(f"phase {phase}: results did not match: {row}")


def host_csr(csr) -> sp.csr_matrix:
    return sp.csr_matrix(
        (np.asarray(csr.data), np.asarray(csr.indices), np.asarray(csr.indptr)),
        shape=csr.shape,
    )


def pick_roots(csr: sp.csr_matrix, count: int, rng: np.random.Generator) -> list[int]:
    candidates = np.flatnonzero(np.diff(csr.indptr) > 0)
    return sorted(int(r) for r in rng.choice(candidates, size=count, replace=False))


# -- one-chip phases ---------------------------------------------------------


def phase_spmv(service, sizes, rng, p: int = 8) -> None:
    start = clock(service)
    a = laplacian_2d(sizes.lap_n)
    ref_matrix = host_csr(a)
    n = a.n_rows
    x = rng.standard_normal(n).astype(np.float32)
    inputs = SpMVInputs(partition_ell(a, p), jnp.asarray(x))
    del a
    ref = ref_matrix @ x.astype(np.float64)
    strategies = (MigratoryStrategy(replicate_x=True), MigratoryStrategy(replicate_x=False))
    requests = [Request(SpMVOp(), inputs, st) for st in strategies for _ in range(2)]
    responses = serve(service, requests)
    errs = [rel_err(np.asarray(gather_result(r.result, n)), ref) for r in responses]
    emit(
        service, "spmv", start, max(errs) <= SPMV_TOL,
        rows=n, nnz=int(ref_matrix.nnz), nodelets=p,
        strategies=["replicate_x", "striped_x"], requests=len(requests),
        input_bytes=tree_bytes((inputs.a, inputs.x)), first_call_seconds=first_call_seconds(responses),
        max_rel_err=max(errs), tol=SPMV_TOL,
        cache_hits=sum(r.report.cache_hit for r in responses),
    )


def _bfs_graph(edges: np.ndarray, scale: int, p: int):
    n = 1 << scale
    csr = edges_to_csr(edges, n)
    host = host_csr(csr)
    graph = partition_graph(csr, p)
    return graph, host


def phase_bfs(service, name, edges, scale, sizes, rng, p: int = 8) -> None:
    start = clock(service)
    graph, host = _bfs_graph(edges, scale, p)
    del edges
    graph_host = jax.device_get(graph)
    roots = pick_roots(host, sizes.roots, rng)
    requests = [
        Request(BFSOp(), BFSInputs(graph, root), MigratoryStrategy(comm=comm))
        for comm in (Comm.MIGRATE, Comm.REMOTE_WRITE)
        for root in roots
    ]
    responses = serve(service, requests)
    trees_valid, reached_match, reached = True, True, []
    for request, response in zip(requests, responses):
        root = request.inputs.root
        parents = np.asarray(response.result)
        trees_valid &= validate_parents(graph_host, root, parents)
        ref_reached = len(breadth_first_order(host, root, return_predecessors=False))
        reached.append(int((parents >= 0).sum()))
        reached_match &= reached[-1] == ref_reached
    emit(
        service, name, start, bool(trees_valid and reached_match),
        scale=scale, vertices=graph.n_vertices, directed_edges=int(host.nnz),
        nodelets=p, padded_k=graph.k, roots=roots,
        strategies=["migrate", "remote_write"], requests=len(requests),
        input_bytes=tree_bytes(graph), first_call_seconds=first_call_seconds(responses),
        reached=reached, trees_valid=bool(trees_valid),
        reached_match_host_bfs=bool(reached_match),
    )


def _gsana_inputs(n: int, seed: int) -> GSANAInputs:
    vs1, vs2, pi = generate_alignment_pair(n, seed=seed)
    grid = pick_grid(n, 32)
    cap = max(bucketize(vs1, grid).cap, bucketize(vs2, grid).cap)
    return GSANAInputs(
        vs1, vs2, bucketize(vs1, grid, cap=cap), bucketize(vs2, grid, cap=cap),
        k=4, ground_truth=pi,
    )


def phase_gsana(service, sizes, seed) -> tuple:
    start = clock(service)
    inputs = _gsana_inputs(sizes.gsana_n, seed)
    st = MigratoryStrategy(scheme=Scheme.PAIR)
    requests = [Request(GSANAOp(), inputs, st) for _ in range(2)]
    responses = serve(service, requests)
    k = inputs.k
    oracle = jax.jit(
        lambda vs1, vs2, b1, b2: compute_similarity(vs1, vs2, b1, b2, k, Scheme.PAIR)
    )(inputs.vs1, inputs.vs2, inputs.b1, inputs.b2)
    cand_o, score_o = (np.asarray(a) for a in oracle)
    identical = all(
        np.array_equal(np.asarray(r.result[0]), cand_o)
        and np.array_equal(np.asarray(r.result[1]), score_o)
        for r in responses
    )
    recall = recall_at_k(responses[0].result[0], inputs.ground_truth)
    emit(
        service, "gsana", start, bool(identical and recall > RECALL_FLOOR),
        n=sizes.gsana_n, scheme="pair", k=k, buckets=inputs.b2.grid ** 2,
        bucket_cap=inputs.b2.cap, requests=len(requests),
        input_bytes=tree_bytes((inputs.vs1, inputs.vs2, inputs.b1, inputs.b2)),
        first_call_seconds=first_call_seconds(responses),
        bit_identical_to_oracle=bool(identical), recall_at_4=recall,
        recall_floor=RECALL_FLOOR,
    )
    return inputs, cand_o, score_o


def phase_pallas(service, gsana_inputs, cand_l, score_l) -> None:
    """GSANA PAIR through the ``topk_sim`` Pallas kernel on the GSANA
    phase's inputs, against that phase's result."""
    start = clock(service)
    sub = PallasSubstrate()
    request = Request(GSANAOp(), gsana_inputs, MigratoryStrategy(scheme=Scheme.PAIR), "pallas")
    response = serve(service, [request])[0]
    cand_p, score_p = (np.asarray(a) for a in response.result)
    fin = np.isfinite(score_l)
    matched = bool(
        np.array_equal(fin, np.isfinite(score_p))
        and np.allclose(score_l[fin], score_p[fin], atol=PALLAS_SCORE_ATOL, rtol=0)
        and recall_at_k(jnp.asarray(cand_p), gsana_inputs.ground_truth) > RECALL_FLOOR
    )
    emit(
        service, "pallas", start, matched,
        interpret=sub.interpret, requests=1,
        first_call_seconds=first_call_seconds([response]),
        candidates_equal_local=float(np.mean(cand_p == cand_l)),
        score_atol=PALLAS_SCORE_ATOL,
    )


def _drive(server, prompts, schedule) -> dict:
    for (prompt, max_new), steps in zip(prompts, schedule):
        server.add(prompt, max_new_tokens=max_new)
        for _ in range(steps):
            server.step()
    server.run_until_drained()
    return dict(server.results)


def phase_moe_decode(service, seed) -> None:
    start = clock(service)
    cfg = get_config("serve-moe")
    params = moe_decode_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [
        (rng.integers(1, cfg.vocab_size, size=int(n)).tolist(), int(m))
        for n, m in zip(rng.integers(2, 6, size=6), (3, 5, 2, 4, 3, 2))
    ]
    schedule = (0, 1, 0, 2, 0, 1)
    modes = (("ep_pull", EP_PULL, 4), ("ep_push", EP_PUSH, 4), ("tp", None, 1))
    matched, steps = {}, 0
    for label, strategy, nodelets in modes:
        mk = dict(capacity=4, max_len=16, nodelets=nodelets, strategy=strategy)
        oracle = _drive(DecodeServer(cfg, params, oracle=True, **mk), prompts, schedule)
        server = DecodeServer(cfg, params, service=service, **mk)
        matched[label] = _drive(server, prompts, schedule) == oracle
        steps += server.steps
    emit(
        service, "moe_decode", start, all(matched.values()),
        config="serve-moe: 1 layer, d_model 32; shows that the path runs, "
        "not how big it can be",
        sequences=len(prompts), requests=steps,
        input_bytes=tree_bytes(params), tokens_match_reference=matched,
    )


# -- four-chip phases ----------------------------------------------------------


def _mesh_devices(p: int) -> list[str]:
    return [str(d) for d in MeshSubstrate().mesh_for(p).devices.flat]


def _spans(result) -> int:
    return len(result.sharding.device_set)


def phase_mesh_spmv(service, sizes, rng, p: int = 4) -> None:
    start = clock(service)
    a = laplacian_2d(sizes.lap_n)
    n = a.n_rows
    inputs = SpMVInputs(partition_ell(a, p), jnp.asarray(rng.standard_normal(n).astype(np.float32)))
    del a
    strategies = (MigratoryStrategy(replicate_x=True), MigratoryStrategy(replicate_x=False))
    requests = [Request(SpMVOp(), inputs, st, sub) for st in strategies for sub in ("local", "mesh")]
    responses = serve(service, requests)
    errs, spans = [], []
    for local, mesh in zip(responses[0::2], responses[1::2]):
        ref = np.asarray(gather_result(local.result, n)).astype(np.float64)
        errs.append(rel_err(np.asarray(gather_result(mesh.result, n)), ref))
        spans.append(_spans(mesh.result))
    emit(
        service, "mesh_spmv", start, max(errs) <= MESH_TOL and min(spans) == p,
        rows=n, nodelets=p, mesh_devices=_mesh_devices(p), result_spans_devices=spans,
        requests=len(requests), input_bytes=tree_bytes((inputs.a, inputs.x)),
        first_call_seconds=first_call_seconds(responses), max_rel_err_vs_local=max(errs),
        tol=MESH_TOL,
    )


def phase_mesh_bfs(service, sizes, rng, seed, p: int = 4) -> None:
    start = clock(service)
    scale = sizes.mesh_urand_scale
    graph, host = _bfs_graph(erdos_renyi_edges(scale, 16, seed=seed), scale, p)
    roots = pick_roots(host, 2, rng)
    requests = [
        Request(BFSOp(), BFSInputs(graph, root), MigratoryStrategy(comm=comm), sub)
        for comm in (Comm.MIGRATE, Comm.REMOTE_WRITE)
        for root in roots
        for sub in ("local", "mesh")
    ]
    responses = serve(service, requests)
    equal = all(
        np.array_equal(np.asarray(local.result), np.asarray(mesh.result))
        for local, mesh in zip(responses[0::2], responses[1::2])
    )
    emit(
        service, "mesh_bfs", start, bool(equal),
        scale=scale, vertices=graph.n_vertices, directed_edges=int(host.nnz),
        nodelets=p, roots=roots, strategies=["migrate", "remote_write"],
        mesh_devices=_mesh_devices(p), requests=len(requests),
        input_bytes=tree_bytes(graph), first_call_seconds=first_call_seconds(responses),
        parents_equal_local=bool(equal),
    )


def phase_mesh_moe(service, sizes, seed, p: int = 4) -> None:
    start = clock(service)
    cfg = ModelConfig(
        name="smoke-moe", family="moe", num_layers=1, d_model=sizes.moe_d_model,
        num_heads=1, num_kv_heads=1, d_ff=sizes.moe_d_ff, vocab_size=64,
        num_experts=8, experts_per_token=2, moe_d_ff=sizes.moe_d_ff,
        dtype="float32", remat=False,
    )
    weights = moe_params(cfg, jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (sizes.moe_tokens, cfg.d_model))
    inputs = MoEDispatchInputs(
        x=x, router=weights["router"], w_gate=weights["w_gate"],
        w_up=weights["w_up"], w_down=weights["w_down"], nodelets=p,
        experts_per_token=2, capacity_factor=2.0,
    )
    modes = (("ep_push", EP_PUSH), ("ep_pull", EP_PULL))
    requests = [
        Request("moe_dispatch", inputs, st, sub) for _, st in modes for sub in ("local", "mesh")
    ]
    responses = serve(service, requests)
    errs, spans = {}, []
    for (label, _), local, mesh in zip(modes, responses[0::2], responses[1::2]):
        errs[label] = rel_err(np.asarray(mesh.result), np.asarray(local.result).astype(np.float64))
        spans.append(_spans(mesh.result))
    emit(
        service, "mesh_moe_dispatch", start, max(errs.values()) <= MESH_TOL and min(spans) == p,
        tokens=sizes.moe_tokens, d_model=cfg.d_model, experts=8, moe_d_ff=sizes.moe_d_ff,
        nodelets=p, mesh_devices=_mesh_devices(p), result_spans_devices=spans,
        requests=len(requests), input_bytes=tree_bytes((x, weights)),
        first_call_seconds=first_call_seconds(responses), max_rel_err_vs_local=errs,
        tol=MESH_TOL,
    )


# -- driver --------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true", help="small sizes (CPU rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.tiny:
        print(f"chip_smoke: no TPU (JAX found {platform}); nothing run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"# cache: {enable_compile_cache()}", flush=True)
    print(f"# devices: {len(devices)} x {devices[0].device_kind} ({platform})", flush=True)
    sizes = TINY if args.tiny else FULL
    rng = np.random.default_rng(args.seed)
    service = EngineService(workers="auto")
    service.start()
    print(f"# placement: {placement_table()}", flush=True)
    print("# phase times are one cold run each (compiles included), not measurements",
          flush=True)
    try:
        if args.chips == 4:
            phase_mesh_spmv(service, sizes, rng)
            phase_mesh_bfs(service, sizes, rng, args.seed)
            phase_mesh_moe(service, sizes, args.seed)
        else:
            phase_spmv(service, sizes, rng)
            gc.collect()
            urand = erdos_renyi_edges(sizes.urand_scale, 16, seed=args.seed)
            phase_bfs(service, "bfs_urand", urand, sizes.urand_scale, sizes, rng)
            del urand
            phase_pallas(service, *phase_gsana(service, sizes, args.seed))
            gc.collect()
            kron = rmat_edges(sizes.kron_scale, 16, seed=args.seed)
            phase_bfs(service, "bfs_kron", kron, sizes.kron_scale, sizes, rng)
            del kron
            gc.collect()
            phase_moe_decode(service, args.seed)
    finally:
        service.stop()
    if platform != "tpu":
        print("chip_smoke: every phase matched, but on the CPU: a rehearsal, "
              "not a chip run", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
