"""Batched serving drivers.

LM decode path: prefill a batch of prompts, then greedy-decode.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --reduced \
        --batch 4 --prompt-len 64 --gen 32

Irregular-op path: drive an ``EngineService`` with a mixed SpMV/BFS request
stream (autotuned strategies, shared compiled-plan cache) and print the
aggregate throughput report — the engine's production-serving smoke. All
submissions go through the unified :class:`repro.engine.Request` shape.
``--ops`` uses the batched drain; ``--ops-async`` starts the worker loop and
feeds it from a synthetic *open-loop* traffic generator (requests arrive at
``--ops-rate`` req/s with jitter, independent of service progress — the
arrival process of real serving), exercising admission control
(``--ops-admission block|reject``), QoS weighting, and the overlapped
compile/execute pipeline.

    PYTHONPATH=src python -m repro.launch.serve --ops --ops-requests 32
    PYTHONPATH=src python -m repro.launch.serve --ops-async --ops-rate 100 \
        --ops-requests 64 --ops-admission reject

MoE decode serving path (DESIGN.md §1g): continuous-batched decode of the
``serve-moe`` config through the worker-loop service with an SLO target,
cross-checked token-for-token against the single-process oracle.

    PYTHONPATH=src python -m repro.launch.serve --decode-serve \
        --serve-dispatch ep_pull --serve-slo-ms 2000

Cluster path (DESIGN.md §1h): serve the same mixed-op stream on an
N-worker multi-process cluster with a bit-parity cross-check against
single-process ``engine.run``; ``--cluster-kill-one`` SIGKILLs a worker
mid-stream to demonstrate heartbeat/EOF failover.

    PYTHONPATH=src python -m repro.launch.serve --cluster 2 [--cluster-kill-one]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from ..configs import get_config, reduced_config
from ..models import Ctx, api


def _ops_workload(shapes: tuple[int, ...], seed: int):
    """The demo's rotating problem signatures (SpMV pool + one BFS graph)."""
    import numpy as np

    from ..core import partition_ell
    from ..engine import BFSInputs, SpMVInputs
    from ..sparse import edges_to_csr, erdos_renyi_edges, laplacian_2d, partition_graph

    rng = np.random.default_rng(seed)
    spmv_pool = []
    for n in shapes:
        a = laplacian_2d(n)
        x = jnp.asarray(rng.standard_normal(n * n).astype(np.float32))
        spmv_pool.append(SpMVInputs(partition_ell(a, 8), x))
    g = edges_to_csr(erdos_renyi_edges(9, 6, seed=seed), 512)
    bfs_inputs = BFSInputs(partition_graph(g, 8), 0)

    def pick(i: int):
        if i % 3 == 2:
            return "bfs", bfs_inputs
        return "spmv", spmv_pool[i % len(spmv_pool)]

    return pick



def _print_spans(stats) -> None:
    """The engine's span totals (mean ms per span, count), the XLA compiles
    per pipeline stage and the per-request counters: the operator's view of
    the served path."""
    spans = ", ".join(
        f"{name} {stats.span_seconds[name] / count * 1e3:.2f} ms x{count}"
        for name, count in stats.span_counts.items()
    )
    print(f"spans: {spans}")
    print(f"xla compiles by stage: {stats.xla_compiles} "
          f"({sum(stats.xla_compile_seconds.values())*1e3:.0f} ms)")
    print(f"counters: {stats.counters}")

def ops_demo(n_requests: int, shapes: tuple[int, ...] = (16, 24), seed: int = 0) -> dict:
    """Serve a mixed irregular-op workload through the batched EngineService.

    Requests rotate over a few problem signatures, so each drain compiles
    once per signature and serves the rest from the plan cache.
    """
    from ..engine import EngineService, Request

    pick = _ops_workload(shapes, seed)
    svc = EngineService(autotune=True)
    for i in range(n_requests):
        op, inputs = pick(i)
        svc.submit(Request(op, inputs))
    responses = svc.drain()
    report = svc.throughput_report()
    stats = svc.stats()
    print(f"served {len(responses)} requests in {stats.wall_seconds*1e3:.0f} ms "
          f"({stats.requests_per_second:.0f} req/s)")
    print(f"compiles: {stats.compiles} ({stats.compile_seconds*1e3:.0f} ms), "
          f"cache hits: {stats.cache_hits}, "
          f"amortization: {stats.amortization:.1f} req/compile")
    _print_spans(stats)
    print(json.dumps(report, default=str))
    return report


def ops_demo_async(
    n_requests: int,
    rate: float = 100.0,
    admission: str = "block",
    max_queue_depth: int = 64,
    shapes: tuple[int, ...] = (16, 24),
    seed: int = 0,
    workers: "int | str" = 1,
) -> dict:
    """Open-loop async serving demo: a synthetic traffic generator submits at
    ``rate`` req/s (jittered, never waiting for responses — open loop) while
    the worker pipeline overlaps compiles with execution. BFS requests get a
    2x QoS weight, so mixed bursts schedule BFS groups first."""
    import numpy as np

    from ..engine import AdmissionError, EngineService, Request

    pick = _ops_workload(shapes, seed)
    rng = np.random.default_rng(seed)
    interval = 1.0 / rate if rate > 0 else 0.0
    svc = EngineService(
        autotune=True,
        workers=workers,
        max_queue_depth=max_queue_depth,
        admission=admission,
        qos={"bfs": 2.0},
        batch_window=0.02,
    )
    svc.start()
    futures = []
    try:
        for i in range(n_requests):
            try:
                op, inputs = pick(i)
                futures.append(svc.submit(Request(op, inputs)))
            except AdmissionError:
                pass  # open loop drops on the floor; counted in stats.rejected
            if interval:
                time.sleep(interval * (0.5 + rng.random()))  # jittered arrivals
        responses = [f.result(timeout=600) for f in futures]
    finally:
        svc.stop()
    report = svc.throughput_report()
    stats = svc.stats()
    print(f"served {len(responses)}/{n_requests} requests "
          f"({stats.rejected} rejected) in {stats.wall_seconds*1e3:.0f} ms "
          f"({stats.requests_per_second:.0f} req/s sustained)")
    print(f"compiles: {stats.compiles} ({stats.compile_seconds*1e3:.0f} ms), "
          f"cache hits: {stats.cache_hits}, "
          f"amortization: {stats.amortization:.1f} req/compile")
    print(f"overlap: {stats.overlap_seconds*1e3:.0f} ms "
          f"({stats.overlap_ratio:.0%} of compile time hidden under execution), "
          f"busy {stats.busy_seconds*1e3:.0f} / wall {stats.wall_seconds*1e3:.0f} ms, "
          f"queue hwm {stats.queue_depth_hwm}")
    if stats.workers > 1:
        print(f"pool: {stats.workers} workers, {stats.steals} steals, "
              f"occupancy {[round(o, 2) for o in stats.worker_occupancy]}")
    _print_spans(stats)
    print(json.dumps(report, default=str))
    return report


def decode_serve_demo(
    n_seqs: int = 8,
    capacity: int = 8,
    max_new: int = 8,
    workers: "int | str" = 2,
    slo_ms: float = 5000.0,
    nodelets: int = 4,
    dispatch: str = "ep_pull",
    seed: int = 0,
) -> dict:
    """Continuous-batched MoE decode serving (DESIGN.md §1g): the ``serve-moe``
    config's expert FFNs run behind ``moe_dispatch`` transport, every decode
    step travels as one :class:`Request` through the worker-loop service with
    an SLO target, and the served tokens are cross-checked bit-for-bit against
    the single-process oracle."""
    import numpy as np

    from ..configs import get_config
    from ..core import Comm, MigratoryStrategy
    from ..engine import DecodeServer, EngineService
    from ..models.transformer import moe_decode_params

    cfg = get_config("serve-moe")
    params = moe_decode_params(cfg, jax.random.PRNGKey(seed))
    strategy = {
        "ep_pull": MigratoryStrategy(comm=Comm.MIGRATE),
        "ep_push": MigratoryStrategy(comm=Comm.REMOTE_WRITE),
    }.get(dispatch)
    nod = 1 if dispatch == "tp" else nodelets
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=int(rng.integers(2, 6))).tolist()
        for _ in range(n_seqs)
    ]

    def drive(server):
        # staggered joins: half the sequences arrive while others are decoding
        for i, prompt in enumerate(prompts):
            server.add(prompt, max_new_tokens=max_new)
            if i % 2:
                server.step()
        server.run_until_drained()
        return dict(server.results)

    svc = EngineService(workers=workers, slo_target_seconds=slo_ms / 1e3)
    svc.start()
    try:
        served = drive(DecodeServer(
            cfg, params, capacity=capacity, max_len=32, nodelets=nod,
            strategy=strategy, service=svc,
        ))
    finally:
        svc.stop()
    stats = svc.stats()
    oracle = drive(DecodeServer(
        cfg, params, capacity=capacity, max_len=32, nodelets=nod,
        strategy=strategy, oracle=True,
    ))
    parity = served == oracle
    print(f"served {len(served)} sequences (dispatch={dispatch}, nodelets={nod}), "
          f"oracle parity: {parity}")
    print(f"latency p50/p99: {stats.total_p50*1e3:.1f}/{stats.total_p99*1e3:.1f} ms; "
          f"SLO {slo_ms:.0f} ms -> {stats.slo_violations}/{stats.slo_checked} violations "
          f"(attainment {stats.slo_attainment})")
    _print_spans(stats)
    report = {**svc.throughput_report(), "oracle_parity": parity}
    print(json.dumps(report, default=str))
    return report


def cluster_demo(
    n_workers: int,
    n_requests: int = 24,
    shapes: tuple[int, ...] = (16, 24),
    seed: int = 0,
    kill_one: bool = False,
) -> dict:
    """Serve the mixed irregular-op stream on a multi-process cluster
    (DESIGN.md §1h) and cross-check every response bit-for-bit against
    single-process ``engine.run``. ``kill_one=True`` SIGKILLs one worker
    mid-stream to demonstrate failover: every future still terminates and
    parity still holds (in-flight requests are retried once on a
    survivor)."""
    import numpy as np

    from ..cluster import launch_cluster
    from ..engine import Request, run

    pick = _ops_workload(shapes, seed)
    requests = [Request(*pick(i)) for i in range(n_requests)]
    t_start = time.perf_counter()
    with launch_cluster(n_workers) as cluster:
        t_up = time.perf_counter() - t_start
        t0 = time.perf_counter()
        futures = [cluster.submit(r) for r in requests]
        if kill_one and n_workers > 1:
            victim = cluster.coordinator.healthy_workers()[0].worker_id
            print(f"SIGKILLing worker {victim} mid-stream ...")
            cluster.kill_worker(victim)
        responses = [f.result() for f in futures]  # every future terminates
        wall = time.perf_counter() - t0
        mismatches = 0
        for request, response in zip(requests, responses):
            oracle, _ = run(request, iters=1, warmup=0)
            if not np.array_equal(np.asarray(response.result), np.asarray(oracle)):
                mismatches += 1
        stats = cluster.stats()
    per_worker = {
        w["worker_id"]: w["served"] for w in stats["workers"]
    }
    print(f"cluster up ({n_workers} workers) in {t_up:.1f}s; served "
          f"{len(responses)} requests in {wall*1e3:.0f} ms "
          f"({len(responses)/max(wall, 1e-9):.0f} req/s)")
    print(f"per-worker served: {per_worker}, retries: {stats['retries']}, "
          f"failovers: {stats['failovers']}, mismatches: {mismatches}")
    report = {
        "n_workers": n_workers,
        "requests": len(responses),
        "wall_seconds": wall,
        "mismatches": mismatches,
        "cluster": stats,
    }
    print(json.dumps(report, default=str))
    if mismatches:
        raise SystemExit(f"{mismatches} responses diverged from engine.run")
    return report


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--ops", action="store_true",
                    help="serve an irregular-op stream via EngineService (batched drain)")
    ap.add_argument("--ops-async", action="store_true",
                    help="serve an open-loop irregular-op stream via the async worker loop")
    ap.add_argument("--ops-requests", type=int, default=24)
    ap.add_argument("--ops-rate", type=float, default=100.0,
                    help="open-loop arrival rate (req/s) for --ops-async")
    ap.add_argument("--ops-admission", choices=("block", "reject"), default="block",
                    help="admission policy when the async queue is full")
    ap.add_argument("--ops-workers", default="1",
                    help="executor-pool width for --ops-async (int or 'auto')")
    ap.add_argument("--decode-serve", action="store_true",
                    help="continuous-batched MoE decode serving with SLO stats")
    ap.add_argument("--serve-seqs", type=int, default=8)
    ap.add_argument("--serve-dispatch", choices=("ep_pull", "ep_push", "tp"),
                    default="ep_pull")
    ap.add_argument("--serve-nodelets", type=int, default=4)
    ap.add_argument("--serve-slo-ms", type=float, default=5000.0,
                    help="per-request SLO target in ms for --decode-serve")
    ap.add_argument("--cluster", type=int, default=0, metavar="N",
                    help="serve the mixed-op stream on an N-worker localhost "
                         "cluster (multi-process, DESIGN.md §1h) with "
                         "bit-parity cross-check against engine.run")
    ap.add_argument("--cluster-kill-one", action="store_true",
                    help="with --cluster: SIGKILL one worker mid-stream to "
                         "demonstrate failover")
    args = ap.parse_args(argv)
    from ..runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.cluster:
        cluster_demo(args.cluster, n_requests=args.ops_requests,
                     kill_one=args.cluster_kill_one)
        return
    if args.decode_serve:
        workers = args.ops_workers if args.ops_workers == "auto" else int(args.ops_workers)
        decode_serve_demo(args.serve_seqs, dispatch=args.serve_dispatch,
                          nodelets=args.serve_nodelets, slo_ms=args.serve_slo_ms,
                          workers=max(2, workers) if workers != "auto" else workers)
        return
    if args.ops_async:
        workers = args.ops_workers if args.ops_workers == "auto" else int(args.ops_workers)
        ops_demo_async(args.ops_requests, rate=args.ops_rate,
                       admission=args.ops_admission, workers=workers)
        return
    if args.ops:
        ops_demo(args.ops_requests)
        return

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    ctx = Ctx(cfg=cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 1, cfg.vocab_size)
    batch = {}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            key, (args.batch, cfg.encoder_frames, cfg.d_model), jnp.float32
        )
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            key, (args.batch, cfg.num_patches, cfg.d_model), jnp.float32
        )

    max_len = args.prompt_len + args.gen + (cfg.num_patches or 0)
    prefill = jax.jit(
        lambda p, toks: api.prefill(ctx, p, toks, max_len=max_len, batch=batch)
    )
    decode = jax.jit(lambda p, tok, st: api.decode_step(ctx, p, tok, st))

    t0 = time.perf_counter()
    logits, state = prefill(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for _ in range(args.gen - 1):
        logits, state = api_decode(decode, params, tok, state)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={args.batch}")
    print(f"prefill: {args.batch * args.prompt_len / t_prefill:.0f} tok/s ({t_prefill*1e3:.0f} ms)")
    print(f"decode:  {args.batch * (args.gen - 1) / max(t_decode, 1e-9):.0f} tok/s")
    print("sample token ids:", gen[0, :16].tolist())


def api_decode(decode_fn, params, tok, state):
    return decode_fn(params, tok, state)


if __name__ == "__main__":
    main()
