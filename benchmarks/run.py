"""Benchmark runner: one suite per paper table/figure + kernel micro-benches
+ the autotune strategy sweeps + the serving suites (sync-vs-async `serve`,
8-device `mesh`) + the engine-served MoE dispatch op (`moe`, writes
`experiments/moe_bench_results.json`) + the beyond-paper HLO-level MoE
dispatch A/B (`moe_dispatch`).

    PYTHONPATH=src python -m benchmarks.run [--bench NAME] [--full] [--quick]

Every row follows the unified RunReport schema (op, strategy_*, substrate,
seconds, cache_hit, compile_seconds, effective_gbps, migrations,
remote_writes, op metrics) so ``bench_results.json`` trajectories are
comparable across suites and PRs. Engine suites share the process-wide
compiled-plan cache, so repeated problem signatures compile once; the final
``_cache`` row records the run's hit-rate (``--require-cache-hits`` turns a
zero hit-rate into a CI failure). Prints ``bench,case,us_per_call,derived``
CSV rows and writes ``experiments/bench_results.json`` (+ the autotune
ranking table to ``experiments/autotune_ranking.json``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

SUITES = {}

# subprocess-heavy suites skipped in --quick smoke runs (still runnable
# explicitly via --bench NAME / --cluster N; the mesh-8dev and
# cluster-smoke CI jobs do exactly that)
SLOW_SUITES = ("moe_dispatch", "mesh", "cluster")


def _register():
    from . import (
        autotune_suite,
        bfs_suite,
        cluster_suite,
        gsana_suite,
        kernels_suite,
        mesh_suite,
        moe_dispatch,
        moe_suite,
        serve_suite,
        spmv_suite,
    )

    SUITES.update({
        "spmv": spmv_suite.run,
        "bfs": bfs_suite.run,
        "gsana": gsana_suite.run,
        "autotune": autotune_suite.run,
        "serve": serve_suite.run,
        "kernels": kernels_suite.run,
        "moe": moe_suite.run,
        "moe_dispatch": moe_dispatch.run,
        "mesh": mesh_suite.run,
        "cluster": cluster_suite.run,
    })


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default=None, help="suite name (default: all)")
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument(
        "--quick", action="store_true",
        help="CI smoke mode: smallest sizes, skip subprocess suites",
    )
    ap.add_argument(
        "--require-cache-hits", action="store_true",
        help="fail (exit 1) if the compiled-plan cache saw zero hits",
    )
    ap.add_argument(
        "--require-overlap", action="store_true",
        help="fail (exit 1) if the serve suite's async pipeline showed zero "
        "compile/execute overlap",
    )
    ap.add_argument(
        "--workers", type=int, default=None,
        help="run the serve suite's pooled execution-plane phase with this "
        "many executor workers (8 forced host devices, mesh substrate; "
        "writes experiments/pool_stats.json)",
    )
    ap.add_argument(
        "--require-pool-speedup", type=float, default=0.0,
        help="with --workers: fail unless pooled drain throughput is at "
        "least this multiple of the workers=1 baseline (asserted inside "
        "the bench subprocess; CI uses 1.3)",
    )
    ap.add_argument(
        "--require-p99", type=float, default=0.0,
        help="fail unless every decode-serving mode's end-to-end p99 stays "
        "under this many milliseconds (asserted inside the serve suite's "
        "decode subprocess — the fail-closed SLO gate; the gate value also "
        "becomes the service's declared slo_target_seconds)",
    )
    ap.add_argument(
        "--cluster", type=int, default=None, metavar="N",
        help="run the cluster suite on an N-worker localhost cluster "
        "(multi-process serving plane; fail-closed parity + distribution "
        "gates asserted inside the suite; writes "
        "experiments/cluster_stats.json)",
    )
    ap.add_argument(
        "--require-wire-reduction", type=float, default=0.0, metavar="X",
        help="with the cluster suite: fail unless the data-plane phase "
        "moved at least X times fewer bytes than the v1 inline encoding "
        "would have, with blob_hits > 0 (asserted inside the suite and "
        "recorded in experiments/cluster_stats.json; CI uses 3)",
    )
    ap.add_argument(
        "--machine-file", default=None,
        help="run suites against this pinned machine file "
        "(sets REPRO_MACHINE_PATH for this process)",
    )
    ap.add_argument(
        "--calibrate", action="store_true",
        help="run the quick microbench suite first and write a fresh "
        "machine file (to --machine-file if given, else "
        "experiments/machine.json); suites then rank in predicted seconds",
    )
    ap.add_argument(
        "--require-model-band", type=float, default=0.0,
        help="fail unless every (op, substrate)'s median modeled-vs-measured "
        "ratio lies within this factor (e.g. 5 -> [1/5, 5]); needs "
        "--calibrate or --machine-file so there is a model to gate",
    )
    ap.add_argument("--out", default=None, help="output JSON path")
    args = ap.parse_args(argv)
    # the pool gate must fail closed: a gate with no pool phase to run
    # (missing/1-wide --workers, or a suite selection that skips serve)
    # would otherwise exit green without ever measuring anything
    if args.require_pool_speedup > 0 and (args.workers is None or args.workers < 2):
        ap.error("--require-pool-speedup needs --workers >= 2 to have a pool to gate")
    if args.workers is not None and args.bench not in (None, "serve"):
        ap.error("--workers drives the serve suite's pool phase; use --bench serve")
    if args.cluster is not None:
        if args.bench not in (None, "cluster"):
            ap.error("--cluster runs the cluster suite; drop --bench or use "
                     "--bench cluster")
        if args.cluster < 1:
            ap.error("--cluster needs at least 1 worker (CI uses 2)")
    # the wire gate fails closed: without the cluster suite in the run
    # there is no data-plane phase to measure, and an unmeasured gate must
    # not pass green
    if args.require_wire_reduction > 0 and args.cluster is None and (
        args.bench != "cluster"
    ):
        ap.error("--require-wire-reduction gates the cluster suite's "
                 "data-plane phase; use --cluster N (or --bench cluster)")
    # the SLO gate fails closed too: gating p99 without the serve suite's
    # decode phase in the run would exit green having measured nothing
    if args.require_p99 > 0 and args.bench not in (None, "serve"):
        ap.error("--require-p99 gates the serve suite's decode phase; "
                 "use --bench serve (or no --bench)")
    # the model gate fails closed the same way: without a calibration there
    # are no predicted columns, and an empty gate must not pass green
    if args.require_model_band > 0 and not (args.calibrate or args.machine_file):
        ap.error("--require-model-band needs --calibrate or --machine-file "
                 "to have a model to gate")
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.machine_file:
        os.environ["REPRO_MACHINE_PATH"] = str(Path(args.machine_file).resolve())
    if args.calibrate:
        from repro.machine import reset_default_machine_cache
        from repro.machine.machine import default_machine_path
        from repro.machine.microbench import calibrate

        path = calibrate(quick=True).save(default_machine_path())
        reset_default_machine_cache()
        print(f"# calibrated machine file -> {path}")
    _register()
    if args.bench:
        if args.bench not in SUITES:
            ap.error(f"unknown suite {args.bench!r}; choose from {sorted(SUITES)}")
        names = [args.bench]
    elif args.cluster is not None:
        names = ["cluster"]  # --cluster N == --bench cluster with N workers
    else:
        names = [n for n in SUITES if not (args.quick and n in SLOW_SUITES)]
    print("bench,case,us_per_call,derived")
    from .util import machine_header

    header = machine_header()
    print(
        f"# machine file: {header['machine_file']} "
        f"(calibrated={header['machine_calibrated']})"
    )
    all_rows = [{"bench": "_machine", "case": "header", **header}]
    for name in names:
        if name == "serve":
            all_rows.extend(SUITES[name](
                full=args.full, quick=args.quick, workers=args.workers,
                min_pool_speedup=args.require_pool_speedup,
                require_p99_ms=args.require_p99,
            ))
        elif name == "cluster":
            all_rows.extend(SUITES[name](
                full=args.full, quick=args.quick,
                n_workers=args.cluster if args.cluster is not None else 2,
                require_wire_reduction=args.require_wire_reduction or None,
            ))
        else:
            all_rows.extend(SUITES[name](full=args.full, quick=args.quick))

    from repro.engine import default_cache

    cache_stats = default_cache().stats()
    all_rows.append({"bench": "_cache", "case": "default_cache", **cache_stats})
    print(
        f"# plan cache: {cache_stats['entries']} entries, "
        f"{cache_stats['hits']} hits / {cache_stats['misses']} misses "
        f"(hit rate {cache_stats['hit_rate']:.0%}), "
        f"{cache_stats['compile_seconds_total']:.2f}s compiling"
    )
    out = (
        Path(args.out)
        if args.out
        else Path(__file__).resolve().parents[1] / "experiments" / "bench_results.json"
    )
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(all_rows, indent=2, default=str))
    print(f"# wrote {out} ({len(all_rows)} rows)")
    if args.require_cache_hits and cache_stats["hits"] == 0:
        print("# FAIL: compiled-plan cache saw zero hits", file=sys.stderr)
        sys.exit(1)
    if args.require_overlap:
        async_rows = [
            r for r in all_rows
            if r.get("bench") == "serve" and r.get("case") == "async_worker"
        ]
        if not async_rows or all(r.get("overlap_ratio", 0) <= 0 for r in async_rows):
            print(
                "# FAIL: serve suite showed zero compile/execute overlap",
                file=sys.stderr,
            )
            sys.exit(1)
    if args.require_model_band > 0:
        _gate_model_band(all_rows, args.require_model_band)


def _gate_model_band(all_rows: list, band: float) -> None:
    """Per-(op, substrate) median modeled-vs-measured ratio must lie within
    [1/band, band]. model_error columns only exist on rows measured under a
    calibrated machine file (subprocess suites with a different forced
    topology legitimately carry none), but *zero* gated rows means the
    calibration never reached the suites — fail, don't pass vacuously."""
    import statistics

    groups: dict[tuple, list] = {}
    for r in all_rows:
        if r.get("model_error") is not None and r.get("op") and r.get("substrate"):
            groups.setdefault((r["op"], r["substrate"]), []).append(
                float(r["model_error"])
            )
    if not groups:
        print(
            "# FAIL: --require-model-band found no rows with model_error "
            "(did calibration happen in this process?)",
            file=sys.stderr,
        )
        sys.exit(1)
    failed = False
    for (op, sub), errs in sorted(groups.items()):
        med = statistics.median(errs)
        ok = (1.0 / band) <= med <= band
        print(
            f"# model band {op}/{sub}: median predicted/measured = {med:.3f} "
            f"over {len(errs)} rows ({'ok' if ok else 'OUT OF BAND'})"
        )
        if not ok:
            failed = True
    if failed:
        print(
            f"# FAIL: modeled-vs-measured outside the {band}x band "
            "(unit-level model bug?)",
            file=sys.stderr,
        )
        sys.exit(1)


if __name__ == "__main__":
    main()
