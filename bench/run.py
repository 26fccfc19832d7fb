#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload spmv.lap.solver --seed 7 --seconds 50 --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. The run builds
its inputs from ``--seed``, warms every program it will use (set-up), serves
requests through the program's ``EngineService`` for ``--seconds``, checks
the answers against the plain references, and prints as its last line one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics from
a profiler trace of the window), ``device``, ``breakdown`` (traced runs)
and ``checks``, each compared number with its limit. The compared numbers
are also the last lines on standard error. With no TPU, or fewer chips than
the cell asks for, it prints no result and exits 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        print(f"bench: no workload {args.workload!r} (known: {sorted(cells)})", file=sys.stderr)
        return 2
    workload = cells[args.workload]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < workload["chips"]:
        print(f"bench: {args.workload} needs {workload['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s). Nothing run.", file=sys.stderr)
        return 2

    from bench.harness import run_cell

    result = run_cell(spec, workload, args.seed, args.seconds, bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
