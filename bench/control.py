#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload spmv.lap.solver --seeds 1,2,3 \\
        --control-seeds 3 --seconds 5

For every seed, in one process: build the cell at its own size, warm it,
serve a short window of the cell's own traffic through ``EngineService``,
then read the numbers ``correct`` compares (the program's readings). For
the first ``--control-seeds`` seeds also read the control: the reference
put in the program's place one step below the configuration's guarantee
(see each kind's ``control``). One JSON line per seed. The benchmark's own
runs never run the control.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(workload: dict, seed: int, seconds: float, control: bool,
             config_overrides: dict | None = None) -> dict:
    import numpy as np

    from bench import harness, load
    from repro.engine import EngineService

    config = harness.read_json(harness.BENCH / "configs" / f"{workload['config']}.json")
    config.update(config_overrides or {})
    traffic = harness.read_json(harness.BENCH / "traffic" / f"{workload['traffic']}.json")
    kind = harness.load_module(harness.BENCH / "kinds" / f"{config['kind']}.py")
    t0 = time.perf_counter()
    cell = kind.Cell(config, seed)
    with EngineService(workers="auto") as service:
        for request in cell.warm_requests():
            np.asarray(service.submit(request).result().result)
        window = load.drive(traffic, service, cell, seconds)
        ctrl = cell.control(service) if control else None
    cell.release()
    checks = cell.check(len(window.records))
    return {
        "seed": seed, "requests": len(window.records), "build_and_window_s": time.perf_counter() - t0,
        "program": {name: value for name, (value, _) in checks.items()},
        "limits": {name: limit for name, (_, limit) in checks.items()},
        "control": ctrl,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()

    import jax

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; nothing run", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    workload = {w["name"]: w for w in spec["workloads"]}[args.workload]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        row = readings(workload, seed, args.seconds, i < args.control_seeds)
        print(json.dumps({"workload": args.workload, **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
