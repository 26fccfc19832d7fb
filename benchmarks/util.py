"""Benchmark harness utilities: warmed, blocked wall-clock timing + the
unified RunReport row schema every suite emits."""
from __future__ import annotations

import os
import time
from pathlib import Path

import jax


def cpu_child_env() -> dict:
    """Environment for a benchmark child process that forces host devices.

    Such a child is a CPU rehearsal: ``JAX_PLATFORMS=cpu`` keeps it off the
    chip, which the parent already holds once it has touched JAX (a chip
    belongs to one process). Rows built from its results say
    ``platform="cpu"``."""
    env = dict(os.environ)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def machine_header() -> dict:
    """The calibration provenance every suite's JSON output carries
    (DESIGN.md §1f): which machine file was active, whether it was
    calibrated, and for which topology. Uncalibrated runs say so instead of
    omitting the key — absence of calibration is itself a measurement
    condition worth recording."""
    from repro.machine import default_machine, default_machine_path

    profile = default_machine()
    return {
        "machine_file": str(default_machine_path()),
        "machine_calibrated": profile.calibrated,
        "machine_fingerprint": profile.fingerprint,
        "machine_quick": profile.quick,
    }


def time_fn(fn, *args, iters: int = 5, warmup: int = 2):
    """Median wall seconds per call of fn(*args) (jit-warmed, blocked)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def emit(bench: str, case: str, seconds: float, **derived) -> dict:
    """Free-form row (kernel micro-benches and model-only sweeps). Carries
    the same core keys as the RunReport schema so JSON rows stay comparable."""
    row = {
        "bench": bench, "case": case, "seconds": seconds,
        "us_per_call": seconds * 1e6, **derived,
    }
    extras = ",".join(f"{k}={v}" for k, v in derived.items())
    print(f"{bench},{case},{row['us_per_call']:.1f},{extras}")
    return row


def emit_report(bench: str, case: str, report, **derived) -> dict:
    """Unified row from an ``engine.RunReport``: op, strategy_*, substrate,
    seconds, traffic counts, effective bandwidth, op metrics."""
    row = {"bench": bench, "case": case, **report.to_dict(), **derived}
    keys = ("op", "substrate", "migrations", "remote_writes", "effective_gbps")
    extras = ",".join(f"{k}={row[k]}" for k in keys if k in row)
    if derived:
        extras += "," + ",".join(f"{k}={v}" for k, v in derived.items())
    print(f"{bench},{case},{row['us_per_call']:.1f},{extras}")
    return row
