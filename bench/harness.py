"""Run one cell of ``BENCHMARK.json`` once and assemble its result line.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name:

- ``bench/configs/<config>.json``: sizes, limits and the ``kind`` of cell;
- ``bench/kinds/<kind>.py``: builds the inputs from the seed, lays them
  out with the program, makes each request, and checks the answers;
- ``bench/traffic/<mix>.json``: parameters of :mod:`bench.load`;
- ``bench/metrics/<metric>.py``: ``read(run) -> float | None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from bench import load
from bench.peaks import peaks

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"

_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compiles",
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_reads",
}


class CompileClock:
    """Counts of XLA compiles, traces and persistent-cache reads, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.counts = dict.fromkeys(_COMPILE_EVENTS.values(), 0)
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        name = _COMPILE_EVENTS.get(event)
        if name is not None:
            with self._lock:
                self.counts[name] += 1
                if name == "compiles":
                    self.compile_s += duration

    def read(self) -> dict:
        with self._lock:
            return dict(self.counts)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(path: Path) -> dict:
    return json.loads(path.read_text())


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    setup_s: float
    window: load.Window
    work: list[dict]  # per record, empty for a request that failed
    service_stats: dict
    peaks: dict
    trace: object = None  # bench.trace.TraceSummary with --trace 1

    def total(self, key: str) -> float | None:
        values = [w[key] for w in self.work if key in w]
        return float(sum(values)) if values else None


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; ``inf`` counts as the largest."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q / 100 * len(ordered)) - 1)]


def metrics_for(spec: dict, workload: str, trace: bool) -> list[dict]:
    table = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in table if workload in m.get("workloads", [workload])]


def enable_compile_cache() -> None:
    """JAX's persistent cache in the checkout, at a fixed path, holding
    every program. No size limit: with one (``JAX_COMPILATION_CACHE_MAX_SIZE``
    on the chip's machine) every write failed on a v5e and every run
    compiled again."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def device_info(devices) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def run_cell(spec: dict, workload: dict, seed: int, seconds: float, trace: bool,
             t_start: float, config_overrides: dict | None = None) -> dict:
    """Build, warm, measure, check. Returns the result line's object."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.engine import EngineService
    from repro.engine.cache import PlanCache

    config = read_json(BENCH / "configs" / f"{workload['config']}.json")
    config.update(config_overrides or {})
    traffic = read_json(BENCH / "traffic" / f"{workload['traffic']}.json")
    kind = load_module(BENCH / "kinds" / f"{config['kind']}.py")
    devices = jax.devices()[: workload["chips"]]
    chip_peaks = peaks(devices[0].device_kind) if devices[0].platform == "tpu" else {}
    clock = CompileClock()
    enable_compile_cache()

    cell = kind.Cell(config, seed)
    plans = PlanCache()
    with EngineService(cache=plans, workers="auto") as warm:
        for request in cell.warm_requests():
            np.asarray(warm.submit(request).result().result)
    service = EngineService(cache=plans, workers="auto").start()
    trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-") if trace else None
    try:
        setup_s = time.perf_counter() - t_start
        before = clock.read()
        if trace_dir:
            _start_trace(trace_dir.name)
        with TraceAnnotation("bench.window"):
            window = load.drive(traffic, service, cell, seconds)
        if trace_dir:
            jax.profiler.stop_trace()
        in_window = {k: v - before[k] for k, v in clock.read().items()}
        device = device_info(devices)
        service_stats = service.stats().to_dict()
    finally:
        service.stop()
    summary = None
    if trace_dir:
        from bench.trace import reduce_file

        with trace_dir:
            summary = reduce_file(_xplane(trace_dir.name))
    cell.release()
    served = [r.done - r.submit for r in window.records if r.ok]
    print("window " + json.dumps({
        "requests": len(window.records), "seconds": window.seconds, "setup_s": setup_s,
        "xla_in_window": in_window, "compile_s_in_setup": clock.compile_s,
        "submit_to_host_ms": {q: percentile(served, q) * 1e3 for q in (5, 50, 95)} if served else None,
    }), flush=True)

    attempted = len(window.records)
    failed = sum(not r.ok for r in window.records)
    checks = cell.check(attempted)
    correct = all(value <= limit for value, limit in checks.values())
    by_tag: dict[int, dict] = {}
    work = [by_tag.setdefault(r.tag, cell.work(r.tag)) if r.ok else {} for r in window.records]
    run = Run(setup_s, window, work, service_stats, chip_peaks, summary)
    metrics = {}
    for metric in metrics_for(spec, workload["name"], trace):
        value = load_module(BENCH / "metrics" / f"{metric['name']}.py").read(run)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    for name, (value, limit) in checks.items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {name: {"value": v, "limit": lim} for name, (v, lim) in checks.items()}
    return result


def _start_trace(path: str) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(path, profiler_options=options)


def _xplane(path: str) -> str:
    found = sorted(Path(path).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {path}, found {len(found)}")
    return str(found[0])
