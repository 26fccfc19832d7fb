"""MoE dispatch through the engine (ISSUE 4): the fourth MigratoryOp's
strategy A/B on the local substrate, the autotuner's ``auto`` pick, and an
async ``EngineService`` serving phase with the value-keyed dedup cache.

Unlike ``moe_dispatch`` (which lowers the full LM MoE sublayer in a
subprocess and reads collective bytes out of the HLO), this suite runs the
*engine-served* ``moe_dispatch`` op in-process at quick-friendly sizes:
every row is a unified RunReport row (modeled traffic = the roofline
collective-bytes cost model the autotuner ranks), plus a ``service`` row
carrying the serving stats (dedup hits, latency percentiles). Writes
``experiments/moe_bench_results.json``.

The **cross-check phase** (ISSUE 8 acceptance) closes the loop between the
two byte counters: for every expert-parallel scenario x {ep_push, ep_pull}
a subprocess with 8 forced host devices runs the *modeled* traffic (the
``TrafficStats.collective_bytes`` the engine report carries — paper-lens
total bytes across all nodelets at kept-slot granularity) and the *lowered*
traffic (``roofline.analyze`` over the compiled mesh kernel's HLO —
per-instruction wire bytes with the standard all_to_all/all_gather
discounts), and asserts their ratio lies inside a generous honest band.
The two counters measure deliberately different things (total modeled
payload vs wire-level estimate), so the band is wide — [1/8, 8]; observed
ratios sit in ~[2.4, 5.4] — but a sign error, a dropped collective, or a
miscounted payload dimension blows straight through it.
"""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from .util import cpu_child_env, emit, emit_report

XCHECK_BAND = 8.0

XCHECK_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import numpy as np, jax, jax.numpy as jnp
from repro.core import Comm, MigratoryStrategy
from repro.engine import MoEDispatchInputs, Request, get_substrate, run
from repro.launch import roofline

band = float(sys.argv[1])
scenarios = json.loads(sys.argv[2])
rng = np.random.default_rng(0)
sub = get_substrate("mesh")
out = []
for name, t, d, e, p in scenarios:
    inputs = MoEDispatchInputs(
        x=jnp.asarray(rng.standard_normal((t, d)).astype(np.float32)),
        router=jnp.asarray(rng.standard_normal((d, e)).astype(np.float32)),
        nodelets=p)
    for mode, st in (("ep_push", MigratoryStrategy(comm=Comm.REMOTE_WRITE)),
                     ("ep_pull", MigratoryStrategy(comm=Comm.MIGRATE))):
        _, rep = run(Request("moe_dispatch", inputs, st, "local"))
        modeled = rep.traffic.collective_bytes
        kern = sub.kernel("moe_dispatch")
        f = jax.jit(lambda x, r, st=st, p=p: kern(
            x, r, strategy=st, nodelets=p,
            experts_per_token=inputs.experts_per_token,
            capacity_factor=inputs.capacity_factor))
        lowered = roofline.analyze(
            f.lower(inputs.x, inputs.router).compile().as_text()
        ).bytes_collective
        ratio = modeled / max(lowered, 1.0)
        ok = (1.0 / band) <= ratio <= band
        out.append({"scenario": name, "mode": mode,
                    "modeled_bytes": int(modeled),
                    "lowered_wire_bytes": float(lowered),
                    "ratio": round(ratio, 4), "in_band": ok})
        assert ok, ("modeled-vs-lowered collective bytes out of band",
                    name, mode, modeled, lowered, ratio, band)
print("MOE-XCHECK-OK" + json.dumps(out))
"""


def _run_xcheck_phase(scenarios) -> list:
    """Subprocess modeled-vs-lowered cross-check over the expert-parallel
    scenarios (tp scenarios carry zero collective bytes on both sides and
    are skipped). Raises if any (scenario, mode) pair leaves the band."""
    cases = [s for s in scenarios if s[3] % s[4] == 0]  # ep needs E % P == 0
    if not cases:
        return []
    env = cpu_child_env()
    proc = subprocess.run(
        [sys.executable, "-c", XCHECK_SCRIPT, str(XCHECK_BAND),
         json.dumps(cases)],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    marker = "MOE-XCHECK-OK"
    if proc.returncode != 0 or marker not in proc.stdout:
        raise RuntimeError(
            f"moe cross-check subprocess failed (rc={proc.returncode}):\n"
            f"stdout={proc.stdout}\nstderr={proc.stderr}"
        )
    line = next(l for l in proc.stdout.splitlines() if l.startswith(marker))
    return json.loads(line[len(marker):])

OUT_PATH = Path(__file__).resolve().parents[1] / "experiments" / "moe_bench_results.json"


def _scenarios(full: bool, quick: bool):
    # (name, tokens, d_model, experts, nodelets)
    if quick:
        return [
            ("t128_e16_p8", 128, 32, 16, 8),
            ("t96_e6_p4_tp", 96, 16, 6, 4),
        ]
    if full:
        return [
            ("t1024_e16_p8", 1024, 128, 16, 8),
            ("t2048_e32_p8", 2048, 128, 32, 8),
            ("t1536_e6_p8_tp", 1536, 96, 6, 8),
        ]
    return [
        ("t256_e16_p8", 256, 64, 16, 8),
        ("t512_e8_p4", 512, 64, 8, 4),
        ("t192_e6_p4_tp", 192, 32, 6, 4),
    ]


def run(full: bool = False, quick: bool = False):
    from repro.engine import (
        EngineService,
        MoEDispatchInputs,
        PlanCache,
        Request,
        candidate_grid,
        choose_strategy,
    )
    from repro.engine import run as engine_run

    rows = []
    rng = np.random.default_rng(0)
    service_cases = []
    scenarios = _scenarios(full, quick)
    for name, t, d, e, p in scenarios:
        inputs = MoEDispatchInputs(
            x=jnp.asarray(rng.standard_normal((t, d)).astype(np.float32)),
            router=jnp.asarray(rng.standard_normal((d, e)).astype(np.float32)),
            nodelets=p,
        )
        for st in candidate_grid("moe_dispatch"):
            _, rep = engine_run("moe_dispatch", inputs, st, "local")
            rows.append(emit_report(
                "moe", f"{name}_{st.comm.value}", rep, scenario=name,
            ))
        auto = choose_strategy("moe_dispatch", inputs)
        _, rep = engine_run("moe_dispatch", inputs, "auto", "local")
        rows.append(emit_report(
            "moe", f"{name}_auto", rep, scenario=name,
            auto_comm=auto.comm.value,
        ))
        service_cases.append((name, inputs))

    # serving phase: repeats of each scenario through the async worker loop
    # with dedup on — repeats after the first completion are answered from
    # the value-keyed response cache
    per = 2 if quick else 4
    svc = EngineService(cache=PlanCache(), dedup=True, batch_window=0.01)
    svc.start()
    try:
        futures = [
            svc.submit(Request("moe_dispatch", inputs, "auto"))
            for _ in range(per)
            for _, inputs in service_cases
        ]
        for f in futures:
            f.result(timeout=600)
    finally:
        svc.stop()
    stats = svc.stats().to_dict()
    rows.append(emit(
        "moe", "service", stats["wall_seconds"],
        op="moe_dispatch", substrate="local",
        requests=stats["requests"],
        dedup_hits=stats["dedup_hits"],
        compiles=stats["compiles"],
        cache_hits=stats["cache_hits"],
        queue_wait_p95=round(stats["queue_wait_p95"], 6),
        service_p50=round(stats["service_p50"], 6),
        service_p95=round(stats["service_p95"], 6),
        service_p99=round(stats["service_p99"], 6),
    ))

    # modeled-vs-lowered collective-bytes cross-check (subprocess, 8 devices)
    for rec in _run_xcheck_phase(scenarios):
        rows.append(emit(
            "moe", f"xcheck_{rec['scenario']}_{rec['mode']}", 0.0,
            op="moe_dispatch", substrate="mesh", platform="cpu",
            scenario=rec["scenario"], dispatch_mode=rec["mode"],
            modeled_bytes=rec["modeled_bytes"],
            lowered_wire_bytes=rec["lowered_wire_bytes"],
            modeled_over_lowered=rec["ratio"],
            band=XCHECK_BAND, in_band=rec["in_band"],
        ))
    from .util import machine_header

    OUT_PATH.parent.mkdir(parents=True, exist_ok=True)
    OUT_PATH.write_text(json.dumps(
        [{"bench": "moe", "case": "_machine", **machine_header()}] + rows,
        indent=2, default=str,
    ))
    print(f"# wrote {OUT_PATH} ({len(rows)} rows)")
    return rows


if __name__ == "__main__":
    run(quick=True)
