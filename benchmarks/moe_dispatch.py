"""Beyond-paper benchmark (paper §7 put/get asymmetry at LM scale): MoE
dispatch strategy A/B — remote-write push (all_to_all) vs migrate pull
(all_gather) vs tp (local dispatch) — measured as per-device collective wire
bytes from the lowered HLO on an 8-device sub-mesh (subprocess, so the main
process keeps 1 device). Dispatch modes are derived from MigratoryStrategy
via ``repro.models.moe.dispatch_from_strategy`` (the engine mapping)."""
from __future__ import annotations

import json
import subprocess
import sys

from .util import cpu_child_env, emit

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.core.strategies import Comm, MigratoryStrategy
from repro.models.config import ModelConfig
from repro.models.layers import Ctx
from repro.models.moe import dispatch_from_strategy, moe_params, moe_sublayer
from repro.models.sharding import make_rules
from repro.launch import roofline

cfg = ModelConfig(
    name="bench-moe", family="moe", num_layers=1, d_model=512, num_heads=8,
    num_kv_heads=8, d_ff=1024, vocab_size=1024, num_experts=16,
    experts_per_token=2, moe_d_ff=1024, dtype="float32", remat=False,
)
mesh = jax.make_mesh(
    (4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2
)
rules = make_rules(mesh, num_experts=cfg.num_experts, num_heads=8, num_kv_heads=8)
ctx = Ctx(cfg=cfg, mesh=mesh, rules=rules)
params = moe_params(cfg, jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (8, 128, 512))
cases = {
    "ep_push": MigratoryStrategy(comm=Comm.REMOTE_WRITE),
    "ep_pull": MigratoryStrategy(comm=Comm.MIGRATE),
    "tp": None,  # S1 replication fallback (explicit mode)
}
out = {}
for name, strat in cases.items():
    mode = name if strat is None else dispatch_from_strategy(
        strat, num_experts=cfg.num_experts, data_axis=mesh.shape["data"])
    assert strat is None or mode == name, (name, mode)
    with mesh:
        co = jax.jit(lambda p, x: moe_sublayer(ctx, p, x, dispatch=mode)).lower(params, x).compile()
    rep = roofline.analyze(co.as_text())
    out[name] = {
        "collective_wire_bytes": rep.bytes_collective,
        "by_kind": rep.collective_counts,
        "flops": rep.flops,
        "strategy_comm": strat.comm.value if strat else "replicate",
    }
print("RESULT" + json.dumps(out))
"""


def run(full: bool = False, quick: bool = False):
    env = cpu_child_env()
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True,
        timeout=600,
    )
    rows = []
    for line in r.stdout.splitlines():
        if line.startswith("RESULT"):
            data = json.loads(line[len("RESULT"):])
            for mode, d in data.items():
                rows.append(emit(
                    "moe_dispatch", mode, 0.0,
                    op="moe_dispatch", substrate=mode, platform="cpu",
                    strategy_comm=d["strategy_comm"],
                    collective_bytes=d["collective_wire_bytes"],
                    collective_wire_mb=round(d["collective_wire_bytes"] / 1e6, 3),
                    kinds="|".join(f"{k}:{round(v/1e6,2)}MB" for k, v in d["by_kind"].items()),
                ))
    if not rows:
        print("moe_dispatch,FAILED,0.0,", r.stderr[-500:])
    return rows
