"""MoE layer: routing invariants, capacity behavior, dispatch-mode
equivalence on a multi-device submesh (subprocess)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ModelConfig
from repro.models.layers import Ctx
from repro.models.moe import (
    _capacity, _local_combine, _local_dispatch, _positions_in_expert,
    _route, moe_params, moe_sublayer,
)


def _cfg(e=4, k=2, cap=2.0):
    return ModelConfig(
        name="t", family="moe", num_layers=1, d_model=64, num_heads=2,
        num_kv_heads=2, d_ff=128, vocab_size=64, num_experts=e,
        experts_per_token=k, moe_d_ff=32, capacity_factor=cap,
        dtype="float32", remat=False,
    )


def test_positions_in_expert():
    ef = jnp.asarray([2, 0, 2, 1, 2, 0], dtype=jnp.int32)
    pos = np.asarray(_positions_in_expert(ef, 3))
    np.testing.assert_array_equal(pos, [0, 0, 1, 0, 2, 1])


def test_route_gates_normalized():
    cfg = _cfg()
    xt = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    router = jax.random.normal(jax.random.PRNGKey(1), (64, 4))
    gates, experts = _route(cfg, xt, router)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    assert int(experts.max()) < 4
    # top-k distinct experts per token
    assert all(len(set(r.tolist())) == 2 for r in np.asarray(experts))


def test_dispatch_combine_roundtrip_identity_experts():
    """With identity expert FFNs, dispatch+combine must reproduce the input
    (for tokens under capacity)."""
    cfg = _cfg(cap=8.0)  # ample capacity: nothing dropped
    t, d = 12, 64
    xt = jax.random.normal(jax.random.PRNGKey(0), (t, d))
    gates = jnp.full((t, 2), 0.5)
    experts = jnp.stack(
        [jnp.arange(t, dtype=jnp.int32) % 4, (jnp.arange(t, dtype=jnp.int32) + 1) % 4],
        axis=1,
    )
    cap = _capacity(cfg, t, 4)
    buf, ef, pos, keep = _local_dispatch(cfg, xt, gates, experts, cap)
    assert bool(keep.all())
    out = _local_combine(cfg, buf, gates, ef, pos, keep, t, d)  # identity "FFN"
    np.testing.assert_allclose(np.asarray(out), np.asarray(xt), rtol=1e-5)


def test_capacity_drops_overflow():
    cfg = _cfg(cap=0.25)
    t = 32
    xt = jax.random.normal(jax.random.PRNGKey(0), (t, 64))
    gates = jnp.full((t, 2), 0.5)
    experts = jnp.zeros((t, 2), jnp.int32)  # everyone wants expert 0
    cap = _capacity(cfg, t, 4)
    _, _, _, keep = _local_dispatch(cfg, xt, gates, experts, cap)
    assert int(keep.sum()) == cap  # exactly capacity kept, rest dropped


def test_single_device_moe_forward():
    cfg = _cfg()
    ctx = Ctx(cfg=cfg)
    p = moe_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64))
    out = moe_sublayer(ctx, p, x)
    assert out.shape == x.shape and not bool(jnp.isnan(out).any())


DISPATCH_EQUIV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.models.config import ModelConfig
from repro.models.layers import Ctx
from repro.models.moe import moe_params, moe_sublayer
from repro.models.sharding import make_rules

cfg = ModelConfig(name="t", family="moe", num_layers=1, d_model=64, num_heads=2,
                  num_kv_heads=2, d_ff=128, vocab_size=64, num_experts=8,
                  experts_per_token=2, moe_d_ff=32, capacity_factor=8.0,
                  dtype="float32", remat=False)
mesh = jax.make_mesh(
    (4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2
)
rules = make_rules(mesh, num_experts=8, num_heads=2, num_kv_heads=2)
ctx = Ctx(cfg=cfg, mesh=mesh, rules=rules)
ctx1 = Ctx(cfg=cfg)
p = moe_params(cfg, jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64))
ref = moe_sublayer(ctx1, p, x)
for mode in ("ep_push", "ep_pull", "tp"):
    with mesh:
        out = jax.jit(lambda p, x: moe_sublayer(ctx, p, x, dispatch=mode))(p, x)
    err = float(jnp.abs(out - ref).max())
    assert err < 1e-3, f"{mode}: {err}"
    print(f"{mode} err={err:.2e}")
print("MOE-DISPATCH-EQUIV-OK")
"""


@pytest.mark.slow
def test_dispatch_modes_equivalent_subprocess():
    """All three distributed dispatch strategies equal the single-device
    semantics (ample capacity so no drops)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", DISPATCH_EQUIV], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "MOE-DISPATCH-EQUIV-OK" in r.stdout
