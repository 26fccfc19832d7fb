"""Beyond-paper perf optimizations must be numerically exact (EXPERIMENTS.md
§Perf): padded-head TP equals the unsharded baseline."""
import os
import subprocess
import sys

import pytest

PAD_HEADS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, dataclasses
from repro.configs import reduced_config
from repro.models import api, Ctx
from repro.models.sharding import make_rules

cfg = dataclasses.replace(
    reduced_config("llama3.2-3b"), num_heads=6, num_kv_heads=2, head_dim=16,
    d_model=96, d_ff=192,
)
mesh = jax.make_mesh(
    (2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2
)
rules = make_rules(mesh, num_heads=6, num_kv_heads=2, vocab_size=cfg.vocab_size)
assert rules.heads4d is None  # 6 % 4 != 0 -> baseline replicates attention
params = api.init_params(cfg, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
m = api.module_for(cfg)
ctx_base = Ctx(cfg=cfg, mesh=mesh, rules=rules)
ctx_pad = Ctx(cfg=dataclasses.replace(cfg, tp_pad_heads=True), mesh=mesh, rules=rules)
with mesh:
    ref = jax.jit(lambda p, t: m.forward(ctx_base, p, t))(params, toks)
    pad = jax.jit(lambda p, t: m.forward(ctx_pad, p, t))(params, toks)
err = float(jnp.abs(ref - pad).max())
assert err < 1e-4, err
print("PAD-HEADS-EXACT-OK", err)
"""


@pytest.mark.slow
def test_padded_head_tp_exact_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", PAD_HEADS], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    assert "PAD-HEADS-EXACT-OK" in r.stdout


def test_pad_heads_inactive_on_single_device():
    """Without a model axis the padded path must not engage (semantics oracle
    stays the plain one)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import reduced_config
    from repro.models import Ctx, api

    cfg = dataclasses.replace(reduced_config("llama3.2-3b"), tp_pad_heads=True)
    ctx = Ctx(cfg=cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    m = api.module_for(cfg)
    base = m.forward(Ctx(cfg=reduced_config("llama3.2-3b")), params, toks)
    padded = m.forward(ctx, params, toks)
    assert float(jnp.abs(base - padded).max()) == 0.0
