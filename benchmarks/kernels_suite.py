"""Kernel micro-benches (interpret-mode correctness-path timings on CPU; on
TPU these run natively — the numbers here track relative effects only):
flash-attention block sizes and fused topk-sim vs unfused reference."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.topk_sim.ops import topk_sim_pairs
from repro.core import bucketize, generate_alignment_pair, neighbor_buckets, pick_grid

from .util import emit, time_fn


def flash_blocks(full: bool = False, quick: bool = False):
    rows = []
    rng = np.random.default_rng(1)
    b, hq, hkv, s, d = 1, 4, 2, (1024 if full else (128 if quick else 256)), 64
    q = jnp.asarray(rng.standard_normal((b, hq, s, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32))
    sec = time_fn(lambda: attention_reference(q, k, v), iters=3)
    rows.append(emit("kernel_flash", "jnp_ref", sec))
    for bq, bk in ((64, 64), (128, 128)):
        sec = time_fn(lambda: flash_attention(q, k, v, block_q=bq, block_k=bk), iters=3)
        rows.append(emit("kernel_flash", f"pallas_{bq}x{bk}", sec))
    return rows


def topk_sim(full: bool = False, quick: bool = False):
    rows = []
    n = 2048 if full else (256 if quick else 512)
    vs1, vs2, _ = generate_alignment_pair(n, seed=5)
    grid = pick_grid(n, 32)
    cap = max(bucketize(vs1, grid).cap, bucketize(vs2, grid).cap)
    b1 = bucketize(vs1, grid, cap=cap)
    b2 = bucketize(vs2, grid, cap=cap)
    nb = neighbor_buckets(grid)
    g2 = grid * grid
    pb2 = jnp.asarray(np.repeat(np.arange(g2), 9))
    pb1 = jnp.asarray(nb.reshape(-1))
    for use_kernel, name in ((False, "jnp_ref"), (True, "pallas_fused")):
        sec = time_fn(
            lambda: topk_sim_pairs(vs1, vs2, b1, b2, pb2, pb1, use_kernel=use_kernel),
            iters=3,
        )
        rows.append(emit("kernel_topk_sim", name, sec, pairs=int(g2 * 9)))
    return rows


def run(full: bool = False, quick: bool = False):
    return flash_blocks(full, quick) + topk_sim(full, quick)
