"""Pallas TPU kernel: fused GSANA bucket-pair similarity + top-k (S3/PAIR).

One grid program = one ⟨B, B'⟩ PAIR task (paper Alg. 5). The irregular
per-vertex metadata (sorted type/attribute arrays) is packed OUTSIDE the
kernel into dense feature planes (histograms + scalars, see ops.py) so the
kernel streams two MXU/VPU-aligned tiles:

    feat_v (A, F), feat_u (B, F)  ->  scores (A, k), idx (A, k)

computing all five σ metrics (Δ, τ, τ_V, τ_E, C_V) as elementwise/reduction
ops on the feature planes, then maintaining the paper's "priority list with
top k elements" entirely in VMEM via k unrolled max-and-mask selection passes
— no global memory traffic for the priority queues.

Feature plane layout (F = 5 + T1 + T2 + T3, padded):
    [0] deg, [1] vtype, [2] |ntypes|, [3] |etypes|, [4] |attrs|,
    [5:5+T1] ntypes hist, [5+T1:5+T1+T2] etypes hist, [...:+T3] attrs hist.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..runtime import resolve_interpret

NEG = float("-inf")


def _sim_from_feats(fv, fu, t1: int, t2: int, t3: int):
    """(A, F) x (B, F) -> (A, B) σ scores (valid-slot masking done by caller)."""
    deg_v, deg_u = fv[:, 0], fu[:, 0]
    s_deg = 1.0 / (1.0 + jnp.abs(deg_v[:, None] - deg_u[None, :]))
    s_typ = (fv[:, 1][:, None] == fu[:, 1][None, :]).astype(jnp.float32)

    def ov(lo, width, nslot):
        hv = fv[:, lo : lo + width]
        hu = fu[:, lo : lo + width]
        inter = jnp.minimum(hv[:, None, :], hu[None, :, :]).sum(-1)
        denom = jnp.maximum(jnp.maximum(fv[:, nslot][:, None], fu[:, nslot][None, :]), 1.0)
        return inter / denom

    o = 5
    s_nt = ov(o, t1, 2)
    s_et = ov(o + t1, t2, 3)
    s_at = ov(o + t1 + t2, t3, 4)
    return 0.2 * (s_deg + s_typ + s_nt + s_et + s_at)


def _topk_sim_kernel(
    fv_ref, fu_ref, mv_ref, mu_ref, score_ref, idx_ref, *, t1, t2, t3, k
):
    fv = fv_ref[0]  # (A, F)
    fu = fu_ref[0]  # (B, F)
    mv = mv_ref[0]  # (A, 1) validity
    mu = mu_ref[0]  # (1, B)
    s = _sim_from_feats(fv, fu, t1, t2, t3)
    valid = (mv > 0) & (mu > 0)
    s = jnp.where(valid, s, NEG)
    a, b = s.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (a, b), 1)
    # k unrolled selection passes: running top-k priority list in VMEM
    for j in range(k):
        m = jnp.max(s, axis=1)
        arg = jnp.argmax(s, axis=1).astype(jnp.int32)
        score_ref[0, :, j] = m
        idx_ref[0, :, j] = arg
        s = jnp.where(cols == arg[:, None], NEG, s)


@functools.partial(jax.jit, static_argnames=("t1", "t2", "t3", "k", "interpret"))
def topk_sim_pallas(
    feat_v: jax.Array,  # (P, A, F) f32
    feat_u: jax.Array,  # (P, B, F) f32
    mask_v: jax.Array,  # (P, A) f32 1/0
    mask_u: jax.Array,  # (P, B) f32 1/0
    *,
    t1: int,
    t2: int,
    t3: int,
    k: int = 4,
    interpret: bool | None = None,
):
    p, a, f = feat_v.shape
    _, b, _ = feat_u.shape
    kernel = functools.partial(_topk_sim_kernel, t1=t1, t2=t2, t3=t3, k=k)
    return pl.pallas_call(
        kernel,
        grid=(p,),
        in_specs=[
            pl.BlockSpec((1, a, f), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, f), lambda i: (i, 0, 0)),
            # masks as (P, A, 1) / (P, 1, B): each block's last two dims
            # are the array's own, which the TPU's (8, 128) tiling accepts
            pl.BlockSpec((1, a, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, b), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, a, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, a, k), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, a, k), jnp.float32),
            jax.ShapeDtypeStruct((p, a, k), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(feat_v, feat_u, mask_v[:, :, None], mask_u[:, None, :])
