"""Public op: pack VertexSet metadata into dense feature planes and run the
fused similarity+top-k kernel over a PAIR task list."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.gsana_data import Buckets, VertexSet
from .kernel import topk_sim_pallas
from .ref import topk_sim_reference


def _hist_f32(a: jax.Array, vocab: int) -> jax.Array:
    oh = jax.nn.one_hot(jnp.where(a >= 0, a, vocab), vocab + 1, dtype=jnp.float32)
    return oh.sum(axis=-2)[..., :vocab]


def pack_features(vs: VertexSet, vocab: tuple[int, int, int]) -> jax.Array:
    """(n, F) dense feature plane: scalars + the three metadata histograms."""
    t1, t2, t3 = vocab
    return jnp.concatenate(
        [
            vs.deg.astype(jnp.float32)[:, None],
            vs.vtype.astype(jnp.float32)[:, None],
            (vs.ntypes >= 0).sum(-1).astype(jnp.float32)[:, None],
            (vs.etypes >= 0).sum(-1).astype(jnp.float32)[:, None],
            (vs.attrs >= 0).sum(-1).astype(jnp.float32)[:, None],
            _hist_f32(vs.ntypes, t1),
            _hist_f32(vs.etypes, t2),
            _hist_f32(vs.attrs, t3),
        ],
        axis=1,
    )


def topk_sim_pairs(
    vs1: VertexSet,
    vs2: VertexSet,
    b1: Buckets,
    b2: Buckets,
    pair_b2: jax.Array,  # (P,) QT2 bucket id per task
    pair_b1: jax.Array,  # (P,) QT1 bucket id per task (-1 = inactive task)
    *,
    vocab: tuple[int, int, int] = (16, 16, 64),
    k: int = 4,
    use_kernel: bool = True,
    interpret: bool | None = None,
):
    """Run all PAIR tasks. Returns (scores (P, cap2, k), u_ids (P, cap2, k))."""
    t1, t2, t3 = vocab
    f1 = pack_features(vs1, vocab)
    f2 = pack_features(vs2, vocab)
    v_idx = b2.vid[pair_b2]  # (P, cap2)
    u_idx = jnp.where(pair_b1[:, None] >= 0, b1.vid[jnp.maximum(pair_b1, 0)], -1)
    fv = f2[jnp.maximum(v_idx, 0)]
    fu = f1[jnp.maximum(u_idx, 0)]
    mv = (v_idx >= 0).astype(jnp.float32)
    mu = (u_idx >= 0).astype(jnp.float32)
    fn = topk_sim_pallas if use_kernel else topk_sim_reference
    kwargs = dict(t1=t1, t2=t2, t3=t3, k=k)
    if use_kernel:
        kwargs["interpret"] = interpret
    scores, local_ix = fn(fv, fu, mv, mu, **kwargs)
    u_ids = jax.vmap(lambda u, ix: u[ix])(u_idx, local_ix)  # (P, cap2, k)
    u_ids = jnp.where(jnp.isfinite(scores), u_ids, -1)
    return scores, u_ids
