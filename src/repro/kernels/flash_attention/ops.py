"""Public flash-attention op: (B, H, S, D) API, folding + padding + dispatch."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import flash_attention_folded
from .ref import attention_reference


def flash_attention(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Skv, D)
    v: jax.Array,  # (B, Hkv, Skv, D)
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    use_kernel: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Tiled attention; pads sequence dims to block multiples internally.
    ``interpret=None`` resolves from the backend (:mod:`repro.kernels.runtime`)."""
    if not use_kernel:
        return attention_reference(q, k, v, causal=causal, window=window, scale=scale)
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(8, skv))
    sq_p = -(-sq // bq) * bq
    skv_p = -(-skv // bk) * bk
    qf = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0))).reshape(b * hq, sq_p, d)
    kf = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0))).reshape(b * hkv, skv_p, d)
    vf = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0))).reshape(b * hkv, skv_p, d)
    o = flash_attention_folded(
        qf, kf, vf, q_len=sq, kv_len=skv, causal=causal, window=window,
        scale=scale, block_q=bq, block_k=bk, interpret=interpret,
    )
    return o.reshape(b, hq, sq_p, d)[:, :, :sq, :]
