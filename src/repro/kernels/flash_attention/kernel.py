"""Pallas TPU kernel: tiled online-softmax (flash) attention.

Grid = (batch*q_heads, q_blocks, k_blocks); the innermost k dimension
accumulates into VMEM scratch (m, l, acc) with the standard online-softmax
rescaling, writing the output tile once on the last k block. GQA is handled
in the BlockSpec index maps (q head -> shared kv head), causal and
sliding-window (Mixtral SWA) masks are applied in-kernel.

VMEM working set per program: q (bq, D) + k,v (bk, D) + acc (bq, D) + the
(bq, bk) score tile — all MXU-aligned for bq, bk, D multiples of 128 (D=64
also allowed; the MXU pads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..runtime import resolve_interpret

NEG_INF = float("-inf")


def _flash_kernel(
    q_ref, k_ref, v_ref,  # in
    o_ref,  # out
    m_scr, l_scr, acc_scr,  # scratch
    *,
    scale: float,
    causal: bool,
    window: int | None,
    q_len: int,
    kv_len: int,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # (bq, D)
    k = k_ref[0]  # (bk, D)
    v = v_ref[0]  # (bk, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = (q_pos < q_len) & (k_pos < kv_len)
    if causal:
        # align query positions to the END of the kv sequence (prefill: q_len
        # == kv_len; chunked decode: q is the tail of the kv stream)
        mask &= (q_pos + (kv_len - q_len)) >= k_pos
    if window is not None:
        mask &= (q_pos + (kv_len - q_len)) - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]  # (bq, 1)
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe)  # rows fully masked -> exp(-inf - 0) = 0
    alpha = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "q_len", "kv_len", "causal", "window", "scale", "block_q", "block_k",
        "interpret",
    ),
)
def flash_attention_folded(
    q: jax.Array,  # (BHq, Sq, D) — batch and q-heads folded
    k: jax.Array,  # (BHkv, Skv, D)
    v: jax.Array,  # (BHkv, Skv, D)
    *,
    q_len: int | None = None,
    kv_len: int | None = None,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    bhq, sq, d = q.shape
    bhkv, skv, _ = k.shape
    assert bhq % bhkv == 0, "q heads must be a multiple of kv heads"
    group = bhq // bhkv
    q_len = q_len or sq
    kv_len = kv_len or skv
    assert sq % block_q == 0 and skv % block_k == 0
    scale = scale if scale is not None else d ** -0.5
    grid = (bhq, sq // block_q, skv // block_k)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window,
        q_len=q_len, kv_len=kv_len, block_q=block_q, block_k=block_k,
        num_k_blocks=skv // block_k,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
