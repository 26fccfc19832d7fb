"""Mixture-of-Experts with migratory-strategy dispatch (DESIGN.md §4).

The token->expert routing problem IS the Emu's irregular-access problem: a
token needs to reach the shard owning its expert's weights. Three dispatch
modes realize the paper's strategies on the TPU mesh's "model" axis:

- ``ep_push``  (S2 remote-write, Alg. 2 analogue): each shard bins its local
  tokens by destination expert-owner shard and pushes them with a single
  ``all_to_all`` (the remote-write packet stream); owners compute their
  experts and push results back with the inverse ``all_to_all``. Requires
  num_experts % model_axis == 0 (moonshot: 64 % 16).
- ``ep_pull``  (S2 migrate, Alg. 1 analogue): every expert-owner shard pulls
  ALL tokens with an ``all_gather`` over the model axis, computes its local
  experts on the full token set, and the combine reduces with ``psum_scatter``.
  Communication grows with the full token volume — the migrating-threads
  baseline.
- ``tp``      (S1-flavored fallback for any expert count, e.g. mixtral's 8
  experts on a 16-way axis): every shard holds an F-slice of EVERY expert
  (replication of the expert *set*, sharding of the FFN dim); dispatch stays
  node-local (pure local scatter) and the only communication is the TP
  all-reduce of the combined output, exactly like a dense TP MLP.

All modes use capacity-factor token dropping (static shapes; the overflow
counter mirrors the paper's SpMV grain/hotspot discussion — §5.1 load
imbalance) and are implemented in ``shard_map`` so the collectives are
explicit and auditable in the dry-run HLO (roofline §collective term).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.strategies import Comm, MigratoryStrategy
from ..core.util import round_up
from .config import ModelConfig
from .layers import Ctx, _dt


def dispatch_from_strategy(
    strategy: MigratoryStrategy | None, *, num_experts: int, data_axis: int
) -> str | None:
    """Map a paper strategy onto an MoE dispatch mode (the engine's
    strategy-to-substrate idea applied to token routing, DESIGN.md §4):
    S2 remote_write -> ep_push (all_to_all packets), S2 migrate -> ep_pull
    (all_gather the token set), and the S1-flavored ``tp`` replication
    fallback whenever expert parallelism cannot divide the data axis."""
    if strategy is None:
        return None
    if data_axis > 1 and num_experts % data_axis == 0:
        return "ep_pull" if strategy.comm == Comm.MIGRATE else "ep_push"
    return "tp"


def moe_params(cfg: ModelConfig, key, stack: tuple[int, ...] = ()) -> dict:
    d, e = cfg.d_model, cfg.num_experts
    f = cfg.moe_d_ff or cfg.d_ff
    dt = _dt(cfg)
    init = jax.nn.initializers.normal(0.02)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": init(k1, (*stack, d, e), jnp.float32),
        "w_gate": init(k2, (*stack, e, d, f), dt),
        "w_up": init(k3, (*stack, e, d, f), dt),
        "w_down": init(k4, (*stack, e, f, d), dt),
    }


def _route(cfg: ModelConfig, xt: jax.Array, router: jax.Array):
    """Token routing: top-k softmax gates. xt: (T, D) -> gates/experts (T, k)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates.astype(xt.dtype), experts.astype(jnp.int32)


def _positions_in_expert(experts_flat: jax.Array, num_experts: int) -> jax.Array:
    """Rank of each routed slot within its expert (stable order). O(T·E) free
    of sorts: cumulative one-hot counts."""
    oh = jax.nn.one_hot(experts_flat, num_experts, dtype=jnp.int32)  # (Tk, E)
    ranks = jnp.cumsum(oh, axis=0) - oh  # occurrences before this slot
    return jnp.sum(ranks * oh, axis=1)  # (Tk,)


def _expert_ffn(cfg: ModelConfig, p: dict, xs: jax.Array) -> jax.Array:
    """xs: (E_local, C, D) -> (E_local, C, D) through each expert's SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", xs, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def expert_ffn(params: dict, xs: jax.Array) -> jax.Array:
    """Public expert-stack entry: each expert's SwiGLU over its capacity
    buffer. ``params`` holds ``w_gate``/``w_up`` (E, D, F) and ``w_down``
    (E, F, D) — the :func:`moe_params` layout; ``xs`` is (E, C, D). This is
    the exact math the engine's ``moe_dispatch`` op applies at the owner
    stage, so engine-served experts and the LM stack share one definition.
    Zero rows map to zero rows (no biases) — padded capacity slots stay
    inert through the FFN."""
    return _expert_ffn(None, params, xs)


def _local_dispatch(cfg: ModelConfig, xt, gates, experts, capacity):
    """Scatter local tokens into per-expert buffers (drop past capacity).

    Returns (buffers (E, C, D), slot_expert (T,k), slot_pos (T,k), kept mask).
    """
    t, d = xt.shape
    k = cfg.experts_per_token
    ef = experts.reshape(-1)
    pos = _positions_in_expert(ef, cfg.num_experts)
    keep = pos < capacity
    xk = jnp.repeat(xt, k, axis=0)  # (T*k, D)
    buf = jnp.zeros((cfg.num_experts, capacity, d), xt.dtype)
    buf = buf.at[jnp.where(keep, ef, 0), jnp.where(keep, pos, 0)].add(
        jnp.where(keep[:, None], xk, 0), mode="drop"
    )
    return buf, ef, pos, keep


def _local_combine(cfg, out_buf, gates, ef, pos, keep, t, d):
    """Gather per-expert outputs back to token order, weighted by gates."""
    k = cfg.experts_per_token
    vals = out_buf[jnp.where(keep, ef, 0), jnp.where(keep, pos, 0)]  # (T*k, D)
    vals = jnp.where(keep[:, None], vals, 0)
    return jnp.sum((vals * gates.reshape(-1)[:, None]).reshape(t, k, d), axis=1)


def moe_sublayer(
    ctx: Ctx,
    p: dict,
    x: jax.Array,
    *,
    dispatch: str | None = None,
    strategy: MigratoryStrategy | None = None,
) -> jax.Array:
    """x: (B, S, D) -> (B, S, D). Dispatch mode: explicit ``dispatch`` wins,
    else derived from ``strategy`` (engine-style), else the config/default
    (the default REMOTE_WRITE strategy, i.e. ep_push where divisible)."""
    cfg = ctx.cfg
    b, s, d = x.shape
    mesh = ctx.mesh
    ms = mesh.shape.get("model", 1) if mesh is not None else 1
    ds = mesh.shape.get("data", 1) if mesh is not None else 1
    if dispatch is None:
        dispatch = dispatch_from_strategy(
            strategy, num_experts=cfg.num_experts, data_axis=ds
        )
    if dispatch is None:
        dispatch = cfg.moe_dispatch
    if dispatch is None:
        dispatch = dispatch_from_strategy(
            MigratoryStrategy(), num_experts=cfg.num_experts, data_axis=ds
        )
    if mesh is None or ms == 1:
        # single-shard semantics path (smoke tests)
        xt = x.reshape(b * s, d)
        gates, experts = _route(cfg, xt, p["router"])
        cap = _capacity(cfg, b * s, cfg.num_experts)
        buf, ef, pos, keep = _local_dispatch(cfg, xt, gates, experts, cap)
        out = _expert_ffn(cfg, p, buf)
        return _local_combine(cfg, out, gates, ef, pos, keep, b * s, d).reshape(b, s, d)

    batch_axes = ctx.rules.batch if ctx.rules else ("data",)
    if dispatch == "tp":
        return _moe_tp(ctx, p, x, batch_axes)
    if dispatch == "ep_push":
        return _moe_ep(ctx, p, x, batch_axes, push=True)
    if dispatch == "ep_pull":
        return _moe_ep(ctx, p, x, batch_axes, push=False)
    raise ValueError(f"unknown dispatch {dispatch}")


def _capacity(cfg: ModelConfig, tokens: int, experts: int) -> int:
    c = int(cfg.capacity_factor * tokens * cfg.experts_per_token / experts)
    return max(8, round_up(c, 8))


def _moe_tp(ctx: Ctx, p: dict, x: jax.Array, batch_axes) -> jax.Array:
    """Every shard: all experts, F-sliced. Local dispatch + one TP all-reduce."""
    cfg = ctx.cfg
    mesh = ctx.mesh
    b, s, d = x.shape
    tl = (b // _axis_size(mesh, batch_axes)) * s  # local tokens

    tc = min(8192, tl)  # token chunk: bounds dispatch buffers (grain size)

    def body(xb, router, wg, wu, wd):
        bl, sl, _ = xb.shape
        t = bl * sl
        xt = xb.reshape(t, d)
        tcc = min(tc, t)
        cap_c = _capacity(cfg, tcc, cfg.num_experts)

        def chunk_fn(xc):
            gates, experts = _route(cfg, xc, router)
            buf, ef, pos, keep = _local_dispatch(cfg, xc, gates, experts, cap_c)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
            h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
            out_p = jnp.einsum("ecf,efd->ecd", h, wd)  # partial over F slices
            out_p = jax.lax.psum(out_p, "model")  # TP reduce (dense-MLP-like)
            return _local_combine(cfg, out_p, gates, ef, pos, keep, xc.shape[0], d)

        if t > tcc:
            nck = t // tcc
            chunk_fn = jax.checkpoint(
                chunk_fn, policy=jax.checkpoint_policies.nothing_saveable
            )
            out = jax.lax.map(chunk_fn, xt.reshape(nck, tcc, d)).reshape(t, d)
        else:
            out = chunk_fn(xt)
        return out.reshape(bl, sl, d)

    return jax.shard_map(
        body,
        mesh=mesh,
        check_vma=False,
        in_specs=(
            P(batch_axes, None, None),
            P(),  # router replicated
            P(None, None, "model"),  # w_gate: F sliced
            P(None, None, "model"),
            P(None, "model", None),  # w_down: F sliced on input dim
        ),
        out_specs=P(batch_axes, None, None),
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _moe_ep(ctx: Ctx, p: dict, x: jax.Array, batch_axes, *, push: bool) -> jax.Array:
    """Expert parallelism along "data" (the axis that shards tokens), with TP
    over "model" inside each expert (F-sliced). Hierarchical across pods:
    experts are replicated per pod, dispatch stays within a pod.

    push (S2 remote-write): bin local tokens by destination expert-owner,
      one all_to_all over "data" there, one back; TP psum folded into token
      space after the return trip.
    pull (S2 migrate): every owner all_gathers ALL tokens over "data",
      computes its experts on the full set, results return via psum_scatter.
    """
    cfg = ctx.cfg
    mesh = ctx.mesh
    ds = mesh.shape["data"]
    ms = mesh.shape.get("model", 1)
    e_local = cfg.num_experts // ds
    b, s, d = x.shape
    k = cfg.experts_per_token

    def body(xb, router, wg, wu, wd):
        bl, sl, _ = xb.shape
        t_full = bl * sl
        xt = xb.reshape(t_full, d)
        # tokens are replicated along "model": slice so each model shard
        # dispatches a distinct 1/ms of them (all_gather back at the end) —
        # cuts dispatch buffers and a2a traffic by ms (DeepSpeed-MoE "dual").
        # Skipped when the local token count is too small to split (decode).
        model_slice = ms > 1 and t_full % ms == 0 and t_full >= ms
        if model_slice:
            t = t_full // ms
            mi = jax.lax.axis_index("model")
            xt = jax.lax.dynamic_slice(xt, (mi * t, jnp.int32(0)), (t, d))
        else:
            t = t_full
        gates, experts = _route(cfg, xt, router)  # (t, k)
        ef = experts.reshape(-1)  # (t*k,)
        owner = ef // e_local  # destination "data" shard
        ffn = {"w_gate": wg, "w_up": wu, "w_down": wd}
        if push:
            # --- remote-write: bin by owner, push with all_to_all ----------
            cap_pair = _capacity(cfg, t, ds)  # slots per (src->dst) pair
            pos = _positions_in_expert(owner, ds)  # rank within owner bin
            keep = pos < cap_pair
            xk = jnp.repeat(xt, k, axis=0)
            ow = jnp.where(keep, owner, 0)
            ps = jnp.where(keep, pos, 0)
            send = jnp.zeros((ds, cap_pair, d), xt.dtype)
            send = send.at[ow, ps].add(jnp.where(keep[:, None], xk, 0), mode="drop")
            send_e = jnp.full((ds, cap_pair), -1, jnp.int32)
            send_e = send_e.at[ow, ps].max(jnp.where(keep, ef, -1), mode="drop")
            recv = jax.lax.all_to_all(send, "data", 0, 0, tiled=False)
            recv_e = jax.lax.all_to_all(send_e, "data", 0, 0, tiled=False)
            # recv: (ds, cap_pair, d) tokens destined to my local experts
            shard = jax.lax.axis_index("data")
            rf = (recv_e - shard * e_local).reshape(-1)
            rf = jnp.where(recv_e.reshape(-1) >= 0, rf, e_local)
            cap_e = _capacity(cfg, t * ds, cfg.num_experts)
            rpos = _positions_in_expert(rf, e_local + 1)
            rkeep = (rf < e_local) & (rpos < cap_e)
            buf = jnp.zeros((e_local, cap_e, d), xt.dtype)
            rx = recv.reshape(-1, d)
            buf = buf.at[jnp.where(rkeep, rf, 0), jnp.where(rkeep, rpos, 0)].add(
                jnp.where(rkeep[:, None], rx, 0), mode="drop"
            )
            out_buf = _expert_ffn(cfg, ffn, buf)  # full-F experts (no TP psum)
            out_slots = out_buf[jnp.where(rkeep, rf, 0), jnp.where(rkeep, rpos, 0)]
            out_slots = jnp.where(rkeep[:, None], out_slots, 0).reshape(ds, cap_pair, d)
            back = jax.lax.all_to_all(out_slots, "data", 0, 0, tiled=False)
            vals = back[ow, ps]
            vals = jnp.where(keep[:, None], vals, 0)
            out = jnp.sum((vals * gates.reshape(-1)[:, None]).reshape(t, k, d), axis=1)
        else:
            # --- migrate: pull every token to every owner -------------------
            xg = jax.lax.all_gather(xt, "data", tiled=True)  # (t*ds, d)
            gg = jax.lax.all_gather(gates.reshape(-1), "data", tiled=True)
            eg = jax.lax.all_gather(ef, "data", tiled=True)  # (t*k*ds,)
            shard = jax.lax.axis_index("data")
            mine = (eg // e_local) == shard
            le = jnp.where(mine, eg - shard * e_local, e_local)
            cap_e = _capacity(cfg, t * ds, cfg.num_experts)
            pos = _positions_in_expert(le, e_local + 1)
            keep = mine & (pos < cap_e)
            xkg = jnp.repeat(xg, k, axis=0)
            buf = jnp.zeros((e_local, cap_e, d), xt.dtype)
            buf = buf.at[jnp.where(keep, le, 0), jnp.where(keep, pos, 0)].add(
                jnp.where(keep[:, None], xkg, 0), mode="drop"
            )
            out_buf = _expert_ffn(cfg, ffn, buf)
            vals = out_buf[jnp.where(keep, le, 0), jnp.where(keep, pos, 0)]
            vals = jnp.where(keep[:, None], vals, 0) * gg[:, None]
            contrib = vals.reshape(ds, t, k, d).sum(2)  # (ds, t, d) per source
            out = jax.lax.psum_scatter(contrib, "data", scatter_dimension=0, tiled=False)
        if model_slice:
            # collect the per-model-shard token slices back together
            out = jax.lax.all_gather(out, "model", tiled=True)
        return out.reshape(bl, sl, d)

    return jax.shard_map(
        body,
        mesh=mesh,
        check_vma=False,
        in_specs=(
            P(batch_axes, None, None),
            P(),
            P("data", None, None),  # E over data (EP), full-F experts
            P("data", None, None),
            P("data", None, None),
        ),
        out_specs=P(batch_axes, None, None),
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _axis_size(mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes or ():
        n *= mesh.shape.get(a, 1)
    return n
