"""MoE token dispatch as the engine's fourth MigratoryOp (DESIGN.md §1e, §4).

Token -> expert routing IS the paper's irregular-access problem: a token
must reach the nodelet owning its expert, and the S2 axis decides how —
``remote_write`` pushes binned tokens with all_to_all packets (Alg. 2),
``migrate`` pulls the whole token set to every owner with an all_gather
(Alg. 1), and the S1-flavored ``tp`` fallback replicates the expert set so
dispatch stays node-local. The mode derivation is exactly
:func:`repro.models.moe.dispatch_from_strategy` — the same mapping the LM
stack uses — so the engine's autotuner ranks real MoE deployment choices.

This file is the registry's proof of decoupling: it registers
``moe_dispatch`` kernels for the ``local`` and ``mesh`` substrate kinds and
an :class:`~repro.engine.registry.OpSpec` (with a roofline collective-bytes
cost model) **without editing any existing Substrate subclass** — pallas
simply has no entry, so ``OpNotSupportedError`` falls out of the registry.

The op executes the dispatch *transport* (routing, capacity binning, the
collectives, and the gate-weighted combine) and, when the inputs carry
expert weights (``w_gate``/``w_up``/``w_down`` in the
:func:`repro.models.moe.moe_params` layout), the real SwiGLU expert FFN at
the owner stage — the same :func:`repro.models.moe.expert_ffn` math the LM
stack runs, applied to the capacity buffers between commit and gather-back.
Without weights the experts are identity and the op degenerates to the
pure transport it was through PR 7. Local and mesh kernels are
bit-identical either way: per-shard math is shared helper code, the
exchanges are pure permutations, expert weights shard over E exactly as
shard_map would slice them, and the pull-mode return trip uses a psum in
which every slot has exactly one nonzero contributor (float-exact by
construction).
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cost import CostEstimate
from ..core.strategies import (
    CONTEXT_BYTES,
    Layout,
    MigratoryStrategy,
    Scheme,
    TrafficStats,
    strategy_grid,
)
from ..core.util import round_up
from ..models.moe import _positions_in_expert, dispatch_from_strategy, expert_ffn
from .api import ExecutionPlan, OpNotSupportedError, plan_key
from .registry import OpSpec, kernel, register_op
from .substrate import Substrate


@dataclasses.dataclass(frozen=True)
class MoEDispatchInputs:
    """One dispatch problem: ``x`` (T, D) token activations, ``router``
    (D, E) routing weights. ``nodelets`` is the expert-parallel width the
    strategy maps onto (the Chick's nodelet count); ep modes additionally
    need ``E % nodelets == 0`` — otherwise every strategy degrades to the
    ``tp`` replication fallback, exactly like the LM stack."""

    x: jax.Array
    router: jax.Array
    nodelets: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    # optional expert weights (moe_params layout): present -> the op runs
    # the real SwiGLU FFN at the owner stage; absent -> identity experts
    w_gate: "jax.Array | None" = None  # (E, D, F)
    w_up: "jax.Array | None" = None  # (E, D, F)
    w_down: "jax.Array | None" = None  # (E, F, D)

    @property
    def num_experts(self) -> int:
        return int(self.router.shape[-1])

    @property
    def has_experts(self) -> bool:
        return self.w_gate is not None

    @property
    def ffn_args(self) -> tuple:
        """The traced weight args, in kernel order — () when identity."""
        if not self.has_experts:
            return ()
        return (self.w_gate, self.w_up, self.w_down)

    def validate_experts(self) -> None:
        ws = (self.w_gate, self.w_up, self.w_down)
        present = [w is not None for w in ws]
        if not any(present):
            return
        if not all(present):
            raise ValueError(
                "moe_dispatch expert weights are all-or-none: pass "
                "w_gate, w_up and w_down together"
            )
        E, D = self.num_experts, int(self.x.shape[-1])
        F = int(self.w_gate.shape[-1])
        want = {"w_gate": (E, D, F), "w_up": (E, D, F), "w_down": (E, F, D)}
        for name, shape in want.items():
            got = tuple(getattr(self, name).shape)
            if got != shape:
                raise ValueError(
                    f"moe_dispatch {name} must have shape {shape} "
                    f"(moe_params layout), got {got}"
                )


def _cap(capacity_factor: float, expected_slots: float) -> int:
    """Static buffer capacity: expected slot count x factor, 8-aligned."""
    return max(8, round_up(int(capacity_factor * expected_slots), 8))


def derive_mode(inputs: MoEDispatchInputs, strategy: MigratoryStrategy) -> str:
    """The strategy -> dispatch-mode mapping, shared with models/moe.py."""
    return dispatch_from_strategy(
        strategy, num_experts=inputs.num_experts, data_axis=inputs.nodelets
    )


# -- shared per-shard pieces (identical code on both substrates) ---------------


def _route_shard(x_s: jax.Array, router: jax.Array, *, k: int):
    """x_s: (t, D) -> normalized top-k gates (t, k) in x.dtype, experts (t, k)."""
    logits = jnp.einsum(
        "td,de->te", x_s.astype(jnp.float32), router.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates.astype(x_s.dtype), experts.astype(jnp.int32)


def _tp_shard(x_s, router, ffn=None, *, k, num_experts, cap):
    """S1 fallback: all experts resident, dispatch is a node-local scatter
    into (E, cap, D) buffers, the (optional) expert FFN, and a gate-weighted
    gather back."""
    t, d = x_s.shape
    gates, experts = _route_shard(x_s, router, k=k)
    ef = experts.reshape(-1)
    pos = _positions_in_expert(ef, num_experts)
    keep = pos < cap
    xk = jnp.repeat(x_s, k, axis=0)
    buf = jnp.zeros((num_experts, cap, d), x_s.dtype)
    buf = buf.at[jnp.where(keep, ef, 0), jnp.where(keep, pos, 0)].add(
        jnp.where(keep[:, None], xk, 0), mode="drop"
    )
    if ffn is not None:
        buf = expert_ffn(ffn, buf)
    vals = buf[jnp.where(keep, ef, 0), jnp.where(keep, pos, 0)]
    vals = jnp.where(keep[:, None], vals, 0)
    return jnp.sum((vals * gates.reshape(-1)[:, None]).reshape(t, k, d), axis=1)


def _push_pre(x_s, router, *, k, P, e_local, cap_pair):
    """Sender side of ep_push: bin local slots by destination owner into the
    (P_dst, cap_pair, D) send buffer (+ expert-id plane, -1 pad)."""
    gates, experts = _route_shard(x_s, router, k=k)
    ef = experts.reshape(-1)
    owner = ef // e_local
    pos = _positions_in_expert(owner, P)
    keep = pos < cap_pair
    xk = jnp.repeat(x_s, k, axis=0)
    ow = jnp.where(keep, owner, 0)
    ps = jnp.where(keep, pos, 0)
    send = jnp.zeros((P, cap_pair, x_s.shape[1]), x_s.dtype)
    send = send.at[ow, ps].add(jnp.where(keep[:, None], xk, 0), mode="drop")
    send_e = jnp.full((P, cap_pair), -1, jnp.int32)
    send_e = send_e.at[ow, ps].max(jnp.where(keep, ef, -1), mode="drop")
    return send, send_e, gates, ow, ps, keep


def _push_owner(recv, recv_e, shard_id, ffn=None, *, e_local, cap_e):
    """Owner side of ep_push: commit received slots into per-local-expert
    buffers (second capacity stage), run the experts (identity when ``ffn``
    is None, the owner's SwiGLU shard otherwise), and hand the slot values
    back in the received (P_src, cap_pair) layout."""
    p_src, cap_pair, d = recv.shape
    rf = (recv_e - shard_id * e_local).reshape(-1)
    rf = jnp.where(recv_e.reshape(-1) >= 0, rf, e_local)  # e_local = pad bin
    rpos = _positions_in_expert(rf, e_local + 1)
    rkeep = (rf < e_local) & (rpos < cap_e)
    rx = recv.reshape(-1, d)
    buf = jnp.zeros((e_local, cap_e, d), recv.dtype)
    buf = buf.at[jnp.where(rkeep, rf, 0), jnp.where(rkeep, rpos, 0)].add(
        jnp.where(rkeep[:, None], rx, 0), mode="drop"
    )
    if ffn is not None:
        buf = expert_ffn(ffn, buf)
    out = buf[jnp.where(rkeep, rf, 0), jnp.where(rkeep, rpos, 0)]
    out = jnp.where(rkeep[:, None], out, 0)
    return out.reshape(p_src, cap_pair, d)


def _push_post(back, gates, ow, ps, keep, *, t, k):
    """Sender-side combine: read each slot's returned value, weight by gate."""
    vals = back[ow, ps]
    vals = jnp.where(keep[:, None], vals, 0)
    return jnp.sum((vals * gates.reshape(-1)[:, None]).reshape(t, k, -1), axis=1)


def _pull_owner(x_full, eg, shard_id, ffn=None, *, k, e_local, cap_e):
    """Owner side of ep_pull: the full gathered slot stream, committed into
    my experts' buffers (then through my expert shard when ``ffn`` is set);
    returns per-slot values, nonzero only for slots I own AND kept (<= one
    nonzero contributor per slot across owners)."""
    mine = (eg // e_local) == shard_id
    le = jnp.where(mine, eg - shard_id * e_local, e_local)
    pos = _positions_in_expert(le, e_local + 1)
    keep = mine & (pos < cap_e)
    xkg = jnp.repeat(x_full, k, axis=0)  # (T*k, D) global slot stream
    buf = jnp.zeros((e_local, cap_e, x_full.shape[1]), x_full.dtype)
    buf = buf.at[jnp.where(keep, le, 0), jnp.where(keep, pos, 0)].add(
        jnp.where(keep[:, None], xkg, 0), mode="drop"
    )
    if ffn is not None:
        buf = expert_ffn(ffn, buf)
    out = buf[jnp.where(keep, le, 0), jnp.where(keep, pos, 0)]
    return jnp.where(keep[:, None], out, 0)  # (T*k, D)


def _pull_combine(vals_local, gates, x_s, *, t, k):
    del x_s  # combine consumes only returned slot values (post-capacity)
    vals = vals_local * gates.reshape(-1)[:, None]
    return jnp.sum(vals.reshape(t, k, -1), axis=1)


# -- local kernel: vmap emulation over the nodelet axis ------------------------


def _ffn_dict(ws: tuple) -> "dict | None":
    """(w_gate, w_up, w_down) kernel args -> expert_ffn params (or None)."""
    if not ws:
        return None
    g, u, d = ws
    return {"w_gate": g, "w_up": u, "w_down": d}


def _ffn_shards(ffn: "dict | None", P: int) -> "dict | None":
    """Slice replicated (E, ...) expert weights into the per-owner blocks
    shard_map's ``P_(axis)`` in_spec would hand each shard — leading axis P,
    so the local vmap emulation sees exactly the mesh shard's weights."""
    if ffn is None:
        return None
    return {k: w.reshape(P, w.shape[0] // P, *w.shape[1:]) for k, w in ffn.items()}


@functools.partial(
    jax.jit,
    static_argnames=("mode", "nodelets", "experts_per_token", "capacity_factor"),
)
def _dispatch_local(
    x, router, w_gate=None, w_up=None, w_down=None, *,
    mode, nodelets, experts_per_token, capacity_factor,
):
    P, k = nodelets, experts_per_token
    T, D = x.shape
    E = router.shape[-1]
    t = T // P
    xs = x.reshape(P, t, D)
    ffn = None if w_gate is None else {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
    if mode == "tp":
        cap = _cap(capacity_factor, t * k / E)
        # tp replicates the whole expert set per shard: weights ride in the
        # closure, broadcast across the vmapped nodelet axis
        body = functools.partial(_tp_shard, ffn=ffn, k=k, num_experts=E, cap=cap)
        return jax.vmap(body, in_axes=(0, None))(xs, router).reshape(T, D)
    e_local = E // P
    cap_e = _cap(capacity_factor, T * k / E)
    ffn_s = _ffn_shards(ffn, P)  # ep modes: weights shard over E
    if mode == "ep_push":
        cap_pair = _cap(capacity_factor, t * k / P)
        pre = functools.partial(_push_pre, k=k, P=P, e_local=e_local, cap_pair=cap_pair)
        send, send_e, gates, ow, ps, keep = jax.vmap(pre, in_axes=(0, None))(xs, router)
        recv = jnp.swapaxes(send, 0, 1)  # the all_to_all, as a transpose
        recv_e = jnp.swapaxes(send_e, 0, 1)
        owner = functools.partial(_push_owner, e_local=e_local, cap_e=cap_e)
        out = jax.vmap(owner)(recv, recv_e, jnp.arange(P), ffn_s)
        back = jnp.swapaxes(out, 0, 1)  # the return all_to_all
        post = functools.partial(_push_post, t=t, k=k)
        return jax.vmap(post)(back, gates, ow, ps, keep).reshape(T, D)
    if mode == "ep_pull":
        route = functools.partial(_route_shard, k=k)
        gates, experts = jax.vmap(route, in_axes=(0, None))(xs, router)
        eg = experts.reshape(-1)  # global slot stream, stripe-major
        owner = functools.partial(_pull_owner, k=k, e_local=e_local, cap_e=cap_e)
        contrib = jax.vmap(owner, in_axes=(None, None, 0, 0))(
            x, eg, jnp.arange(P), ffn_s
        )
        vals_all = contrib.sum(0)  # exact: <= 1 nonzero contributor per slot
        vals = vals_all.reshape(P, t * k, D)
        comb = functools.partial(_pull_combine, t=t, k=k)
        return jax.vmap(comb)(vals, gates, xs).reshape(T, D)
    raise ValueError(f"unknown dispatch mode {mode!r}")


# -- mesh kernel: the same per-shard pieces under shard_map --------------------


def _dispatch_mesh(
    x, router, w_gate=None, w_up=None, w_down=None, *,
    mode, nodelets, experts_per_token, capacity_factor, mesh, axis_name,
):
    from jax.sharding import PartitionSpec as P_

    P, k = nodelets, experts_per_token
    T, D = x.shape
    E = router.shape[-1]
    t = T // P
    ffn_args = () if w_gate is None else (w_gate, w_up, w_down)
    if mode == "tp":
        cap = _cap(capacity_factor, t * k / E)
        w_spec = P_()  # tp: full expert set resident on every shard

        def body(x_s, router, *ws):
            return _tp_shard(
                x_s, router, _ffn_dict(ws), k=k, num_experts=E, cap=cap
            )

    elif mode == "ep_push":
        e_local = E // P
        cap_e = _cap(capacity_factor, T * k / E)
        cap_pair = _cap(capacity_factor, t * k / P)
        w_spec = P_(axis_name)  # ep: each owner holds its E/P expert block

        def body(x_s, router, *ws):
            send, send_e, gates, ow, ps, keep = _push_pre(
                x_s, router, k=k, P=P, e_local=e_local, cap_pair=cap_pair
            )
            recv = jax.lax.all_to_all(send, axis_name, 0, 0, tiled=False)
            recv_e = jax.lax.all_to_all(send_e, axis_name, 0, 0, tiled=False)
            shard = jax.lax.axis_index(axis_name)
            out = _push_owner(
                recv, recv_e, shard, _ffn_dict(ws), e_local=e_local, cap_e=cap_e
            )
            back = jax.lax.all_to_all(out, axis_name, 0, 0, tiled=False)
            return _push_post(back, gates, ow, ps, keep, t=t, k=k)

    elif mode == "ep_pull":
        e_local = E // P
        cap_e = _cap(capacity_factor, T * k / E)
        w_spec = P_(axis_name)

        def body(x_s, router, *ws):
            gates, experts = _route_shard(x_s, router, k=k)
            ef = experts.reshape(-1)
            x_full = jax.lax.all_gather(x_s, axis_name, tiled=True)  # (T, D)
            eg = jax.lax.all_gather(ef, axis_name, tiled=True)  # (T*k,)
            shard = jax.lax.axis_index(axis_name)
            contrib = _pull_owner(
                x_full, eg, shard, _ffn_dict(ws), k=k, e_local=e_local, cap_e=cap_e
            )
            # return trip: each slot has exactly one nonzero contributor, so
            # the float psum is exact and order-free
            vals_all = jax.lax.psum(contrib, axis_name)
            vals = jax.lax.dynamic_slice(
                vals_all, (shard * t * k, jnp.int32(0)), (t * k, D)
            )
            return _pull_combine(vals, gates, x_s, t=t, k=k)

    else:
        raise ValueError(f"unknown dispatch mode {mode!r}")

    f = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(P_(axis_name), P_()) + (w_spec,) * len(ffn_args),
        out_specs=P_(axis_name),
    )
    return f(x, router, *ffn_args)


# -- kernels: the registry's proof (no Substrate subclass edited) --------------


@kernel("moe_dispatch", "local")
def _moe_dispatch_local(
    sub: Substrate, x, router, *ws, strategy, nodelets, experts_per_token,
    capacity_factor,
):
    mode = dispatch_from_strategy(
        strategy, num_experts=int(router.shape[-1]), data_axis=nodelets
    )
    return _dispatch_local(
        x, router, *ws, mode=mode, nodelets=nodelets,
        experts_per_token=experts_per_token, capacity_factor=capacity_factor,
    )


@kernel("moe_dispatch", "mesh")
def _moe_dispatch_mesh(
    sub, x, router, *ws, strategy, nodelets, experts_per_token, capacity_factor
):
    mode = dispatch_from_strategy(
        strategy, num_experts=int(router.shape[-1]), data_axis=nodelets
    )
    mesh = sub.mesh_for(nodelets)
    # an explicit substrate mesh of a different width would silently shard
    # T/nodelets-sized capacity buffers over the wrong token stripes
    axis_size = dict(mesh.shape).get(sub.axis_name)
    if axis_size != nodelets:
        raise OpNotSupportedError(
            f"moe_dispatch needs a {nodelets}-way {sub.axis_name!r} mesh axis "
            f"(inputs.nodelets), got {axis_size}"
        )
    return _dispatch_mesh(
        x, router, *ws, mode=mode, nodelets=nodelets,
        experts_per_token=experts_per_token, capacity_factor=capacity_factor,
        mesh=mesh, axis_name=sub.axis_name,
    )


def moe_dispatch_reference(
    inputs: MoEDispatchInputs, strategy: MigratoryStrategy | None = None
) -> jax.Array:
    """Direct path, no engine: derive the mode with
    :func:`dispatch_from_strategy` and run the local dispatch — the oracle
    the service's ``moe_dispatch`` responses must be bit-identical to."""
    strategy = strategy if strategy is not None else MigratoryStrategy()
    return _dispatch_local(
        inputs.x, inputs.router, *inputs.ffn_args,
        mode=derive_mode(inputs, strategy),
        nodelets=inputs.nodelets, experts_per_token=inputs.experts_per_token,
        capacity_factor=inputs.capacity_factor,
    )


# -- traffic replay + roofline cost model --------------------------------------


_REPLAY_MEMO: "dict[int, tuple[Any, dict[str, Any]]]" = {}
_REPLAY_MEMO_MAX = 64


def _routing_replay_cached(inputs: MoEDispatchInputs) -> dict[str, Any]:
    """Cross-plan replay memo: the service rebuilds a plan per request, so
    ``plan.meta`` caching alone would rerun the O(T*k) host replay for every
    served request of the same inputs. Keyed by object identity, validated
    with a weakref so a recycled id of a collected object can never alias."""
    key = id(inputs)
    hit = _REPLAY_MEMO.get(key)
    if hit is not None and hit[0]() is inputs:
        return hit[1]
    replay = _routing_replay(inputs)
    if len(_REPLAY_MEMO) >= _REPLAY_MEMO_MAX:
        _REPLAY_MEMO.clear()
    try:
        _REPLAY_MEMO[key] = (weakref.ref(inputs), replay)
    except TypeError:
        pass  # unweakrefable inputs: still correct, just uncached
    return replay


def _routing_replay(inputs: MoEDispatchInputs) -> dict[str, Any]:
    """Host-side routing replay (strategy-independent): runs the same jax
    routing once and derives the per-mode capacity/keep statistics the
    traffic model, cost model, and metrics all share."""
    P, k = inputs.nodelets, inputs.experts_per_token
    T, D = inputs.x.shape
    E = inputs.num_experts
    t = T // P
    xs = inputs.x.reshape(P, t, D)
    _, experts = jax.vmap(
        functools.partial(_route_shard, k=k), in_axes=(0, None)
    )(xs, inputs.router)
    ef = np.asarray(experts).reshape(P, t * k)  # slot stream per source shard
    out: dict[str, Any] = {"routed_slots": T * k}
    if P > 1 and E % P == 0:
        e_local = E // P
        owner = ef // e_local
        cap_pair = _cap(inputs.capacity_factor, t * k / P)
        cap_e = _cap(inputs.capacity_factor, T * k / E)
        src = np.repeat(np.arange(P)[:, None], t * k, axis=1)
        # pair-stage keep: rank of each slot within its (src, owner) bin
        pair_rank = np.zeros_like(owner)
        for s in range(P):
            for o in range(P):
                m = owner[s] == o
                pair_rank[s, m] = np.arange(int(m.sum()))
        pair_keep = pair_rank < cap_pair
        out["push_offshard_kept"] = int((pair_keep & (owner != src)).sum())
        out["push_pair_dropped"] = int((~pair_keep).sum())
        # expert-stage keep at each owner, in the deterministic recv order
        # (src-major per owner, matching the all_to_all concat layout)
        expert_kept = 0
        for o in range(P):
            seen: dict[int, int] = {}
            for s in range(P):
                sel = np.flatnonzero(pair_keep[s] & (owner[s] == o))
                for e in ef[s][sel]:
                    r = seen.get(int(e), 0)
                    seen[int(e)] = r + 1
                    expert_kept += int(r < cap_e)
        out["push_kept"] = expert_kept
        # pull mode: every owner ranks the full global slot stream
        pull_kept = 0
        eg = ef.reshape(-1)
        counts: dict[int, int] = {}
        for e in eg:
            r = counts.get(int(e), 0)
            counts[int(e)] = r + 1
            pull_kept += int(r < cap_e)
        out["pull_kept"] = pull_kept
    cap_tp = _cap(inputs.capacity_factor, t * k / E)
    tp_kept = 0
    for s in range(P):
        counts = {}
        for e in ef[s]:
            r = counts.get(int(e), 0)
            counts[int(e)] = r + 1
            tp_kept += int(r < cap_tp)
    out["tp_kept"] = tp_kept
    return out


def moe_dispatch_traffic(
    inputs: MoEDispatchInputs, strategy: MigratoryStrategy, replay: dict[str, Any]
) -> TrafficStats:
    """The paper-lens traffic of one dispatch under ``strategy`` — exactly
    what the cost model ranks, so sweeps and rankings cross-check.

    - ``ep_push`` (S2 remote write): each off-shard kept slot is one
      remote-write packet; wire payload = token there + id + result back.
    - ``ep_pull`` (S2 migrate): every token's context is pulled by each of
      the P-1 remote owners (the all_gather), ids ride along, and every
      routed slot's result crosses back (the psum return trip).
    - ``tp`` (S1 replication): dispatch is node-local — zero traffic, the
      cost is paid in replicated expert residency instead.
    """
    P, k = inputs.nodelets, inputs.experts_per_token
    T, D = inputs.x.shape
    itemsize = jnp.dtype(inputs.x.dtype).itemsize
    mode = derive_mode(inputs, strategy)
    if mode == "tp":
        return TrafficStats(0, 0, 0)
    if mode == "ep_push":
        remote = replay["push_offshard_kept"]
        return TrafficStats(
            migrations=0,
            remote_writes=remote,
            collective_bytes=remote * (2 * D * itemsize + 4),
        )
    gather = T * (P - 1) * D * itemsize + T * k * (P - 1) * 4
    ret = T * k * (P - 1) * D * itemsize
    return TrafficStats(
        migrations=T * (P - 1), remote_writes=0, collective_bytes=gather + ret
    )


def _kept_for(replay: dict[str, Any]) -> dict[str, int]:
    """Kept (non-dropped) routed slots per dispatch mode, from one replay —
    the single source both the cost model and op metrics read."""
    return {
        "tp": replay["tp_kept"],
        "ep_push": replay.get("push_kept", 0),
        "ep_pull": replay.get("pull_kept", 0),
    }


def moe_dispatch_cost_model(inputs: MoEDispatchInputs):
    """Autotuner factory: one routing replay, then a cheap per-strategy
    estimator in report-identical traffic units. Balance penalty = dropped
    slot fraction (the §5.1 hotspot/overflow lens)."""
    replay = _routing_replay_cached(inputs)
    routed = replay["routed_slots"]
    kept_for = _kept_for(replay)
    T, D = inputs.x.shape
    itemsize = jnp.dtype(inputs.x.dtype).itemsize
    # per-stage working set of the emulation: the (T*k, D) slot stream is
    # materialized ~4x per stage chain (repeat, capacity-buffer scatter,
    # gather-back, gated combine) plus the routing logits; row-contiguous
    # scatters move whole D-vectors, so this is stream-class, not the
    # element-wise scatter path
    stage_bytes = (
        4 * T * inputs.experts_per_token * D * itemsize
        + T * inputs.num_experts * 4
    )

    def estimate(st: MigratoryStrategy) -> CostEstimate:
        traffic = moe_dispatch_traffic(inputs, st, replay)
        mode = derive_mode(inputs, st)
        dropped = routed - kept_for[mode]
        # collective dispatches per mode: push = scatter + compute + return
        # (3), pull = all-gather + return (2), tp = none (pure local compute)
        launches = {"tp": 0, "ep_push": 3, "ep_pull": 2}[mode]
        return CostEstimate(
            strategy=st,
            traffic_bytes=traffic.total_bytes,
            balance_penalty=dropped / max(routed, 1),
            detail={
                "dispatch_mode": mode,
                "migrations": traffic.migrations,
                "dropped_slots": dropped,
                "collective_launches": launches,
                "memory_bytes_per_launch": stage_bytes,
                "memory_access": "stream",
            },
            traffic=traffic,
        )

    return estimate


def moe_dispatch_grid() -> list[MigratoryStrategy]:
    """MoE dispatch reads only the S2 axis (comm -> push/pull); the grid
    pins the inert axes so the autotuner ranks 2 candidates, not 16."""
    return strategy_grid(
        replicates=(True,), layouts=(Layout.HCB,), schemes=(Scheme.PAIR,)
    )


# -- the op --------------------------------------------------------------------


class MoEDispatchOp:
    """MigratoryOp adapter: plan/traffic/bytes_moved/metrics for dispatch."""

    name = "moe_dispatch"

    def plan(
        self, inputs: MoEDispatchInputs, strategy: MigratoryStrategy,
        substrate: Substrate,
    ) -> ExecutionPlan:
        T = int(inputs.x.shape[0])
        if T % inputs.nodelets != 0:
            raise ValueError(
                f"moe_dispatch needs T % nodelets == 0, got T={T}, "
                f"nodelets={inputs.nodelets}"
            )
        inputs.validate_experts()
        kern = substrate.kernel(self.name)
        # expert weights are traced args: plan_key covers their shapes and
        # the executor threads them straight into the kernel
        args = (inputs.x, inputs.router) + inputs.ffn_args
        statics = (
            inputs.nodelets, inputs.experts_per_token, inputs.capacity_factor,
        )
        nodelets, k, cf = statics
        return ExecutionPlan(
            op=self.name,
            strategy=strategy,
            substrate=substrate.name,
            inputs=inputs,
            executor=lambda x, r, *ws: kern(
                x, r, *ws, strategy=strategy, nodelets=nodelets,
                experts_per_token=k, capacity_factor=cf,
            ),
            args=args,
            meta={"mode": derive_mode(inputs, strategy)},
            key=plan_key(self.name, substrate, strategy, args, static=statics),
        )

    def _replay(self, plan: ExecutionPlan) -> dict[str, Any]:
        if "replay" not in plan.meta:
            plan.meta["replay"] = _routing_replay_cached(plan.inputs)
        return plan.meta["replay"]

    def traffic(self, plan: ExecutionPlan) -> TrafficStats:
        return moe_dispatch_traffic(plan.inputs, plan.strategy, self._replay(plan))

    def bytes_moved(self, plan: ExecutionPlan) -> int:
        """Useful bytes of one dispatch: tokens read + combined output
        written + router weights read + expert weights read (when present)."""
        i = plan.inputs
        T, D = i.x.shape
        itemsize = jnp.dtype(i.x.dtype).itemsize
        total = 2 * T * D * itemsize + i.router.size * jnp.dtype(i.router.dtype).itemsize
        for w in i.ffn_args:
            total += w.size * jnp.dtype(w.dtype).itemsize
        return total

    def metrics(self, plan: ExecutionPlan, result: Any, seconds: float) -> dict[str, Any]:
        i = plan.inputs
        replay = self._replay(plan)
        mode = plan.meta["mode"]
        kept = _kept_for(replay)[mode]
        routed = replay["routed_slots"]
        return {
            "dispatch_mode": mode,
            "experts": i.num_experts,
            "nodelets": i.nodelets,
            "expert_ffn": i.has_experts,
            "routed_slots": routed,
            "dropped_slots": routed - kept,
            "drop_fraction": (routed - kept) / max(routed, 1),
        }


register_op(OpSpec(
    name="moe_dispatch",
    factory=MoEDispatchOp,
    inputs_type=MoEDispatchInputs,
    cost_model=moe_dispatch_cost_model,
    grid=moe_dispatch_grid,
))
