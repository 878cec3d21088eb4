"""Mixture-of-Experts routing + three dispatch implementations.

  * ``dense``  — every expert on every token, combine by gate (reference /
                 smoke-test oracle; O(T·E) FLOPs, exact when nothing drops).
  * ``einsum`` — Mesh-TF-style one-hot capacity dispatch. Exact up to
                 capacity drops; efficient for SMALL token counts (decode).
  * ``a2a``    — shard_map expert parallelism: tokens sharded over all mesh
                 axes, experts sharded over ``expert`` axes; two sorts +
                 ``all_to_all`` exchange + per-expert padded GEMMs. The
                 train/prefill path (see DESIGN.md §5).

All paths share the router: softmax -> top-k -> renormalize, plus the
switch-style load-balance auxiliary loss.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..launch.sharding import current_mesh, logical_to_mesh, rules, shard

__all__ = ["route", "moe_ffn"]


def _act(cfg):
    return jax.nn.silu


def route(cfg: ModelConfig, x2d: jnp.ndarray, router_w: jnp.ndarray):
    """x2d (T, d) -> (gate_w (T, k), gate_idx (T, k), aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, cfg.top_k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    # load-balance aux: E * sum_e f_e * P_e
    E = cfg.num_experts
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (T, k, E)
    f_e = onehot.mean(axis=(0, 1))  # fraction routed (per slot-averaged)
    P_e = probs.mean(axis=0)
    aux = E * jnp.sum(f_e * P_e)
    return gate_w, gate_idx, aux


def _expert_mlp(experts, xs, act):
    """xs (..., C, d) grouped per expert on leading E axis of `experts`."""
    h = act(jnp.einsum("ecd,edf->ecf", xs, experts["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", xs, experts["w_in"]
    )
    return jnp.einsum("ecf,efd->ecd", h, experts["w_out"])


# ---------------------------------------------------------------------------
# dense reference
# ---------------------------------------------------------------------------


def _moe_dense(cfg, x2d, experts, gate_w, gate_idx):
    act = _act(cfg)
    h = act(jnp.einsum("td,edf->etf", x2d, experts["w_gate"])) * jnp.einsum(
        "td,edf->etf", x2d, experts["w_in"]
    )
    y_all = jnp.einsum("etf,efd->etd", h, experts["w_out"])  # (E, T, d)
    onehot = jax.nn.one_hot(gate_idx, cfg.num_experts, dtype=x2d.dtype)  # (T,k,E)
    w = (gate_w.astype(x2d.dtype)[..., None] * onehot).sum(1)  # (T, E)
    return jnp.einsum("te,etd->td", w, y_all)


# ---------------------------------------------------------------------------
# einsum one-hot capacity dispatch (small T)
# ---------------------------------------------------------------------------


def _moe_einsum(cfg, x2d, experts, gate_w, gate_idx, capacity: int):
    T, d = x2d.shape
    E, k = cfg.num_experts, cfg.top_k
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (T, k, E)
    # position of each (t, slot) within its expert, counted t-major
    flat = onehot.reshape(T * k, E)
    pos = jnp.cumsum(flat, axis=0) - flat  # (T*k, E) position if routed
    pos = (pos * flat).sum(-1).reshape(T, k)  # (T, k)
    keep = pos < capacity
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity, dtype=jnp.float32)
    dispatch = jnp.einsum("tke,tkc->tec", onehot, pos_oh)  # (T, E, C) 0/1
    combine = jnp.einsum("tk,tke,tkc->tec", gate_w.astype(jnp.float32), onehot, pos_oh)
    xs = jnp.einsum("tec,td->ecd", dispatch, x2d.astype(jnp.float32)).astype(x2d.dtype)
    ys = _expert_mlp(experts, xs, _act(cfg))  # (E, C, d)
    y = jnp.einsum("tec,ecd->td", combine, ys.astype(jnp.float32))
    return y.astype(x2d.dtype)


# ---------------------------------------------------------------------------
# all-to-all expert parallelism (shard_map)
# ---------------------------------------------------------------------------


def _sort_group(ids, num_groups, capacity, *payloads):
    """Groups rows by ``ids`` into (num_groups, capacity, ...) padded buffers.

    Returns (bufs..., meta) where meta lets :func:`_ungroup` scatter results
    back to the original row order. Rows beyond capacity are dropped.
    """
    N = ids.shape[0]
    order = jnp.argsort(ids)  # stable
    sorted_ids = ids[order]
    start = jnp.searchsorted(sorted_ids, jnp.arange(num_groups), side="left")
    pos_in_group = jnp.arange(N) - start[sorted_ids]
    valid = pos_in_group < capacity
    dest = jnp.where(valid, sorted_ids * capacity + pos_in_group, num_groups * capacity)
    bufs = []
    for pl in payloads:
        flat = jnp.zeros((num_groups * capacity,) + pl.shape[1:], pl.dtype)
        bufs.append(flat.at[dest].set(pl[order], mode="drop").reshape((num_groups, capacity) + pl.shape[1:]))
    meta = (order, dest, valid)
    return bufs, meta


def _ungroup(buf, meta, N):
    """Inverse of _sort_group for one payload: (G, C, ...) -> (N, ...)."""
    order, dest, valid = meta
    flat = buf.reshape((-1,) + buf.shape[2:])
    gathered = jnp.where(
        valid.reshape((-1,) + (1,) * (flat.ndim - 1)),
        flat[jnp.minimum(dest, flat.shape[0] - 1)],
        0,
    )
    inv = jnp.argsort(order)
    return gathered[inv]


def _a2a_local(x, gate_w, gate_idx, experts, *, cfg, ep_axes, n_peers, e_local,
               cap_send, cap_expert):
    """Per-device body under shard_map.

    x (Tl, d); gate_w/idx (Tl, k); experts leaves with leading E_local axis.
    """
    Tl, d = x.shape
    k = cfg.top_k
    flat_ids = gate_idx.reshape(-1)  # (Tl*k,) global expert ids
    flat_x = jnp.repeat(x, k, axis=0)  # (Tl*k, d) token copies
    dest_peer = flat_ids // e_local
    local_eid = flat_ids % e_local

    (send_x, send_eid), meta_send = _sort_group(
        dest_peer, n_peers, cap_send, flat_x, local_eid.astype(jnp.int32)
    )
    # exchange: recv[p] = what peer p sent to me (dim0 == n_peers, so each
    # peer receives one (cap_send, d) block per sender)
    a2a_ax = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    recv_x = jax.lax.all_to_all(send_x, a2a_ax, 0, 0, tiled=True)
    recv_eid = jax.lax.all_to_all(send_eid, a2a_ax, 0, 0, tiled=True)
    # per-slot validity travels implicitly: invalid slots carry eid pointing
    # at a zero row (x == 0), harmless after the expert MLP and combine.
    flat_recv_x = recv_x.reshape(-1, d)
    flat_recv_eid = recv_eid.reshape(-1)

    (grp_x,), meta_grp = _sort_group(flat_recv_eid, e_local, cap_expert, flat_recv_x)
    grp_y = _expert_mlp(experts, grp_x, _act(cfg))  # (e_local, cap_expert, d)
    flat_y = _ungroup(grp_y, meta_grp, flat_recv_eid.shape[0])
    back = flat_y.reshape(n_peers, cap_send, d)
    ret = jax.lax.all_to_all(back, a2a_ax, 0, 0, tiled=True)
    flat_ret = _ungroup(ret, meta_send, flat_ids.shape[0])  # (Tl*k, d)
    y = (flat_ret.reshape(Tl, k, d).astype(jnp.float32) * gate_w[..., None]).sum(1)
    return y.astype(x.dtype)


def _moe_a2a(cfg, x2d, experts, gate_w, gate_idx):
    mesh = current_mesh()
    assert mesh is not None, "a2a MoE requires an active mesh"
    r = rules()
    token_axes = tuple(mesh.axis_names)  # shard tokens over everything
    ep_axes = r["expert"]
    ep_axes = (ep_axes,) if isinstance(ep_axes, str) else tuple(ep_axes)
    n_peers = 1
    for a in ep_axes:
        n_peers *= int(mesh.shape[a])
    e_local = cfg.num_experts // n_peers
    T = x2d.shape[0]
    n_tok_shards = 1
    for a in token_axes:
        n_tok_shards *= mesh.shape[a]
    Tl = T // n_tok_shards
    cap_send = max(8, int(-(-Tl * cfg.top_k * cfg.capacity_factor // n_peers) // 8 * 8 + 8))
    cap_expert = max(8, int(-(-n_peers * cap_send * cfg.capacity_factor // e_local) // 8 * 8 + 8))

    body = functools.partial(
        _a2a_local, cfg=cfg, ep_axes=ep_axes, n_peers=n_peers, e_local=e_local,
        cap_send=cap_send, cap_expert=cap_expert,
    )
    expert_specs = jax.tree.map(lambda _: P(ep_axes if len(ep_axes) > 1 else ep_axes[0]), experts)
    y = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(token_axes), P(token_axes), P(token_axes), expert_specs),
        out_specs=P(token_axes),
        check_vma=False,
    )(x2d, gate_w, gate_idx, experts)
    return y


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def moe_ffn(cfg: ModelConfig, p, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """p: {router (d,E), experts {w_gate,w_in,w_out} (E,...) [, shared {...}]}.

    x (B, S, d) -> (y (B, S, d), aux_loss).
    """
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    gate_w, gate_idx, aux = route(cfg, x2d, p["router"])
    gate_w = gate_w.astype(jnp.float32)

    impl = cfg.moe_impl
    if impl == "a2a" and current_mesh() is None:
        impl = "dense"
    if impl == "dense":
        y = _moe_dense(cfg, x2d, p["experts"], gate_w, gate_idx)
    elif impl == "einsum":
        cap = max(8, int(B * S * cfg.top_k * cfg.capacity_factor / cfg.num_experts) + 8)
        y = _moe_einsum(cfg, x2d, p["experts"], gate_w, gate_idx, cap)
    elif impl == "a2a":
        y = _moe_a2a(cfg, x2d, p["experts"], gate_w, gate_idx)
    else:
        raise ValueError(cfg.moe_impl)

    if "shared" in p:  # deepseek-style always-on shared expert(s)
        sh = p["shared"]
        h = jax.nn.silu(jnp.einsum("td,df->tf", x2d, sh["w_gate"])) * jnp.einsum(
            "td,df->tf", x2d, sh["w_in"]
        )
        y = y + jnp.einsum("tf,fd->td", h, sh["w_out"])
    return y.reshape(B, S, d), aux
