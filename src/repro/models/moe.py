"""Mixture-of-Experts FFN — grouped top-k routing with gather/scatter dispatch.

Token-local: routing and the expert FFN act position-wise, so TeraPipe token
slicing is exact for MoE layers (each token's routing decision is independent
of other positions).  Experts are sharded over the ``model`` mesh axis
("experts" logical axis); dispatch/combine lower to all-to-all-style
collectives under GSPMD.

Scalability: tokens are routed per *group* (one group per sequence), GShard
style, with per-group capacity C = ceil(cap_factor * S * k / E).  Dispatch is
built with gather/scatter (O(E*C + S*k) memory) instead of the classic dense
(N, E, C) one-hot einsum, which is infeasible at 10^6-token batches.

Supports DeepSeek-MoE fine-grained experts: ``n_shared_experts`` always-on
dense experts of width ``n_shared * d_expert`` plus ``n_experts`` routed
experts with top-k gating.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import ModelConfig, dense_init, swiglu
from .layers import ffn as dense_ffn, init_ffn


def _dispatch_axes(n_groups: int):
    """Data axes to shard_map the dispatch over, or None.

    Skips when no activation sharding is configured, the group count is
    not divisible, or we are already inside a shard_map (axes Manual —
    TeraPipe pipeline); the dispatch then runs under plain GSPMD
    propagation."""
    from .common import _ACT_AXES
    from repro.compat import auto_axis_names, current_mesh
    if not _ACT_AXES:
        return None
    mesh = current_mesh()
    if mesh is None:
        return None
    usable = auto_axis_names(mesh)
    total = 1
    for a in _ACT_AXES:
        if a not in usable:
            return None
        total *= mesh.shape[a]
    if n_groups % total != 0:
        return None
    return tuple(_ACT_AXES)


def init_moe(key, cfg: ModelConfig):
    ks = jax.random.split(key, 5)
    e, d, dff = cfg.n_experts, cfg.d_model, cfg.d_expert
    p = {
        "router": dense_init(ks[0], (d, e)),
        "w_gate": dense_init(ks[1], (e, d, dff), in_axis=-2),
        "w_up": dense_init(ks[2], (e, d, dff), in_axis=-2),
        "w_down": dense_init(ks[3], (e, dff, d), in_axis=-2),
    }
    s = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", None),
        "w_up": ("experts", "embed", None),
        "w_down": ("experts", None, "embed"),
    }
    if cfg.n_shared_experts:
        p_sh, s_sh = init_ffn(ks[4], cfg, d_ff=cfg.n_shared_experts * dff)
        p["shared"], s["shared"] = p_sh, s_sh
    return p, s


def _route_group(p, cfg: ModelConfig, xt: jnp.ndarray) -> jnp.ndarray:
    """Route one token group.  xt: (S, D) -> (S, D).

    Under manual TP (cfg.tp_axis) each device holds a contiguous slice of the
    expert dim (expert parallelism): routing is computed globally (router is
    replicated), non-local assignments fall into the overflow bin, and the
    partial combine is psum'd by the caller.
    """
    s, d = xt.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    e_local = p["w_gate"].shape[0]                                        # ≤ e under EP
    capacity = max(1, math.ceil(cfg.capacity_factor * s * k / e))

    logits = (xt @ p["router"].astype(xt.dtype)).astype(jnp.float32)      # (S, E)
    gates = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(gates, k)                                  # (S, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # slot position of each (token, choice) within its expert queue
    # (computed over GLOBAL experts — identical on every EP shard)
    flat_e = topi.reshape(-1)                                             # (S*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)                   # (S*k, E)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)  # (S*k,)
    keep = pos < capacity

    if e_local < e:
        off = jax.lax.axis_index(cfg.tp_axis) * e_local
        local = (flat_e >= off) & (flat_e < off + e_local)
        keep = keep & local
        flat_local = flat_e - off
    else:
        flat_local = flat_e

    # expert_in[e, c] = xt[token assigned to that slot] (zeros for empty slots)
    tok_idx = jnp.repeat(jnp.arange(s), k)                                # (S*k,)
    slot = jnp.where(keep, flat_local * capacity + pos,
                     e_local * capacity)                                  # overflow bin
    slot_tok = jnp.zeros((e_local * capacity + 1,), jnp.int32).at[slot].set(tok_idx + 1)
    gathered = jnp.concatenate([jnp.zeros((1, d), xt.dtype), xt], axis=0)[slot_tok]
    expert_in = gathered[:-1].reshape(e_local, capacity, d)               # drop overflow

    h = swiglu(jnp.einsum("ecd,edf->ecf", expert_in, p["w_gate"].astype(xt.dtype)),
               jnp.einsum("ecd,edf->ecf", expert_in, p["w_up"].astype(xt.dtype)))
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(xt.dtype))
    flat_out = jnp.concatenate(
        [expert_out.reshape(e_local * capacity, d), jnp.zeros((1, d), xt.dtype)], axis=0)

    # combine: out[t] += w * expert_out[slot(t, j)]
    per_choice = flat_out[slot]                                            # (S*k, D)
    w = (topw.reshape(-1) * keep.astype(topw.dtype)).astype(xt.dtype)
    out = jnp.zeros((s, d), xt.dtype).at[tok_idx].add(per_choice * w[:, None])
    return out


def moe_ffn(p, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D).

    Routing groups are fixed ``cfg.moe_block``-token blocks (never whole
    sequences).  This makes TeraPipe token slicing *exact* under finite
    capacity: a slice that is a multiple of moe_block contains whole routing
    groups, so capacity-based drops are identical whether the sequence is
    executed in one pass or in slices.  (With per-sequence groups, the drop
    pattern would depend on the slice boundaries.)
    """
    b, s, d = x.shape
    blk = min(cfg.moe_block, s)
    assert s % blk == 0, f"seq {s} not a multiple of moe_block {blk}"
    xg = x.reshape(b * (s // blk), blk, d)
    route = jax.vmap(lambda xt: _route_group(p, cfg, xt))
    # XLA's SPMD propagation replicates the group dim through the dispatch
    # gather/scatter (verified via buffer dumps: expert activations came out
    # group-replicated, 8-16x memory).  Force group-parallelism by mapping the
    # dispatch over the data axes with a subset shard_map; expert weights stay
    # under auto sharding (model axis) inside.
    dax = _dispatch_axes(xg.shape[0])
    if dax:
        from jax.sharding import PartitionSpec as P
        out = jax.shard_map(
            lambda xl: route(xl), axis_names=set(dax),
            in_specs=P(dax, None, None), out_specs=P(dax, None, None),
            check_vma=False)(xg)
    else:
        out = route(xg)
    out = out.reshape(b, s, d)
    if cfg.n_shared_experts:
        out = out + dense_ffn(p["shared"], x)       # partial under TP (row-sharded)
    if cfg.tp_axis is not None:
        out = jax.lax.psum(out, cfg.tp_axis)
    return out


def aux_load_balance_loss(p, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Switch-style auxiliary loss: E * sum_e f_e * p_e."""
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    logits = (xt @ p["router"].astype(x.dtype)).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(gates, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, cfg.n_experts, dtype=jnp.float32), axis=0)
    pbar = jnp.mean(gates, axis=0)
    return cfg.n_experts * jnp.sum(f * pbar)
