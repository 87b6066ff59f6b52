"""Mixture-of-Experts FFN with capacity-based scatter dispatch.

Dispatch avoids the (T, E, C) one-hot tensors of GShard-style einsum MoE:
  1. router top-k per token,
  2. rank within each expert via cumsum over the token dim (exclusive),
  3. capacity-clipped scatter into an (E*C, D) buffer,
  4. batched per-expert SwiGLU einsum (experts dim shards over "model"),
  5. gather-back weighted by normalized gates (dropped tokens contribute 0
     and fall through on the residual path).

Aux load-balancing loss per Switch/GShard: E * sum_e f_e * p_e.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed import sharding as shd
from ..distributed.sharding import constrain
from .layers import dense_init, dtype_of, pdtype_of


def moe_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 5)
    pd = pdtype_of(cfg)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.expert_ff
    std = d ** -0.5
    p = {
        "w_router": dense_init(ks[0], d, e, jnp.float32),
        "we_g": (jax.random.normal(ks[1], (e, d, f)) * std).astype(pd),
        "we_u": (jax.random.normal(ks[2], (e, d, f)) * std).astype(pd),
        "we_d": (jax.random.normal(ks[3], (e, f, d)) * std
               * cfg.residual_scale).astype(pd),
    }
    if cfg.n_shared_experts > 0:
        width = cfg.expert_ff * cfg.n_shared_experts
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wg": dense_init(kk[0], d, width, pd),
            "wu": dense_init(kk[1], d, width, pd),
            "wd": dense_init(kk[2], width, d, pd, scale=cfg.residual_scale),
        }
    return p


def moe_apply(p, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss).  Routes to the shard_map
    expert-parallel path when a mesh with a >1 "model" axis is active."""
    mesh = shd._ACTIVE_MESH.get()
    if mesh is not None and shd.axis_size("model") > 1:
        rules = shd.current_rules() or {}
        dp = rules.get("batch")
        dp_axes = (dp,) if isinstance(dp, str) else (dp or ())
        return moe_apply_sharded(p, x, cfg, mesh=mesh, dp_axes=dp_axes)
    return _moe_apply_gspmd(p, x, cfg)


def _moe_apply_gspmd(p, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    dt = dtype_of(cfg)
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    # capacity exists for load-balance memory bounds at scale; for small
    # token counts (decode steps, smoke tests) drops would be an artifact,
    # so floor at 8 slots (or the no-drop bound t*k when even smaller).
    cap = min(t * k, max(int(cfg.capacity_factor * t * k / e), 8))

    xt = x.reshape(t, d)
    xt = constrain(xt, ("batch", None))
    logits = constrain(xt.astype(jnp.float32) @ p["w_router"],
                       ("batch", None))                      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # aux loss: fraction routed vs mean prob, per expert
    onehot_all = jax.nn.one_hot(expert_ids, e, dtype=jnp.float32)  # (T,k,E)
    f_e = jnp.mean(jnp.sum(onehot_all, axis=1), axis=0)      # (E,)
    p_e = jnp.mean(probs, axis=0)
    aux = cfg.router_aux_coef * e * jnp.sum(f_e * p_e)

    # rank within expert via stable sort (the (T*k, E) one-hot cumsum
    # alternative costs O(T*k*E) memory traffic and lowers to a serial
    # reduce-window; sort is O(n log n) and shards cleanly)
    flat_e = expert_ids.reshape(-1)                          # (T*k,)
    flat_g = gate_vals.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    group_start = jnp.searchsorted(sorted_e, jnp.arange(e))  # (E,)
    rank_sorted = jnp.arange(t * k) - group_start[sorted_e]
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    keep = rank < cap

    # shard expert compute over BOTH axes: experts (EP) on "model", token
    # slots on the DP axes — otherwise data-ranks within a model group
    # redundantly compute the same expert block (16x wasted flops, found
    # via the dry-run useful-flops ratio).  The capacity buffer is sharded
    # FROM CREATION; over-capacity assignments fall off via mode="drop".
    ebuf0 = constrain(jnp.zeros((e, cap, d), dt),
                      ("experts", "batch", None))
    ebuf = ebuf0.at[flat_e, rank].set(xt[flat_tok].astype(dt),
                                      mode="drop")
    ebuf = constrain(ebuf, ("experts", "batch", None))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ebuf, p["we_g"].astype(dt))) \
        * jnp.einsum("ecd,edf->ecf", ebuf, p["we_u"].astype(dt))
    h = constrain(h, ("experts", "batch", None))
    y = jnp.einsum("ecf,efd->ecd", h, p["we_d"].astype(dt))
    y = constrain(y, ("experts", "batch", None))

    contrib = jnp.where(
        keep[:, None],
        y[flat_e, jnp.minimum(rank, cap - 1)] * flat_g[:, None].astype(dt),
        0.0)
    contrib = constrain(contrib, ("batch", None))
    out0 = constrain(jnp.zeros((t, d), dt), ("batch", None))
    out = out0.at[flat_tok].add(contrib)

    if "shared" in p:
        sp = p["shared"]
        hs = jax.nn.silu(xt @ sp["wg"].astype(dt)) * (xt @ sp["wu"].astype(dt))
        out = out + hs @ sp["wd"].astype(dt)
    return out.reshape(b, s, d), aux


def moe_apply_reference(p, x, cfg: ModelConfig) -> jax.Array:
    """Dense loop-over-experts oracle (no capacity drops) for tests."""
    dt = dtype_of(cfg)
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    logits = xt.astype(jnp.float32) @ p["w_router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, cfg.top_k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    out = jnp.zeros_like(xt)
    for ei in range(cfg.n_experts):
        h = jax.nn.silu(xt @ p["we_g"][ei].astype(dt)) \
            * (xt @ p["we_u"][ei].astype(dt))
        ye = h @ p["we_d"][ei].astype(dt)
        w = jnp.sum(jnp.where(expert_ids == ei, gate_vals, 0.0), axis=-1)
        out = out + ye * w[:, None].astype(dt)
    if "shared" in p:
        sp = p["shared"]
        hs = jax.nn.silu(xt @ sp["wg"].astype(dt)) * (xt @ sp["wu"].astype(dt))
        out = out + hs @ sp["wd"].astype(dt)
    return out.reshape(b, s, d)


# ---------------------------------------------------------------------------
# shard_map expert-parallel path (DESIGN.md §5).
#
# On a (pod, data, model) mesh, activations are replicated across "model",
# so MoE dispatch needs NO token all-to-all: each model rank extracts the
# tokens routed to ITS experts (local gather + capacity scatter), runs the
# expert FFN locally, and the per-rank partial outputs are psum'd over
# "model".  Communication per layer = one (T_local, D) all-reduce — GSPMD's
# auto-partitioned scatter for the same computation replicated the capacity
# buffers instead (354 GB/chip temp, 7.5e16 collective bytes; see
# EXPERIMENTS.md §Dry-run).
# ---------------------------------------------------------------------------

def _moe_dispatch_local(xt, gate_vals, expert_ids, we_g, we_u, we_d, *,
                        cap_local: int, model_axis: str, dt):
    """Per-shard body. xt: (T_loc, D); we_*: (E_loc, D, F)."""
    t_loc, d = xt.shape
    e_loc = we_g.shape[0]
    k = expert_ids.shape[-1]
    rank_id = jax.lax.axis_index(model_axis)
    my_lo = rank_id * e_loc

    local_ids = expert_ids.reshape(-1) - my_lo               # (T_loc*k,)
    mine = (local_ids >= 0) & (local_ids < e_loc)
    flat_e = jnp.where(mine, local_ids, e_loc)               # sentinel last
    flat_g = jnp.where(mine, gate_vals.reshape(-1), 0.0)
    flat_tok = jnp.repeat(jnp.arange(t_loc), k)

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    group_start = jnp.searchsorted(sorted_e, jnp.arange(e_loc + 1))
    rank_sorted = jnp.arange(t_loc * k) - group_start[sorted_e]
    rank = jnp.zeros((t_loc * k,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    keep = mine & (rank < cap_local)

    ebuf = jnp.zeros((e_loc, cap_local, d), dt).at[
        jnp.where(keep, flat_e, e_loc),           # OOB expert -> dropped
        rank].set(xt[flat_tok].astype(dt), mode="drop")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ebuf, we_g.astype(dt))) \
        * jnp.einsum("ecd,edf->ecf", ebuf, we_u.astype(dt))
    y = jnp.einsum("ecf,efd->ecd", h, we_d.astype(dt))

    contrib = jnp.where(
        keep[:, None],
        y[jnp.minimum(flat_e, e_loc - 1), jnp.minimum(rank, cap_local - 1)]
        * flat_g[:, None].astype(dt),
        0.0)
    out = jnp.zeros((t_loc, d), dt).at[flat_tok].add(contrib)
    return jax.lax.psum(out, model_axis)


def moe_apply_sharded(p, x, cfg: ModelConfig, *, mesh, dp_axes,
                      model_axis: str = "model"):
    """Expert-parallel MoE via shard_map (router/aux stay GSPMD-global)."""
    from jax.sharding import PartitionSpec as P

    dt = dtype_of(cfg)
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    model_size = int(dict(mesh.shape).get(model_axis, 1))
    dp_size = 1
    for a in (dp_axes or ()):
        dp_size *= int(dict(mesh.shape).get(a, 1))
    t_loc = t // max(dp_size, 1)
    cap_local = min(t_loc * k,
                    max(int(cfg.capacity_factor * t_loc * k / e), 8))

    xt = constrain(x.reshape(t, d), ("batch", None))
    logits = constrain(xt.astype(jnp.float32) @ p["w_router"],
                       ("batch", None))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    onehot_all = jax.nn.one_hot(expert_ids, e, dtype=jnp.float32)
    f_e = jnp.mean(jnp.sum(onehot_all, axis=1), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = cfg.router_aux_coef * e * jnp.sum(f_e * p_e)

    dp = tuple(dp_axes) if dp_axes else None
    body = functools.partial(_moe_dispatch_local, cap_local=cap_local,
                             model_axis=model_axis, dt=dt)
    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dp, None), P(dp, None), P(dp, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=P(dp, None),
    )(xt, gate_vals, expert_ids, p["we_g"], p["we_u"], p["we_d"])

    if "shared" in p:
        sp = p["shared"]
        hs = jax.nn.silu(xt @ sp["wg"].astype(dt)) * (xt @ sp["wu"].astype(dt))
        out = out + hs @ sp["wd"].astype(dt)
    return out.reshape(b, s, d), aux
