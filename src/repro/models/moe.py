"""Mixture-of-Experts FFN: a dropless layer that holds a share of the
experts.

The router scores every token over its full width (``router_width``
experts) and picks ``top_k`` of them; this layer holds the routed experts
``[expert_lo, expert_lo + n_experts)`` and computes only their part of the
result, plus the shared experts once.  What experts held elsewhere would
add is left to the chips that hold them (under a mesh, ``shard_map`` gives
each "model" rank its slice and sums the parts).  Routing:

  softmax   top_k of softmax(logits), gates renormalised (Qwen3-MoE)
  sigmoid   DeepSeek-V3's noaux_tc: scores = sigmoid(logits); a
            correction bias added to the scores chooses, and nothing else:
            the n_group groups are ranked by the sum of their 2 best
            biased scores, experts outside the topk_group best groups are
            set to 0, the top_k of the rest are chosen, and their gates
            are the unbiased scores, renormalised.
  Gates are then multiplied by ``routed_scaling_factor``.

No token is dropped: the (token, expert) pairs routed to held experts are
sorted by expert, each expert's rows starting on a tile of the product,
and go through ``jax.lax.ragged_dot``, whose work follows the tiles
present.  The pairs run in chunks of ``CHUNK_ROWS``, as many as they
fill; the loop's static bound is the worst case, every token routed to
min(top_k, n_experts) held experts.  Rows of a chunk past the pairs lie
in no group and are cleared.  A few tokens (a decode step) instead run
through every held expert, each weighted by its gates (0 where not
routed).

Aux load-balancing loss per Switch/GShard over the router's width:
width * sum_e f_e * p_e.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed import sharding as shd
from ..distributed.sharding import constrain
from .layers import dense_init, dtype_of, pdtype_of

#: rows of the sorted (token, held expert) pairs one ragged product takes:
#: the pairs present run in as many such chunks as they fill (one, at
#: a 4,096-token prompt of 4 sequences and 8 of 256 experts held).  A
#: multiple of 512, the rows of a tile of the TPU's ragged product (a
#: row count that 512 does not divide falls to tiles of 8 rows)
CHUNK_ROWS = 16384
#: up to this many tokens (a decode step's batch), every held expert runs
#: over all of them instead: no sort, and each expert's weights are read
#: once, where the ragged products of a layer inside the layer scan would
#: first copy the layer's experts out of the stacked weights
FEW_TOKENS = 64


def moe_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 5)
    pd = pdtype_of(cfg)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.expert_ff
    std = d ** -0.5
    p = {
        "w_router": dense_init(ks[0], d, cfg.router_width, jnp.float32),
        "we_g": (jax.random.normal(ks[1], (e, d, f)) * std).astype(pd),
        "we_u": (jax.random.normal(ks[2], (e, d, f)) * std).astype(pd),
        "we_d": (jax.random.normal(ks[3], (e, f, d)) * std
               * cfg.residual_scale).astype(pd),
    }
    if cfg.router_score == "sigmoid":
        p["e_score_correction_bias"] = jnp.zeros((cfg.router_width,),
                                                 jnp.float32)
    if cfg.n_shared_experts > 0:
        width = cfg.expert_ff * cfg.n_shared_experts
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wg": dense_init(kk[0], d, width, pd),
            "wu": dense_init(kk[1], d, width, pd),
            "wd": dense_init(kk[2], width, d, pd, scale=cfg.residual_scale),
        }
    return p


def route(p, xt, cfg: ModelConfig):
    """xt (T, D) -> gates (T, top_k) float32, expert ids (T, top_k) over
    the router's width, and the aux loss."""
    logits = xt.astype(jnp.float32) @ p["w_router"].astype(jnp.float32)
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + p["e_score_correction_bias"].astype(jnp.float32)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        scores = probs = jax.nn.softmax(logits, axis=-1)
        choice = scores
    t, w = choice.shape
    if cfg.n_group > 1:
        grouped = choice.reshape(t, cfg.n_group, w // cfg.n_group)
        best2 = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, groups = jax.lax.top_k(best2, cfg.topk_group)
        keep = jnp.zeros((t, cfg.n_group), bool).at[
            jnp.arange(t)[:, None], groups].set(True)
        choice = jnp.where(keep[:, :, None], grouped, 0.0).reshape(t, w)
    _, ids = jax.lax.top_k(choice, cfg.top_k)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    gates = gates * cfg.routed_scaling_factor

    f_e = jnp.mean(jnp.sum(jax.nn.one_hot(ids, w, dtype=jnp.float32),
                           axis=1), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = cfg.router_aux_coef * w * jnp.sum(f_e * p_e)
    return gates, ids, aux


def held_experts(xt, gates, ids, we_g, we_u, we_d, lo, dt):
    """The held experts' part of the layer for tokens xt (T, D): expert
    ids ``[lo, lo + E)`` of ``ids`` (T, k) are held, with weights
    we_* (E, ...); every pair routed to one of them is computed."""
    t, d = xt.shape
    e, k = we_g.shape[0], ids.shape[1]
    local = ids - lo
    mine = (local >= 0) & (local < e)
    gates = jnp.where(mine, gates, 0.0)
    if t <= FEW_TOKENS:
        return _each_expert(xt, gates, jnp.where(mine, local, e), we_g,
                            we_u, we_d, dt)
    key = jnp.where(mine, local, e).reshape(-1)          # others sort last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
    starts = jnp.cumsum(sizes) - sizes
    held = jnp.sum(sizes)
    worst = t * min(k, e)                                # held pairs, at most
    rows = min(CHUNK_ROWS, -(-worst // 512) * 512)
    flat_gates = gates.reshape(-1)

    def chunk(c, out):
        first = c * rows
        at = order[jnp.minimum(first + jnp.arange(rows), t * k - 1)]
        tok = at // k
        present = first + jnp.arange(rows) < held
        # each expert's rows of this chunk of the sorted pairs
        part = jnp.clip(jnp.minimum(starts + sizes, first + rows)
                        - jnp.maximum(starts, first), 0, rows)
        xs = xt[tok].astype(dt)
        h = jax.nn.silu(jax.lax.ragged_dot(xs, we_g.astype(dt), part)) \
            * jax.lax.ragged_dot(xs, we_u.astype(dt), part)
        y = jax.lax.ragged_dot(h, we_d.astype(dt), part)
        # rows past the held pairs lie in no group, where the ragged
        # product's output is not defined: cleared, not multiplied by 0
        y = jnp.where(present[:, None],
                      y.astype(jnp.float32) * flat_gates[at][:, None], 0.0)
        return out.at[tok].add(y)

    out = jax.lax.fori_loop(
        0, -(-worst // rows),
        lambda c, out: jax.lax.cond(c * rows < held, chunk,
                                    lambda c, out: out, c, out),
        jnp.zeros((t, d), jnp.float32))
    return out.astype(dt)


def _each_expert(xt, gates, local, we_g, we_u, we_d, dt):
    """Few tokens: every held expert over all of them, weighted by their
    gates there (0 where not routed): each held expert's weights read
    once, in one product over the experts."""
    t, e = xt.shape[0], we_g.shape[0]
    weight = jnp.zeros((t, e + 1), jnp.float32).at[
        jnp.arange(t)[:, None], local].add(gates)[:, :e]
    x = xt.astype(dt)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, we_g.astype(dt))) \
        * jnp.einsum("td,edf->tef", x, we_u.astype(dt))
    y = jnp.einsum("tef,efd->ted", h, we_d.astype(dt))
    return jnp.einsum("ted,te->td", y, weight.astype(dt))


def _shared(p, xt, dt):
    sp = p["shared"]
    hs = jax.nn.silu(xt @ sp["wg"].astype(dt)) * (xt @ sp["wu"].astype(dt))
    return hs @ sp["wd"].astype(dt)


def moe_apply(p, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss).  Under a mesh with a >1 "model"
    axis, the experts are split over it by ``shard_map``."""
    dt = dtype_of(cfg)
    b, s, d = x.shape
    xt = constrain(x.reshape(b * s, d), ("batch", None))
    mesh = shd._ACTIVE_MESH.get()
    gates, ids, aux = route(p, xt, cfg)
    if mesh is not None and shd.axis_size("model") > 1:
        out = _held_sharded(p, xt, gates, ids, cfg, mesh)
    else:
        out = held_experts(xt, gates, ids, p["we_g"], p["we_u"], p["we_d"],
                           cfg.expert_lo, dt)
    if "shared" in p:
        out = out + _shared(p, xt, dt)
    return out.reshape(b, s, d), aux


def moe_apply_reference(p, x, cfg: ModelConfig) -> jax.Array:
    """Dense loop over the held experts (the oracle for tests): every
    token through every held expert, weighted by its gate there."""
    dt = dtype_of(cfg)
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    gates, ids, _ = route(p, xt, cfg)
    out = jnp.zeros_like(xt)
    for ei in range(cfg.n_experts):
        h = jax.nn.silu(xt @ p["we_g"][ei].astype(dt)) \
            * (xt @ p["we_u"][ei].astype(dt))
        ye = h @ p["we_d"][ei].astype(dt)
        w = jnp.sum(jnp.where(ids == cfg.expert_lo + ei, gates, 0.0),
                    axis=-1)
        out = out + ye * w[:, None].astype(dt)
    if "shared" in p:
        out = out + _shared(p, xt, dt)
    return out.reshape(b, s, d)


# ---------------------------------------------------------------------------
# expert parallelism under a mesh.
#
# Activations are replicated across "model", so the layer needs no token
# all-to-all: each model rank computes the part of its own slice of the
# experts (``held_experts`` with the rank's offset), and the parts are
# psum'd over "model".  Communication per layer: one (T_local, D)
# all-reduce.
# ---------------------------------------------------------------------------

def _held_sharded(p, xt, gates, ids, cfg: ModelConfig, mesh,
                  model_axis: str = "model"):
    from jax.sharding import PartitionSpec as P

    rules = shd.current_rules() or {}
    dp = rules.get("batch")
    dp = ((dp,) if isinstance(dp, str) else tuple(dp or ())) or None
    e_loc = cfg.n_experts // int(dict(mesh.shape)[model_axis])

    def body(xt, gates, ids, we_g, we_u, we_d):
        lo = cfg.expert_lo + jax.lax.axis_index(model_axis) * e_loc
        out = held_experts(xt, gates, ids, we_g, we_u, we_d, lo,
                           dtype_of(cfg))
        return jax.lax.psum(out, model_axis)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None), P(dp, None), P(dp, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=P(dp, None),
        # the skip branch of the chunk loop's cond does not vary over the
        # model axis as the experts' products do
        check_vma=False,
    )(xt, gates, ids, p["we_g"], p["we_u"], p["we_d"])
