"""Plain float32 forward pass of DeepSeek-V3: multi-head latent attention,
leading dense SwiGLU layers, then layers of routed plus shared experts.

Per layer, with h = RMSNorm(x) and positions t = 0..S-1:
  c_q = RMSNorm(Wqa h);  q = Wqb c_q -> per head [q_nope (128), q_pe (64)]
  [c_kv, k_pe] = Wdkv h;  c_kv = RMSNorm(c_kv)
  k_nope = Wuk c_kv, v = Wuv c_kv (per head);  q_pe, k_pe rotated
  score(t, s) = (q_nope.k_nope + q_pe.k_pe) * scale, s <= t
  x += Wo softmax(score) v
then x += SwiGLU(RMSNorm(x)) in the dense layers, or in the expert layers
  x += shared(h) + sum over the chosen experts e held here of g_e * E_e(h)
with h = RMSNorm(x).  Routing (noaux_tc): s = sigmoid(Wr h) over the
router's full width; the groups are ranked by the sum of the 2 best of
s + bias in each, experts outside the top groups are set to 0, the top-k
of s + bias are chosen; g = s of the chosen, over their sum, times the
scaling factor.  Only the experts held here (ids expert_lo.. +
n_experts) are computed: what the others add is the other chips' share,
left out alike in the program.

Rotary embedding as the release's modelling code applies it: each
(even, odd) pair of the 64 rope dimensions de-interleaved to [evens,
odds], then rotate-half with YaRN inverse frequencies (extrapolated above
the beta_fast correction dimension, interpolated by the factor below
beta_slow's, a linear ramp between) and cos/sin times mscale(factor,
mscale) / mscale(factor, mscale_all_dim), mscale(f, m) = 0.1 m ln f + 1;
scale = (qk_nope + qk_rope)^-1/2 * mscale(factor, mscale_all_dim)^2.

Departures from the release: the vocabulary is the slice the chip holds
(the head gives logits over it), experts held elsewhere add nothing, and
there is no multi-token-prediction module (it is not on the serving
path).  Queries attend in blocks and layers are upcast to float32 one at
a time in a scan, so that a 4,224-token sequence fits beside the weights.

Reads the parameter tree by its leaf names; imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import F32, HIGHEST, matmul, rmsnorm, silu

QUERY_BLOCK = 128
#: the release's rope_scaling besides its factor and original positions
BETA_FAST, BETA_SLOW, MSCALE, MSCALE_ALL_DIM = 32.0, 1.0, 1.0, 1.0


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _inv_freq(m):
    """YaRN inverse frequencies of the rope dimensions."""
    d, base = m["rope_head_dim"], m["rope_theta"]
    extra = 1.0 / base ** (jnp.arange(0, d, 2, dtype=F32) / d)
    factor = m.get("yarn_factor", 0.0)
    if factor <= 0:
        return extra
    orig = m["yarn_original_max_pos"]

    def corr_dim(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(BETA_FAST)), 0)
    high = min(math.ceil(corr_dim(BETA_SLOW)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    inter = extra / factor
    return inter * ramp + extra * (1.0 - ramp)


def _rope(x, m):
    """x: (S, heads, d) at positions 0..S-1."""
    s, _, d = x.shape
    x = x.reshape(s, -1, d // 2, 2).swapaxes(-1, -2).reshape(s, -1, d)
    ang = jnp.arange(s, dtype=F32)[:, None] * _inv_freq(m)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    factor = m.get("yarn_factor", 0.0)
    sc = _mscale(factor, MSCALE) / _mscale(factor, MSCALE_ALL_DIM)
    cos = (jnp.cos(ang) * sc)[:, None, :]
    sin = (jnp.sin(ang) * sc)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def _scale(m):
    scale = (m["head_dim"] + m["rope_head_dim"]) ** -0.5
    factor = m.get("yarn_factor", 0.0)
    if factor > 0:
        scale *= _mscale(factor, MSCALE_ALL_DIM) ** 2
    return scale


def _attention(w, a, m, fp8):
    """MLA over a: (S, D) -> (S, D)."""
    s = a.shape[0]
    h, dn, dr = m["n_heads"], m["head_dim"], m["rope_head_dim"]
    dv = m.get("v_head_dim") or dn
    r = m["kv_lora_rank"]
    eps = m["norm_eps"]
    cq = rmsnorm(matmul(a, w["w_qa"], fp8), w["q_norm"], eps)
    q = matmul(cq, w["w_qb"], fp8).reshape(s, h, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], m)
    ckv = matmul(a, w["w_dkv"], fp8)
    c = rmsnorm(ckv[:, :r], w["kv_norm"], eps)
    k_pe = _rope(ckv[:, None, r:], m)[:, 0]
    k_nope = matmul(c, w["w_uk"], fp8).reshape(s, h, dn)
    v = matmul(c, w["w_uv"], fp8).reshape(s, h, dv)
    scale = _scale(m)

    nb = -(-s // QUERY_BLOCK)
    pad = nb * QUERY_BLOCK - s
    qn = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0))).reshape(
        nb, QUERY_BLOCK, h, dn)
    qp = jnp.pad(q_pe, ((0, pad), (0, 0), (0, 0))).reshape(
        nb, QUERY_BLOCK, h, dr)

    def block(inp):
        i, qn_i, qp_i = inp
        sc = (jnp.einsum("thd,shd->hts", qn_i, k_nope, precision=HIGHEST)
              + jnp.einsum("thd,sd->hts", qp_i, k_pe, precision=HIGHEST))
        t = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)[:, None]
        keep = jnp.arange(s)[None, :] <= t
        p = jax.nn.softmax(jnp.where(keep, sc * scale, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(nb), qn, qp))
    o = o.reshape(nb * QUERY_BLOCK, h * dv)[:s]
    return matmul(o, w["wo"], fp8)


def _swiglu(f, a, fp8):
    return matmul(silu(matmul(a, f["wg"], fp8)) * matmul(a, f["wu"], fp8),
                  f["wd"], fp8)


def _route(e, a, m):
    """Gates (S, held) of the experts held here."""
    s = a.shape[0]
    width, k = m["n_routed_experts"], m["top_k"]
    groups, top_groups = m["n_group"], m["topk_group"]
    scores = jax.nn.sigmoid(matmul(a, e["w_router"]))
    choice = scores + e["e_score_correction_bias"].astype(F32)
    per = choice.reshape(s, groups, width // groups)
    best2 = jnp.sort(per, axis=-1)[..., -2:].sum(-1)            # (S, G)
    rank = jnp.argsort(jnp.argsort(-best2, axis=-1), axis=-1)
    keep = jnp.repeat(rank < top_groups, width // groups, axis=-1)
    choice = jnp.where(keep, choice, 0.0)
    kth = jnp.sort(choice, axis=-1)[:, -k][:, None]
    chosen = choice >= kth
    gates = jnp.where(chosen, scores, 0.0)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    gates = gates * m.get("routed_scaling_factor", 1.0)
    lo = m.get("expert_lo", 0)
    return gates[:, lo:lo + m["n_experts"]]


def _experts(e, a, m, fp8):
    gates = _route(e, a, m)
    out = _swiglu(e["shared"], a, fp8)
    for i in range(m["n_experts"]):
        f = {"wg": e["we_g"][i], "wu": e["we_u"][i], "wd": e["we_d"][i]}
        out = out + gates[:, i:i + 1] * _swiglu(f, a, fp8)
    return out


def forward(params, m, tokens, first: int, fp8: bool = False):
    """tokens: (S,) int -> float32 logits (S - first, V) of positions
    first..S-1."""
    eps = m["norm_eps"]
    x = params["tok_embed"][tokens].astype(F32)

    def dense(x, p):
        x = x + _attention(p["attn"], rmsnorm(x, p["ln1"], eps), m, fp8)
        return x + _swiglu(p["mlp"], rmsnorm(x, p["ln2"], eps), fp8), None

    def expert(x, p):
        x = x + _attention(p["attn"], rmsnorm(x, p["ln1"], eps), m, fp8)
        return x + _experts(p["moe"], rmsnorm(x, p["ln2"], eps), m,
                            fp8), None

    if m.get("first_dense", 0):
        x, _ = jax.lax.scan(dense, x, params["prefix"])
    x, _ = jax.lax.scan(expert, x, params["groups"]["b0"])
    x = rmsnorm(x[first:], params["final_norm"], eps)
    return matmul(x, params["lm_head"], fp8) * m.get("logit_scale", 1.0)
