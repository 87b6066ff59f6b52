"""Operations and bytes that a DeepSeek-V3-style model (latent attention,
leading dense layers, routed plus shared experts) needs, from a
configuration's shapes alone.

As in ``flops.py``, the counts follow the mathematics and not the program:
a multiply-add is 2 operations; attention is counted over the live,
causal prefix; a prefill needs the head for its last token only.  Beyond
that:

* latent attention has two exact forms.  Each phase is counted in the
  smaller: the expanded form in a prefill (keys and values expanded once
  per token, 320 operations per head per query-key pair), the absorbed
  one in decode (the query taken into latent space, 1,088 per head per
  cached key, where the expanded form would also have to expand every
  cached latent again);
* the routed experts held here are counted at their expected share of the
  tokens, top_k * held / router width (a quarter of an expert per token
  at 8 of 256, top-8);
* a decode step reads every weight once, except the held experts, each
  read if any of the batch's tokens is routed to it: held * (1 - (1 -
  top_k / width)^batch) in expectation.  The live latent prefix is read
  and the new entry written.

``m`` is a configuration file's ``model`` block; ``sb`` its ``state_bytes``
block (``latent``: bytes per element of the latent cache).
"""
from __future__ import annotations

from typing import Dict

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def applies(m: Dict) -> bool:
    return bool(m.get("use_mla")) and "moe" in m.get("pattern", ())


def _dims(m: Dict):
    h, dn, dr = m["n_heads"], m["head_dim"], m["rope_head_dim"]
    return h, dn, dr, m.get("v_head_dim") or dn, m["kv_lora_rank"]


def width(m: Dict) -> int:
    return m.get("n_routed_experts") or m["n_experts"]


def mla_macs(m: Dict) -> int:
    """Multiply-adds per token in latent attention's weight matmuls: q
    down and up (or one q matmul), the latent and rope key, the key and
    value up-projections (or, absorbed, the query into latent space and
    the output out of it: as many), the output projection."""
    d = m["d_model"]
    h, dn, dr, dv, r = _dims(m)
    ql = m.get("q_lora_rank", 0)
    q = d * ql + ql * h * (dn + dr) if ql else d * h * (dn + dr)
    return q + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def swiglu_macs(m: Dict, ff: int) -> int:
    return 3 * m["d_model"] * ff


def moe_macs(m: Dict) -> float:
    """Per token: the router, the shared experts, and the held routed
    experts at their expected share."""
    d, ff = m["d_model"], m["d_expert"]
    share = m["top_k"] * m["n_experts"] / width(m)
    return (d * width(m) + m.get("n_shared_experts", 0) * swiglu_macs(m, ff)
            + share * swiglu_macs(m, ff))


def dense_layers(m: Dict) -> int:
    return m.get("first_dense", 0)


def moe_layers(m: Dict) -> int:
    if list(m["pattern"]) != ["moe"]:
        raise ValueError("expected the pattern ['moe'] after the dense "
                         "layers")
    return m["n_layers"] - dense_layers(m)


def token_macs(m: Dict) -> float:
    """Weight multiply-adds per token through every layer (no head)."""
    mla = mla_macs(m)
    return (dense_layers(m) * (mla + swiglu_macs(m, m["d_ff"]))
            + moe_layers(m) * (mla + moe_macs(m)))


def head_macs(m: Dict) -> int:
    return m["d_model"] * m["vocab"]


def expanded_pair_flops(m: Dict) -> int:
    """One query against one key, all heads, expanded: q.k over
    qk_nope + qk_rope and p.v over v_head_dim."""
    h, dn, dr, dv, _ = _dims(m)
    return 2 * h * (dn + dr + dv)


def absorbed_key_flops(m: Dict) -> int:
    """One query against one cached latent, all heads, absorbed: the
    score over kv_lora + qk_rope and the output over kv_lora."""
    h, _, dr, _, r = _dims(m)
    return 2 * h * (2 * r + dr)


def expand_key_flops(m: Dict) -> int:
    """Expanding one cached latent to keys and values, all heads."""
    h, dn, _, dv, r = _dims(m)
    return 2 * r * h * (dn + dv)


def prefill_score_flops(m: Dict, prompt_len: int) -> int:
    """One sequence's causal query-key pairs in one layer, in the smaller
    form."""
    pairs = prompt_len * (prompt_len + 1) // 2
    return pairs * min(expanded_pair_flops(m), absorbed_key_flops(m))


def decode_score_flops(m: Dict, pos: int) -> int:
    """The query at ``pos`` against its pos + 1 cached latents in one
    layer, in the smaller form."""
    return (pos + 1) * min(expanded_pair_flops(m) + expand_key_flops(m),
                           absorbed_key_flops(m))


def prefill_flops(m: Dict, batch: int, prompt_len: int) -> float:
    layers = m["n_layers"]
    per_seq = (2 * token_macs(m) * prompt_len
               + layers * prefill_score_flops(m, prompt_len)
               + 2 * head_macs(m))
    return batch * per_seq


def decode_flops(m: Dict, batch: int, pos: int) -> float:
    per_tok = (2 * token_macs(m) + m["n_layers"] * decode_score_flops(m, pos)
               + 2 * head_macs(m))
    return batch * per_tok


def experts_touched(m: Dict, batch: int) -> float:
    """Held experts that a batch of tokens reaches, in expectation."""
    return m["n_experts"] * (1 - (1 - m["top_k"] / width(m)) ** batch)


def weight_bytes(m: Dict, batch: int) -> float:
    """Bytes of weights one decode step reads."""
    pb = _BYTES[m["param_dtype"]]
    d = m["d_model"]
    _, _, _, _, r = _dims(m)
    ql = m.get("q_lora_rank", 0)
    mla = (mla_macs(m) + r + ql) * pb                 # with the latent norms
    norms = 2 * d * pb
    dense = mla + norms + swiglu_macs(m, m["d_ff"]) * pb
    expert = swiglu_macs(m, m["d_expert"]) * pb
    router = d * width(m) * 4 + width(m) * 4          # float32, with the bias
    moe = (mla + norms + router
           + m.get("n_shared_experts", 0) * expert
           + experts_touched(m, batch) * expert)
    total = dense_layers(m) * dense + moe_layers(m) * moe
    total += d * pb + head_macs(m) * pb + batch * d * pb   # norm, head, rows
    return total


def cache_bytes(m: Dict, sb: Dict, batch: int, pos: int) -> int:
    """The live latent prefix read and the new entry written, all
    layers."""
    _, _, dr, _, r = _dims(m)
    per = (r + dr) * sb["latent"]
    return m["n_layers"] * batch * per * (pos + 2)


def decode_bytes(m: Dict, sb: Dict, batch: int, pos: int) -> float:
    return weight_bytes(m, batch) + cache_bytes(m, sb, batch, pos)


def decode_least_s(m: Dict, sb: Dict, batch: int, pos: int,
                   peak_flops: float, peak_bw: float) -> float:
    return max(decode_flops(m, batch, pos) / peak_flops,
               decode_bytes(m, sb, batch, pos) / peak_bw)
