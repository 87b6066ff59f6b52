"""Sharding rules: logical axes -> mesh axes, param specs by naming
convention, activation constraints.

Parallelism layout (DESIGN.md §5):
  * batch ("batch")            -> ("pod", "data")     DP across pods+pod-local
  * params (FSDP dim)          -> "data"              ZeRO-3 inside a pod,
                                                      replicated across pods
  * heads / ffn / experts /
    vocab ("tensor" dims)      -> "model"             TP/EP
  * long-context KV seq        -> "data"              SP (batch=1 decode)

Param placement is inferred from leaf NAMES (naming convention, enforced by
the model code):
  TP on last dim : wq wk wv wg wu wi w_router w_dkv w_uk w_uv w_qa w_qb
                   lm_head w_gates
  TP on first dim: wo wd w_out
  tok_embed      : vocab dim (0) on "model"
  1-D / conv / scalars: replicated.
FSDP shards the largest non-TP dim on "data".
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

TP_LAST = {"wq", "wk", "wv", "wg", "wu", "wi", "w_router", "w_dkv", "w_uk",
           "w_uv", "w_qa", "w_qb", "lm_head", "w_gates", "w_in", "wx", "wy",
           "w_z", "w_xs", "w_dtp"}
# mamba2's w_b / w_c deliberately NOT TP (2N per token is tiny; computing
# B/C replicated avoids per-head all-reduces in the SSD contraction)
TP_FIRST = {"wo", "wd", "w_out"}
EXPERT = {"we_g", "we_u", "we_d"}          # (E, in, out): EP on dim 0
EMBED = {"tok_embed", "frame_embed", "patch_embed"}

_ACTIVE_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "repro_mesh", default=None)
_RULES: contextvars.ContextVar = contextvars.ContextVar(
    "repro_rules", default=None)
_MANUAL: contextvars.ContextVar = contextvars.ContextVar(
    "repro_manual", default=False)


@contextlib.contextmanager
def manual_region():
    """Mark a shard_map body: constrain() must no-op on manual axes."""
    tok = _MANUAL.set(True)
    try:
        yield
    finally:
        _MANUAL.reset(tok)

# logical activation axis -> mesh axes
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": None,            # set to "data" for long-context SP plans
    "heads": "model",
    "head_shard": "model",     # inner (vectorized) head axis in SSD blocks
    "embed": None,
    "ffn": "model",
    "vocab": "model",
    "experts": "model",
    "fsdp": "data",
}


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[dict] = None):
    """Install mesh + rules for constrain()/param_sharding() lookups."""
    rules = dict(DEFAULT_RULES, **(rules or {}))
    # drop mesh axes that don't exist (single-pod meshes have no "pod")
    axis_names = set(mesh.axis_names)

    def filt(v):
        if v is None:
            return None
        if isinstance(v, str):
            return v if v in axis_names else None
        vv = tuple(a for a in v if a in axis_names)
        return vv or None
    rules = {k: filt(v) for k, v in rules.items()}
    tok_m = _ACTIVE_MESH.set(mesh)
    tok_r = _RULES.set(rules)
    try:
        with mesh:
            yield
    finally:
        _ACTIVE_MESH.reset(tok_m)
        _RULES.reset(tok_r)


def active_mesh() -> Optional[Mesh]:
    """The mesh installed by the innermost ``use_mesh``, else None."""
    return _ACTIVE_MESH.get()


def current_rules() -> Optional[dict]:
    return _RULES.get()


def axis_size(name: str) -> int:
    """Size of a mesh axis under the active mesh (1 outside use_mesh)."""
    mesh = _ACTIVE_MESH.get()
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get(name, 1))


def constrain(x, logical: Tuple[Optional[str], ...]):
    """with_sharding_constraint via logical axis names; no-op outside
    use_mesh()."""
    mesh = _ACTIVE_MESH.get()
    rules = _RULES.get()
    if mesh is None or rules is None or _MANUAL.get():
        return x
    spec = P(*(rules.get(a) if a else None for a in logical))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


def _axes_size(mesh_shape: Optional[dict], axes) -> int:
    if mesh_shape is None or axes is None:
        return 1
    if isinstance(axes, str):
        return int(mesh_shape.get(axes, 1))
    n = 1
    for a in axes:
        n *= int(mesh_shape.get(a, 1))
    return n


def _guard(spec_list, shape, mesh_shape):
    """Replace axis assignments whose size does not divide the dim with
    None (divisibility guard; e.g. minicpm's 122753 vocab)."""
    out = []
    for dim, axes in zip(shape, spec_list):
        if axes is None:
            out.append(None)
            continue
        n = _axes_size(mesh_shape, axes)
        out.append(axes if n > 0 and dim % n == 0 else None)
    return out


def leaf_spec(path: str, shape, *, rules: dict,
              stacked: bool = False,
              mesh_shape: Optional[dict] = None) -> P:
    """PartitionSpec for one param leaf from its name + shape."""
    parts = path.split("/")
    name = parts[-1]
    # q8 moment leaves (optim/quantized_moments.q8nd_*): inherit the parent
    # weight's spec on the leading dims; q carries an extra trailing
    # (blocks, 256) split of the last dim, scale carries (blocks[, 2]).
    if name in ("q", "scale") and len(parts) >= 2:
        parent = parts[-2]
        if name == "q" and len(shape) >= 2:
            base = leaf_spec("/".join(parts[:-1]), shape[:-1], rules=rules,
                             stacked=stacked, mesh_shape=mesh_shape)
            return P(*base, None)
        if name == "scale" and len(shape) >= 1:
            # nonneg scales end with a packed [lmin, lrange] pair dim
            trailing_pair = shape[-1] == 2 and len(shape) >= 2
            core = shape[:-1] if trailing_pair else shape
            base = leaf_spec("/".join(parts[:-1]), core, rules=rules,
                             stacked=stacked, mesh_shape=mesh_shape)
            return P(*base, None) if trailing_pair else base
    tp = rules.get("heads") or rules.get("ffn")
    fsdp = rules.get("fsdp")
    lead_n = 1 if stacked else 0
    body = len(shape) - lead_n
    bshape = shape[lead_n:]
    lead = (None,) * lead_n

    if body <= 1:
        return P(*lead, *((None,) * body))
    if name in EMBED:
        spec = [tp, fsdp] + [None] * (body - 2)    # (V, D)
    elif name in EXPERT:
        spec = [tp, fsdp] + [None] * (body - 2)    # (E, in, out): EP
    elif name in TP_LAST:
        spec = [None] * body
        spec[-1] = tp
        spec[0] = fsdp
    elif name in TP_FIRST:
        spec = [None] * body
        spec[0] = tp
        spec[-1] = fsdp
    else:
        spec = [None] * body
        spec[0] = fsdp if body >= 2 else None
    spec = _guard(spec, bshape, mesh_shape)
    return P(*lead, *spec)


def _path_str(kp) -> str:
    parts = []
    for k in kp:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_specs(params, *, rules: Optional[dict] = None,
                mesh=None,
                stacked_prefixes: Tuple[str, ...] = ("blocks", "groups",
                                                     "prefix")):
    """PartitionSpec pytree mirroring ``params``.

    Leaves under ``stacked_prefixes`` carry a leading layer-stacking dim
    (scan-over-layers) which is never sharded.  ``mesh`` (or the active
    mesh) enables the divisibility guard.
    """
    rules = rules if rules is not None else (_RULES.get() or DEFAULT_RULES)
    mesh = mesh if mesh is not None else _ACTIVE_MESH.get()
    mesh_shape = dict(mesh.shape) if mesh is not None else None

    def spec_of(kp, leaf):
        path = _path_str(kp)
        stacked = any(path.startswith(p) or f"/{p}" in path
                      for p in stacked_prefixes)
        return leaf_spec(path, leaf.shape, rules=rules, stacked=stacked,
                         mesh_shape=mesh_shape)

    return jax.tree_util.tree_map_with_path(spec_of, params)


def param_shardings(mesh: Mesh, params, **kw):
    specs = param_specs(params, mesh=mesh, **kw)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# batch / cache shardings
# ---------------------------------------------------------------------------

def batch_specs_tree(batch, *, rules: Optional[dict] = None,
                     mesh=None):
    """PartitionSpecs for a data batch: dim 0 (global batch) over the DP
    axes, guarded for divisibility (long_500k has batch 1 -> replicated)."""
    rules = rules if rules is not None else (_RULES.get() or DEFAULT_RULES)
    mesh = mesh if mesh is not None else _ACTIVE_MESH.get()
    mesh_shape = dict(mesh.shape) if mesh is not None else None
    dp = rules.get("batch")

    def spec_of(leaf):
        if leaf.ndim == 0:
            return P()
        spec = [dp] + [None] * (leaf.ndim - 1)
        return P(*_guard(spec, leaf.shape, mesh_shape))

    return jax.tree.map(spec_of, batch)


# cache leaf name -> (which dim gets the DP axes, which gets "model")
_CACHE_LAYOUT = {
    # stacked caches: dim0 = layer group
    "k": (1, 2),        # (G, B, S, Hkv, hd): B->dp, S->model (seq shard)
    "v": (1, 2),
    "latent": (1, 2),   # (G, B, S, rank)
    "k_rope": (1, 2),
    "ssm": (1, 2),      # (G, B, H, N, P): B->dp, H->model
    "conv": (1, 3),     # (G, B, w, C): B->dp, C->model
    "h": (1, 2),        # (G, B, W): B->dp, W->model
}


def cache_specs_tree(cache, *, rules: Optional[dict] = None, mesh=None):
    """PartitionSpecs for decode caches (divisibility-guarded)."""
    rules = rules if rules is not None else (_RULES.get() or DEFAULT_RULES)
    mesh = mesh if mesh is not None else _ACTIVE_MESH.get()
    mesh_shape = dict(mesh.shape) if mesh is not None else None
    dp = rules.get("batch")
    tp = rules.get("heads") or rules.get("ffn")

    def spec_of(kp, leaf):
        name = _path_str(kp).split("/")[-1]
        layout = _CACHE_LAYOUT.get(name)
        spec = [None] * leaf.ndim
        if layout is not None:
            dp_dim, tp_dim = layout
            if dp_dim < leaf.ndim:
                spec[dp_dim] = dp
            if tp_dim < leaf.ndim:
                spec[tp_dim] = tp
        return P(*_guard(spec, leaf.shape, mesh_shape))

    return jax.tree_util.tree_map_with_path(spec_of, cache)


def tree_shardings(mesh: Mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))
